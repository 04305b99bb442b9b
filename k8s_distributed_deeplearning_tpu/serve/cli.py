"""Serving CLI: drive the continuous-batching engine over a synthetic
mixed-length workload and emit JSONL serving metrics.

Usage (via the launch entry point)::

  python -m k8s_distributed_deeplearning_tpu.launch serve \\
      --preset tiny --requests 32 --slots 4 --out-len 8 32

Emits one ``serve_request`` event per completion and a final
``serve_summary`` (tokens/sec, TTFT/latency percentiles, slot occupancy)
through :class:`utils.metrics.MetricsLogger` — the same stdout→Promtail→
Loki JSONL contract as training. Parameters are randomly initialized (a
synthetic-workload demo of the serving path; production serving would
restore trained parameters in front of this same engine).
"""
from __future__ import annotations

import argparse
import os
import sys


def _drain_status(engines) -> dict:
    """/healthz body for the serving process: ``status`` is the preStop
    hook's one-word answer — "ok" until drain() is called, "draining"
    while any replica still holds work, "drained" once everything
    finished (safe to kill)."""
    draining = any(e.draining for e in engines)
    drained = all(e.drained for e in engines)
    return {"status": ("drained" if draining and drained
                       else "draining" if draining else "ok"),
            "draining": draining, "drained": drained}


def preset_config(preset: str, max_seq_len: int):
    """The model configuration behind ``--preset``: tiny (the test config,
    f32) or small (the 124M llama config, bf16, unrolled layers)."""
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.models import llama
    if preset == "small":
        return llama.config_tiny(
            vocab_size=32000, dim=768, n_layers=12, n_heads=12, n_kv_heads=4,
            mlp_dim=2048, max_seq_len=max_seq_len, dtype=jnp.bfloat16,
            scan_layers=False)
    return llama.config_tiny(max_seq_len=max_seq_len, dtype=jnp.float32)


def _ran_on(engine) -> dict:
    """What the summary says about the hardware: the device as JAX reports
    it, per-device allocator bytes, and which paged-attention
    implementation each of the engine's programs resolved to. A remote
    gateway builds no engine and must not touch a backend (its
    replica-servers own the chips): it reports nothing here."""
    if engine is None:
        return {}
    from k8s_distributed_deeplearning_tpu import backend
    from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
    return {**mesh_lib.topology().device_fields(),
            "device_bytes_in_use": backend.device_bytes_in_use(),
            "attention_impls": engine.attention_impls()}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="launch serve",
        description="continuous-batching serving demo on a synthetic "
                    "mixed-length workload")
    ap.add_argument("--preset", choices=["tiny", "small"], default="tiny",
                    help="model size: tiny (test config) or small (the "
                         "124M bench config)")
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel width (graftmesh): run each "
                         "engine's compiled decode/prefill/verify programs "
                         "under shard_map over the first N devices, with "
                         "attention/MLP weights and the paged KV pool "
                         "sharded along the head dimension (0 = "
                         "single-device, no mesh)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="run this many in-process engine replicas behind "
                         "the failover gateway (serve/gateway.py): health-"
                         "routed dispatch, per-replica circuit breakers, "
                         "and in-flight migration off sick/draining "
                         "replicas. 1 = a bare engine (no gateway)")
    ap.add_argument("--hedge-after-s", type=float, default=None,
                    metavar="S",
                    help="gateway only: duplicate a request's dispatch on "
                         "a second replica when its first token is still "
                         "missing after S seconds (first stream wins, "
                         "loser is cancelled); omitted = no hedging")
    ap.add_argument("--replica-server", action="store_true",
                    help="run ONE engine as a standalone replica-server "
                         "process (serve/transport.py): the transport "
                         "endpoints (/submit /poll /cancel /drain "
                         "/shutdown) share the /metrics exporter on "
                         "--metrics-port, a remote gateway drives the "
                         "workload, and SIGTERM drains then exits 0")
    ap.add_argument("--port-file", default=None, metavar="PATH",
                    help="replica-server only: write the bound port here "
                         "once listening (use with --metrics-port 0 for "
                         "an ephemeral port in tests)")
    ap.add_argument("--heartbeat-dir", default=None, metavar="DIR",
                    help="replica-server only: advertise this replica's "
                         "metrics_addr through heartbeat files in DIR "
                         "(the gateway's --replica-discovery-dir reads "
                         "the same directory)")
    ap.add_argument("--replica-rank", type=int, default=0,
                    help="replica-server only: heartbeat rank / identity "
                         "of this replica process")
    ap.add_argument("--advertise-host", default="127.0.0.1",
                    help="replica-server only: host written into the "
                         "advertised metrics_addr (the address peers "
                         "dial, not the bind address)")
    ap.add_argument("--role", choices=["decode", "prefill"],
                    default="decode",
                    help="replica-server only: disagg serving role "
                         "(serve/disagg.py). decode = the normal engine; "
                         "prefill = admission + prefill only — finished "
                         "prompt KV pages are exported over /exports for "
                         "a coordinator to ship to a decode replica. The "
                         "role rides the heartbeat beacon, so gateways "
                         "and autoscalers never adopt a prefill worker "
                         "as a decode replica")
    ap.add_argument("--disagg", action="store_true",
                    help="remote coordinator mode (needs "
                         "--replica-discovery-dir): route prompts through "
                         "prefill-role replica-servers discovered in the "
                         "heartbeat dir and ship their finished KV pages "
                         "to the least-loaded decode replica over /pages "
                         "(serve/disagg.py); with no healthy prefill "
                         "worker the coordinator falls back to unified "
                         "decode-local prefill, so disagg is a "
                         "performance mode, never an availability "
                         "dependency")
    ap.add_argument("--prefill-endpoints", default=None, metavar="LIST",
                    help="with --disagg: static comma-separated "
                         "host:port list of prefill-role replica-servers "
                         "(the rendered k8s topology passes stable pod "
                         "DNS here); with --replica-discovery-dir "
                         "instead, prefill workers are discovered by "
                         "their role heartbeat and this flag is not "
                         "needed")
    ap.add_argument("--disagg-prefill", type=int, default=0, metavar="N",
                    help="in-process disagg: run N prefill-only engines "
                         "in front of the --replicas decode engines and "
                         "route through the DisaggCoordinator (0 = off)")
    ap.add_argument("--replica-endpoints", default=None, metavar="LIST",
                    help="run the gateway over REMOTE replica-server "
                         "processes at these comma-separated host:port "
                         "endpoints instead of in-process engines (no "
                         "local model is built)")
    ap.add_argument("--replica-discovery-dir", default=None, metavar="DIR",
                    help="like --replica-endpoints, but discover the "
                         "fleet from heartbeat files carrying "
                         "metrics_addr (written by replica-servers "
                         "started with --heartbeat-dir DIR)")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the SLO-driven fleet controller "
                         "(serve/autoscale.py) over the gateway: scale "
                         "the replica set between --autoscale-min and "
                         "--autoscale-max on fast-window SLO burn / "
                         "queue pressure (drain-safe scale-down, zero "
                         "lost requests) and walk the reversible "
                         "brownout ladder at max scale")
    ap.add_argument("--autoscale-min", type=int, default=1,
                    help="elastic floor: never drain below this many "
                         "replicas")
    ap.add_argument("--autoscale-max", type=int, default=4,
                    help="elastic ceiling: at this many replicas, "
                         "sustained overload escalates the brownout "
                         "ladder instead of adding capacity")
    ap.add_argument("--autoscale-interval-s", type=float, default=0.5,
                    metavar="S",
                    help="minimum seconds between control rounds")
    ap.add_argument("--autoscale-up-cooldown-s", type=float, default=2.0,
                    metavar="S",
                    help="minimum seconds between scale-up (or brownout "
                         "escalation) actuations")
    ap.add_argument("--autoscale-down-cooldown-s", type=float,
                    default=5.0, metavar="S",
                    help="minimum seconds between scale-down (or "
                         "brownout de-escalation) actuations")
    ap.add_argument("--autoscale-brownout", default=None, metavar="LIST",
                    help="comma-separated brownout ladder stages in "
                         "escalation order (default: shed_batch,"
                         "no_hedge,tight_admission)")
    ap.add_argument("--autoscale-k8s-job", default=None, metavar="NAME",
                    help="actuate by patching this Indexed replica "
                         "Job's parallelism through kubectl instead of "
                         "spawning local processes (the rendered "
                         "gateway role passes this)")
    ap.add_argument("--autoscale-k8s-namespace", default="default",
                    help="namespace of --autoscale-k8s-job")
    ap.add_argument("--autoscale-endpoint-template", default=None,
                    metavar="FMT",
                    help="host:port format string with an {i} "
                         "completion-index placeholder — how the k8s "
                         "backend names the endpoint of a freshly "
                         "scaled-up replica pod (Indexed-Job DNS is "
                         "deterministic)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission queue bound (default: number of "
                         "requests)")
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(32, 128),
                    metavar=("LO", "HI"))
    ap.add_argument("--out-len", type=int, nargs=2, default=(16, 64),
                    metavar=("LO", "HI"))
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend this many shared tokens to every prompt "
                         "(models fleet traffic with a common system "
                         "prompt — the prefix cache's target workload)")
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="prefix-reuse trie budget in MiB (0 = off): "
                         "prompts sharing a prefix MAP its cached pages "
                         "into their block tables instead of recomputing "
                         "— the bytes draw from the shared paged KV pool "
                         "(--kv-pool-pages), not a separate arena")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="size of the shared paged KV pool in pages "
                         "(0 = num_slots * max_blocks, the dense-arena "
                         "equivalent); smaller pools trade peak "
                         "concurrency for HBM via admission back-pressure")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=0,
                    help="bound each iteration's prefill work to this many "
                         "prompt tokens (0 = off); must be a multiple of "
                         "the 32-token prefill bucket granularity")
    ap.add_argument("--tenants", default=None, metavar="SPEC",
                    help="multi-tenant scheduler config: inline JSON or "
                         "@/path to a JSON file (same addressing as fault "
                         "plans). Workload requests are assigned round-"
                         "robin across the configured tenants; omitted = "
                         "single unlimited default tenant (FCFS)")
    ap.add_argument("--draft-model", choices=["micro", "tiny"], default=None,
                    help="enable speculative decoding with this draft "
                         "preset (micro: 1-layer width-32; tiny: the test "
                         "config) — built with the TARGET's vocab, "
                         "max-seq-len and dtype so proposals are target "
                         "token ids; requires --spec-k")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens proposed per slot per iteration "
                         "(>= 1; requires --draft-model). Each iteration "
                         "then emits 1..k+1 tokens per slot, bit-identical "
                         "to non-speculative decoding")
    ap.add_argument("--kv-quant", choices=["int8"],
                    default=os.environ.get("TPUJOB_KV_QUANT") or None,
                    help="quantize the paged KV pool: int8 arenas with "
                         "per-token-per-head f32 scales, dequantized on "
                         "read inside the decode kernel (graftquant). "
                         "Defaults from $TPUJOB_KV_QUANT (launch/render)")
    ap.add_argument("--weight-quant", choices=["int8"],
                    default=os.environ.get("TPUJOB_WEIGHT_QUANT") or None,
                    help="per-output-channel int8 serving weights, "
                         "dequantized at use inside the compiled programs "
                         "(matmul kernels only — embeddings, norms and the "
                         "lm_head stay fp). Defaults from "
                         "$TPUJOB_WEIGHT_QUANT (launch/render)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-path", default=None,
                    help="also append JSONL events to this file")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics (+ /healthz) on this "
                         "port; serving gauges update per scrape")
    ap.add_argument("--trace", action="store_true",
                    help="emit span events (prefill/decode/admission) "
                         "through the JSONL stream")
    ap.add_argument("--request-trace-sample", type=float, default=0.0,
                    metavar="FRAC",
                    help="emit one request_trace lifecycle event (submit→"
                         "queue→prefill→decode→finish) for this fraction "
                         "of finished requests, sampled deterministically "
                         "by request id (0 = off, 1 = every request); "
                         "analyze with `graftscope requests`")
    ap.add_argument("--debug-dir", default=None, metavar="DIR",
                    help="enable the exporter's on-demand debug surface "
                         "(requires --metrics-port): /debug/spans serves "
                         "an in-memory ring of recent spans, "
                         "/debug/profile?ms=N captures a windowed "
                         "jax.profiler trace into DIR")
    ap.add_argument("--flight-ring", type=int, default=0, metavar="N",
                    help="black-box flight recorder: keep the last N "
                         "per-step engine/gateway snapshots in memory and "
                         "dump them as JSONL on breaker trip, drain "
                         "completion, SIGTERM, injected fault, or "
                         "/debug/flight?dump=1 (0 = off); read dumps with "
                         "`graftscope postmortem`")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="directory for flight-recorder dump files "
                         "(requires --flight-ring; omitted = dumps stay "
                         "in memory, visible only via /debug/flight)")
    args = ap.parse_args(argv)

    # Flag validation BEFORE the heavy imports/model build: a bad flag
    # dies with usage text instead of a traceback from ServeEngine (the
    # engine re-checks the same invariants for library callers).
    min_bucket = 32
    if args.prefill_chunk_tokens and (
            args.prefill_chunk_tokens < min_bucket
            or args.prefill_chunk_tokens % min_bucket):
        ap.error(f"--prefill-chunk-tokens ({args.prefill_chunk_tokens}) "
                 f"must be a multiple of the prefill bucket granularity "
                 f"({min_bucket})")
    if args.prefix_cache_mb < 0:
        ap.error(f"--prefix-cache-mb must be >= 0, got "
                 f"{args.prefix_cache_mb}")
    if args.kv_pool_pages < 0:
        ap.error(f"--kv-pool-pages must be >= 0, got "
                 f"{args.kv_pool_pages}")
    if args.shared_prefix_len < 0:
        ap.error(f"--shared-prefix-len must be >= 0, got "
                 f"{args.shared_prefix_len}")
    if not 0.0 <= args.request_trace_sample <= 1.0:
        ap.error(f"--request-trace-sample must be in [0, 1], got "
                 f"{args.request_trace_sample}")
    if args.debug_dir is not None and args.metrics_port is None:
        ap.error("--debug-dir requires --metrics-port (the debug surface "
                 "rides the metrics exporter)")
    if args.replicas < 1:
        ap.error(f"--replicas must be >= 1, got {args.replicas}")
    if args.tp < 0:
        ap.error(f"--tp must be >= 0 (0 = single-device), got {args.tp}")
    remote = (args.replica_endpoints is not None
              or args.replica_discovery_dir is not None)
    if args.replica_endpoints is not None and args.replica_discovery_dir:
        ap.error("--replica-endpoints and --replica-discovery-dir are "
                 "mutually exclusive (static list vs heartbeat discovery)")
    if args.replica_server and remote:
        ap.error("--replica-server runs the engine side; "
                 "--replica-endpoints/--replica-discovery-dir run the "
                 "gateway side — pick one per process")
    if args.replica_server and args.replicas != 1:
        ap.error("--replica-server wraps exactly one engine per process "
                 f"(got --replicas {args.replicas}); scale out by "
                 "starting more replica-server processes")
    if args.replica_server and args.metrics_port is None:
        ap.error("--replica-server requires --metrics-port (the transport "
                 "endpoints ride the metrics exporter; 0 = ephemeral "
                 "with --port-file)")
    if args.port_file is not None and not args.replica_server:
        ap.error("--port-file only makes sense with --replica-server")
    if args.heartbeat_dir is not None and not args.replica_server:
        ap.error("--heartbeat-dir only makes sense with --replica-server "
                 "(gateways discover via --replica-discovery-dir)")
    if args.role != "decode" and not args.replica_server:
        ap.error("--role only makes sense with --replica-server (the "
                 "coordinator side learns roles from heartbeat beacons)")
    if args.role == "prefill" and args.spec_k:
        ap.error("--role prefill runs admission + prefill only; "
                 "speculative decoding is a decode-side knob")
    if args.disagg_prefill < 0:
        ap.error(f"--disagg-prefill must be >= 0, got "
                 f"{args.disagg_prefill}")
    if args.disagg and not remote:
        ap.error("--disagg needs a remote decode fleet "
                 "(--replica-endpoints or --replica-discovery-dir); "
                 "use --disagg-prefill N for in-process disagg")
    if args.prefill_endpoints is not None and not args.disagg:
        ap.error("--prefill-endpoints only makes sense with --disagg")
    if args.prefill_endpoints is not None \
            and args.replica_discovery_dir is not None:
        ap.error("--prefill-endpoints is the static alternative to "
                 "role-heartbeat discovery; with "
                 "--replica-discovery-dir the prefill fleet is "
                 "discovered from the same directory")
    if args.disagg_prefill and (remote or args.replica_server):
        ap.error("--disagg-prefill runs in-process prefill engines; "
                 "use --disagg for a remote fleet, or start prefill "
                 "replica-servers with --role prefill")
    if (args.disagg or args.disagg_prefill) and args.autoscale:
        ap.error("--disagg and --autoscale are not yet composable in "
                 "one process: the controller actuates through the "
                 "gateway, which the disagg coordinator replaces (run "
                 "per-role controllers instead)")
    if (args.disagg or args.disagg_prefill) \
            and args.hedge_after_s is not None:
        ap.error("--hedge-after-s is a gateway knob; the disagg "
                 "coordinator does not hedge")
    if remote and args.draft_model is not None:
        ap.error("speculative decoding is an engine-side knob: pass "
                 "--draft-model to the replica-server processes, not "
                 "the remote gateway")
    if args.hedge_after_s is not None and args.replicas < 2 and not remote:
        ap.error("--hedge-after-s needs --replicas >= 2 (hedging "
                 "duplicates a dispatch onto a PEER replica)")
    if args.hedge_after_s is not None and args.hedge_after_s <= 0:
        ap.error(f"--hedge-after-s must be > 0, got {args.hedge_after_s}")
    if (args.draft_model is None) != (args.spec_k == 0):
        ap.error("speculative decoding needs BOTH --draft-model and "
                 f"--spec-k >= 1 (got --draft-model {args.draft_model}, "
                 f"--spec-k {args.spec_k})")
    if args.spec_k < 0:
        ap.error(f"--spec-k must be >= 1 (0 = off), got {args.spec_k}")
    if args.flight_ring < 0:
        ap.error(f"--flight-ring must be >= 0, got {args.flight_ring}")
    if args.flight_dir is not None and not args.flight_ring:
        ap.error("--flight-dir requires --flight-ring >= 1 (there is "
                 "nothing to dump with the recorder off)")
    if args.autoscale:
        if args.replica_server:
            ap.error("--autoscale runs gateway-side; a replica-server "
                     "is the thing being scaled")
        if args.autoscale_min < 1:
            ap.error(f"--autoscale-min must be >= 1, got "
                     f"{args.autoscale_min}")
        if args.autoscale_max < args.autoscale_min:
            ap.error(f"--autoscale-min ({args.autoscale_min}) must be "
                     f"<= --autoscale-max ({args.autoscale_max})")
        if args.autoscale_up_cooldown_s <= 0 \
                or args.autoscale_down_cooldown_s <= 0:
            ap.error("autoscale cooldowns must be > 0")
        if args.autoscale_brownout is not None:
            # Literal copy of serve.autoscale.BROWNOUT_STAGE_NAMES so a
            # typo dies with usage text before the heavy imports; a
            # parity test keeps the two tuples identical.
            known = ("shed_batch", "no_hedge", "tight_admission")
            for stage in args.autoscale_brownout.split(","):
                if stage.strip() not in known:
                    ap.error(f"--autoscale-brownout stage "
                             f"{stage.strip()!r} is not one of {known}")
        if args.autoscale_k8s_job is not None and not remote:
            ap.error("--autoscale-k8s-job needs the remote gateway "
                     "(--replica-endpoints/--replica-discovery-dir): "
                     "the k8s backend scales replica-server pods")
        if args.replica_endpoints is not None \
                and args.autoscale_k8s_job is None:
            ap.error("--autoscale over a static --replica-endpoints "
                     "list has nothing to start/stop replicas with; "
                     "pass --autoscale-k8s-job, or use "
                     "--replica-discovery-dir for the local process "
                     "backend")
    elif args.autoscale_k8s_job is not None \
            or args.autoscale_endpoint_template is not None:
        ap.error("--autoscale-k8s-job/--autoscale-endpoint-template "
                 "only make sense with --autoscale")

    import signal

    import jax
    import jax.numpy as jnp
    import numpy as np

    from k8s_distributed_deeplearning_tpu import backend
    backend.use_compile_cache()

    from k8s_distributed_deeplearning_tpu.models import llama
    from k8s_distributed_deeplearning_tpu.serve import (QueueFull, Request,
                                                        SamplingParams,
                                                        ServeEngine,
                                                        ServeGateway,
                                                        load_tenants)
    from k8s_distributed_deeplearning_tpu.utils.metrics import (
        MetricsLogger, ServingStats)

    tenant_cfgs = None
    if args.tenants:
        try:
            tenant_cfgs = load_tenants(args.tenants)
        except (OSError, ValueError) as e:
            ap.error(f"--tenants: {e}")

    cfg = preset_config(args.preset, args.max_seq_len)
    if not remote:
        model = llama.LlamaLM(cfg)
        params = model.init(jax.random.PRNGKey(args.seed),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    draft_model = draft_params = None
    if args.draft_model is not None:
        # Draft presets are depth/width recipes stamped with the TARGET's
        # vocab, max_seq_len and dtype (the engine requires both models to
        # speak the same token ids over the same positions).
        if args.draft_model == "micro":
            dcfg = llama.config_tiny(
                vocab_size=cfg.vocab_size, dim=32, n_layers=1, n_heads=2,
                n_kv_heads=1, mlp_dim=64, max_seq_len=cfg.max_seq_len,
                dtype=cfg.dtype)
        else:
            dcfg = llama.config_tiny(
                vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
                dtype=cfg.dtype)
        draft_model = llama.LlamaLM(dcfg)
        draft_params = draft_model.init(
            jax.random.PRNGKey(args.seed + 1),
            jnp.zeros((1, 8), jnp.int32))["params"]

    p_lo, p_hi = args.prompt_len
    o_lo, o_hi = args.out_len
    # A replica server generates no workload of its own — the gateway
    # shapes every request it serves — so the synthetic-workload bounds
    # only apply to the driving modes.
    if not args.replica_server and \
            args.shared_prefix_len + p_hi + o_hi > cfg.max_seq_len:
        ap.error(f"shared-prefix-len ({args.shared_prefix_len}) + "
                 f"prompt-len hi ({p_hi}) + out-len hi ({o_hi}) exceeds "
                 f"--max-seq-len ({cfg.max_seq_len})")
    rng = np.random.default_rng(args.seed)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    logger = MetricsLogger(job="serve", path=args.metrics_path)
    flight = None
    if args.flight_ring:
        from k8s_distributed_deeplearning_tpu.telemetry.flight import (
            FlightRecorder)
        # ONE recorder shared by every replica and the gateway: the dump
        # is the whole process's flight path, sources interleaved.
        flight = FlightRecorder(args.flight_ring, dump_dir=args.flight_dir,
                                logger=logger, job="serve")
    tracer = None
    if args.trace or args.debug_dir is not None:
        from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
        # --debug-dir without --trace: a record-only tracer (no logger)
        # still fills the ring buffer behind /debug/spans without putting
        # span events on the JSONL stream.
        tracer = Tracer(logger if args.trace else None,
                        ring_size=512 if args.debug_dir is not None else 0)
    # ONE ServingStats shared by every replica AND the gateway: replica
    # activity and gateway counters aggregate into a single summary()/
    # scrape surface (the process is single-threaded, so increment-only
    # sharing is safe).
    stats = ServingStats()
    engines = [] if remote else [
        ServeEngine(
            model, params, num_slots=args.slots,
            max_queue=args.max_queue or args.requests,
            eos_id=args.eos_id, tracer=tracer, tenants=tenant_cfgs,
            prefill_chunk_tokens=args.prefill_chunk_tokens or None,
            prefix_cache_mb=args.prefix_cache_mb or None,
            kv_pool_pages=args.kv_pool_pages or None,
            request_trace_sample=args.request_trace_sample,
            request_log=logger, stats=stats,
            draft_model=draft_model, draft_params=draft_params,
            spec_k=args.spec_k, flight=flight, tp=args.tp,
            kv_quant=args.kv_quant, weight_quant=args.weight_quant,
            prefill_only=(args.role == "prefill"),
            replica_id=(f"r{i}" if args.replicas > 1 or args.autoscale
                        else None))
        for i in range(args.replicas)]
    engine = engines[0] if engines else None
    prefill_engines = []
    if args.disagg_prefill:
        prefill_engines = [
            ServeEngine(
                model, params, num_slots=args.slots,
                max_queue=args.max_queue or args.requests,
                eos_id=args.eos_id, tracer=tracer, tenants=tenant_cfgs,
                prefill_chunk_tokens=args.prefill_chunk_tokens or None,
                prefix_cache_mb=args.prefix_cache_mb or None,
                kv_pool_pages=args.kv_pool_pages or None,
                request_log=logger, stats=stats, flight=flight,
                tp=args.tp, kv_quant=args.kv_quant,
                weight_quant=args.weight_quant,
                prefill_only=True, replica_id=f"p{i}")
            for i in range(args.disagg_prefill)]
    clients = None
    gateway = None
    coordinator = None
    if remote:
        from k8s_distributed_deeplearning_tpu.serve.transport import (
            ReplicaClient, discover_replica_clients)
        if args.replica_discovery_dir is not None:
            clients = discover_replica_clients(
                args.replica_discovery_dir, stats=stats, logger=logger,
                flight=flight)
            if not clients:
                ap.error(f"--replica-discovery-dir "
                         f"{args.replica_discovery_dir}: no heartbeat "
                         f"advertises a metrics_addr (are the "
                         f"replica-servers up, with --heartbeat-dir?)")
        else:
            clients = [
                ReplicaClient(ep.strip(), stats=stats, logger=logger,
                              flight=flight)
                for ep in args.replica_endpoints.split(",") if ep.strip()]
            if not clients:
                ap.error("--replica-endpoints: empty endpoint list")
        if args.hedge_after_s is not None and len(clients) < 2:
            ap.error("--hedge-after-s needs >= 2 remote replicas")
        if args.disagg:
            # Coordinator mode replaces the gateway: decode clients take
            # dispatches, prefill-role clients (possibly none — then
            # every request takes the unified fallback) feed them pages.
            from k8s_distributed_deeplearning_tpu.serve.disagg import (
                DisaggCoordinator, RemotePrefillWorker)
            if args.prefill_endpoints is not None:
                prefill_clients = [
                    ReplicaClient(ep.strip(), stats=stats, logger=logger,
                                  flight=flight)
                    for ep in args.prefill_endpoints.split(",")
                    if ep.strip()]
            elif args.replica_discovery_dir is not None:
                prefill_clients = discover_replica_clients(
                    args.replica_discovery_dir, stats=stats,
                    logger=logger, flight=flight, role="prefill")
            else:
                prefill_clients = []
            coordinator = DisaggCoordinator(
                clients,
                [RemotePrefillWorker(c) for c in prefill_clients],
                stats=stats, logger=logger)
        else:
            gateway = ServeGateway(clients, stats=stats, logger=logger,
                                   hedge_after_s=args.hedge_after_s,
                                   flight=flight)
    elif args.disagg_prefill:
        from k8s_distributed_deeplearning_tpu.serve.disagg import (
            DisaggCoordinator, PrefillWorker)
        coordinator = DisaggCoordinator(
            engines, [PrefillWorker(e) for e in prefill_engines],
            stats=stats, logger=logger)
    elif args.replicas > 1 or args.autoscale:
        # --autoscale forces the gateway even at one replica: the
        # controller actuates through its dynamic membership.
        gateway = ServeGateway(engines, stats=stats, logger=logger,
                               hedge_after_s=args.hedge_after_s,
                               flight=flight)
    if coordinator is not None:
        front = coordinator
    elif gateway is not None:
        front = gateway
    else:
        front = engine
    # What the probes report on: remote mode watches the clients' cached
    # replica states, local mode the engines themselves.
    status_objs = clients if clients is not None else engines

    controller = None
    autoscale_backend = None
    slo = None
    if args.autoscale:
        import time as _time_mod

        from k8s_distributed_deeplearning_tpu.serve.autoscale import (
            EngineFactoryBackend, FleetController, K8sParallelismBackend,
            LocalProcessBackend, default_brownout_stages,
            heartbeat_discoverer)
        from k8s_distributed_deeplearning_tpu.telemetry.slo import (
            SLOEngine, SLOTarget, objectives_from_tenants)
        objectives = (objectives_from_tenants(tenant_cfgs)
                      if tenant_cfgs is not None else {})
        if not objectives:
            # No tenant slo blocks: synthesize a 99%-over-60s objective
            # per tenant (fast window = 5s) so the burn signal is live
            # at demo timescales instead of the 1h production default.
            ids = ([c.tenant_id for c in tenant_cfgs]
                   if tenant_cfgs is not None else ["default"])
            objectives = {tid: SLOTarget(availability=0.99,
                                         window_s=60.0) for tid in ids}
        # Same monotonic clock as the controller: observe() stamps and
        # evaluate() windows must live on one timeline.
        slo = SLOEngine(objectives, emit=logger.emit,
                        clock=_time_mod.monotonic)
        if args.autoscale_k8s_job is not None:
            from k8s_distributed_deeplearning_tpu.launch.watch import (
                Kubectl)
            autoscale_backend = K8sParallelismBackend(
                Kubectl(), args.autoscale_k8s_job,
                args.autoscale_k8s_namespace,
                initial_replicas=len(clients),
                endpoint_template=args.autoscale_endpoint_template,
                client_kwargs=dict(stats=stats, logger=logger,
                                   flight=flight))
        elif remote:
            autoscale_backend = LocalProcessBackend(
                args.replica_discovery_dir, preset=args.preset,
                slots=args.slots,
                client_kwargs=dict(stats=stats, logger=logger,
                                   flight=flight))
        else:
            def _make_engine():
                return ServeEngine(
                    model, params, num_slots=args.slots,
                    max_queue=args.max_queue or args.requests,
                    eos_id=args.eos_id, tracer=tracer,
                    tenants=tenant_cfgs,
                    prefill_chunk_tokens=args.prefill_chunk_tokens
                    or None,
                    prefix_cache_mb=args.prefix_cache_mb or None,
                    kv_pool_pages=args.kv_pool_pages or None,
                    request_trace_sample=args.request_trace_sample,
                    request_log=logger, stats=stats,
                    draft_model=draft_model,
                    draft_params=draft_params,
                    spec_k=args.spec_k, flight=flight, tp=args.tp,
                    kv_quant=args.kv_quant,
                    weight_quant=args.weight_quant)
            autoscale_backend = EngineFactoryBackend(_make_engine)
        discover = None
        if (args.autoscale_k8s_job is not None
                and args.replica_discovery_dir is not None):
            # Async membership: pods scaled up by the Job patch join
            # when their heartbeat beacon lands in the shared dir.
            discover = heartbeat_discoverer(
                args.replica_discovery_dir,
                client_kwargs=dict(stats=stats, logger=logger,
                                   flight=flight))
        stages = None
        if args.autoscale_brownout is not None:
            stages = default_brownout_stages(tuple(
                s.strip() for s in args.autoscale_brownout.split(",")))
        controller = FleetController(
            gateway, autoscale_backend, slo=slo,
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max,
            interval_s=args.autoscale_interval_s,
            up_cooldown_s=args.autoscale_up_cooldown_s,
            down_cooldown_s=args.autoscale_down_cooldown_s,
            brownout_stages=stages, discover=discover, logger=logger)

    def _fleet_engines():
        # Membership is dynamic under --autoscale: resolve the probe
        # targets per call instead of freezing the startup list.
        if controller is not None:
            return [gateway.replica_engine(rid)
                    for rid in gateway.replica_ids()]
        return status_objs

    # SIGTERM → cooperative drain → exit 0: the k8s eviction handshake.
    # The handler only flips drain mode (stop admitting); the serving
    # loop below keeps stepping until everything held has finished, and
    # /healthz reports {"draining": ..., "drained": ...} so a preStop
    # hook can poll for safe-to-kill.
    drain_requested = False

    def _on_sigterm(signum, frame):
        nonlocal drain_requested
        drain_requested = True
        # Dump the black box at signal receipt — the state the eviction
        # interrupted — before drain mode starts changing it.
        if flight is not None:
            flight.dump("sigterm")
        if coordinator is not None:
            # Coordinator mode: clearing the feed (below) stops new
            # admissions; in-flight requests finish wherever they are —
            # draining the decode fleet here would strand pages exported
            # by still-running prefill workers.
            pass
        elif clients is not None or controller is not None:
            # Remote or elastic fleet: cooperative drain THROUGH the
            # gateway so queued work migrates between replicas instead
            # of dying with this process's view of them (under
            # --autoscale the startup `engines` list is stale anyway).
            for rid in list(gateway.snapshot()["replicas"]):
                gateway.drain_replica(rid)
        else:
            for e in engines:
                e.drain()

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:
        pass              # not the main thread (embedded use): no handler

    if args.replica_server:
        # Engine side of the wire: no local workload — a remote gateway
        # submits over the transport endpoints. Blocks until /shutdown
        # or a SIGTERM-initiated drain finishes (then exits 0: the k8s
        # eviction handshake, proven end-to-end in tests/test_transport).
        import time as _time

        from k8s_distributed_deeplearning_tpu.serve.transport import (
            ReplicaServer)
        engine.replica_id = engine.replica_id or f"r{args.replica_rank}"
        server = ReplicaServer(
            engine, host="0.0.0.0", port=args.metrics_port,
            advertise_host=args.advertise_host, logger=logger,
            heartbeat_dir=args.heartbeat_dir, rank=args.replica_rank,
            role=args.role, flight=flight).start()
        if args.port_file:
            with open(args.port_file, "w") as f:
                f.write(f"{server.port}\n")
        logger.emit("start", role="replica_server", port=server.port,
                    replica=engine.replica_id, preset=args.preset,
                    serve_role=args.role, num_slots=args.slots)
        while not server.shutting_down:
            if drain_requested and server.drained:
                break
            _time.sleep(0.02)
        logger.emit("replica_drained", replica=engine.replica_id)
        logger.emit("serve_summary", num_slots=args.slots,
                    preset=args.preset, replicas=1, **_ran_on(engine),
                    **stats.summary())
        logger.emit("compile", **backend.compile_log().summary())
        server.close()
        logger.close()
        return 0

    exporter = None
    if args.metrics_port is not None:
        from k8s_distributed_deeplearning_tpu.telemetry import bridge
        from k8s_distributed_deeplearning_tpu.telemetry.exporter import (
            MetricsExporter)
        from k8s_distributed_deeplearning_tpu.telemetry.registry import (
            MetricsRegistry)
        registry = MetricsRegistry()
        bridge.serving_collector(registry, stats)
        if engines:
            # Remote mode has no local engines; replica-servers export
            # their own serve_tp (and compile seconds) from their own
            # /metrics.
            bridge.tp_collector(registry, engines)
            bridge.compile_collector(registry)
        if gateway is not None:
            bridge.gateway_collector(registry, gateway)
            if controller is not None:
                bridge.autoscale_collector(registry, controller)
        elif engine is not None and coordinator is None:
            # Per-tenant labeled gauges are per-scheduler; with replicas
            # each engine has its own and the labels would collide (the
            # coordinator and remote modes both fan out over several
            # schedulers, so they skip the per-tenant surface too).
            bridge.sched_collector(registry, engine.queue)
        exporter = MetricsExporter(
            registry, port=args.metrics_port,
            tracer=tracer if args.debug_dir is not None else None,
            profile_dir=args.debug_dir, flight=flight,
            healthz=lambda: _drain_status(_fleet_engines()),
            # Readiness splits from liveness: 503 the moment a drain
            # starts (stop routing here) while /healthz stays 200 (do
            # not restart a draining pod).
            readyz=lambda: {
                "ready": not any(e.draining
                                 for e in _fleet_engines()),
                **_drain_status(_fleet_engines())}).start()
    shared = rng.integers(0, cfg.vocab_size, size=args.shared_prefix_len)
    if engine is not None:
        tenant_ids = engine.queue.tenant_ids()
    elif tenant_cfgs is not None:
        # Remote mode: admission control lives replica-side; the feed
        # only needs the ids to tag requests with.
        tenant_ids = [c.tenant_id for c in tenant_cfgs]
    else:
        tenant_ids = ["default"]
    from collections import deque
    feed = deque()
    tenant_of = {}          # request_id -> tenant, for the SLO feed
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(p_lo, p_hi + 1)))
        prompt = np.concatenate([shared, prompt])
        req = Request(
            prompt=prompt.astype(np.int32),
            max_new_tokens=int(rng.integers(o_lo, o_hi + 1)),
            sampling=sampling, seed=args.seed + i,
            tenant=tenant_ids[i % len(tenant_ids)])
        tenant_of[req.request_id] = req.tenant
        feed.append(req)

    # Drive iteration-by-iteration so completions stream out as they
    # happen — the same loop a network front-end would run. Requests are
    # fed under back-pressure: a tenant whose bounded queue is full sheds
    # (logged) and the front end retries it after the next iteration.
    slo_finished = {}       # tenant -> cumulative {reason: count}
    while feed or front.busy():
        if drain_requested and feed:
            feed.clear()        # draining: the unsubmitted tail is shed
        while feed:
            try:
                front.submit(feed[0])
            except QueueFull:
                logger.emit("sched_shed", tenant=feed[0].tenant,
                            request_id=feed[0].request_id, retried=True)
                break
            feed.popleft()
        for out in front.step():
            logger.emit("serve_request", request_id=out.request_id,
                        prompt_len=out.prompt_len,
                        new_tokens=len(out.tokens),
                        finish_reason=out.finish_reason,
                        cached_prompt_tokens=out.cached_prompt_tokens,
                        queue_ms=round(out.queue_s * 1e3, 3),
                        ttft_ms=(round(out.ttft_s * 1e3, 3)
                                 if out.ttft_s is not None else None),
                        latency_ms=round(out.latency_s * 1e3, 3))
            if controller is not None:
                by = slo_finished.setdefault(
                    tenant_of.get(out.request_id, "default"), {})
                by[out.finish_reason] = by.get(out.finish_reason,
                                               0) + 1
        if controller is not None and not drain_requested:
            # The serving loop IS the scrape cadence: feed cumulative
            # finish counts to the burn windows, then give the control
            # loop its (self-rate-limited) slice.
            slo.observe(finished=slo_finished)
            controller.maybe_round()
    if drain_requested:
        for e in engines:
            logger.emit("replica_drained",
                        replica=e.replica_id if e.replica_id is not None
                        else "r0")
    logger.emit("serve_summary", num_slots=args.slots,
                preset=args.preset, replicas=args.replicas,
                **_ran_on(engine), **stats.summary())
    logger.emit("compile", **backend.compile_log().summary())
    if controller is not None:
        logger.emit("autoscale_summary", **controller.snapshot())
        reap = getattr(autoscale_backend, "reap_all", None)
        if reap is not None:
            reap()               # LocalProcessBackend child teardown
    if args.spec_k:
        summ = stats.summary()
        logger.emit("spec_summary", draft=args.draft_model,
                    spec_k=args.spec_k,
                    spec_steps=summ["spec_steps"],
                    spec_proposed_tokens=summ["spec_proposed_tokens"],
                    spec_accepted_tokens=summ["spec_accepted_tokens"],
                    spec_acceptance_rate=summ["spec_acceptance_rate"],
                    spec_accept_hist=summ["spec_accept_hist"])
    if args.kv_quant or args.weight_quant:
        summ = stats.summary()
        logger.emit("quant_summary", kv_quant=args.kv_quant,
                    weight_quant=args.weight_quant,
                    kv_quant_bytes_saved=summ["kv_quant_bytes_saved"],
                    weight_quant_bytes_saved=summ[
                        "weight_quant_bytes_saved"])
    if tenant_cfgs is not None:
        for e in engines:
            snap = e.queue.snapshot()
            for tid, t in snap["tenants"].items():
                logger.emit("sched_tenant_summary", tenant=tid,
                            replica=e.replica_id, **t)
    logger.close()
    if exporter is not None:
        exporter.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
