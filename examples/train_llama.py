"""Distributed Llama-family LM pretraining — the flagship training script.

Single-program, rank-parameterized (same contract as ``train_mnist.py`` and
the reference's per-rank scripts, ``deploy_stack.sh:64-84``): every host runs
this file; the K8s-injected env forms the world; the mesh axes requested on
the CLI are laid over the global device set and XLA derives the collectives.

Parallelism is fully flag-driven — any mix of:
  --dp N     data parallelism               (gradient all-reduce)
  --fsdp N   ZeRO-3-style param sharding    (all-gather + reduce-scatter)
  --tp N     Megatron-style tensor parallel (sharded matmuls + psum)
  --sp N     sequence/context parallel      (ring attention over ICI)

Examples:
  # single host, 8-chip FSDP x TP:
  python examples/train_llama.py --preset small --fsdp 4 --tp 2
  # CPU CI (8 virtual devices), tiny model, ring attention:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_llama.py --preset tiny --dp 2 --sp 4 \
          --attention ring --num-steps 20
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from k8s_distributed_deeplearning_tpu import backend, config as cfg
from k8s_distributed_deeplearning_tpu.models import llama
from k8s_distributed_deeplearning_tpu.parallel import (
    context_parallel as cp,
    distributed,
    mesh as mesh_lib,
    sharding,
)
from k8s_distributed_deeplearning_tpu.train import (
    Checkpointer,
    data as data_lib,
    loop,
    optim,
    prefetch,
)
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
from k8s_distributed_deeplearning_tpu.train.preemption import PreemptionHandler
from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger
from k8s_distributed_deeplearning_tpu.utils.profiling import StepProfiler

PRESETS = {
    # name: overrides on llama.config_tiny / config_llama3_8b
    "tiny": dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 mlp_dim=128, max_seq_len=512),
    # small: remat 'dots' + unrolled layers measured fastest at S=2048
    # (round 3, on chip: 108.8k tok/s/chip vs 85.2k scanned/no-remat).
    # Unrolling changes the checkpoint tree (block_0..block_11 instead of
    # the scanned blocks/[L,...]) — resume pre-round-3 runs with
    # --scan-layers, and --pp forces the scanned layout back on.
    "small": dict(vocab_size=32000, dim=768, n_layers=12, n_heads=12,
                  n_kv_heads=4, mlp_dim=2048, max_seq_len=2048, remat=True,
                  scan_layers=False),
    "1b": dict(vocab_size=32000, dim=2048, n_layers=16, n_heads=32,
               n_kv_heads=8, mlp_dim=8192, max_seq_len=4096, remat=True),
    "8b": dict(),          # the true Llama-3 8B architecture numbers
}


def build_config(args) -> "llama.TransformerConfig":
    overrides = dict(PRESETS[args.preset])
    if args.preset == "8b":
        base = llama.config_llama3_8b
    else:
        base = llama.config_tiny
    if args.seq_len:
        overrides["max_seq_len"] = max(args.seq_len,
                                       overrides.get("max_seq_len", 0))
    overrides["dtype"] = (jnp.bfloat16 if args.dtype == "bfloat16"
                          else jnp.float32)
    overrides["remat"] = args.remat or overrides.get("remat", False)
    if getattr(args, "scan_layers", None) is not None:
        overrides["scan_layers"] = args.scan_layers
    if getattr(args, "pp", 1) > 1:
        # The pipeline engine slices the scan-stacked [L, ...] layout.
        overrides["scan_layers"] = True
    if args.attention in ("flash", "xla"):
        overrides["attention_impl"] = args.attention
    return base(**overrides)


def main(argv: list[str] | None = None) -> dict:
    parser = argparse.ArgumentParser(description=__doc__)
    cfg.add_train_flags(parser)
    parser.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    parser.add_argument("--seq-len", type=int, default=None,
                        help="training sequence length (default: preset's)")
    parser.add_argument("--dp", type=int, default=-1, help="data axis (-1: rest)")
    parser.add_argument("--fsdp", type=int, default=1)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence-parallel axis (ring attention)")
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline-parallel stages (GPipe over the "
                        "scan-stacked layers; composes with --dp only)")
    parser.add_argument("--pp-microbatches", type=int, default=None,
                        help="pipeline microbatches (default: --pp)")
    parser.add_argument("--pp-schedule", choices=["gpipe", "1f1b", "interleaved"],
                        default="gpipe",
                        help="pipeline schedule: gpipe = O(M) activation "
                        "memory, bubble (P-1)/(M+P-1); 1f1b = same bubble "
                        "at O(P) memory (invalid slots cond-skipped — "
                        "measured 6x less temp at M=16, P=4); interleaved "
                        "= virtual-stage 1f1b, bubble (P-1)/(MV+P-1) — "
                        "fastest AND smallest (round-4 timings)")
    parser.add_argument("--pp-virtual", type=int, default=2,
                        help="virtual chunks per stage for "
                        "--pp-schedule interleaved")
    parser.add_argument("--moe-experts", type=int, default=0,
                        help="swap every MLP for a mixture-of-experts layer "
                        "with N experts (models/moe.py MoELM; 0 = dense). "
                        "Composes with --pack/--sp/--fsdp/--tp/--ep/"
                        "--chunked-ce; not with --pp")
    parser.add_argument("--moe-top-k", type=int, default=2)
    parser.add_argument("--moe-capacity-factor", type=float, default=1.25)
    parser.add_argument("--moe-dispatch", default="index",
                        choices=["index", "einsum", "ragged"],
                        help="expert dispatch: capacity index scatter "
                        "(default), dense one-hot einsums, or the DROPLESS "
                        "grouped-GEMM path (ops/pallas_gmm — no capacity, "
                        "no overflow drops; batch-shard_map'd over "
                        "data/fsdp, but the expert axis stays index-only: "
                        "not with --ep > 1)")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel mesh axis (shards the "
                        "'expert' logical axis of MoE weights/buffers)")
    parser.add_argument("--attention",
                        choices=["auto", "xla", "flash", "ring", "ulysses"],
                        default="auto",
                        help="auto = measured crossover: Pallas flash on TPU "
                        "at S>=1024, XLA otherwise (round-4 sweep)")
    parser.add_argument("--remat", action="store_true",
                        help="checkpoint each block (long-context memory lever)")
    parser.add_argument("--scan-layers", dest="scan_layers",
                        action="store_true", default=None,
                        help="stack layers via nn.scan (params under "
                        "blocks/[L,...]); default: preset's choice. NOTE: "
                        "scanned and unrolled layouts have different "
                        "checkpoint trees — keep the setting a run started "
                        "with when resuming")
    parser.add_argument("--no-scan-layers", dest="scan_layers",
                        action="store_false",
                        help="unroll layers (block_0..block_{L-1} params; "
                        "measured faster at S=2048, round 3)")
    parser.add_argument("--data-path", type=str, default=None,
                        help="byte-level corpus file; default synthetic tokens")
    parser.add_argument("--pack", action="store_true",
                        help="pack variable-length documents into fixed rows "
                        "with segment ids (segment-masked attention, "
                        "per-document RoPE, padding out of the loss)")
    parser.add_argument("--pack-sep-id", type=int, default=None,
                        help="document separator token id for --pack "
                        "(default: seeded pseudo-document splits)")
    parser.add_argument("--chunked-ce", dest="chunked_ce", action="store_true",
                        default=None,
                        help="chunked LM-head loss (never materializes "
                        "[B,S,V] logits); default: on for --preset 8b")
    parser.add_argument("--no-chunked-ce", dest="chunked_ce",
                        action="store_false")
    parser.add_argument("--optimizer", choices=optim.OPTIMIZERS,
                        default="adamw")
    parser.add_argument("--moment-dtype", choices=["float32", "bfloat16"],
                        default=None,
                        help="first-moment storage dtype: adam/adamw mu, "
                        "lion's moment, sgd's momentum trace (bfloat16 "
                        "halves its HBM footprint and update-step "
                        "traffic; adam's second moment stays f32)")
    parser.add_argument("--schedule", choices=optim.SCHEDULES,
                        default="constant")
    parser.add_argument("--warmup-steps", type=int, default=0)
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="capture a jax.profiler trace of steps 10..15")
    parser.set_defaults(grad_clip=1.0)   # LM pretraining hygiene default
    args = parser.parse_args(argv)
    conf = cfg.train_config_from_args(args)
    backend.use_compile_cache()

    distributed.initialize_from_env()
    topo = mesh_lib.topology()
    use_pp = args.pp > 1
    use_cp = args.sp > 1 or args.attention in ("ring", "ulysses")
    if use_pp and (args.fsdp > 1 or args.tp > 1 or use_cp):
        raise ValueError(
            "--pp composes with --dp only (GPipe engine); drop "
            "--fsdp/--tp/--sp/ring/ulysses or use the sharded trainer")
    if use_pp:
        dp = args.dp if args.dp > 0 else len(jax.devices()) // args.pp
        mesh = mesh_lib.make_mesh({"pipeline": args.pp, "data": dp})
    else:
        # Context-parallel shard_map specs name the "sequence" axis, so keep
        # it in the mesh even at size 1 when CP attention is requested.
        mesh = mesh_lib.make_mesh(cfg.MeshConfig(
            data=args.dp, fsdp=args.fsdp, tensor=args.tp,
            sequence=args.sp, expert=args.ep).to_axis_sizes(
                keep=("sequence",) if use_cp else ()))

    model_cfg = build_config(args)
    seq_len = args.seq_len or min(model_cfg.max_seq_len, 512)
    moe_cfg = None
    if args.moe_experts:
        if use_pp:
            raise ValueError(
                "--moe-experts does not compose with --pp: the pipeline "
                "block adapter builds dense Blocks, so it would silently "
                "train a dense model — use the sharded-trainer axes "
                "(--dp/--fsdp/--tp/--sp) for MoE")
        from k8s_distributed_deeplearning_tpu.models import moe as moe_lib
        if args.moe_dispatch == "ragged" and args.ep > 1:
            raise ValueError(
                "--moe-dispatch ragged is single-shard expert compute "
                "(XLA cannot partition through the grouped-GEMM kernel); "
                "use --moe-dispatch index with --ep")
        moe_cfg = moe_lib.MoEConfig(
            num_experts=args.moe_experts, top_k=args.moe_top_k,
            capacity_factor=args.moe_capacity_factor,
            dispatch=args.moe_dispatch)
        # shard_mesh: the ragged grouped-GEMM shard_maps over the batch
        # axes (a Pallas call has no GSPMD rule — unwrapped it would run
        # replicated on every device); no-op for the other dispatches.
        model = moe_lib.MoELM(model_cfg, moe_cfg, shard_mesh=(
            mesh if args.moe_dispatch == "ragged" else None))
    else:
        model = llama.LlamaLM(model_cfg)

    attention_fn = None
    cp_impl = cp_inner = None
    if use_cp:
        # Resolution when sequence parallelism is on: explicit ring/ulysses
        # keep the XLA inner; --attention flash composes Ulysses with the
        # Pallas kernel when the head count divides the sequence axis, else
        # ring (itself blockwise online-softmax, i.e. flash-structured).
        # The resolved scheme lands in the start event so substitutions are
        # visible.
        sp_size = mesh.shape["sequence"]
        if args.attention in ("ring", "ulysses"):
            cp_impl, cp_inner = args.attention, "xla"
        elif args.attention == "flash" and model_cfg.n_heads % sp_size == 0:
            cp_impl, cp_inner = "ulysses", "flash"
        else:
            cp_impl, cp_inner = "ring", "xla"
        attention_fn = cp.make_context_parallel_attention(
            mesh, cp_impl, inner_impl=cp_inner)
    elif not use_pp and any(mesh.shape.get(a, 1) > 1
                            for a in ("data", "fsdp", "tensor")):
        # Multi-way GSPMD mesh without CP: shard-map the attention op
        # over batch (data x fsdp) and heads (tensor). Without this the
        # Pallas flash call has no partitioning rule and GSPMD REPLICATES
        # attention on every chip (ops.attention.make_mesh_attention_fn).
        from k8s_distributed_deeplearning_tpu.ops import attention as att_ops
        attention_fn = att_ops.make_mesh_attention_fn(
            mesh, impl=model_cfg.attention_impl)

    # Chunked CE defaults on for the 8B preset, where the [B,S,V] logits
    # tensor (V=128256) is the single largest activation in the step —
    # MoE included (moe.loss_fn composes since round 5; an 8B-vocab MoE
    # run has the same logits hazard). 32k-vocab presets gain nothing
    # from it (round-5 timings), so their default stays off.
    chunked = (args.chunked_ce if args.chunked_ce is not None
               else args.preset == "8b")

    # LM convention: --num-steps is the optimizer-step budget as given (the
    # reference's steps//world rule, tensorflow_mnist.py:146, presumes a fixed
    # total-sample budget — for LM runs the step budget is the contract).
    num_steps = conf.num_steps
    optimizer = optim.make_optimizer(
        args.optimizer,
        optim.make_schedule(args.schedule, conf.lr, num_steps,
                            args.warmup_steps),
        grad_clip=args.grad_clip or None,
        moment_dtype=args.moment_dtype)
    init = lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"]
    if use_pp:
        from k8s_distributed_deeplearning_tpu.parallel import pipeline_lm
        trainer = pipeline_lm.PipelineTrainer(
            model, optimizer, mesh,
            num_microbatches=args.pp_microbatches or args.pp,
            chunked_ce=chunked, schedule=args.pp_schedule,
            num_virtual=args.pp_virtual)
        loss = trainer.loss_fn
        state = trainer.init(init, jax.random.key(conf.seed))
        step_fn = trainer.make_step(donate=True)
        if conf.grad_accum > 1:
            raise ValueError("--grad-accum with --pp: raise --pp-microbatches "
                             "instead (the pipeline already microbatches)")
    else:
        if moe_cfg is not None:
            def loss(params, batch, rng):
                # moe_lib bound where moe_cfg was built (same function).
                return moe_lib.loss_fn(model, moe_cfg, params, batch, rng,
                                       attention_fn=attention_fn,
                                       chunked=chunked)
        else:
            def loss(params, batch, rng):
                return llama.loss_fn(model, params, batch, rng,
                                     attention_fn=attention_fn,
                                     chunked=chunked)
        trainer = sharding.ShardedTrainer(loss, optimizer, mesh)
        state = trainer.init(init, jax.random.key(conf.seed))
        step_fn = trainer.make_step(donate=True, microbatches=conf.grad_accum)

    # Per-host batch: the global batch split across processes (each host
    # contributes its local slice; shard_batch assembles the global array).
    # Checked BEFORE metrics/checkpointer construction so a config error
    # can't leak resources; never silently resized.
    global_batch = conf.batch_size
    if global_batch % topo.num_processes:
        raise ValueError(
            f"--batch-size {global_batch} (global) must divide evenly across "
            f"{topo.num_processes} processes")
    per_host = global_batch // topo.num_processes

    streaming = bool(args.data_path) and os.path.isdir(args.data_path)
    if streaming:
        # Directory of pre-tokenized shards: the large-corpus streaming
        # path (memory-mapped, resident = touched pages). Packing needs
        # whole documents in memory — point --pack at a file instead.
        if args.pack:
            raise ValueError(
                "--pack needs an in-memory corpus (document packing is a "
                "whole-corpus host pass): pass --data-path FILE, not a "
                "shard directory")
        probe = data_lib.TokenShardBatcher(
            args.data_path, per_host, seq_len, seed=conf.seed,
            vocab_size=model_cfg.vocab_size)
        n_eval = max(2 * (seq_len + 1),
                     min(probe.final_shard_tokens // 10, 64 * seq_len))
        batcher = data_lib.TokenShardBatcher(
            args.data_path, per_host, seq_len, seed=conf.seed,
            process_index=topo.process_index,
            num_processes=topo.num_processes,
            hold_out_tail=n_eval,
            vocab_size=model_cfg.vocab_size)
        eval_tokens = batcher.tail_tokens()
        metrics_extra = {"data": "sharded-streaming",
                         "num_windows": batcher.num_windows}
    else:
        tokens = data_lib.load_tokens(args.data_path,
                                      vocab_size=model_cfg.vocab_size,
                                      seed=conf.seed)
        # Hold out the corpus tail for eval — disjoint from every training
        # epoch (each epoch permutes the SAME training windows, so "future
        # step indices" are not held out).
        n_eval = max(2 * (seq_len + 1), int(0.05 * len(tokens)))
        eval_tokens, tokens = tokens[-n_eval:], tokens[:-n_eval]
        if args.pack:
            docs = data_lib.split_documents(tokens, args.pack_sep_id,
                                            seed=conf.seed)
            batcher = data_lib.PackedTokenBatcher(
                docs, per_host, seq_len, seed=conf.seed,
                process_index=topo.process_index,
                num_processes=topo.num_processes)
            metrics_extra = {"packing_efficiency":
                             round(batcher.packing_efficiency, 4)}
        else:
            batcher = data_lib.TokenBatcher(tokens, per_host, seq_len,
                                            seed=conf.seed,
                                            process_index=topo.process_index,
                                            num_processes=topo.num_processes)
            metrics_extra = {}

    if conf.keep_best and not conf.eval_every:
        raise ValueError("--keep-best needs --eval-every N (best-by-metric "
                         "retention tracks the held-out eval loss)")

    # Held-out eval (in-training cadence AND the final eval share this):
    # mean loss over up to 4 windows of the reserved corpus tail, sharded
    # across processes like training data.
    _eval_loss_cache: list = []

    def make_eval_loss_fn():
        # Built once, shared by the --eval-every cadence and the final
        # eval (a second jit of the same step would recompile).
        if _eval_loss_cache:
            return _eval_loss_cache[0]
        windows_per_proc = (((len(eval_tokens) - 1) // seq_len)
                            // topo.num_processes)
        eval_b = min(per_host, windows_per_proc)
        if use_pp:
            # The pipeline schedule needs the batch divisible into its
            # microbatches; round the eval batch down.
            m = args.pp_microbatches or args.pp
            eval_b = (eval_b // m) * m
        if eval_b < 1:
            _eval_loss_cache.append(None)
            return None
        eval_batcher = data_lib.TokenBatcher(
            eval_tokens, eval_b, seq_len,
            seed=conf.seed, process_index=topo.process_index,
            num_processes=topo.num_processes)
        eval_step = jax.jit(lambda p, b: loss(p, b, None)[0])
        n_batches = min(4, eval_batcher.batches_per_epoch)

        def eval_loss(state):
            vals = [float(eval_step(state.params, trainer.shard_batch(
                eval_batcher.batch_at(s)))) for s in range(n_batches)]
            return sum(vals) / len(vals)

        _eval_loss_cache.append(eval_loss)
        return eval_loss

    metrics = MetricsLogger(enabled=distributed.is_primary(), job="llama")
    ckpt = Checkpointer(conf.checkpoint_dir,
                        max_to_keep=conf.max_checkpoints_to_keep,
                        keep_best_metric="loss" if conf.keep_best else None,
                        best_mode="min",
                        async_save=conf.async_checkpoint,
                        # Canonical on-disk layout: checkpoints written
                        # under one pipeline schedule restore under any
                        # other (the interleaved trainer's chunk-arranged
                        # blocks reshape to/from the natural [L, ...] form).
                        portable_transforms=getattr(
                            trainer, "portable_transforms", lambda: None)())
    preemption = PreemptionHandler.install()
    profiler = (StepProfiler(args.profile_dir, start_step=10, num_steps=5,
                             enabled=distributed.is_primary())
                if args.profile_dir else None)

    n_params = sum(x.size for x in jax.tree.leaves(sharding.unbox(state.params)))
    metrics.emit("start", world_size=topo.world_size, num_steps=num_steps,
                 preset=args.preset, params=n_params, seq_len=seq_len,
                 mesh={k: int(v) for k, v in
                       zip(mesh.axis_names, mesh.devices.shape)},
                 attention=args.attention,
                 **({"cp_impl": cp_impl, "cp_inner": cp_inner}
                    if cp_impl else {}),
                 **({"moe": {"experts": moe_cfg.num_experts,
                             "top_k": moe_cfg.top_k,
                             "capacity_factor": moe_cfg.capacity_factor}}
                    if moe_cfg is not None else {}),
                 **metrics_extra,
                 **topo.device_fields())

    prefetchers: list = []

    def global_batches(start_step: int):
        return prefetch.maybe(batcher.iter_from(start_step),
                              trainer.shard_batch, args.prefetch, prefetchers)

    if moe_cfg is not None:
        flops_per_example = moe_lib.flops_per_token(
            model_cfg, moe_cfg, seq_len=seq_len) * seq_len
    else:
        flops_per_example = llama.flops_per_token(model_cfg,
                                                  seq_len=seq_len) * seq_len
    eval_fn = None
    if conf.eval_every:
        eval_loss = make_eval_loss_fn()
        if eval_loss is None:
            raise ValueError("--eval-every: held-out set smaller than one "
                             "eval batch (or one pipeline microbatch "
                             "group) per process — lower --seq-len or grow "
                             "the corpus")
        import math

        def eval_fn(state):
            ev = eval_loss(state)
            return {"loss": ev, "perplexity": math.exp(ev)}

    try:
        state = loop.fit(
            step_fn, state, global_batches, num_steps,
            jax.random.key(conf.seed),
            metrics=metrics, checkpointer=ckpt,
            checkpoint_every=conf.checkpoint_every, log_every=conf.log_every,
            global_batch_size=global_batch,
            flops_per_example=flops_per_example,
            peak_flops=mesh_lib.peak_flops_per_device(args.dtype),
            preemption=preemption, profiler=profiler,
            # A record-only tracer (no JSONL line, no ring): inside the
            # profiler's window its spans are written into the trace as
            # program:<name> host annotations beside the device's timeline.
            tracer=Tracer() if profiler is not None else None,
            eval_every=conf.eval_every, eval_fn=eval_fn,
        )

        result: dict = {"num_steps": int(jax.device_get(state.step)),
                        "world_size": topo.world_size, "params": int(n_params)}
        # Skip eval when preempted: the grace period is for checkpointing,
        # and an "eval" event would make an evicted run look completed.
        if conf.eval_final and not preemption.triggered:
            # Held-out perplexity on the reserved corpus tail (same
            # machinery as the --eval-every cadence).
            eval_loss = make_eval_loss_fn()
            if eval_loss is None:
                metrics.emit("eval_skipped",
                             reason="held-out set smaller than one window "
                             "per process")
            else:
                import math
                ev = eval_loss(state)
                metrics.emit("eval", loss=ev, perplexity=math.exp(ev))
                result["eval_loss"] = ev
    finally:
        preemption.uninstall()
        prefetch.close_all(prefetchers)
        ckpt.close()
        metrics.close()
    return result


if __name__ == "__main__":
    main(sys.argv[1:])
