"""The ``jamba`` cell's benchmark parts on the CPU: its rehearsal ends
``correct``; its fp8 control, an altered token and a zeroed state do not leave
the logits where they were; the plain reference against an independent
loop-by-position numpy form; ``counts_jamba`` against hand sums and the
program's own parameter tree at the published widths; and each reader the
cell brings on hand-made records — a number where the spans and the trace
carry what it reads, ``None`` where they do not (the parent commit: no such
kernel)."""
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import counts_jamba as C
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_jamba as ref
from benchmarks.harness import spans, weights

CELL = "jamba2-3b.chat-backlog-wide"
CONFIG = "ai21-jamba2-3b"
MAN = M.load_manifest()
SEED = 3_700_000_019
FIXTURE_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                                "jamba", "manifest.json")
NEW_READERS = ("selective_update_roofline", "selective_scan_roofline")


def test_cell_rehearsal_ends_correct(capsys):
    """Through the fixture manifest: the manifest's own entries for the cell
    plus the readers that wait there."""
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                         "--trace", "1", "--rehearsal", "--manifest", FIXTURE_MANIFEST])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["compared"]["logit_gap_max"] == [0.0, 1.0]      # a share of the limits
    assert set(line["metrics_reported"]) >= {"engine_batch_occupancy", "serve_state_peak_bytes",
                                             "setup_trace_lower_s"}
    assert not set(line["metrics_reported"]) & set(NEW_READERS)  # no chip, no kernel time
    assert "metrics" not in line and "device" not in line       # nothing from a CPU
    win = next(json.loads(l) for l in out.out.splitlines() if '"event": "window"' in l)
    assert win["compiles_in_window"] == 0 and win["requests_finished_in_window"] > 0
    assert win["attention_impls"]["decode"] == "xla ssm=xla scan=xla"


def _tiny():
    cell = M.Cell(MAN, CELL)
    cell.apply_rehearsal()
    return cell


def _served_sample(cell):
    from k8s_distributed_deeplearning_tpu.models import generate
    fam = cell.family()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    rng = np.random.default_rng(5)
    sample = []
    for n in (70, 41, 23):
        p = rng.integers(0, cell.config["vocab_size"], size=n).astype(np.int32)
        toks = generate.generate(model, params, jnp.asarray(p)[None], max_new_tokens=12)
        sample.append({"prompt": p, "tokens": np.asarray(toks[0])})
    return fam, sample


def test_the_control_an_altered_token_and_a_zeroed_state_are_seen():
    """At this width rounding cannot flip a served token, so the control and
    the zeroed state are shown on the logits themselves — each moves them far
    further than the program lies from the reference (5e-5: the engine tests) —
    and an altered token fails the limits outright."""
    cell = _tiny()
    fam, sample = _served_sample(cell)
    cfg = cell.config
    prog = fam.reference.score_served(cfg, SEED, sample)
    assert cell.options["limits"]["logit_gap_max"] == 1.0
    assert prog["logit_gap_max"] <= 1.0 and prog["not_reference_best"] == 0
    assert prog["tokens"] == 36 and set(prog["gap_limits"]) == {"all_max", "all_mean"}
    assert prog["logit_gap_max"] == max(prog["gaps"][k] / prog["gap_limits"][k]
                                        for k in prog["gap_limits"])
    seqs = [np.concatenate([s["prompt"], s["tokens"][:-1]]) for s in sample]
    rows = [np.arange(len(q)) for q in seqs]
    exact = fam.reference.forward_logits(cfg, SEED, seqs, rows)
    low = fam.reference.forward_logits(cfg, SEED, seqs, rows, cfg["control_precision"])
    lost = fam.reference.forward_logits(cfg, SEED, seqs, rows, zero_state_at=cfg["zero_state_at"])
    assert max(np.abs(a - b).max() for a, b in zip(exact, low)) > 1e-2
    at = cfg["zero_state_at"]
    assert all(np.abs(a[:at] - b[:at]).max() == 0.0 for a, b in zip(exact, lost))
    assert min(np.abs(a[at:] - b[at:]).max() for a, b in zip(exact, lost)) > 1e-3
    bad = fam.reference.score_served(cfg, SEED, sample, fault="alter")
    assert bad["logit_gap_max"] > 1.0 and bad["gap_shares"]["all_max"] > 1.0


def _numpy_forward(cfg, seed, toks):
    """The equations of ISSUE 37 once more, position by position in numpy
    float64: the scan a loop over tokens, channels and state columns, sharing
    nothing with the reference but the weights' names and how a leaf is
    drawn."""
    f8 = lambda a: np.asarray(a, np.float64)
    dt_ = jnp.dtype(cfg["torch_dtype"])
    leaf = lambda name, shape: f8(ref.leaf(cfg, weights.seed_operand(seed), name, shape, dt_))
    d, eps, t_len = cfg["hidden_size"], cfg["rms_norm_eps"], len(toks)
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda a: a / (1.0 + np.exp(-a))
    softplus = lambda a: np.log1p(np.exp(a))
    emb = leaf("transformer/tok_embed/embedding", (cfg["vocab_size"], d))
    x = emb[np.asarray(toks)]
    e, n, r, k = ref.mamba_sizes(cfg)
    for l in range(cfg["num_hidden_layers"]):
        w = lambda name: leaf(f"transformer/block_{l}/{name}", ref.layer_shapes(cfg, l)[name])
        xh = rms(x, w("attn_norm/scale"))
        if ref.is_attention(cfg, l):
            hd = cfg["head_dim"]
            q = np.einsum("td,dhk->thk", xh, w("attn/q_proj/kernel"))
            kk = np.einsum("td,dhk->thk", xh, w("attn/k_proj/kernel"))[:, 0]
            v = np.einsum("td,dhk->thk", xh, w("attn/v_proj/kernel"))[:, 0]
            o = np.zeros_like(q)
            for t in range(t_len):
                for h in range(q.shape[1]):
                    s = kk[:t + 1] @ q[t, h] / np.sqrt(hd)
                    p = np.exp(s - s.max())
                    o[t, h] = (p / p.sum()) @ v[:t + 1]
            mix = np.einsum("thk,hkd->td", o, w("attn/o_proj/kernel"))
        else:
            uz = xh @ w("attn/in_proj/kernel")
            u, z = uz[:, :e], uz[:, e:]
            taps, cb = w("attn/conv"), w("attn/conv_bias")
            conv = np.zeros_like(u)
            for t in range(t_len):
                for j in range(k):
                    src = t - (k - 1) + j
                    if src >= 0:
                        conv[t] += taps[j] * u[src]
            u = silu(conv + cb)
            rbc = u @ w("attn/x_proj/kernel")
            dr = rms(rbc[:, :r], w("attn/dt_norm/scale"))
            bm = rms(rbc[:, r:r + n], w("attn/b_norm/scale"))
            cm = rms(rbc[:, r + n:], w("attn/c_norm/scale"))
            dt = softplus(dr @ w("attn/dt_proj/kernel") + w("attn/dt_proj/bias"))
            a = -np.exp(w("attn/A_log"))
            dd = w("attn/D")
            h = np.zeros((e, n))
            y = np.zeros((t_len, e))
            for t in range(t_len):
                for c in range(e):
                    for s in range(n):
                        h[c, s] = (np.exp(dt[t, c] * a[c, s]) * h[c, s]
                                   + dt[t, c] * bm[t, s] * u[t, c])
                    y[t, c] = h[c] @ cm[t] + dd[c] * u[t, c]
            mix = (y * silu(z)) @ w("attn/out_proj/kernel")
        x = x + mix
        hh = rms(x, w("mlp_norm/scale"))
        x = x + (silu(hh @ w("mlp/gate_proj/kernel")) * (hh @ w("mlp/up_proj/kernel"))
                 ) @ w("mlp/down_proj/kernel")
    return rms(x, leaf("transformer/final_norm/scale", (d,))) @ emb.T


def test_reference_against_a_loop_by_position_numpy_form():
    cfg = _tiny().config
    toks = np.random.default_rng(8).integers(0, cfg["vocab_size"], size=11)
    got = ref.forward_logits(cfg, SEED, [toks], [np.arange(len(toks))])[0]
    want = _numpy_forward(cfg, SEED, toks)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.abs(want).max() > 0.1


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    text = inspect.getsource(ref)
    assert "k8s_distributed_deeplearning_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan(one_token" in text and 'default_matmul_precision("highest")' in text


def test_counts_against_hand_sums_and_the_programs_tree_at_the_published_widths():
    cfg = M.Cell(MAN, CELL).config
    assert C.layers(cfg) == (26, 2)                         # layers 7 and 21 attend
    mixer = (2560 * 10240 + 5 * 5120 + 5120 * 192 + 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    assert C.mamba_params(cfg) == mixer == 41_241_792
    assert C.attention_params(cfg) == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13_762_560
    assert C.mlp_params(cfg) == 62_914_560
    assert mixer + 62_914_560 + 2 * 2560 == 104_161_472        # one Mamba layer
    assert 13_762_560 + 62_914_560 + 2 * 2560 == 76_682_240      # one attention layer
    total = 26 * 104_161_472 + 2 * 76_682_240 + 65_536 * 2560 + 2560
    assert C.param_count(cfg) == total == 3_029_337_472         # 6.06 GB in bf16
    assert C.state_bytes_per_slot(cfg) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    assert C.kv_bytes_per_token(cfg) == 1_024
    assert C.selective_update_call(cfg, 128) == {"bytes": 128 * 2 * 327_680,
                                                 "flops": 128 * 5 * 81_920}
    scan = C.selective_scan_call(cfg, 512)
    assert scan == {"bytes": 4 * (3 * 512 * 5120 + 2 * 512 * 16 + 2 * 81_920) + 4 * 81_920,
                    "flops": 7 * 512 * 81_920}
    # the program's own tree, from shapes alone, holds exactly that many
    from benchmarks.harness import family_jamba
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    model = PatternLM(*family_jamba.program_config(cfg, 4096))
    tree = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)) == total
    names = weights.named_leaves(tree)
    for l in (0, 7):
        for name, shape in ref.layer_shapes(cfg, l).items():
            assert names[f"transformer/block_{l}/{name}"].shape == shape


def _run(records, device_ops=None, trace=(10.0, 14.0), rehearsal=False):
    tr = spans.BenchTracer()
    tr.records = records
    win = {"tracer": tr, "t_open": 0.0, "t_close": 20.0,
           "trace": {"t0": trace[0], "t1": trace[1]} if trace else None}
    red = None
    if device_ops is not None:
        red = {"device_ops": [[k, v] for k, v in device_ops.items()],
               "device_op_calls": {k: 1 for k in device_ops}, "programs": {}}
    return {"cell": M.Cell(MAN, CELL), "win": win, "trace": red, "rehearsal": rehearsal,
            "device_kind": "TPU v5 lite", "sut": {}, "end_to_end": {}}


SLOT = 9_318_400
DECODE = ("decode", 11.0, 11.02, {"active": 120, "rows": 120, "context_tokens": 110_000,
                                  "state_rows": 120, "state_bytes_moved": 2 * 120 * SLOT})
CHUNK = ("prefill", 11.1, 11.15, {"chunk": 512, "tokens": 512, "start": 512,
                                  "state_from": "carried"})
FINAL = ("prefill", 11.2, 11.25, {"bucket": 512, "tokens": 200, "start": 0,
                                  "state_from": "zero"})
# what the parent's program writes for another model: no state fields, no such kernel
OLD = [("decode", 11.0, 11.02, {"active": 32, "rows": 32, "context_tokens": 9000}),
       ("prefill", 11.1, 11.15, {"chunk": 512, "tokens": 512, "start": 0})]


def test_selective_update_roofline_reads_the_rows_the_steps_advanced():
    read = M.load_reader("selective_update_roofline")
    ops = {"jit__decode_program/selective_update:f32[128,16,5120]": 0.006,
           "jit__decode_program/fusion": 0.5, "jit__chunk_program/selective_scan": 0.5}
    second = ("decode", 12.0, 12.02, dict(DECODE[3], state_rows=128))
    got = read(_run([DECODE, second, CHUNK], ops))
    least = 26 * (120 + 128) * 2 * 327_680 / 819e9             # 26 layers, the state twice
    assert got == pytest.approx(100 * least / 0.006) and 50 < got < 100
    assert read(_run([DECODE], {"jit__decode_program/fusion": 0.004})) is None   # no kernel
    assert read(_run(OLD, ops)) is None                         # no state_rows field
    assert read(_run([DECODE], None)) is None                   # no device trace
    assert read(_run([DECODE], ops, rehearsal=True)) is None
    assert read(_run([DECODE], ops, trace=None)) is None
    # the arena staged through VMEM by copies of the program's own (the first
    # trace on the chip): the kernel's time holds none of the HBM traffic
    staged = dict(ops, **{"jit__decode_program/copy-done:f32[128,16,5120]": 0.005})
    assert read(_run([DECODE, second], staged)) is None


def test_selective_scan_roofline_counts_the_width_each_chunk_walks():
    read = M.load_reader("selective_scan_roofline")
    ops = {"jit__chunk_program/selective_scan:f32[1,512,5120]": 0.004,
           "jit__final_chunk_program/selective_scan:f32[1,512,5120]": 0.004,
           "jit__decode_program/selective_update": 0.5}
    got = read(_run([DECODE, CHUNK, FINAL], ops))
    c = C.selective_scan_call(M.Cell(MAN, CELL).config, 1024)   # the final chunk's bucket too
    least = max(c["bytes"] / 819e9, c["flops"] / 197e12)
    assert got == pytest.approx(100 * 26 * least / 0.008) and 0 < got < 100
    assert read(_run([DECODE, CHUNK], {"jit__chunk_program/fusion": 0.004})) is None
    assert read(_run([DECODE], ops)) is None                    # no chunk in the stretch
    assert read(_run([CHUNK], None)) is None
    assert read(_run([CHUNK], ops, rehearsal=True)) is None


@pytest.mark.parametrize("metric, listed", [
    ("serve_out_tokens_per_s", True), ("itl_p95_ms", True),
    ("decode_step_device_ms", True), ("engine_batch_occupancy", True),
    ("setup_trace_lower_s", True),
    # a dense GQA count that multiplies by every layer; name matches on `attn`
    ("serve_mfu", False), ("paged_attn_roofline", False), ("engine_host_ms_per_step", False),
    ("kv_pool_peak_fill", False), ("moe_gmm_roofline", False)])
def test_the_cell_is_listed_where_a_reader_reads_it_rightly(metric, listed):
    m = next(x for x in MAN["per_layer"] + MAN["end_to_end"] if x["name"] == metric)
    assert (CELL in m["workloads"]) == listed


def test_the_new_readers_wait_in_a_fixture_manifest_for_a_benchmark_pr():
    """As PRs 31 and 33's: ``test_perfbench_flash_roofline.py:75`` holds
    ``flash_attn_roofline`` to the LAST place of ``per_layer`` and a new entry
    may only be appended, so this configuration's readers are files beside the
    others, listed in ``fixtures/jamba/manifest.json`` — the manifest's own
    entries for the cell plus theirs — which ``run.py --manifest`` takes."""
    assert not {m["name"] for m in MAN["per_layer"]} & set(NEW_READERS)
    assert MAN["per_layer"][-1]["name"] == "flash_attn_roofline"
    fx = M.load_manifest(FIXTURE_MANIFEST)
    cell = M.Cell(fx, CELL)
    assert cell.config == M.Cell(MAN, CELL).config and cell.options == M.Cell(MAN, CELL).options
    assert fx["configs"] == [c for c in MAN["configs"] if c["name"] == CONFIG]
    assert fx["workloads"] == [w for w in MAN["workloads"] if w["name"] == CELL]
    strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"} for m in ms]
    assert strip(fx["end_to_end"]) == strip(M.Cell(MAN, CELL).end_to_end())
    names = [m["name"] for m in cell.per_layer()]
    assert names == ([m["name"] for m in M.Cell(MAN, CELL).per_layer()] + list(NEW_READERS)
                     + ["serve_state_peak_bytes"])
    for m in fx["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms" and callable(
            M.load_reader(m["name"]))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"] == "%") == ("roofline" in m["name"])
    assert {m["layer"] for m in fx["per_layer"]} <= {m["layer"] for m in MAN["per_layer"]}


def test_the_configuration_file_holds_the_catalogs_config_uncut():
    cfg = M.Cell(MAN, CELL).config
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20,
        "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["source_values"] == {}
    assert cfg["family"] == "jamba" and cfg["head_dim"] == 128
    assert cfg["ssm_state_dtype"] == "float32" and cfg["torch_dtype"] == "bfloat16"
    assert {"layer_order", "ratio", "feed_forward", "position", "head_dim",
            "mamba_initialisation"} <= set(cfg["assumed"])
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert all(len(e["why"]) <= 200 for e in MAN["configs"] + MAN["workloads"])
    eng = M.Cell(MAN, CELL).options["engine"]
    assert eng == {"max_seq_len": 4096, "num_slots": 128, "max_queue": 4096, "min_bucket": 512,
                   "prefill_chunk_tokens": 512, "prefix_cache_mb": 0, "kv_pool_pages": 16384,
                   "page_tokens": 32}
    # the accepted traffic file, as the lfm2 and nemotron cells run it
    assert M.Cell(MAN, CELL).traffic == M.Cell(MAN, "lfm2-8b-a1b-d14.chat-backlog-wide").traffic
    assert M.Cell(MAN, CELL).chips == 1
