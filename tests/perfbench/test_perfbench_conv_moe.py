"""The ``conv-moe`` cell's benchmark parts on the CPU: its rehearsal ends
``correct`` and its fp8 control and an altered token do not; the plain
reference against an independent loop-by-position numpy form;
``counts_conv_moe`` against hand sums at the published widths; and each
reader the cell lists on hand-made records — a number where the spans and
the trace carry what it reads, ``None`` where they do not (a program without
the spans: the parent commit)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import counts_conv_moe as C
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_conv_moe as ref
from benchmarks.harness import spans, weights

CELL = "lfm2-8b-a1b-d14.chat-backlog-wide"
MAN = M.load_manifest()
SEED = 3_100_000_019


FIXTURE_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                                "conv_moe", "manifest.json")


def test_cell_rehearsal_ends_correct(capsys):
    """Through the fixture manifest: the manifest's own entries for the cell
    plus the four readers that wait there (see the test of that name)."""
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                         "--trace", "1", "--rehearsal", "--manifest", FIXTURE_MANIFEST])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["compared"]["logit_gap_max"] == [0.0, 1.0]      # a share of the limits
    assert set(line["metrics_reported"]) >= {
        "engine_batch_occupancy", "moe_expert_load_max_over_mean", "serve_state_peak_bytes",
        "setup_trace_lower_s"}
    assert "metrics" not in line and "device" not in line       # nothing from a CPU
    win = next(json.loads(l) for l in out.out.splitlines() if '"event": "window"' in l)
    assert win["compiles_in_window"] == 0 and win["requests_finished_in_window"] > 0
    assert win["attention_impls"]["decode"].endswith("experts=dense")


def _tiny():
    cell = M.Cell(MAN, CELL)
    cell.apply_rehearsal()
    return cell


def _served_sample(cell):
    """Prompts and the program's own greedy tokens at the tiny size."""
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


def test_fp8_control_and_an_altered_token_are_not_correct():
    """At this width the tied head makes the input token its own best
    continuation by a wide margin (``hidden_size x 0.02^2`` over a residual
    stream the embedding dominates: at the published width the feed-forwards
    do), so rounding cannot flip a served token here; the control is shown
    on the logits themselves — fp8 moves them a thousand times further than
    the program lies from the reference — and an altered token fails."""
    cell = _tiny()
    fam, sample = _served_sample(cell)
    cfg = cell.config
    prog = fam.reference.score_served(cfg, SEED, sample)
    limit = cell.options["limits"]["logit_gap_max"]
    assert limit == 1.0                          # the largest share of the three limits
    assert prog["logit_gap_max"] <= limit and prog["not_reference_best"] == 0
    assert prog["router_flips"] == 0            # float32 served type: nothing is rounded
    assert prog["tokens"] == 36 and 0 < prog["tokens_decided"] <= 36
    assert set(prog["gap_limits"]) == {"all_max", "all_mean"}       # those the file names
    assert prog["logit_gap_max"] == max(prog["gaps"][k] / prog["gap_limits"][k]
                                        for k in prog["gap_limits"])
    three = fam.reference.score_served(
        dict(cfg, served_gap_limits={"all_max": 1.0, "all_mean": 1.0, "decided_max": 1.0}),
        SEED, sample)
    assert set(three["gap_shares"]) == {"all_max", "all_mean", "decided_max"}
    seqs = [np.concatenate([s["prompt"], s["tokens"][:-1]]) for s in sample]
    rows = [np.arange(len(q)) for q in seqs]
    exact, _, _ = fam.reference.forward_logits(cfg, SEED, seqs, rows)
    low, _, _ = fam.reference.forward_logits(cfg, SEED, seqs, rows, cfg["control_precision"])
    moved = max(np.abs(a - b).max() for a, b in zip(exact, low))
    assert moved > 1e-2                          # the engine tests hold the program to 5e-5
    control = fam.reference.score_served(cfg, SEED, sample, precision=cfg["control_precision"])
    assert set(control["gaps"]) == {"all_max", "all_mean", "decided_max"}
    bad = fam.reference.score_served(cfg, SEED, sample, fault="alter")
    assert bad["logit_gap_max"] > limit and bad["gap_shares"]["all_max"] > limit


def _numpy_forward(cfg, seed, toks):
    """The equations of ISSUE 31 once more, position by position in numpy
    float64: every sum an explicit loop over the positions, heads and
    experts it runs over, sharing nothing with the reference but the
    weights' names."""
    f8 = lambda a: np.asarray(a, np.float64)
    dt = jnp.dtype(cfg["torch_dtype"])
    leaf = lambda name, shape: f8(weights.leaf(weights.seed_operand(seed), name, shape, dt))
    d, eps, t_len = cfg["hidden_size"], cfg["norm_eps"], len(toks)
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda a: a / (1.0 + np.exp(-a))
    emb = leaf("transformer/tok_embed/embedding", (cfg["vocab_size"], d))
    x = emb[np.asarray(toks)]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    for l, kind in enumerate(cfg["layer_types"][:cfg["num_hidden_layers"]]):
        pre = f"transformer/block_{l}/"
        xh = rms(x, leaf(pre + "attn_norm/scale", (d,)))
        mix = np.zeros_like(x)
        if kind == "conv":
            w_in = leaf(pre + "attn/in_proj/kernel", (d, 3 * d))
            taps = leaf(pre + "attn/conv", (cfg["conv_L_cache"], d))
            w_out = leaf(pre + "attn/out_proj/kernel", (d, d))
            bcu = xh @ w_in
            z = bcu[:, :d] * bcu[:, 2 * d:]
            width = cfg["conv_L_cache"]
            for t in range(t_len):
                v = np.zeros(d)
                for j in range(width):
                    src = t - (width - 1) + j
                    if src >= 0:                        # zeros before the sequence
                        v += taps[j] * z[src]
                mix[t] = (bcu[t, d:2 * d] * v) @ w_out
        else:
            wq = leaf(pre + "attn/q_proj/kernel", (d, h, hd))
            wk = leaf(pre + "attn/k_proj/kernel", (d, kv, hd))
            wv = leaf(pre + "attn/v_proj/kernel", (d, kv, hd))
            wo = leaf(pre + "attn/o_proj/kernel", (h, hd, d))
            gq, gk = leaf(pre + "attn/q_norm/scale", (hd,)), leaf(pre + "attn/k_norm/scale", (hd,))
            inv = float(cfg["rope_theta"]) ** (-np.arange(0, hd, 2) / hd)

            def rope(vec, pos):
                out = np.empty_like(vec)
                c, s = np.cos(pos * inv), np.sin(pos * inv)
                out[0::2] = vec[0::2] * c - vec[1::2] * s
                out[1::2] = vec[1::2] * c + vec[0::2] * s
                return out
            q = np.einsum("td,dhk->thk", xh, wq)
            k = np.einsum("td,dhk->thk", xh, wk)
            v = np.einsum("td,dhk->thk", xh, wv)
            for t in range(t_len):
                for i in range(h):
                    q[t, i] = rope(rms(q[t, i], gq), t)
                for i in range(kv):
                    k[t, i] = rope(rms(k[t, i], gk), t)
            for t in range(t_len):
                for i in range(h):
                    g = i // (h // kv)
                    sc = np.array([q[t, i] @ k[u, g] for u in range(t + 1)]) * hd ** -0.5
                    p = np.exp(sc - sc.max())
                    p /= p.sum()
                    mix[t] += sum(p[u] * v[u, g] for u in range(t + 1)) @ wo[i]
        x = x + mix
        xh = rms(x, leaf(pre + "mlp_norm/scale", (d,)))
        if l < cfg["num_dense_layers"]:
            f = cfg["intermediate_size"]
            w1, w3, w2 = (leaf(pre + "mlp/gate_proj/kernel", (d, f)),
                          leaf(pre + "mlp/up_proj/kernel", (d, f)),
                          leaf(pre + "mlp/down_proj/kernel", (f, d)))
            x = x + (silu(xh @ w1) * (xh @ w3)) @ w2
            continue
        f, e, top = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_experts_per_tok"]
        wr, b = leaf(pre + "mlp/router", (d, e)), leaf(pre + "mlp/router_bias", (e,))
        w1, w3, w2 = (leaf(pre + "mlp/w_gate", (e, d, f)), leaf(pre + "mlp/w_up", (e, d, f)),
                      leaf(pre + "mlp/w_down", (e, f, d)))
        for t in range(t_len):
            s = 1.0 / (1.0 + np.exp(-(xh[t] @ wr)))
            chosen = np.argsort(-(s + b), kind="stable")[:top]
            total = s[chosen].sum() + 1e-6
            for c in chosen:
                g = cfg["routed_scaling_factor"] * s[c] / total
                x[t] = x[t] + g * ((silu(xh[t] @ w1[c]) * (xh[t] @ w3[c])) @ w2[c])
    return rms(x, leaf("transformer/final_norm/scale", (d,))) @ emb.T


def test_reference_against_a_loop_by_position_numpy_form():
    cfg = _tiny().config
    toks = np.random.default_rng(8).integers(0, cfg["vocab_size"], size=29)
    got, margins, _ = ref.forward_logits(cfg, SEED, [toks], [np.arange(len(toks))])
    want = _numpy_forward(cfg, SEED, toks)
    assert np.isfinite(margins[0]).all() and margins[0].min() > 1e-6    # no tie decides
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert np.abs(want).max() > 0.1


def test_the_references_weights_are_weights_leaf_value_for_value():
    """The reference makes a layer's weights with the names' checksums as a
    traced operand (three compiled programs, not fourteen): the same values
    as the harness's one rule, ``weights.leaf``, gives for the full name."""
    cfg = _tiny().config
    dt = jnp.dtype(cfg["torch_dtype"])
    for layer in (0, 2, 5):
        made = ref.make_layer(cfg, SEED, layer)
        assert set(made) == set(ref.layer_shapes(cfg, layer))
        for name, shape in ref.layer_shapes(cfg, layer).items():
            full = f"transformer/block_{layer}/{name}"      # compiled, as the program's are
            want = jax.jit(lambda s: weights.leaf(s, full, shape, dt))(weights.seed_operand(SEED))
            np.testing.assert_array_equal(np.asarray(made[name]), np.asarray(want, np.float32))
    assert abs(float(made["attn_norm/scale"].mean()) - 1.0) < 0.02      # a gain: 1 + noise
    outer = ref.make_outer(cfg, SEED)
    np.testing.assert_array_equal(
        np.asarray(outer["transformer/final_norm/scale"]),
        np.asarray(weights.leaf(weights.seed_operand(SEED), "transformer/final_norm/scale",
                                (cfg["hidden_size"],), dt), np.float32))


def test_counts_against_hand_sums_at_the_published_widths():
    cfg = M.Cell(MAN, CELL).config
    assert C.mixers(cfg) == (11, 3)                     # c c A c c c A c c c A c c c
    expert = 3 * 2048 * 1792
    assert C.expert_params(cfg) == expert == 11_010_048
    assert C.expert_bytes(cfg) == 22_020_096 and C.expert_flops(cfg) == 22_020_096
    conv = 3 * 2048 * 2048 + 3 * 2048 + 2048 * 2048                 # W_in, taps, W_out
    attn = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 2 * 64          # q, o; k, v; two gains
    assert (C.conv_mixer_params(cfg), C.attention_mixer_params(cfg)) == (conv, attn)
    assert (conv, attn) == (16_783_360, 10_485_888)
    router = 2048 * 32 + 32
    total = (11 * conv + 3 * attn + 2 * 3 * 2048 * 7168 + 12 * (32 * expert + router)
             + 2 * 2048 * 14 + 2048 + 65_536 * 2048)
    assert C.param_count(cfg) == total == 4_667_077_376             # 4.67 B: 9.33 GB in bf16
    whole = dict(cfg, num_hidden_layers=24)
    assert C.mixers(whole) == (18, 6) and C.param_count(whole) == 8_339_930_560   # 8.34 B
    assert C.kv_bytes_per_token(cfg) == 3 * 2 * 8 * 64 * 2 == 6_144
    assert C.slot_state_bytes(cfg) == 11 * 2 * 2048 * 2 == 90_112
    assert C.kv_bytes_per_token(whole) == 12_288                    # the full model's 12 KB
    # one token through everything but the routed experts, 2 operations a parameter + the taps
    per_token = (11 * (2 * 4 * 2048 * 2048 + 2 * 3 * 2048) + 3 * 2 * (attn - 128)
                 + 2 * 3 * 2 * 2048 * 7168 + 12 * 2 * 2048 * 32)
    assert C.token_flops(cfg) == per_token
    # a decode step: 128 rows attending 115,000 positions, every row's 4 picks in 12 layers
    want = (128 * per_token + 3 * 4 * 32 * 64 * 115_000 + 128 * 4 * 12 * 22_020_096
            + 128 * 2 * 2048 * 65_536)
    assert C.decode_step_flops(cfg, 128, 115_000, 128 * 4 * 12) == want
    # a final chunk of 300 real tokens at 512
    attended = 300 * 512 + 300 * 301 / 2
    want = (300 * per_token + 3 * 4 * 32 * 64 * attended + 300 * 48 * 22_020_096
            + 2 * 2048 * 65_536)
    assert C.prefill_flops(cfg, 300, 512, 300 * 48, head=True) == want
    assert C.prefill_flops(cfg, 300, 512, 300 * 48, head=False) == want - 2 * 2048 * 65_536
    assert C.paged_attention_call(cfg, 128, 115_000) == {
        "flops": 4 * 32 * 64 * 115_000, "bytes": 2 * 8 * 64 * 2 * 115_000 + 2 * 128 * 32 * 64 * 2}
    assert C.conv_call(cfg, 128, 128) == {"flops": 2 * 3 * 2048 * 128,
                                          "bytes": (2 * 128 + 2 * 128 * 2) * 2048 * 2}
    outside = total - 12 * 32 * expert
    assert C.decode_stream_bytes(cfg, 128, 115_000, 384) == (
        384 * 22_020_096 + outside * 2 + 6_144 * 115_000 + 2 * 128 * 90_112)


def _run(records, device_ops=None, programs=None, trace=(10.0, 14.0), rehearsal=False,
         counters=None):
    tr = spans.BenchTracer()
    tr.records = records
    win = {"tracer": tr, "t_open": 0.0, "t_close": 20.0, "counters": counters,
           "trace": {"t0": trace[0], "t1": trace[1]} if trace else None}
    red = None
    if device_ops is not None:
        red = {"device_ops": [[k, v] for k, v in device_ops.items()],
               "device_op_calls": {k: 1 for k in device_ops}, "programs": programs or {}}
    return {"cell": M.Cell(MAN, CELL), "win": win, "trace": red, "rehearsal": rehearsal,
            "device_kind": "TPU v5 lite", "sut": {}, "end_to_end": {}}


DECODE = ("decode", 11.0, 11.02, {"active": 120, "rows": 120, "context_tokens": 110_000,
                                  "moe_assignments": 6144, "moe_experts_touched": 380,
                                  "moe_max_rows": 31})
CHUNK_SPAN = ("prefill", 11.1, 11.15, {"chunk": 512, "tokens": 512, "start": 512,
                                       "state_from": "carried"})
CHUNK_COUNTS = ("prefill_counts", 11.19, 11.19, {
    "chunk": 512, "tokens": 512, "start": 512, "state_from": "carried",
    "moe_assignments": 24_576, "moe_experts_touched": 384, "moe_max_rows": 96})
FINAL_COUNTS = ("prefill_counts", 11.3, 11.3, {
    "bucket": 512, "tokens": 256, "start": 0, "state_from": "zero",
    "moe_assignments": 24_576, "moe_experts_touched": 384, "moe_max_rows": 80})
EPILOGUES = [("epilogue", 11.03, 11.031, {"active": 120, "pages_used": 4000, "pages_total": 16384,
                                          "state_slots": 123, "state_bytes": 123 * 90_112}),
             ("epilogue", 12.03, 12.031, {"active": 128, "pages_used": 4100, "pages_total": 16384,
                                          "state_slots": 128, "state_bytes": 128 * 90_112})]
OLD = [("decode", 11.0, 11.02, {"active": 32}), ("prefill", 11.1, 11.15, {"chunk": 512}),
       ("epilogue", 11.03, 11.031, {"active": 32, "pages_used": 10, "pages_total": 20})]


def test_serve_conv_moe_mfu_counts_the_windows_spans():
    read = M.load_reader("serve_conv_moe_mfu")
    cfg = M.Cell(MAN, CELL).config
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL_COUNTS]))
    flops = (C.decode_step_flops(cfg, 120, 110_000, 6144 * 120 / 128)
             + C.prefill_flops(cfg, 512, 512, 24_576.0, head=False)
             + C.prefill_flops(cfg, 256, 0, 24_576 * 256 / 512, head=True))
    assert got == pytest.approx(100 * flops / 20.0 / 197e12) and 0 < got < 100
    assert read(_run(OLD)) is None and read(_run([DECODE], rehearsal=True)) is None
    assert read({"win": {}, "rehearsal": False}) is None


def test_hybrid_paged_attn_roofline_counts_the_attention_layers_only():
    read = M.load_reader("hybrid_paged_attn_roofline")
    ops = {"jit__decode_program/paged_attn:bf16[128,8,4,64]": 0.002,
           "jit__decode_program/moe_gmm:bf16[4096,1792]": 0.5,
           "jit__final_chunk_program/fusion": 0.5}
    got = read(_run([DECODE, CHUNK_SPAN], ops))
    bytes_ = 2 * 8 * 64 * 2 * 110_000 + 2 * 120 * 32 * 64 * 2      # bytes bind
    assert bytes_ / 819e9 > 4 * 32 * 64 * 110_000 / 197e12
    assert got == pytest.approx(100 * 3 * (bytes_ / 819e9) / 0.002) and 0 < got < 100
    assert read(_run([DECODE], {"jit__decode_program/fusion": 0.004})) is None   # no kernel
    assert read(_run(OLD, ops)) is None                         # a program without the fields
    assert read(_run([DECODE], None)) is None                   # no device trace
    assert read(_run([DECODE], ops, rehearsal=True)) is None


def test_decode_stream_roofline_is_a_lower_bound_over_the_programs_time():
    read = M.load_reader("decode_stream_roofline")
    cfg = M.Cell(MAN, CELL).config
    programs = {"jit__decode_program": {"seconds": 0.044, "calls": 2},
                "jit__chunk_program": {"seconds": 0.03, "calls": 2}}
    second = ("decode", 12.0, 12.02, dict(DECODE[3], rows=128, context_tokens=118_000,
                                          moe_experts_touched=384))
    got = read(_run([DECODE, second, CHUNK_SPAN], {}, programs))
    bytes_ = (C.decode_stream_bytes(cfg, 120, 110_000, 380)
              + C.decode_stream_bytes(cfg, 128, 118_000, 384))
    assert got == pytest.approx(100 * (bytes_ / 2 / 819e9) / 0.022) and 50 < got < 100
    assert read(_run(OLD, {}, programs)) is None                # no counters on the spans
    assert read(_run([DECODE], {}, {})) is None                 # the program did not run
    assert read(_run([DECODE], None)) is None
    assert read(_run([DECODE], {}, programs, rehearsal=True)) is None


def test_serve_state_peak_bytes_reads_the_epilogues():
    read = M.load_reader("serve_state_peak_bytes")
    assert read(_run(EPILOGUES)) == 128 * 90_112
    assert read(_run(OLD)) is None and read({"win": {}}) is None


def test_the_accepted_readers_read_this_configurations_records():
    """Those the cell is appended to: the load ratio over the chunk calls (12
    expert layers x 32 experts), the grouped product's roofline from the
    chunk programs, the batch's occupancy."""
    got = M.load_reader("moe_expert_load_max_over_mean")(_run([DECODE, CHUNK_COUNTS, FINAL_COUNTS]))
    mean_rows = 24_576 / (32 * 12)
    assert got == pytest.approx((96 / mean_rows + 80 / mean_rows) / 2)
    ops = {"jit__chunk_program/moe_gmm:bf16[6144,1792]": 0.02,
           "jit__final_chunk_program/moe_gmm:bf16[6144,1792]": 0.02,
           "jit__decode_program/fusion": 0.01}
    got = M.load_reader("moe_gmm_roofline")(_run([DECODE, CHUNK_COUNTS, FINAL_COUNTS], ops))
    bytes_ = 768 * 22_020_096 + 49_152 * 2 * 2048 * 2
    assert bytes_ / 819e9 > 49_152 * 22_020_096 / 197e12            # bytes bind
    assert got == pytest.approx(100 * (bytes_ / 819e9) / 0.04) and 0 < got < 100
    occ = M.load_reader("engine_batch_occupancy")(_run([], counters={"occupancy": 0.93}))
    assert occ == pytest.approx(93.0)


NEW_READERS = ("serve_conv_moe_mfu", "hybrid_paged_attn_roofline", "decode_stream_roofline",
               "serve_state_peak_bytes")


@pytest.mark.parametrize("metric, listed", [
    ("decode_step_device_ms", True), ("engine_batch_occupancy", True),
    ("moe_expert_load_max_over_mean", True), ("moe_gmm_roofline", True),
    ("setup_trace_lower_s", True),
    # a dense count, a latent count, a name match over 14 layers where 3 attend, and the three
    # metrics test_perfbench_host_spans pins to the Mistral cell
    ("serve_mfu", False), ("serve_moe_mfu", False), ("paged_attn_roofline", False),
    ("engine_host_ms_per_step", False), ("engine_emit_ms_per_step", False),
    ("kv_pool_peak_fill", False)])
def test_the_cell_is_listed_where_a_reader_reads_it_rightly(metric, listed):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    assert (CELL in m["workloads"]) == listed


def test_the_four_new_readers_wait_in_a_fixture_manifest_for_a_benchmark_pr():
    """``test_perfbench_flash_roofline.py:75`` holds ``flash_attn_roofline`` to
    the LAST place of ``per_layer``, a new entry may only be appended, and
    neither file is this PR's to edit: the four readers this configuration
    brings are files beside the others, read the records above, and are listed
    in ``fixtures/conv_moe/manifest.json`` — the manifest's own entries for
    the cell plus theirs — which ``run.py --manifest`` takes as it is. A
    ``benchmark`` PR appends the four entries and relaxes that line."""
    assert not {m["name"] for m in MAN["per_layer"]} & set(NEW_READERS)
    assert MAN["per_layer"][-1]["name"] == "flash_attn_roofline"
    fx = M.load_manifest(FIXTURE_MANIFEST)
    cell = M.Cell(fx, CELL)
    assert cell.config == M.Cell(MAN, CELL).config and cell.options == M.Cell(MAN, CELL).options
    assert fx["configs"] == [c for c in MAN["configs"] if c["name"] == "lfm2-8b-a1b-d14"]
    assert fx["workloads"] == [w for w in MAN["workloads"] if w["name"] == CELL]
    strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"} for m in ms]
    assert strip(fx["end_to_end"]) == strip(M.Cell(MAN, CELL).end_to_end())
    names = [m["name"] for m in cell.per_layer()]
    assert names == [m["name"] for m in M.Cell(MAN, CELL).per_layer()] + list(NEW_READERS)
    for m in fx["per_layer"][-4:]:
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms" and callable(
            M.load_reader(m["name"]))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"] == "%") == ("roofline" in m["name"] or "mfu" in m["name"])
    assert {m["layer"] for m in fx["per_layer"]} <= {m["layer"] for m in MAN["per_layer"]}


def test_the_configuration_file_holds_the_source_and_the_cut():
    cat_keys = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                "intermediate_size": 7168, "max_position_embeddings": 128000,
                "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
                "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
                "num_experts": 32, "num_experts_per_tok": 4, "num_key_value_heads": 8,
                "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
                "vocab_size": 65536}
    cfg = M.Cell(MAN, CELL).config
    assert {k: cfg[k] for k in cat_keys} == cat_keys
    assert len(cfg["layer_types"]) == 24 and cfg["layer_types"].count("full_attention") == 6
    assert cfg["layer_types"][:14] == ["conv", "conv", "full_attention", "conv", "conv", "conv",
                                       "full_attention", "conv", "conv", "conv", "full_attention",
                                       "conv", "conv", "conv"]
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 14
    assert cfg["source_values"] == {"num_hidden_layers": 24}
    assert {"head_dim", "tie_word_embeddings", "torch_dtype"} <= set(cfg["assumed"])
    assert cfg["head_dim"] == 64 and cfg["tie_word_embeddings"] and cfg["torch_dtype"] == "bfloat16"
    eng = M.Cell(MAN, CELL).options["engine"]
    assert eng["num_slots"] == 128 and eng["max_seq_len"] == 4096
    assert eng["kv_pool_pages"] * eng["page_tokens"] == 524_288 and not eng["prefix_cache_mb"]
    mix, chat = M.Cell(MAN, CELL).traffic, M.Cell(MAN, "mistral-7b-d16.chat-backlog").traffic
    same = ("prompt_tokens", "output_tokens", "group", "pairing_seed", "ramp_seconds", "arrivals")
    assert all(mix[k] == chat[k] for k in same)
    assert mix["ramp_requests"] == 256 and mix["backlog_requests"] % 128 == 0
    assert eng["max_queue"] >= mix["backlog_requests"]
