"""The ``ssm-moe`` cell's benchmark parts on the CPU: its rehearsal ends
``correct`` and its fp8 control, an altered token and a zeroed state do not
leave the logits where they were; the plain reference against an independent
loop-by-position numpy form; ``counts_ssm_moe`` against hand sums at the
published widths; and each reader the cell brings on hand-made records — a
number where the spans and the trace carry what it reads, ``None`` where they
do not (a program without the spans: the parent commit)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import counts_ssm_moe as C
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_ssm_moe as ref
from benchmarks.harness import spans, weights

CELL = "nemotron-3-super-ep4-d11.chat-backlog-wide"
CONFIG = "nemotron-3-super-120b-ep4-d11"
MAN = M.load_manifest()
SEED = 3_300_000_019
FIXTURE_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                                "ssm_moe", "manifest.json")
NEW_READERS = ("ssm_update_roofline", "latent_moe_gmm_roofline", "serve_ssm_moe_mfu",
               "ssm_moe_decode_stream_roofline", "serve_state_peak_bytes")


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
    assert "metrics" not in line and "device" not in line       # nothing from a CPU
    win = next(json.loads(l) for l in out.out.splitlines() if '"event": "window"' in l)
    assert win["compiles_in_window"] == 0 and win["requests_finished_in_window"] > 0
    assert win["attention_impls"]["decode"] == "xla experts=dense ssm=xla"


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
    assert prog["router_flips"] == 0 and prog["tokens"] == 36
    assert prog["router_choices"] == 3 * 512 * 5                # 3 sequences x PAD x E layers
    assert set(prog["gap_limits"]) == {"all_max", "all_mean"}
    assert prog["logit_gap_max"] == max(prog["gaps"][k] / prog["gap_limits"][k]
                                        for k in prog["gap_limits"])
    seqs = [np.concatenate([s["prompt"], s["tokens"][:-1]]) for s in sample]
    rows = [np.arange(len(q)) for q in seqs]
    exact, _, _ = fam.reference.forward_logits(cfg, SEED, seqs, rows)
    low, _, _ = fam.reference.forward_logits(cfg, SEED, seqs, rows, cfg["control_precision"])
    lost, _, _ = fam.reference.forward_logits(cfg, SEED, seqs, rows,
                                              zero_state_at=cfg["zero_state_at"])
    assert max(np.abs(a - b).max() for a, b in zip(exact, low)) > 1e-2
    at = cfg["zero_state_at"]
    assert all(np.abs(a[:at] - b[:at]).max() == 0.0 for a, b in zip(exact, lost))
    assert min(np.abs(a[at:] - b[at:]).max() for a, b in zip(exact, lost)) > 1e-3
    for kw in ({"precision": cfg["control_precision"]}, {"fault": "zero_state"}):
        assert set(fam.reference.score_served(cfg, SEED, sample, **kw)["gaps"]) == {
            "all_max", "all_mean", "decided_max"}
    bad = fam.reference.score_served(cfg, SEED, sample, fault="alter")
    assert bad["logit_gap_max"] > 1.0 and bad["gap_shares"]["all_max"] > 1.0


def _numpy_forward(cfg, seed, toks):
    """The equations of ISSUE 33 once more, position by position in numpy
    float64: the recurrence a loop over tokens and heads, every sum an explicit
    loop, sharing nothing with the reference but the weights' names and how a
    leaf is drawn."""
    f8 = lambda a: np.asarray(a, np.float64)
    dt = jnp.dtype(cfg["torch_dtype"])
    leaf = lambda name, shape: f8(ref.leaf(cfg, weights.seed_operand(seed), name, shape, dt))
    d, eps, t_len = cfg["hidden_size"], cfg["norm_eps"], len(toks)
    rms = lambda x, g: x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g
    silu = lambda a: a / (1.0 + np.exp(-a))
    relu2 = lambda a: np.square(np.maximum(a, 0.0))
    x = leaf("transformer/tok_embed/embedding", (cfg["vocab_size"], d))[np.asarray(toks)]
    hh, p, g, n, k = ref.mamba_sizes(cfg)
    inner, conv = hh * p, hh * p + 2 * g * n
    for l, kind in enumerate(ref.pattern(cfg)):
        pre = f"transformer/block_{l}/"
        mix = np.zeros_like(x)
        if kind == "M":
            xh = rms(x, leaf(pre + "attn_norm/scale", (d,)))
            zxd = xh @ leaf(pre + "attn/in_proj/kernel", (d, inner + conv + hh))
            z, xbc, step = zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]
            taps, bias = leaf(pre + "attn/conv", (k, conv)), leaf(pre + "attn/conv_bias", (conv,))
            act = np.zeros_like(xbc)
            for t in range(t_len):
                v = bias.copy()
                for j in range(k):
                    if t - (k - 1) + j >= 0:                    # zeros before the sequence
                        v += taps[j] * xbc[t - (k - 1) + j]
                act[t] = silu(v)
            step = np.log1p(np.exp(step + leaf(pre + "attn/dt_bias", (hh,))))
            a = -np.exp(leaf(pre + "attn/A_log", (hh,)))
            skip, gain = leaf(pre + "attn/D", (hh,)), leaf(pre + "attn/norm/scale", (inner,))
            state = np.zeros((hh, p, n))
            y = np.zeros((t_len, inner))
            for t in range(t_len):
                for h in range(hh):
                    grp = h // (hh // g)
                    xs = act[t, h * p:(h + 1) * p]
                    b = act[t, inner + grp * n:inner + (grp + 1) * n]
                    c = act[t, inner + g * n + grp * n:inner + g * n + (grp + 1) * n]
                    state[h] = np.exp(step[t, h] * a[h]) * state[h] + step[t, h] * np.outer(xs, b)
                    y[t, h * p:(h + 1) * p] = state[h] @ c + skip[h] * xs
            y = y * silu(z)
            width = inner // g
            for t in range(t_len):
                for grp in range(g):
                    part = y[t, grp * width:(grp + 1) * width]
                    y[t, grp * width:(grp + 1) * width] = part / np.sqrt(np.mean(part * part) + eps)
            mix = (y * gain) @ leaf(pre + "attn/out_proj/kernel", (inner, d))
        elif kind == "*":
            xh = rms(x, leaf(pre + "attn_norm/scale", (d,)))
            h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
            q = np.einsum("td,dhk->thk", xh, leaf(pre + "attn/q_proj/kernel", (d, h, hd)))
            kk = np.einsum("td,dhk->thk", xh, leaf(pre + "attn/k_proj/kernel", (d, kv, hd)))
            v = np.einsum("td,dhk->thk", xh, leaf(pre + "attn/v_proj/kernel", (d, kv, hd)))
            wo = leaf(pre + "attn/o_proj/kernel", (h, hd, d))
            for t in range(t_len):                              # no positional embedding
                for i in range(h):
                    grp = i // (h // kv)
                    sc = np.array([q[t, i] @ kk[u, grp] for u in range(t + 1)]) * hd ** -0.5
                    pr = np.exp(sc - sc.max())
                    pr /= pr.sum()
                    mix[t] += sum(pr[u] * v[u, grp] for u in range(t + 1)) @ wo[i]
        else:
            xh = rms(x, leaf(pre + "mlp_norm/scale", (d,)))
            e, held, top = cfg["router_outputs"], cfg["n_routed_experts"], cfg["num_experts_per_tok"]
            lat, f, fs = (cfg["moe_latent_size"], cfg["moe_intermediate_size"],
                          cfg["moe_shared_expert_intermediate_size"])
            wr, b = leaf(pre + "mlp/router", (d, e)), leaf(pre + "mlp/router_bias", (e,))
            fc1, fc2 = (leaf(pre + "mlp/fc1_latent/kernel", (d, lat)),
                        leaf(pre + "mlp/fc2_latent/kernel", (lat, d)))
            w1, w2 = leaf(pre + "mlp/w_up", (held, lat, f)), leaf(pre + "mlp/w_down", (held, f, lat))
            s1, s2 = (leaf(pre + "mlp/shared/up_proj/kernel", (d, fs)),
                      leaf(pre + "mlp/shared/down_proj/kernel", (fs, d)))
            lo = cfg.get("expert_offset", 0)
            for t in range(t_len):
                s = 1.0 / (1.0 + np.exp(-(xh[t] @ wr)))
                chosen = np.argsort(-(s + b), kind="stable")[:top]
                u, acc = xh[t] @ fc1, np.zeros(lat)
                for c in chosen:
                    if lo <= c < lo + held:                     # the experts held HERE
                        gate = cfg["routed_scaling_factor"] * s[c] / s[chosen].sum()
                        acc += gate * (relu2(u @ w1[c - lo]) @ w2[c - lo])
                mix[t] = acc @ fc2 + relu2(xh[t] @ s1) @ s2
        x = x + mix
    return (rms(x, leaf("transformer/final_norm/scale", (d,)))
            @ leaf("head/lm_head/kernel", (d, cfg["vocab_size"])))


def test_reference_against_a_loop_by_position_numpy_form():
    cfg = _tiny().config
    toks = np.random.default_rng(8).integers(0, cfg["vocab_size"], size=23)
    got, margins, _ = ref.forward_logits(cfg, SEED, [toks], [np.arange(len(toks))])
    want = _numpy_forward(cfg, SEED, toks)
    assert np.isfinite(margins[0]).all() and margins[0].min() > 1e-6    # no tie decides
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    assert np.abs(want).max() > 0.1


def test_the_reference_imports_nothing_of_the_program():
    import inspect
    text = inspect.getsource(ref)
    assert "k8s_distributed_deeplearning_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan(one_token" in text and 'default_matmul_precision("highest")' in text


def test_counts_against_hand_sums_at_the_published_widths():
    cfg = M.Cell(MAN, CELL).config
    assert C.layers(cfg) == (5, 1, 5)                       # M E M E M E M * E M E
    mamba = (4096 * (8192 + 10240 + 128) + 5 * 10240 + 3 * 128 + 8192 + 8192 * 4096 + 4096)
    attn = 2 * 4096 * 32 * 128 + 2 * 4096 * 2 * 128 + 4096
    outside = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096
    expert = 2 * 1024 * 2688
    assert (C.mamba_params(cfg), C.attention_params(cfg), C.expert_layer_outside_params(cfg),
            C.expert_params(cfg)) == (mamba, attn, outside, expert)
    assert (mamba, attn, outside, expert) == (109_640_064, 35_655_680, 54_530_560, 5_505_024)
    total = 5 * mamba + 5 * (outside + 128 * expert) + attn + 2 * 32_768 * 4096 + 4096
    assert C.param_count(cfg) == total == 4_648_163_712     # 9.30 GB in bf16
    assert C.ssm_state_bytes(cfg) == 128 * 64 * 128 * 4 == 4_194_304
    assert C.conv_state_bytes(cfg) == 3 * 10_240 * 2 == 61_440
    assert C.slot_state_bytes(cfg) == 5 * (4_194_304 + 61_440) == 21_278_720
    assert 128 * C.slot_state_bytes(cfg) == 2_723_676_160   # the arena: 2.72 GB
    assert C.kv_bytes_per_token(cfg) == 2 * 2 * 128 * 2 == 1_024
    pub = C.published_counts(cfg)
    assert pub == {"total": 120_668_707_840, "active": 12_233_366_528}     # 120.67 B / 12.23 B
    assert pub["total"] == (40 * mamba + 8 * attn + 40 * (outside + 512 * expert)
                            + 2 * 131_072 * 4096 + 4096)
    per_token = (5 * (2 * 4096 * 18_560 + 2 * 4 * 10_240 + 5 * 128 * 64 * 128 + 2 * 8192 * 4096)
                 + 2 * (attn - 4096)
                 + 5 * 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376))
    assert C.token_flops(cfg) == per_token
    want = (128 * per_token + 4 * 32 * 128 * 115_000 + 700 * 2 * expert
            + 128 * 2 * 4096 * 32_768)
    assert C.decode_step_flops(cfg, 128, 115_000, 700) == want
    attended = 300 * 512 + 300 * 301 / 2
    want = 300 * per_token + 4 * 32 * 128 * attended + 8000 * 2 * expert + 2 * 4096 * 32_768
    assert C.prefill_flops(cfg, 300, 512, 8000, head=True) == want
    assert C.prefill_flops(cfg, 300, 512, 8000, head=False) == want - 2 * 4096 * 32_768
    assert C.ssm_update_call(cfg, 120) == {"flops": 5 * 1_048_576 * 120,
                                           "bytes": 2 * 4_194_304 * 120}
    scan = C.ssm_scan_call(cfg, 512)
    assert scan["flops"] == 4 * 128 * 2 * 128 * (128 * 128 + 128 * 64 + 2 * 64 * 128)
    assert scan["bytes"] == 512 * (2 * 8192 + 2 * 1024 + 128) * 2 + 2 * 4_194_304
    assert C.expert_products(cfg, 640, 2816) == {"flops": 2816 * 2 * expert,
                                                 "bytes": 640 * expert * 2}
    rest = total - 5 * 128 * expert - 32_768 * 4096
    assert C.decode_stream_bytes(cfg, 128, 115_000, 640) == (
        640 * expert * 2 + rest * 2 + 1_024 * 115_000 + 2 * 128 * 21_278_720)
    assert 14.4e9 < C.decode_stream_bytes(cfg, 128, 115_000, 640) < 14.7e9


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


SLOT = 21_278_720
DECODE = ("decode", 11.0, 11.02, {"active": 120, "rows": 120, "context_tokens": 110_000,
                                  "state_rows": 120, "state_bytes_moved": 2 * 120 * SLOT,
                                  "moe_assignments": 3520, "moe_experts_touched": 630,
                                  "moe_max_rows": 14})
CHUNK_SPAN = ("prefill", 11.1, 11.15, {"chunk": 512, "tokens": 512, "start": 512,
                                       "state_from": "carried"})
CHUNK_COUNTS = ("prefill_counts", 11.19, 11.19, {
    "chunk": 512, "tokens": 512, "start": 512, "state_from": "carried",
    "moe_assignments": 14_080, "moe_experts_touched": 640, "moe_max_rows": 60})
FINAL_COUNTS = ("prefill_counts", 11.3, 11.3, {
    "bucket": 512, "tokens": 256, "start": 0, "state_from": "zero",
    "moe_assignments": 14_080, "moe_experts_touched": 640, "moe_max_rows": 55})
EPILOGUES = [("epilogue", 11.03, 11.031, {"active": 120, "pages_used": 4000, "pages_total": 16384,
                                          "state_slots": 123, "state_bytes": 123 * SLOT}),
             ("epilogue", 12.03, 12.031, {"active": 128, "pages_used": 4100, "pages_total": 16384,
                                          "state_slots": 128, "state_bytes": 128 * SLOT})]
# what the parent's program writes: no state_rows, no counts
OLD = [("decode", 11.0, 11.02, {"active": 32, "rows": 32, "context_tokens": 9000}),
       ("prefill", 11.1, 11.15, {"chunk": 512, "tokens": 512, "start": 0}),
       ("epilogue", 11.03, 11.031, {"active": 32, "pages_used": 10, "pages_total": 20})]


def test_ssm_update_roofline_reads_the_rows_the_steps_advanced():
    read = M.load_reader("ssm_update_roofline")
    ops = {"jit__decode_program/ssm_update:f32[128,8192,128]": 0.016,
           "jit__decode_program/fusion": 0.5, "jit__chunk_program/fusion": 0.5}
    second = ("decode", 12.0, 12.02, dict(DECODE[3], state_rows=128))
    got = read(_run([DECODE, second, CHUNK_SPAN], ops))
    least = 5 * (120 + 128) * 2 * 4_194_304 / 819e9            # 5 layers, the state twice
    assert got == pytest.approx(100 * least / 0.016) and 50 < got < 100
    assert read(_run([DECODE], {"jit__decode_program/fusion": 0.004})) is None   # no kernel
    assert read(_run(OLD, ops)) is None                         # a program without the field
    assert read(_run([DECODE], None)) is None                   # no device trace
    assert read(_run([DECODE], ops, rehearsal=True)) is None
    assert read(_run([DECODE], ops, trace=None)) is None


def test_latent_moe_gmm_roofline_counts_two_products_of_the_latent_width():
    read = M.load_reader("latent_moe_gmm_roofline")
    ops = {"jit__chunk_program/moe_gmm:bf16[27648,2688]": 0.012,
           "jit__final_chunk_program/moe_gmm:bf16[27648,1024]": 0.012,
           "jit__decode_program/fusion": 0.01}
    got = read(_run([DECODE, CHUNK_COUNTS, FINAL_COUNTS], ops))
    bytes_ = 1280 * 11_010_048
    assert bytes_ / 819e9 > 28_160 * 11_010_048 / 197e12        # bytes bind
    assert got == pytest.approx(100 * (bytes_ / 819e9) / 0.024) and 0 < got < 100
    # where the decode program runs the kernel, its own counts are read
    dec = {"jit__decode_program/moe_gmm:bf16[4096,2688]": 0.01}
    assert read(_run([DECODE, CHUNK_COUNTS], dec)) == pytest.approx(
        100 * (630 * 11_010_048 / 819e9) / 0.01)
    assert read(_run([DECODE, CHUNK_COUNTS], {"jit__decode_program/fusion": 0.01})) is None
    assert read(_run(OLD, ops)) is None and read(_run([DECODE], None)) is None


def test_serve_ssm_moe_mfu_counts_the_windows_spans():
    read = M.load_reader("serve_ssm_moe_mfu")
    cfg = M.Cell(MAN, CELL).config
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL_COUNTS]))
    flops = (C.decode_step_flops(cfg, 120, 110_000, 3520 * 120 / 128)
             + C.prefill_flops(cfg, 512, 512, 14_080.0, head=False)
             + C.prefill_flops(cfg, 256, 0, 14_080 * 256 / 512, head=True))
    assert got == pytest.approx(100 * flops / 20.0 / 197e12) and 0 < got < 100
    assert read(_run(OLD)) is None and read(_run([DECODE], rehearsal=True)) is None
    assert read({"win": {}, "rehearsal": False}) is None


def test_ssm_moe_decode_stream_roofline_is_a_lower_bound_over_the_programs_time():
    read = M.load_reader("ssm_moe_decode_stream_roofline")
    cfg = M.Cell(MAN, CELL).config
    programs = {"jit__decode_program": {"seconds": 0.048, "calls": 2},
                "jit__chunk_program": {"seconds": 0.03, "calls": 2}}
    second = ("decode", 12.0, 12.02, dict(DECODE[3], state_rows=128, context_tokens=118_000,
                                          moe_experts_touched=640))
    got = read(_run([DECODE, second, CHUNK_SPAN], {}, programs))
    bytes_ = (C.decode_stream_bytes(cfg, 120, 110_000, 630)
              + C.decode_stream_bytes(cfg, 128, 118_000, 640))
    assert got == pytest.approx(100 * (bytes_ / 2 / 819e9) / 0.024) and 50 < got < 100
    assert read(_run(OLD, {}, programs)) is None
    assert read(_run([DECODE], {}, {})) is None and read(_run([DECODE], None)) is None
    assert read(_run([DECODE], {}, programs, rehearsal=True)) is None


def test_serve_state_peak_bytes_reads_gigabytes_now():
    read = M.load_reader("serve_state_peak_bytes")
    assert read(_run(EPILOGUES)) == 128 * SLOT == 2_723_676_160
    assert read(_run(OLD)) is None


@pytest.mark.parametrize("metric, listed", [
    ("serve_out_tokens_per_s", True), ("itl_p95_ms", True),
    ("decode_step_device_ms", True), ("engine_batch_occupancy", True),
    ("setup_trace_lower_s", True),
    # three products of hidden x moe_intermediate where these experts are two of latent x
    # moe_intermediate (six times too high); a reader that takes every layer behind
    # first_k_dense_replace for an expert layer; dense and latent-attention counts; name matches
    ("moe_gmm_roofline", False), ("moe_expert_load_max_over_mean", False),
    ("serve_mfu", False), ("serve_moe_mfu", False), ("paged_attn_roofline", False),
    ("latent_attn_roofline", False), ("engine_host_ms_per_step", False),
    ("kv_pool_peak_fill", False)])
def test_the_cell_is_listed_where_a_reader_reads_it_rightly(metric, listed):
    m = next(x for x in MAN["per_layer"] + MAN["end_to_end"] if x["name"] == metric)
    assert (CELL in m["workloads"]) == listed


def test_the_new_readers_wait_in_a_fixture_manifest_for_a_benchmark_pr():
    """As PR 31's four: ``test_perfbench_flash_roofline.py:75`` holds
    ``flash_attn_roofline`` to the LAST place of ``per_layer`` and a new entry
    may only be appended, so this configuration's readers are files beside the
    others, listed in ``fixtures/ssm_moe/manifest.json`` — the manifest's own
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
    assert names == [m["name"] for m in M.Cell(MAN, CELL).per_layer()] + list(NEW_READERS)
    for m in fx["per_layer"][-len(NEW_READERS):]:
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms" and callable(
            M.load_reader(m["name"]))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"] == "%") == ("roofline" in m["name"] or "mfu" in m["name"])
    assert {m["layer"] for m in fx["per_layer"]} <= {m["layer"] for m in MAN["per_layer"]}


def test_the_configuration_file_holds_the_catalogs_config_and_the_cut():
    cfg = M.Cell(MAN, CELL).config
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 2688,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True}
    assert {k: cfg[k] for k in published} == published
    pat = cfg["hybrid_override_pattern"]
    assert len(pat) == 88 and (pat.count("M"), pat.count("E"), pat.count("*")) == (40, 40, 8)
    assert pat[:11] == "MEMEMEM*EME"
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (11, 128, 32768)
    assert cfg["source_values"] == {"num_hidden_layers": 88, "n_routed_experts": 512,
                                    "vocab_size": 131072}
    assert cfg["router_outputs"] == 512 and cfg["expert_offset"] == 0
    assert cfg["ssm_state_dtype"] == "float32" and cfg["torch_dtype"] == "bfloat16"
    assert {"position", "ssm_state_dtype", "ssm_initialisation", "router"} <= set(cfg["assumed"])
    assert any("multi-token-prediction" in d for d in cfg["departures"])
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert all(len(e["why"]) <= 200 for e in MAN["configs"] + MAN["workloads"])
    eng = M.Cell(MAN, CELL).options["engine"]
    assert eng["num_slots"] == 128 and eng["max_seq_len"] == 4096 and not eng["prefix_cache_mb"]
    assert eng["kv_pool_pages"] * eng["page_tokens"] == 128 * 4096
    assert eng["min_bucket"] == eng["prefill_chunk_tokens"]
    # the accepted traffic file, as the lfm2 cell runs it
    assert M.Cell(MAN, CELL).traffic == M.Cell(MAN, "lfm2-8b-a1b-d14.chat-backlog-wide").traffic
    assert M.Cell(MAN, CELL).chips == 1
