"""The ``latent-moe`` cell's benchmark parts on the CPU: its rehearsal ends
``correct`` and its fp8 control does not, ``counts_latent_moe`` against hand
arithmetic at the published widths, and each new reader on hand-made spans —
a number where the spans and the trace carry what it reads, ``None`` where
they do not (a program without the spans: the parent commit)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import counts_latent_moe as C
from benchmarks.harness import manifest as M
from benchmarks.harness import spans

CELL = "sarvam-105b-ep4-d6.docs-backlog"
MAN = M.load_manifest()
SEED = 3_000_000_019


def test_cell_rehearsal_ends_correct(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                         "--trace", "1", "--rehearsal"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["compared"]["logit_gap_max"] == [0.0, 1.0]      # a share of the limits
    assert "moe_expert_load_max_over_mean" in line["metrics_reported"]
    assert "metrics" not in line and "device" not in line       # nothing from a CPU
    win = next(json.loads(l) for l in out.out.splitlines() if '"event": "window"' in l)
    assert win["compiles_in_window"] == 0 and win["requests_finished_in_window"] > 0


def _served_sample(cell, n_prompts=3):
    """Prompts and the program's own greedy tokens at the tiny size."""
    from k8s_distributed_deeplearning_tpu.models import generate
    fam = cell.family()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    rng = np.random.default_rng(5)
    sample = []
    for n in (70, 41, 23)[:n_prompts]:
        p = rng.integers(0, cell.config["vocab_size"], size=n).astype(np.int32)
        toks = generate.generate(model, params, jnp.asarray(p)[None], max_new_tokens=12)
        sample.append({"prompt": p, "tokens": np.asarray(toks[0])})
    return fam, sample


def _scored():
    cell = M.Cell(MAN, CELL)
    cell.apply_rehearsal()
    fam, sample = _served_sample(cell)
    return cell, fam, sample, fam.reference.score_served(cell.config, SEED, sample)


def test_fp8_control_and_an_altered_token_are_not_correct():
    cell, fam, sample, prog = _scored()
    limit = cell.options["limits"]["logit_gap_max"]
    assert limit == 1.0                          # the largest share of the three limits
    assert prog["logit_gap_max"] <= limit and prog["not_reference_best"] == 0
    assert prog["router_flips"] == 0            # float32 served type: nothing is rounded
    assert prog["tokens"] == 36 and 0 < prog["tokens_decided"] <= 36
    low = fam.reference.score_served(cell.config, SEED, sample,
                                     precision=cell.config["control_precision"])
    assert low["logit_gap_max"] > limit and low["gap_shares"]["all_mean"] > limit
    bad = fam.reference.score_served(cell.config, SEED, sample, fault="alter")
    assert bad["logit_gap_max"] > limit


def test_every_sampled_token_is_held_to_a_limit():
    """The one compared number is the largest of three gaps as a share of its
    limit; a single wrong token fails the limit on EVERY token's gap wherever
    it sits."""
    cell, fam, sample, prog = _scored()
    cfg = cell.config
    assert M.Cell(MAN, CELL).config["served_gap_limits"] == {
        "all_max": 3.0, "all_mean": 0.045, "decided_max": 0.2}
    assert set(prog["gaps"]) == set(prog["gap_limits"]) == {"all_max", "all_mean", "decided_max"}
    assert prog["logit_gap_max"] == max(prog["gaps"][k] / prog["gap_limits"][k]
                                        for k in prog["gaps"])
    # the served tokens of the first request, one by one made another token
    toks = sample[0]["tokens"]
    sound = fam.reference.score_served(cfg, SEED, sample[:1])
    assert sound["logit_gap_max"] <= 1.0
    caught = []
    for i in range(len(toks)):
        wrong = toks.copy()
        wrong[i] = (wrong[i] + 1) % cfg["vocab_size"]
        sc = fam.reference.score_served(cfg, SEED, [dict(sample[0], tokens=wrong)])
        # teacher forcing: the wrong token shows at its own position alone
        caught.append(sc["gap_shares"]["all_max"] > 1.0)
        assert sc["gaps"]["all_max"] == pytest.approx(sc["per_request_max_all"][0])
    assert all(caught)


def test_counts_against_hand_arithmetic_at_the_published_widths():
    cfg = M.Cell(MAN, CELL).config
    assert C.expert_flops(cfg) == 2 * 25_165_824 == 50_331_648      # 3 x 4096 x 2048 MACs
    assert C.expert_bytes(cfg) == 50_331_648                         # the same count, 2 B each
    # W_Q 50.33 M + W_DKV 2.36 M + W_O 33.55 M + W_UK|UV 8.39 M parameters, 2 ops each
    per_layer = 2 * (4096 * 64 * 192 + 4096 * 576 + 64 * 128 * 4096 + 512 * 64 * 256)
    assert C.layer_token_flops(cfg) == per_layer == 189_267_968
    # one token's feed-forward: dense layer 0 (3 x 4096 x 16384), and in each of 5 expert
    # layers the router (4096 x 128) and the shared expert; 7 picks landed on held experts
    ff = (2 * 3 * 4096 * 16384 + 5 * (2 * 4096 * 128 + 50_331_648)) + 7 * 50_331_648
    assert C.feed_forward_flops(cfg, 1, 7) == ff
    # a decode step: 32 rows attending 300,000 positions in all, 64 picks landed
    attn = 2 * 64 * (576 + 512) * 300_000
    want = (6 * (32 * per_layer + attn) + C.feed_forward_flops(cfg, 32, 64)
            + 32 * 2 * 4096 * 65_536)
    assert C.decode_step_flops(cfg, 32, 300_000, 64) == want
    # a chunk of 1,024 tokens at start 4,096: causal, 192-wide keys and 128-wide values
    attended = 1024 * 4096 + 1024 * 1025 / 2
    want = (6 * (1024 * per_layer + 2 * 64 * 320 * attended)
            + C.feed_forward_flops(cfg, 1024, 2048) + 2 * 4096 * 65_536)
    assert C.prefill_flops(cfg, 1024, 4096, 2048, head=True) == want
    assert C.prefill_flops(cfg, 1024, 4096, 2048, head=False) == want - 2 * 4096 * 65_536
    c = C.latent_attention_call(cfg, 32, 300_000)
    assert c == {"flops": attn, "bytes": 576 * 2 * 300_000 + 32 * 64 * 1088 * 2}
    c = C.expert_products(cfg, 28, 64)
    assert c == {"flops": 64 * 50_331_648, "bytes": 28 * 50_331_648 + 64 * 2 * 4096 * 2}


def _run(records, device_ops=None, trace=(10.0, 14.0), rehearsal=False):
    tr = spans.BenchTracer()
    tr.records = records
    win = {"tracer": tr, "t_open": 0.0, "t_close": 20.0,
           "trace": {"t0": trace[0], "t1": trace[1]} if trace else None}
    red = None
    if device_ops is not None:
        red = {"device_ops": [[k, v] for k, v in device_ops.items()],
               "device_op_calls": {k: 1 for k in device_ops}}
    return {"cell": M.Cell(MAN, CELL), "win": win, "trace": red, "rehearsal": rehearsal,
            "device_kind": "TPU v5 lite", "sut": {}, "end_to_end": {}}


DECODE = ("decode", 11.0, 11.02, {"active": 32, "rows": 32, "context_tokens": 300_000,
                                  "moe_assignments": 64, "moe_experts_touched": 140,
                                  "moe_max_rows": 4})
# an intermediate chunk: its span, and its counts in a record of their own at the next fence
CHUNK_SPAN = ("prefill", 11.1, 11.15, {"chunk": 1024, "tokens": 1024, "start": 4096})
CHUNK_COUNTS = ("prefill_counts", 11.19, 11.19, {"chunk": 1024, "tokens": 1024, "start": 4096,
                                                 "moe_assignments": 2048,
                                                 "moe_experts_touched": 160, "moe_max_rows": 20})
FINAL = ("prefill", 11.2, 11.25, {"bucket": 1024, "tokens": 512, "start": 8192,
                                  "moe_assignments": 2048, "moe_experts_touched": 160,
                                  "moe_max_rows": 16})
OLD = [("decode", 11.0, 11.02, {"active": 32}), ("prefill", 11.1, 11.15, {"chunk": 1024})]


def test_latent_attn_roofline_reads_the_traced_decode_spans():
    read = M.load_reader("latent_attn_roofline")
    ops = {"jit__decode_program/latent_attn:bf16[32,64,512]": 0.004,
           "jit__final_chunk_program/fusion": 0.5}
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS], ops))
    bytes_ = 576 * 2 * 300_000 + 32 * 64 * 1088 * 2            # bytes bind: 0.427 ms > 0.212
    assert got == pytest.approx(100 * 6 * (bytes_ / 819e9) / 0.004)
    assert read(_run([DECODE], {"jit__decode_program/fusion": 0.004})) is None   # no kernel
    assert read(_run(OLD, ops)) is None                         # a program without the fields
    assert read(_run([DECODE], None)) is None                   # no device trace
    assert read(_run([DECODE], ops, rehearsal=True)) is None


def test_moe_gmm_roofline_reads_decode_where_the_kernel_runs_there_else_the_chunks():
    read = M.load_reader("moe_gmm_roofline")
    in_decode = {"jit__decode_program/moe_gmm:bf16[512,2048]": 0.010}
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS], in_decode))
    assert got == pytest.approx(100 * ((140 * 50_331_648 + 64 * 2 * 4096 * 2) / 819e9) / 0.010)
    in_chunks = {"jit__chunk_program/moe_gmm:bf16[12288,2048]": 0.012,
                 "jit__final_chunk_program/moe_gmm:bf16[12288,2048]": 0.012,
                 "jit__decode_program/fusion": 0.010}
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL], in_chunks))
    bytes_ = 320 * 50_331_648 + 4096 * 2 * 4096 * 2
    assert got == pytest.approx(100 * (bytes_ / 819e9) / 0.024)
    assert read(_run(OLD, in_chunks)) is None and read(_run([CHUNK_SPAN, CHUNK_COUNTS], {})) is None
    assert read(_run([CHUNK_SPAN, CHUNK_COUNTS], None)) is None


def test_serve_moe_mfu_counts_the_windows_spans():
    read = M.load_reader("serve_moe_mfu")
    cfg = M.Cell(MAN, CELL).config
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL]))
    flops = (C.decode_step_flops(cfg, 32, 300_000, 64 * 32 / 32)
             + C.prefill_flops(cfg, 1024, 4096, 2048.0, head=False)
             + C.prefill_flops(cfg, 512, 8192, 2048 * 512 / 1024, head=True))
    assert got == pytest.approx(100 * flops / 20.0 / 197e12)
    assert read(_run(OLD)) is None and read(_run([DECODE], rehearsal=True)) is None


def test_moe_expert_load_reads_the_windows_chunk_calls():
    read = M.load_reader("moe_expert_load_max_over_mean")
    # mean rows a held expert in a call: 2048 / (32 held x 5 expert layers) = 12.8
    assert read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL])) == pytest.approx((20 / 12.8 + 16 / 12.8) / 2)
    assert read(_run(OLD)) is None and read(_run([DECODE])) is None


def test_latent_chunk_attn_roofline_reads_the_traced_chunk_calls():
    read = M.load_reader("latent_chunk_attn_roofline")
    ops = {"jit__chunk_program/latent_chunk_attn:bf16[1,64,1024,128]": 0.020,
           "jit__final_chunk_program/latent_chunk_attn:bf16[1,64,1024,128]": 0.030,
           "jit__decode_program/latent_attn:bf16[32,64,512]": 0.5}
    got = read(_run([DECODE, CHUNK_SPAN, CHUNK_COUNTS, FINAL], ops))
    # causal pairs: 1,024 tokens at 4,096 and 512 real tokens at 8,192; operations bind
    attended = (1024 * 4096 + 1024 * 1025 / 2) + (512 * 8192 + 512 * 513 / 2)
    flops = 2 * 64 * 320 * attended
    assert flops / 197e12 > (576 * 2 * (5120 + 8704) + 1536 * 64 * 320 * 2) / 819e9
    assert got == pytest.approx(100 * 6 * (flops / 197e12) / 0.050)
    assert C.latent_chunk_attention_call(M.Cell(MAN, CELL).config, 1536, attended, 13824) == {
        "flops": flops, "bytes": 576 * 2 * 13824 + 1536 * 64 * 320 * 2}
    assert read(_run([CHUNK_SPAN], {"jit__chunk_program/fusion": 0.02})) is None    # no kernel
    assert read(_run(OLD, ops)) is None                         # a program without the fields
    assert read(_run([CHUNK_SPAN], None)) is None and read(_run([DECODE], ops)) is None
    assert read(_run([CHUNK_SPAN], ops, rehearsal=True)) is None
