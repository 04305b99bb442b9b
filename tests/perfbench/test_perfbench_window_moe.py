"""The ``window-moe`` cell's benchmark parts on the CPU: its rehearsal ends
``correct`` through the fixture manifest; ``counts_window_moe`` against hand
sums and the program's own parameter tree at the published widths; the
reference's weights are ``weights.leaf``'s and it imports nothing of the
program; each reader the cell brings on hand-made records — a number where the
spans and the trace carry what it reads, ``None`` where they do not (the
parent commit: no window fields); the configuration file against the catalog;
where the cell is listed."""
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.harness import counts_latent_moe
from benchmarks.harness import counts_window_moe as C
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_window_moe as ref
from benchmarks.harness import spans, weights

CELL = "k-exaone-ep8-d5.docs-backlog-wide"
CONFIG = "k-exaone-236b-a23b-ep8-d5"
MAN = M.load_manifest()
SEED = 3_900_000_037
FIXTURE_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                                "window_moe", "manifest.json")
NEW_READERS = ("window_moe_decode_stream_roofline", "serve_window_moe_mfu",
               "window_attn_roofline")


def test_cell_rehearsal_ends_correct_through_the_fixture_manifest(capsys):
    """The manifest's own entries for the cell plus the readers that wait in
    the fixture manifest: the run ends correct, the span readers find their
    records, the trace readers have no chip to read."""
    rc = bench_run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                         "--trace", "1", "--rehearsal", "--manifest", FIXTURE_MANIFEST])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["failed"] == 0
    assert line["compared"]["logit_gap_max"] == [0.0, 1.0]      # a share of the limits
    assert set(line["metrics_reported"]) >= {"engine_batch_occupancy", "serve_state_peak_bytes",
                                             "setup_trace_lower_s",
                                             "moe_expert_load_max_over_mean"}
    assert not set(line["metrics_reported"]) & {
        "window_moe_decode_stream_roofline", "hybrid_paged_attn_roofline", "moe_gmm_roofline",
        "window_attn_roofline"}
    assert "metrics" not in line and "device" not in line       # nothing from a CPU
    win = next(json.loads(l) for l in out.out.splitlines() if '"event": "window"' in l)
    assert win["compiles_in_window"] == 0 and win["requests_finished_in_window"] > 0
    assert win["attention_impls"]["decode"] == "xla experts=dense window=ring-xla"


def _make_against_leaf():
    cell = M.Cell(MAN, CELL)
    cell.apply_rehearsal()
    return cell.config


def test_the_references_weights_are_weights_leaf_and_it_imports_nothing_of_the_program():
    import inspect
    cfg = _make_against_leaf()
    made = ref.make_layer(cfg, SEED, 3)
    for name in ("attn/q_norm/scale", "mlp/router_bias", "mlp/w_up", "attn_post_norm/scale"):
        want = weights.leaf(weights.seed_operand(SEED), f"transformer/block_3/{name}",
                            ref.layer_shapes(cfg, 3)[name], jnp.float32)
        # one float32 step at 1: the gains' 1 + x is fused in one program, not the other
        np.testing.assert_allclose(np.asarray(made[name]), np.asarray(want), rtol=0, atol=1.2e-7)
    text = inspect.getsource(ref)
    assert "k8s_distributed_deeplearning_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text and "lax.scan(one_expert" in text


def test_counts_against_hand_sums_and_the_programs_tree_at_the_published_widths():
    cfg = M.Cell(MAN, CELL).config
    assert C.mixers(cfg) == (4, 1) and C.feed_forwards(cfg) == (1, 4)     # L L L G L
    assert C.attention_params(cfg) == 113_246_208 + 256
    assert C.router_params(cfg) == 786_432 + 128
    assert C.expert_params(cfg) == 37_748_736
    assert C.dense_layer_params(cfg) == 452_997_376
    assert C.expert_layer_params(cfg) == 755_773_824
    assert 2 * 19_200 * 6_144 == 235_929_600
    total = 452_997_376 + 4 * 755_773_824 + 235_929_600 + 6_144
    assert C.param_count(cfg) == total == 3_712_028_416       # 7.42 GB in bf16
    every, active = C.published_params(cfg)
    assert round(every / 1e9, 1) == 236.6 and round(active / 1e9, 1) == 23.7
    assert C.kv_bytes_per_token(cfg) == 4_096                  # the global layer alone
    assert C.ring_bytes_per_slot(cfg) == 4 * 512 * 1024        # 512 KiB a window layer
    # what moe_gmm_roofline counts an expert at: its three products, 75.5 MB
    assert counts_latent_moe.expert_bytes(cfg) == C.expert_bytes(cfg) == 75_497_472
    least = C.decode_stream_bytes(cfg, context_tokens=64 * 9_000, window_tokens=64 * 128,
                                  experts_touched=4 * 16)
    assert 9.0e9 < least < 9.8e9                               # ~11.5 ms at 819 GB/s
    # the program's own tree, from shapes alone, holds exactly that many
    from benchmarks.harness import family_window_moe
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    model = PatternLM(*family_window_moe.program_config(cfg, 17_408))
    tree = jax.eval_shape(lambda: nn.meta.unbox(model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)) == total
    names = weights.named_leaves(tree)
    for l in (0, 3):
        for name, shape in ref.layer_shapes(cfg, l).items():
            assert names[f"transformer/block_{l}/{name}"].shape == shape
    for name, shape in ref.outer_shapes(cfg).items():
        assert names[name].shape == shape


def _run(records, device_ops=None, programs=None, trace=(10.0, 14.0), rehearsal=False):
    tr = spans.BenchTracer()
    tr.records = records
    win = {"tracer": tr, "t_open": 0.0, "t_close": 20.0,
           "trace": {"t0": trace[0], "t1": trace[1]} if trace else None}
    red = None
    if device_ops is not None:
        red = {"device_ops": [[k, v] for k, v in device_ops.items()],
               "device_op_calls": {k: 1 for k in device_ops}, "programs": programs or {}}
    return {"cell": M.Cell(MAN, CELL), "win": win, "trace": red, "rehearsal": rehearsal,
            "device_kind": "TPU v5 lite", "sut": {}, "end_to_end": {}}


DECODE = ("decode", 11.0, 11.02, {"active": 64, "rows": 64, "context_tokens": 600_000,
                                  "window_tokens": 64 * 128, "moe_assignments": 2048,
                                  "moe_experts_touched": 64, "moe_max_rows": 9})
CHUNK = ("prefill_counts", 11.1, 11.15, {"chunk": 2048, "tokens": 2048, "start": 2048,
                                         "window_pairs": 2048 * 128, "moe_assignments": 2048,
                                         "moe_experts_touched": 64, "moe_max_rows": 60})
FINAL = ("prefill", 11.2, 11.25, {"bucket": 2048, "tokens": 100, "start": 0,
                                  "window_pairs": 100 * 101 // 2,
                                  "moe_assignments": 2048, "moe_experts_touched": 64,
                                  "moe_max_rows": 50})
# what the parent's program writes for another model: no window fields
OLD = [("decode", 11.0, 11.02, {"active": 32, "rows": 32, "context_tokens": 9000,
                                "moe_assignments": 64, "moe_experts_touched": 60}),
       ("prefill", 11.1, 11.15, {"bucket": 1024, "tokens": 512, "start": 0,
                                 "moe_assignments": 1024, "moe_experts_touched": 60})]


def test_window_moe_decode_stream_roofline_reads_the_bytes_a_step_must_read():
    read = M.load_reader("window_moe_decode_stream_roofline")
    progs = {"jit__decode_program": {"calls": 2, "seconds": 0.028}}
    second = ("decode", 12.0, 12.02, dict(DECODE[3], context_tokens=640_000))
    got = read(_run([DECODE, second], {"jit__decode_program/fusion": 0.02}, progs))
    cfg = M.Cell(MAN, CELL).config
    least = (C.decode_stream_bytes(cfg, 600_000, 64 * 128, 64)
             + C.decode_stream_bytes(cfg, 640_000, 64 * 128, 64)) / 2 / 819e9
    assert got == pytest.approx(100 * least / 0.014) and 50 < got < 100
    assert read(_run(OLD, {"x": 1.0}, progs)) is None           # no window_tokens field
    assert read(_run([DECODE], None)) is None                   # no device trace
    assert read(_run([DECODE], {"x": 1.0}, progs, rehearsal=True)) is None
    assert read(_run([DECODE], {"x": 1.0}, progs, trace=None)) is None
    assert read(_run([DECODE], {"x": 1.0}, {})) is None         # no decode program traced


def test_serve_window_moe_mfu_counts_every_span_in_the_window():
    read = M.load_reader("serve_window_moe_mfu")
    cfg = M.Cell(MAN, CELL).config
    got = read(_run([DECODE, CHUNK, FINAL]))
    flops = (C.decode_step_flops(cfg, 64, 600_000, 64 * 128, 2048)
             + C.prefill_flops(cfg, 2048, 2048, 2048 * 128, 2048, head=False)
             + C.prefill_flops(cfg, 100, 0, FINAL[3]["window_pairs"], 2048 * 100 / 2048,
                               head=True))
    assert got == pytest.approx(100 * flops / 20.0 / 197e12) and 0 < got < 100
    assert read(_run(OLD)) is None                              # the parent's records
    assert read(_run([DECODE], rehearsal=True)) is None
    assert read({"win": {}, "rehearsal": False, "cell": M.Cell(MAN, CELL)}) is None


def test_hybrid_paged_attn_roofline_counts_the_one_global_layer():
    """The accepted reader on this cell's records: the global layer's paged
    kernel in decode, the context of the live rows times ONE attention layer
    (``counts_conv_moe.mixers`` counts ``full_attention`` among the layers
    run)."""
    from benchmarks.harness import counts, counts_conv_moe
    read = M.load_reader("hybrid_paged_attn_roofline")
    cfg = M.Cell(MAN, CELL).config
    assert counts_conv_moe.mixers(cfg) == (0, 1)
    got = read(_run([DECODE], {"jit__decode_program/paged_attn:bf16[64,1,64,128]": 0.004}))
    c = counts_conv_moe.paged_attention_call(cfg, 64, 600_000)
    least, bound = counts.roofline_seconds(c["flops"], c["bytes"], {"bf16_flops": 197e12,
                                                                   "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and got == pytest.approx(100 * least / 0.004) and 0 < got < 100


def test_window_attn_roofline_reads_the_band_of_the_traced_chunks():
    read = M.load_reader("window_attn_roofline")
    cfg = M.Cell(MAN, CELL).config
    chunk = ("prefill", 11.1, 11.15, dict(CHUNK[3]))
    ops = {"jit__chunk_program/window_attn:bf16[1,2048,8192]": 0.0008,
           "jit__final_chunk_program/window_attn:bf16[1,2048,8192]": 0.0002,
           "jit__decode_program/fusion": 0.5}
    got = read(_run([DECODE, chunk, FINAL], ops))
    c = C.window_attention_call(cfg, 2048 + 100, 2048 * 128 + 5050)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    assert c["bytes"] / 819e9 > c["flops"] / 197e12                # the bytes bind
    assert got == pytest.approx(100 * 4 * least / 0.001) and 0 < got < 100
    assert read(_run([chunk], {"jit__chunk_program/fusion": 0.004})) is None   # XLA path
    assert read(_run(OLD, ops)) is None                         # no window_pairs field
    assert read(_run([chunk], None)) is None
    assert read(_run([chunk], ops, rehearsal=True)) is None


@pytest.mark.parametrize("metric, listed", [
    ("itl_p95_ms", True), ("decode_step_device_ms", True), ("engine_batch_occupancy", True),
    ("setup_trace_lower_s", True), ("moe_gmm_roofline", True),
    ("moe_expert_load_max_over_mean", True),
    # not reported: throughput spreads too widely over seeds with this length mix
    # (PERF.md section 2); a dense GQA count over every layer; a name match on
    # `attn`; the latent family's count
    ("serve_out_tokens_per_s", False), ("kv_pool_peak_fill", False), ("serve_mfu", False),
    ("paged_attn_roofline", False), ("serve_moe_mfu", False)])
def test_the_cell_is_listed_where_a_reader_reads_it_rightly(metric, listed):
    m = next(x for x in MAN["per_layer"] + MAN["end_to_end"] if x["name"] == metric)
    assert (CELL in m["workloads"]) == listed


def test_the_new_readers_wait_in_a_fixture_manifest_for_a_benchmark_pr():
    """As PRs 31, 33 and 37's: ``test_perfbench_flash_roofline.py:75`` holds
    ``flash_attn_roofline`` to the LAST place of ``per_layer`` and a new entry
    may only be appended, so this configuration's readers are files beside the
    others, listed in ``fixtures/window_moe/manifest.json`` — the manifest's
    own entries for the cell plus theirs — which ``run.py --manifest`` takes."""
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
                     + ["hybrid_paged_attn_roofline", "serve_state_peak_bytes"])
    for m in fx["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms" and callable(
            M.load_reader(m["name"]))
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"] == "%") == ("roofline" in m["name"] or "mfu" in m["name"])
        assert m["layer"] == {"window_attn_roofline": "kernels",
                              "hybrid_paged_attn_roofline": "kernels",
                              "serve_window_moe_mfu": "whole step against the chip",
                              "serve_state_peak_bytes": "serving engine"}.get(m["name"], "models")
    assert {m["layer"] for m in fx["per_layer"]} <= {m["layer"] for m in MAN["per_layer"]}


def test_the_configuration_file_holds_the_catalogs_config():
    """Every number of the catalog entry's ``config`` under the same key, but
    the four cut and listed in ``reduced`` with their published values."""
    cfg = M.Cell(MAN, CELL).config
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
        "intermediate_size": 18432, "max_position_embeddings": 262144,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048, "n_group": 1,
        "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_key_value_heads": 8, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_pattern": "LLLG", "tie_word_embeddings": False, "topk_group": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0]}
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 12
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert cfg["sliding_windows"] == [128, 128, 128, 0] * 12
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                              "num_nextn_predict_layers"]
    assert {k: cfg[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200,
        "num_nextn_predict_layers": 0}
    assert cfg["source_values"] == {"num_hidden_layers": 48, "num_experts": 128,
                                    "vocab_size": 153600, "num_nextn_predict_layers": 1}
    assert cfg["num_experts_published"] == cfg["router_outputs"] == 128
    assert cfg["family"] == "window-moe" and cfg["torch_dtype"] == "bfloat16"
    assert {"block_norms", "qk_norm", "positions", "window", "selection_bias",
            "torch_dtype"} <= set(cfg["assumed"])
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert all(len(e["why"]) <= 200 for e in MAN["configs"] + MAN["workloads"])
    eng = M.Cell(MAN, CELL).options["engine"]
    assert eng == {"max_seq_len": 17408, "num_slots": 64, "max_queue": 4096, "min_bucket": 2048,
                   "prefill_chunk_tokens": 2048, "prefix_cache_mb": 0, "kv_pool_pages": 28672,
                   "page_tokens": 32}
    # docs-backlog's lengths, unchanged; only the backlog is wider
    mine, docs = M.Cell(MAN, CELL).traffic, M.Cell(MAN, "sarvam-105b-ep4-d6.docs-backlog").traffic
    for k in ("prompt_tokens", "output_tokens", "group", "pairing_seed", "ramp_seconds",
              "sample_requests", "arrivals", "rehearsal"):
        assert mine[k] == docs[k]
    assert (mine["backlog_requests"], mine["ramp_requests"]) == (512, 96)
    assert M.Cell(MAN, CELL).chips == 1
