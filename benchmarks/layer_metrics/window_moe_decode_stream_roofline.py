"""How near the decode program runs to the time it takes just to read what a
step has to, for the ``window-moe`` family: over the traced decode steps,
``counts_window_moe.decode_stream_bytes`` — the weights of every held expert a
row landed on (``moe_experts_touched``, 75.5 MB each at the published widths),
every other weight once (not the embedding table), the global layer's K/V of
the live contexts (``context_tokens``) and the ring entries the live rows
attend in each window layer (``window_tokens``) — at 819 GB/s, over the decode
program's device time in the traced stretch (``XLA Modules`` line). The count
is a lower bound, so the share cannot pass 100 %. ``None`` where the decode
spans carry no ``window_tokens`` (a program with no window layer)."""
from benchmarks.harness import counts_window_moe, peaks, span_math, trace_reduce


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, calls = trace_reduce.program_stats(red, r"jit__decode_program")
    steps = [f for _, _, _, f in span_math.inside(
        got[0], "decode", win["trace"]["t0"], win["trace"]["t1"])
        if "moe_experts_touched" in f and "window_tokens" in f]
    if not secs or not calls or not steps:
        return None
    cfg = run["cell"].config
    bytes_ = sum(counts_window_moe.decode_stream_bytes(
        cfg, f["context_tokens"], f["window_tokens"], f["moe_experts_touched"])
        for f in steps)
    # the traced programs and the traced spans are the same steps but for the
    # edges: per step on both sides
    least = bytes_ / len(steps) / peaks.peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (secs / calls)
