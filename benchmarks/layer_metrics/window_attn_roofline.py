"""The sliding-window attention kernel's share of its roofline in the prefill
chunks: the least time the chip could take for the traced chunks' window
attention — the larger of operations / 197 TFLOP/s (the band's (query, key)
pairs, ``window_pairs``, through scores and values in every query head) and
bytes / 819 GB/s (queries in, outputs out, each chunk's keys and values and
the ring's read once: ``counts_window_moe.window_attention_call``) — times the
window layers, over the device time of the ``window_attn`` kernel inside the
chunk programs' runs in the traced stretch. The kernel computes whole tiles
and masks what lies outside the band, which is not counted: the share cannot
reach 100 %.

The chunks are the ``prefill`` spans that lie in the traced stretch (an
intermediate chunk's and a final one's alike carry ``tokens`` and
``window_pairs``); a call cut by the stretch's edge falls on one side only.
``None`` where no ``window_attn`` ran (the parent's program, or an XLA path)."""
from benchmarks.harness import counts, counts_window_moe, peaks, span_math, trace_reduce

KERNEL = r"_(final_)?chunk_program/.*window_attn"


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    chunks = [f for _, _, _, f in span_math.inside(
        got[0], "prefill", win["trace"]["t0"], win["trace"]["t1"])
        if "window_pairs" in f]
    if not secs or not chunks:
        return None
    cfg = run["cell"].config
    c = counts_window_moe.window_attention_call(
        cfg, sum(f["tokens"] for f in chunks), sum(f["window_pairs"] for f in chunks))
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks.peaks_for(run["device_kind"]))
    return 100.0 * counts_window_moe.mixers(cfg)[0] * least / secs
