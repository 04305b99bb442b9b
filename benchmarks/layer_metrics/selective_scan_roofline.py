"""The Mamba-1 chunk scan's share of its roofline: the least time the chip
could take for the scans of the prefill chunks that ran in the traced
stretch — the larger of bytes / HBM rate and operations / peak
(``counts_jamba.selective_scan_call`` over the width each chunk's program
walks: an intermediate chunk's ``chunk`` tokens, a final chunk's whole
``bucket``), times the Mamba layers — over the device time of the
``selective_scan`` kernel inside the chunk programs.

The calls are the ``prefill`` spans that lie in the traced stretch. Nothing
to read where the trace has no such kernel or no chunk ran in it (a model
without Mamba-1 layers, the parent commit): ``None``."""
from benchmarks.harness import counts, counts_jamba, peaks, span_math, trace_reduce

KERNEL = r"jit__(final_)?chunk_program/.*selective_scan"


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    widths = [f.get("chunk", f.get("bucket")) for _, _, _, f in span_math.inside(
        got[0], "prefill", win["trace"]["t0"], win["trace"]["t1"])]
    widths = [w for w in widths if w]
    if not secs or not widths:
        return None
    cfg = run["cell"].config
    c = counts_jamba.selective_scan_call(cfg, sum(widths))
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks.peaks_for(run["device_kind"]))
    return 100.0 * counts_jamba.layers(cfg)[0] * least / secs
