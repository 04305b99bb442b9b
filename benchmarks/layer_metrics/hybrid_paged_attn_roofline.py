"""The paged-attention kernel's share of its roofline in the decode steps of
a model whose attention layers are SOME of its layers (``conv-moe``): the
least time the chip could take for the traced decode steps' attention — the
larger of operations / 197 TFLOP/s and bytes / 819 GB/s, the bytes being the
K/V of the live contexts read once plus queries and outputs
(``counts_conv_moe.paged_attention_call``; bytes bind) — per attention
layer times the ATTENTION layers, over the device time of the ``paged_attn``
kernel inside the decode program's runs in the traced stretch.

The steps are the ``decode`` spans that lie in the traced stretch; each
carries its live ``rows`` and ``context_tokens``. A step cut by the stretch's
edge falls on one side only: about one part in a hundred."""
from benchmarks.harness import counts, counts_conv_moe, peaks, span_math, trace_reduce

KERNEL = r"_decode_program/.*paged_attn"


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    steps = [f for _, _, _, f in span_math.inside(
        got[0], "decode", win["trace"]["t0"], win["trace"]["t1"])
        if "context_tokens" in f]
    if not secs or not steps:
        return None
    cfg = run["cell"].config
    c = counts_conv_moe.paged_attention_call(
        cfg, sum(f["rows"] for f in steps), sum(f["context_tokens"] for f in steps))
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks.peaks_for(run["device_kind"]))
    return 100.0 * counts_conv_moe.mixers(cfg)[1] * least / secs
