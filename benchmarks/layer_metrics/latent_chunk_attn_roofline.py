"""The expanded chunk kernel's share of its roofline: the least time the chip
could take for the attention of the prefill chunks that ran in the traced
stretch — the larger of operations / 197 TFLOP/s (every attended pair under
the causal mask through 192-wide keys and 128-wide values in 64 heads: the
operations bind) and bytes / 819 GB/s (``counts_latent_moe.
latent_chunk_attention_call``), per layer times the layers — over the device
time of the ``latent_chunk_attn`` kernel inside the chunk programs' runs.

The calls are the ``prefill`` spans that lie in the traced stretch; each
carries its real ``tokens`` and its ``start``. A final chunk computes its
whole bucket and is counted by its real tokens; the kernel's up-projection
of the prefix, repeated by every chunk, is not counted: the share reads low
rather than high."""
from benchmarks.harness import counts, counts_latent_moe, peaks, span_math, trace_reduce

KERNEL = r"jit__(final_)?chunk_program/.*latent_chunk_attn"


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    calls = [f for _, _, _, f in span_math.inside(
        got[0], "prefill", win["trace"]["t0"], win["trace"]["t1"])
        if "tokens" in f and "start" in f]
    if not secs or not calls:
        return None
    cfg = run["cell"].config
    c = counts_latent_moe.latent_chunk_attention_call(
        cfg, sum(f["tokens"] for f in calls),
        sum(f["tokens"] * f["start"] + f["tokens"] * (f["tokens"] + 1) / 2 for f in calls),
        sum(f["start"] + f["tokens"] for f in calls))
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                       peaks.peaks_for(run["device_kind"]))
    return 100.0 * cfg["num_hidden_layers"] * least / secs
