"""The expert products' share of their roofline: the least time the chip
could take — the larger of bytes / 819 GB/s (each held expert that had a row
reads its 50 MB of weights once a call: ``moe_experts_touched``) and
operations / 197 TFLOP/s (``moe_assignments`` rows through three products) —
over the device time of the ``moe_gmm`` kernel in the traced stretch. The
count reads the work (the spans' counters), whatever implements it.

Where the decode program runs the grouped kernel, the decode steps are read;
where decode keeps the plain XLA form (it won on the chip at ~2 rows an
expert, PERF.md 6) the kernel lives in the chunk programs and their own
counts are read: a final chunk's from its ``prefill`` span, an intermediate
chunk's from the ``prefill_counts`` record the program writes at the step's
next fence (a few milliseconds later: a call at the stretch's edge may fall
on the other side of it than its kernel's time, one in sixty)."""
from benchmarks.harness import counts, counts_latent_moe, peaks, span_math, trace_reduce

WHERE = ((r"jit__decode_program/.*moe_gmm", ("decode",)),
         (r"jit__(final_)?chunk_program/.*moe_gmm", ("prefill", "prefill_counts")))


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    for kernel, spans in WHERE:
        secs, _ = trace_reduce.op_seconds(red, kernel)
        calls = [f for span in spans for _, _, _, f in span_math.inside(
            got[0], span, win["trace"]["t0"], win["trace"]["t1"])
            if "moe_assignments" in f]
        if secs and calls:
            c = counts_latent_moe.expert_products(
                run["cell"].config, sum(f["moe_experts_touched"] for f in calls),
                sum(f["moe_assignments"] for f in calls))
            least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                               peaks.peaks_for(run["device_kind"]))
            return 100.0 * least / secs
    return None
