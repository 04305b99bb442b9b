"""The latent experts' grouped products' share of their roofline — as
``moe_gmm_roofline``, with this family's count (``counts_ssm_moe
.expert_products``: TWO products of ``moe_latent_size x moe_intermediate_size``
an expert, 11 MB of weights, where the accepted reader counts three of
``hidden_size x moe_intermediate_size``): the larger of bytes / 819 GB/s (each
held expert that had a row reads its weights once a call:
``moe_experts_touched``) and operations / 197 TFLOP/s (``moe_assignments`` rows
through both products), over the device time of the ``moe_gmm`` kernel in the
traced stretch — in ``_decode_program`` if it runs there, else in the chunk
programs with their own counts (a final chunk's from its ``prefill`` span, an
intermediate chunk's from its ``prefill_counts`` record). ``None`` where no
program runs the kernel (every program in the dense form)."""
from benchmarks.harness import counts, counts_ssm_moe, peaks, span_math, trace_reduce

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
            c = counts_ssm_moe.expert_products(
                run["cell"].config, sum(f["moe_experts_touched"] for f in calls),
                sum(f["moe_assignments"] for f in calls))
            least, _ = counts.roofline_seconds(c["flops"], c["bytes"],
                                               peaks.peaks_for(run["device_kind"]))
            return 100.0 * least / secs
    return None
