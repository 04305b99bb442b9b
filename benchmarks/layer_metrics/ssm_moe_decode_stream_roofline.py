"""How near the decode program runs to the time it takes just to read and
write what a step has to, for the ``ssm-moe`` family: over the traced decode
steps, ``counts_ssm_moe.decode_stream_bytes`` — the weights of every held
expert a row landed on (``moe_experts_touched``), every other weight once
(mixers, routers, latent projections, shared experts, the head; not the
embedding table), the K/V of the live contexts (``context_tokens``) and the
advanced rows' state read AND written (``state_rows``) — at 819 GB/s, over the
decode program's device time in the traced stretch (``XLA Modules`` line). The
count is a lower bound, so the share cannot pass 100 %."""
from benchmarks.harness import counts_ssm_moe, peaks, span_math, trace_reduce


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, calls = trace_reduce.program_stats(red, r"jit__decode_program")
    steps = [f for _, _, _, f in span_math.inside(
        got[0], "decode", win["trace"]["t0"], win["trace"]["t1"])
        if "moe_experts_touched" in f and "state_rows" in f]
    if not secs or not calls or not steps:
        return None
    cfg = run["cell"].config
    bytes_ = sum(counts_ssm_moe.decode_stream_bytes(
        cfg, f["state_rows"], f["context_tokens"], f["moe_experts_touched"]) for f in steps)
    # the traced programs and the traced spans are the same steps but for the
    # edges: per step on both sides
    least = bytes_ / len(steps) / peaks.peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (secs / calls)
