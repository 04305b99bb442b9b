"""The paged-attention kernel's share of its roofline in decode: the least
time the chip could take for the decode steps' attention — the larger of
operations / 197 TFLOP/s and bytes / 819 GB/s, where the bytes are the K/V
the live contexts need (``counts.paged_attention_call``; bytes bind) — over
the kernel's device time in the traced stretch.

The least time is worked out from every output token handed over while the
trace was on: each is one row of one decode step attending its own context
(prompt + tokens so far), in every layer. The stretch's two edge steps may
fall on one side only: about one part in eighty."""
from benchmarks.harness import counts, peaks, trace_reduce

# the Pallas kernel's custom call, inside the decode program's runs
KERNEL = r"_decode_program/.*(attn|custom-call)"


def read(run):
    red, win = run.get("trace"), run["win"]
    if not red or run["rehearsal"] or not win.get("traced_decode"):
        return None
    secs, _ = trace_reduce.op_seconds(red, KERNEL)
    if not secs:
        return None
    cfg = run["cell"].config
    t = win["traced_decode"]
    c = counts.paged_attention_call(cfg, [t["positions"]], sq=1)
    c["bytes"] += 2 * (t["tokens"] - 1) * cfg["num_attention_heads"] * cfg["head_dim"] * 2
    least, _ = counts.roofline_seconds(c["flops"], c["bytes"], peaks.peaks_for(run["device_kind"]))
    return 100.0 * cfg["num_hidden_layers"] * least / secs
