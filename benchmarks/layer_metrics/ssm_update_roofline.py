"""The state-space decode update's share of its roofline: the least time to
read and write the float32 state of the rows the traced decode steps advanced
(the ``decode`` spans' ``state_rows`` x 2 x 4 MiB a row a layer,
``counts_ssm_moe.ssm_update_call``, x the Mamba-2 layers — the bytes bind: 5
operations an element against 8 bytes) at 819 GB/s, over the device time of
the ``ssm_update`` kernel inside ``_decode_program``. The convolution's tail
(1.4 % of a slot's ``state_bytes_moved``) is not the kernel's and is left
out. Nothing to read where the program has no such span field or the trace no
such kernel: ``None``."""
from benchmarks.harness import counts_ssm_moe, peaks, span_math, trace_reduce


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, r"jit__decode_program/.*ssm_update")
    rows = [f["state_rows"] for _, _, _, f in span_math.inside(
        got[0], "decode", win["trace"]["t0"], win["trace"]["t1"]) if "state_rows" in f]
    if not secs or not rows:
        return None
    cfg = run["cell"].config
    call = counts_ssm_moe.ssm_update_call(cfg, sum(rows))
    least = (counts_ssm_moe.layers(cfg)[0] * call["bytes"]
             / peaks.peaks_for(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / secs
