"""The Mamba-1 decode update's share of its roofline: the least time to read
and write the float32 state of the rows the traced decode steps advanced
(the ``decode`` spans' ``state_rows`` x 2 x 327,680 B a row a layer at the
published widths, ``counts_jamba.selective_update_call``, x the Mamba
layers — the bytes bind: 5 operations an element against 8 bytes) at the
chip's HBM rate, over the device time of the ``selective_update`` kernel
inside ``_decode_program``. The convolution's tail (8.6 % of a slot's
``state_bytes_moved``) is not the kernel's and is left out.

Nothing to read (``None``) where the program has no such span field or the
trace no such kernel (a model without Mamba-1 layers, the parent commit) —
and where the decode program moves a layer's arena between HBM and VMEM
with copies of its own around the kernel (an arena-shaped ``copy-done`` in
``_decode_program``): the kernel then reads and writes VMEM, its time holds
none of the HBM traffic the count is of, and no share of HBM's roofline can
be read from it (PERF.md section 5, PR 37: 252 % on the first trace)."""
from benchmarks.harness import counts_jamba, peaks, reference_jamba, span_math, trace_reduce


def read(run):
    red, win = run.get("trace"), run["win"]
    got = span_math.records_of(run)
    if not red or run["rehearsal"] or got is None or not win.get("trace"):
        return None
    secs, _ = trace_reduce.op_seconds(red, r"jit__decode_program/.*selective_update")
    rows = [f["state_rows"] for _, _, _, f in span_math.inside(
        got[0], "decode", win["trace"]["t0"], win["trace"]["t1"]) if "state_rows" in f]
    if not secs or not rows:
        return None
    cfg = run["cell"].config
    e, n, _, _ = reference_jamba.mamba_sizes(cfg)
    staged, _ = trace_reduce.op_seconds(
        red, rf"jit__decode_program/copy-done:f32\[\d+,{n},{e}\]")
    if staged:
        return None
    call = counts_jamba.selective_update_call(cfg, sum(rows))
    least = (counts_jamba.layers(cfg)[0] * call["bytes"]
             / peaks.peaks_for(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / secs
