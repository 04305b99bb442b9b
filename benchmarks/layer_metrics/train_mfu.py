"""Whole train step against the chip's peak: the operations the forward and
backward passes need per token (``counts.py``; recomputation not counted)
times tokens per second per chip, over the chip's bf16 peak.

The rate is the steady one — the tokens between two of ``fit``'s syncs over
the MEDIAN gap between syncs — because this metric is read in the traced run,
whose window also holds the profiler's start and stop."""
from benchmarks.harness import peaks


def read(run):
    win, cell = run["win"], run["cell"]
    if run["rehearsal"] or not win.get("sync_gap_median_s"):
        return None
    job = cell.traffic
    tokens = win["log_every"] * job["rows_per_chip"] * job["seq_len"]     # per chip
    rate = tokens / win["sync_gap_median_s"]
    flops = run["sut"]["family"].flops_per_token(cell.config, job["seq_len"])
    return 100.0 * flops * rate / peaks.peaks_for(run["device_kind"])["bf16_flops"]
