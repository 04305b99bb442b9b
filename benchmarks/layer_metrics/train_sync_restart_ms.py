"""How long the device has nothing queued after each of ``fit``'s
log-cadence fences: from the close of a ``log_sync`` span (the fence on the
loss of the step just dispatched has returned, so the device's queue is
empty) to the close of the next ``step`` span (the next program is
dispatched). The median over the window's syncs: the sync at which the
harness starts the profiler is an outlier."""
import statistics

from benchmarks.harness import span_math


def read(run):
    got = span_math.records_of(run)
    gaps = span_math.sync_restarts(*got) if got else []
    return 1e3 * statistics.median(gaps) if gaps else None
