"""The chip's idle a decode step while the host sat in a blocking read: the
idle gaps of the run's trace reduction labelled ``program:device_wait`` or
with one of the reads inside it (``fetch_tokens``, ``fetch_counts``,
``fetch_keys``), over the traced calls of the decode program. With a chunk
queued behind the decode the fence is covered and this reads ~0; a
decode-only step pays the fence's way back here.

``None`` without a trace, in a rehearsal, where no decode program ran in the
traced stretch, and for a program that opens no ``fetch_tokens`` span (a
parent commit, a training cell)."""
from benchmarks.harness import trace_reduce
from benchmarks.layer_metrics.engine_dispatch_idle_share import idle_under

LABELS = ("device_wait", "fetch_tokens", "fetch_counts", "fetch_keys")


def read(run):
    idle = idle_under(run, LABELS, ("fetch_tokens",))
    if idle is None:
        return None
    _, calls = trace_reduce.program_stats(run["trace"], r"jit__decode_program")
    return 1e3 * idle / calls if calls else None
