"""Host time of one engine iteration AFTER the decode's fence: the ``emit``
span (cursor and token writes, ``on_token`` callbacks, finishing requests)
and the ``epilogue`` span (pool gauges, flight record), summed over the
``engine_step`` spans that lie in the window, over their number. This work
needs the step's tokens but not the device: it could run under the next
decode."""
from benchmarks.harness import span_math


def read(run):
    got = span_math.engine_steps(run)
    if got is None:
        return None
    records, steps, lo, hi = got
    after = (span_math.inside(records, "emit", lo, hi)
             + span_math.inside(records, "epilogue", lo, hi))
    return 1e3 * span_math.seconds(after) / len(steps) if after else None
