"""Host time of one engine iteration outside the waits for the device:
the ``engine_step`` spans that lie in the window, minus the ``device_wait``
spans inside them (the blocking reads of a decode's tokens and of a first
token), over the number of steps. What is left is sweeps, admission, the
prefill and decode dispatches, page growth, the per-token loop and the
epilogue — the time a device with an empty queue waits for the host."""
from benchmarks.harness import span_math


def read(run):
    got = span_math.engine_steps(run)
    if got is None:
        return None
    records, steps, lo, hi = got
    waits = span_math.inside(records, "device_wait", lo, hi)
    return 1e3 * (span_math.seconds(steps) - span_math.seconds(waits)) / len(steps)
