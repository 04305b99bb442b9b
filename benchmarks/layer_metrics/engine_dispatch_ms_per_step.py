"""Host time of one engine iteration spent getting programs into the device's
queue: the ``decode_call`` (register copies and the jitted decode call, to
its return), ``chunk_operands`` (a chunk's numpy operands), ``first_key`` (a
final chunk's ``PRNGKey``, made on the device and read back) and
``chunk_call`` (the jitted chunk call alone) spans, summed over the
``engine_step`` spans that lie in the window, over their number. The part of
``engine_host_ms_per_step`` that is dispatch — timed from inside, not by
subtraction. ``None`` for a program that opens none of these spans (a parent
commit, a training cell)."""
from benchmarks.harness import span_math

SPANS = ("decode_call", "chunk_operands", "first_key", "chunk_call")


def read(run):
    got = span_math.engine_steps(run)
    if got is None:
        return None
    records, steps, lo, hi = got
    parts = [s for name in SPANS for s in span_math.inside(records, name, lo, hi)]
    return 1e3 * span_math.seconds(parts) / len(steps) if parts else None
