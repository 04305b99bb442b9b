"""The fullest the paged KV pool got in the window: the largest
``pages_used / pages_total`` over the window's ``epilogue`` spans (the
engine reads the pool's counters once a step and hands them to the span).
A gauge — it says whether pages or slots bind; the manifest's ``better`` is
``lower`` only because it asks for one."""
from benchmarks.harness import span_math


def read(run):
    got = span_math.records_of(run)
    if got is None:
        return None
    records, t_open, t_close = got
    fills = [f["pages_used"] / f["pages_total"]
             for _, _, _, f in span_math.inside(records, "epilogue", t_open, t_close)
             if f.get("pages_total")]
    return 100.0 * max(fills) if fills else None
