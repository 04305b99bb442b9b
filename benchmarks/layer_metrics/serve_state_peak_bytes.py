"""The most per-slot state (a short convolution's tail, beside the page
pool) the engine held in the window: the largest ``state_bytes`` over the
window's ``epilogue`` spans (slots a request holds x bytes a slot). A gauge —
beside ``memory_peak_bytes`` it says how little of the chip the state takes;
the manifest's ``better`` is ``lower`` only because it asks for one."""
from benchmarks.harness import span_math


def read(run):
    got = span_math.records_of(run)
    if got is None:
        return None
    records, t_open, t_close = got
    held = [f["state_bytes"]
            for _, _, _, f in span_math.inside(records, "epilogue", t_open, t_close)
            if "state_bytes" in f]
    return max(held) if held else None
