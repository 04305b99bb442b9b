"""Device time of one prefill program run (128-token chunk or final chunk):
seconds of ``_chunk_program`` / ``_final_chunk_program`` on the ``XLA
Modules`` line over their calls."""
from benchmarks.harness import trace_reduce


def read(run):
    red = run.get("trace")
    if not red:
        return None
    secs, calls = trace_reduce.program_stats(red, r"jit__(final_)?chunk_program")
    return 1e3 * secs / calls if calls else None
