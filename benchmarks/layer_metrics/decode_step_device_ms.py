"""Device time of one decode step: seconds of ``_decode_program`` on the
``XLA Modules`` line over its calls."""
from benchmarks.harness import trace_reduce


def read(run):
    red = run.get("trace")
    if not red:
        return None
    secs, calls = trace_reduce.program_stats(red, r"jit__decode_program")
    return 1e3 * secs / calls if calls else None
