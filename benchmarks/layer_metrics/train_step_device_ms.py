"""Device time of one train step: busy seconds of the traced window (first
device) over the runs of the step program in it (``XLA Modules`` line)."""
from benchmarks.harness import trace_reduce


def read(run):
    red = run.get("trace")
    if not red or "steps" not in run["win"]:
        return None
    secs, calls = trace_reduce.program_stats(red, r"^jit_step")
    if not calls:
        return None
    return 1e3 * red["per_device"][0]["busy_ns"] / 1e9 / calls
