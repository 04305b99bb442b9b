"""Seconds of set-up spent tracing Python into jaxprs and lowering them to
MLIR — the part of compilation a warm persistent cache cannot answer:
the program's compile log (``backend.compile_log()``, fed by
``jax.monitoring``), phases ``trace`` and ``lower``, events received before
the window opened. Nested events count once (the log's seconds are the
union of the events' intervals)."""


def read(run):
    from k8s_distributed_deeplearning_tpu import backend
    if not hasattr(backend, "compile_log"):
        return None
    log, t_open = backend.compile_log(), run["win"]["t_open"]
    phases = ("trace", "lower")
    if not log.count(phases, t_hi=t_open):
        return None
    return log.seconds(phases, t_hi=t_open)
