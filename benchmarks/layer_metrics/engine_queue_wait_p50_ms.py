"""Median time a request of the window waited in the engine's queue before
admission (``RequestOutput.queue_s``), over the window's finished requests."""


def read(run):
    return run["win"].get("queue_wait_p50_ms")
