"""Tracing / profiling — the subsystem the reference lacks entirely.

SURVEY.md §5: the reference's closest thing to profiling is a rank-0
TensorBoard callback in the undeployed Keras variant
(``tensorflow_mnist_gpu.py:157-158``); nothing measures step time or device
activity. Here profiling is first-class and TPU-native:

- :func:`trace` / :class:`StepProfiler` wrap ``jax.profiler`` — the traces
  land in a TensorBoard/XProf-readable directory with host + device
  timelines, XLA HLO, and (on TPU) per-op MXU/HBM utilization;
- while either has a session on, every span of the program's
  :class:`telemetry.trace.Tracer` is also written into it as a
  ``program:<name>`` host annotation (:func:`_start` / :func:`_stop` flip
  the tracer's switch), so data wait, dispatch and blocking time sit
  beside the device's timeline.

Only the primary process should write traces (rank-0 discipline, parity with
``tensorflow_mnist.py:159``); pass ``enabled=is_primary()``.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

import jax

from k8s_distributed_deeplearning_tpu.telemetry import trace as _spans

__all__ = ["trace", "StepProfiler"]


def _start(log_dir: str) -> None:
    jax.profiler.start_trace(log_dir)
    _spans.profiler_session(jax.profiler.TraceAnnotation)


def _stop() -> None:
    _spans.profiler_session(None)
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str, enabled: bool = True) -> Iterator[None]:
    """Capture a jax.profiler trace for the enclosed block into *log_dir*
    (view with TensorBoard's profile plugin / XProf)."""
    if not enabled:
        yield
        return
    _start(log_dir)
    try:
        yield
    finally:
        _stop()


class StepProfiler:
    """Trace a step window inside a training loop.

    ``step_hook(step)`` starts the trace at the first step >= ``start_step``
    and stops it after ``num_steps`` — the standard "skip warmup/compile,
    profile steady state" recipe. The >= (with a run-once latch) matters for
    resumed runs: a restore past start_step still captures a window instead
    of silently skipping the user's profile request. Safe when the window
    never arrives (stop() is idempotent).
    """

    def __init__(self, log_dir: str, start_step: int, num_steps: int = 5,
                 enabled: bool = True):
        self.log_dir = log_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.enabled = enabled
        self._active = False
        self._done = False
        self._stop_step = start_step + num_steps

    def step_hook(self, step: int) -> None:
        if not self.enabled or self._done:
            return
        if not self._active and step >= self.start_step:
            _start(self.log_dir)
            self._active = True
            self._stop_step = step + self.num_steps
        elif self._active and step >= self._stop_step:
            self.stop()

    def stop(self) -> None:
        if self._active:
            _stop()
            self._active = False
            self._done = True
