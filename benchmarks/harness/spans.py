"""In-memory spans and counters of one run — the benchmark's own recorder.

``BenchTracer`` has the surface ``train.loop.fit`` and ``ServeEngine`` ask of
a tracer (``span(name, **fields)`` as a context manager, ``last_span``,
``logger``), keeps every span as ``(name, t0, t1, fields)`` on the
``time.perf_counter`` clock, writes nothing, and — while a profiler trace is
being taken — also enters a ``jax.profiler.TraceAnnotation`` named
``program:<name>`` so that the program's host spans land on the profiler's
clock beside the device's operations (the idle-gap attribution reads them).
"""
from __future__ import annotations

import contextlib
import time


class _Span:
    __slots__ = ("tr", "name", "fields", "t0", "ann")

    def __init__(self, tr, name, fields):
        self.tr, self.name, self.fields, self.ann = tr, name, fields, None

    def __enter__(self):
        if self.tr.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.tr.prefix + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.tr.records.append((self.name, self.t0, t1, self.fields))
        self.tr.last_span = self.name


class BenchTracer:
    logger = None
    enabled = True

    def __init__(self, prefix: str = "program:"):
        self.records: list[tuple] = []
        self.last_span: str | None = None
        self.annotate = False
        self.prefix = prefix

    def span(self, name: str, **fields):
        return _Span(self, name, fields)

    def total_s(self, name: str, t_lo: float, t_hi: float) -> float:
        """Seconds of spans *name* that fall inside [t_lo, t_hi] (clipped)."""
        return sum(max(0.0, min(t1, t_hi) - max(t0, t_lo))
                   for n, t0, t1, _ in self.records if n == name)

    def count(self, name: str, t_lo: float, t_hi: float) -> int:
        return sum(1 for n, t0, t1, _ in self.records
                   if n == name and t_lo <= t1 <= t_hi)


@contextlib.contextmanager
def bench_annotation(name: str, on: bool):
    """A host span of the benchmark's own (``bench:<name>``) on the
    profiler's clock; free when no trace is being taken."""
    if not on:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation("bench:" + name):
        yield


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) through
    ``jax.monitoring``: inside the measured window the count has to be 0."""

    _installed = None

    def __init__(self):
        self.events: list[str] = []
        self.active = False

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax.monitoring as mon
            c = cls()

            def on_duration(name, secs, **kw):
                if c.active and ("backend_compile" in name
                                 or "cache_retrieval" in name):
                    c.events.append(name)
            mon.register_event_duration_secs_listener(on_duration)
            cls._installed = c
        return cls._installed

    def start(self):
        self.events.clear()
        self.active = True

    def stop(self) -> int:
        self.active = False
        return len(self.events)


class GcCounter:
    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self._t = None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def __enter__(self):
        import gc
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._cb)
