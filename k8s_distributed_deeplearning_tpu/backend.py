"""The device this process runs on, and where its compiled programs are kept.

Two questions every entry point and every Pallas kernel asks, answered in
one place so the answers cannot drift:

- *which device?* ``on_tpu()`` reads what JAX reports. There is no
  ``except``: a backend that fails to initialise raises here instead of
  quietly selecting interpret mode or a CPU run. (With ``JAX_PLATFORMS``
  unset and no reachable chip, JAX itself logs a libtpu error and hands back
  the CPU — which is why every entry point stamps
  ``parallel.mesh.Topology.device_fields()`` into its first event, and why
  ``chip_smoke.py`` pins ``JAX_PLATFORMS=tpu``.)
- *where is the compile cache?* ``use_compile_cache()``: the directory named
  by ``JAX_COMPILATION_CACHE_DIR`` when the deployment sets it (JAX reads
  the variable itself, nothing is set in code), else ``<checkout>/.jax_cache``.
  The path is part of the cache key's neighbourhood on disk — a directory
  that moves never hits — so it is never a temp name, a pid or a time.
- *what did compiling cost?* ``compile_log()``: every jaxpr trace, lowering,
  backend compile and persistent-cache retrieval JAX reports through
  ``jax.monitoring``, kept in memory from ``use_compile_cache()`` on. A
  restart that pays a minute before its first step shows in which phase.

jax is imported inside the functions: control-plane processes (``launch
render``/``validate``/``run-local``, the smoke's parent) import the package
without initialising a backend.
"""
from __future__ import annotations

import os
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (Pallas kernels compile
    through Mosaic there and run in the interpreter everywhere else)."""
    import jax
    return jax.devices()[0].platform == "tpu"


def device_bytes_in_use() -> list[int | None]:
    """``memory_stats()["bytes_in_use"]`` per local device, in
    ``jax.local_devices()`` order (None where the backend keeps no allocator
    stats, e.g. CPU). Per device, not summed: on a multi-chip host the
    question is whether EVERY chip holds its share."""
    import jax
    out: list[int | None] = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append(int(stats["bytes_in_use"])
                   if stats and "bytes_in_use" in stats else None)
    return out


def compile_cache_dir() -> str:
    """Where compiled programs are kept: ``$JAX_COMPILATION_CACHE_DIR`` if
    set, else ``<checkout>/.jax_cache``. Pure — no jax import."""
    return os.environ.get(CACHE_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return the directory; start the :func:`compile_log`. Call first
    thing in an entry point, before anything compiles. When the environment
    names the directory, JAX has already read it and nothing is set here."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    compile_log()
    return path


# jax.monitoring duration events -> the phase names of the compile log.
# ``backend_compile`` spans JAX's whole compile-or-load call, so on a cache
# hit it contains that hit's ``cache_retrieval``; ``cache_saved`` is the
# compile time the hit's entry recorded minus the retrieval (JAX's figure).
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved",
}


class CompileLog:
    """``(perf_counter at receipt, phase, seconds, function name or None)``
    per compile-phase event of this process, oldest first. Appends come from
    whichever thread compiles; readers take a snapshot.

    JAX reports a jit traced inside another's trace as an event of its own,
    inside the outer one's seconds, so a phase's seconds are NOT the sum of
    its events: :meth:`seconds` is the length of the union of the events'
    intervals ``[receipt - seconds, receipt]`` — wall time in that phase."""

    def __init__(self):
        self._events: list[tuple[float, str, float, str | None]] = []

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        phase = COMPILE_PHASES.get(event)
        if phase is not None:
            self._events.append((time.perf_counter(), phase, float(seconds),
                                 kw.get("fun_name")))

    def events(self) -> list[tuple[float, str, float, str | None]]:
        return list(self._events)

    def _in(self, phase, t_lo: float, t_hi: float) -> list[tuple[float, float]]:
        phases = (phase,) if isinstance(phase, str) else phase
        return [(t, s) for t, p, s, _ in self.events()
                if p in phases and t_lo <= t <= t_hi]

    def count(self, phase: str | tuple[str, ...], t_lo: float = float("-inf"),
              t_hi: float = float("inf")) -> int:
        """Events of *phase* (one name or several) received in
        [t_lo, t_hi], on the ``time.perf_counter`` clock."""
        return len(self._in(phase, t_lo, t_hi))

    def seconds(self, phase: str | tuple[str, ...],
                t_lo: float = float("-inf"),
                t_hi: float = float("inf")) -> float:
        """Wall seconds in *phase* (one name or several) over the events
        received in [t_lo, t_hi]: nested and overlapping events count
        once. (``cache_saved`` is a figure, not an interval: its events
        may be negative and are summed.)"""
        got = self._in(phase, t_lo, t_hi)
        if phase == "cache_saved":
            return sum(s for _, s in got)
        total, end = 0.0, float("-inf")
        for a, b in sorted((t - s, t) for t, s in got):
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    def summary(self) -> dict[str, dict[str, float]]:
        """``{phase: {"count", "seconds"}}`` since the log started — the
        body of the ``compile`` event."""
        return {phase: {"count": self.count(phase),
                        "seconds": round(self.seconds(phase), 6)}
                for phase in COMPILE_PHASES.values() if self.count(phase)}


_compile_log: CompileLog | None = None


def compile_log() -> CompileLog:
    """This process's compile log; the first call installs its one
    ``jax.monitoring`` listener (listeners cannot be removed, so there is
    one log per process)."""
    global _compile_log
    if _compile_log is None:
        import jax.monitoring
        _compile_log = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            _compile_log._on_duration)
    return _compile_log
