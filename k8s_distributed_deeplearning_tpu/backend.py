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

jax is imported inside the functions: control-plane processes (``launch
render``/``validate``/``run-local``, the smoke's parent) import the package
without initialising a backend.
"""
from __future__ import annotations

import os

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
    and return the directory. Call first thing in an entry point, before
    anything compiles. When the environment names the directory, JAX has
    already read it and nothing is set here."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
