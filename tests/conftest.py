"""Test harness: fake an 8-device TPU mesh on CPU.

The JAX-native "fake backend" (SURVEY.md §4): ``xla_force_host_platform_device_count``
gives N CpuDevices so every collective, sharding rule, and rank-gating branch
runs in CI without hardware. ``JAX_PLATFORMS=cpu`` pins the backend.

Must run before jax is imported, hence top-of-conftest.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# Persistent XLA compilation cache: the suite is compile-bound on CPU, and
# the same step functions recompile run after run. A warm cache cuts e.g.
# tests/test_sharding.py from ~136s to ~21s. Same placement rule as every
# entry point (backend.compile_cache_dir: $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache), exported so subprocess tests inherit it. Safe to
# share: keys include HLO + flags + backend.
from k8s_distributed_deeplearning_tpu import backend  # noqa: E402

os.environ.setdefault(backend.CACHE_ENV, backend.compile_cache_dir())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
    return mesh_lib.make_mesh({"data": -1})


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Drop each test module's compiled programs when it ends. A loaded
    XLA:CPU executable holds about a dozen memory mappings; a single pytest
    process that keeps every program of every module reaches
    ``vm.max_map_count`` (65530) some 570 tests in, and the next executable
    load segfaults inside jaxlib (seen at the seed of PR 21 in
    tests/test_quant.py, taking the rest of the run with it)."""
    yield
    jax.clear_caches()
