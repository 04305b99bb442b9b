"""Profiling: trace capture produces an XProf-readable dir, and while a
session of the program's own is on, the program's spans are in it."""
import glob
import os

import jax
import jax.numpy as jnp

from benchmarks.harness import trace_reduce
from k8s_distributed_deeplearning_tpu.telemetry import trace as trace_lib
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
from k8s_distributed_deeplearning_tpu.utils import profiling


def _host_events(trace_dir: str) -> list[str]:
    """Names of the ``program:`` / ``bench:`` host events the benchmark's
    own reduction finds in the newest trace under *trace_dir*."""
    ex = trace_reduce.extract(trace_reduce.find_xplane(trace_dir))
    return [e[0] for p in ex["planes"] if not p["name"].startswith("/device:")
            for ln in p["lines"] for e in ln["events"]]


def test_trace_writes_profile_dir(tmp_path):
    """A session started through ``profiling.trace`` carries the spans of an
    enabled Tracer as ``program:<name>`` host events — what
    ``trace_reduce.HOST_SPAN`` reads — nested spans included; a disabled
    tracer writes none."""
    d = str(tmp_path / "trace")
    tr, off = Tracer(), Tracer(enabled=False)
    with profiling.trace(d):
        with tr.span("matmul", step=3):
            with tr.span("inner"):
                x = jnp.ones((64, 64))
                jax.block_until_ready(jnp.dot(x, x))
        with off.span("silent"):
            pass
    files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any("trace" in f or f.endswith(".pb") or f.endswith(".json.gz")
               for f in files), files
    names = _host_events(d)
    assert names.count("program:matmul") == 1
    assert names.count("program:inner") == 1
    assert "program:silent" not in names


def test_no_annotation_outside_a_session(tmp_path):
    """The switch is off before a session, on inside, off after (also when
    the traced block raises); a profiler session the program did NOT start
    (``jax.profiler.start_trace`` directly) gets no span of ours."""
    assert trace_lib._annotation is None
    try:
        with profiling.trace(str(tmp_path / "a")):
            assert trace_lib._annotation is jax.profiler.TraceAnnotation
            raise KeyError("boom")
    except KeyError:
        pass
    assert trace_lib._annotation is None
    d = str(tmp_path / "b")
    tr = Tracer()
    jax.profiler.start_trace(d)
    try:
        with tr.span("foreign"):
            jax.block_until_ready(jnp.ones((8, 8)) * 2)
    finally:
        jax.profiler.stop_trace()
    assert "program:foreign" not in _host_events(d)


def test_trace_disabled_writes_nothing(tmp_path):
    d = str(tmp_path / "trace")
    with profiling.trace(d, enabled=False):
        jax.block_until_ready(jnp.ones((8, 8)) * 2)
    assert not os.path.exists(d)


def test_step_profiler_window(tmp_path):
    """Steps 2 and 3 are inside the window: their spans are annotated, the
    others' are not."""
    d = str(tmp_path / "prof")
    p = profiling.StepProfiler(d, start_step=2, num_steps=2)
    tr = Tracer()
    for step in range(6):
        p.step_hook(step)
        with tr.span(f"s{step}"):
            jax.block_until_ready(jnp.ones((8, 8)) + step)
    p.stop()   # idempotent
    assert glob.glob(os.path.join(d, "**", "*"), recursive=True)
    names = _host_events(d)
    assert [n for n in names if n.startswith("program:")] == [
        "program:s2", "program:s3"]


def test_step_profiler_starts_on_resumed_run(tmp_path):
    """A run restored past start_step must still capture a window (>= latch)."""
    d = str(tmp_path / "prof_resume")
    p = profiling.StepProfiler(d, start_step=10, num_steps=2)
    for step in range(100, 105):     # resumed at step 100
        p.step_hook(step)
        jax.block_until_ready(jnp.ones((4, 4)) + step)
    p.stop()
    assert glob.glob(os.path.join(d, "**", "*"), recursive=True)
    # Done latch: a later window does not restart the trace.
    p.step_hook(200)
    assert not p._active
