"""Telemetry subsystem: spans → JSONL, registry → Prometheus exposition,
heartbeats → stall detection in watch, and tracing that changes no result."""
import io
import json
import re
import threading
import urllib.request

import pytest

from k8s_distributed_deeplearning_tpu.config import JobConfig
from k8s_distributed_deeplearning_tpu.launch import watch as watch_mod
from k8s_distributed_deeplearning_tpu.telemetry import (
    HeartbeatWriter, MetricsExporter, MetricsRegistry, Tracer, detect_stalls)
from k8s_distributed_deeplearning_tpu.telemetry import bridge
from k8s_distributed_deeplearning_tpu.utils.metrics import (
    MetricsLogger, ServingStats)


def _tracer(**kw):
    buf = io.StringIO()
    return Tracer(MetricsLogger(stream=buf, job="test"), **kw), buf


def _events(buf):
    return [json.loads(line) for line in buf.getvalue().strip().splitlines()]


# ------------------------------------------------------------------ spans

def test_nested_spans_emit_wellformed_jsonl():
    tr, buf = _tracer(rank=2)
    with tr.span("step", step=7):
        with tr.span("data_wait"):
            pass
        with tr.span("checkpoint"):
            pass
    recs = _events(buf)
    # Inner spans close (and emit) before the outer one.
    assert [r["name"] for r in recs] == ["data_wait", "checkpoint", "step"]
    for r in recs:
        assert r["event"] == "span" and r["rank"] == 2
        assert isinstance(r["dur_ms"], float) and r["dur_ms"] >= 0
    inner, _, outer = recs
    assert inner["parent"] == "step" and inner["depth"] == 1
    assert outer["parent"] is None and outer["depth"] == 0
    assert outer["step"] == 7                 # caller fields ride along
    assert tr.last_span == "step"


def test_disabled_tracer_is_noop():
    tr, buf = _tracer(enabled=False)
    with tr.span("step"):
        pass
    assert buf.getvalue() == "" and tr.last_span is None


def test_min_dur_filter_suppresses_fast_spans():
    tr, buf = _tracer(min_dur_ms=1e6)
    with tr.span("step"):
        pass
    assert buf.getvalue() == ""
    assert tr.last_span == "step"             # still tracked for heartbeat


def test_span_stacks_are_thread_local():
    tr, buf = _tracer()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with tr.span("decode"):
            inside.set()
            release.wait(5)

    t = threading.Thread(target=worker)
    with tr.span("step"):
        t.start()
        inside.wait(5)
        with tr.span("data_wait"):
            pass
        release.set()
        t.join(5)
    by_name = {r["name"]: r for r in _events(buf)}
    # The worker's span must not see the main thread's "step" as parent.
    assert by_name["decode"]["parent"] is None and by_name["decode"]["depth"] == 0
    assert by_name["data_wait"]["parent"] == "step"


# ------------------------------------- Prometheus exposition + exporter

_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (.+)$")


def _parse_exposition(text):
    """Minimal Prometheus text-format parser: {(name, frozenset(labels)):
    value} plus {name: type}."""
    samples, types = {}, {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split(None, 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, _, labels, value = m.groups()
        pairs = frozenset(
            re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                       labels or ""))
        v = float("inf") if value == "+Inf" else float(value)
        samples[(name, pairs)] = v
    return samples, types


def test_metrics_exposition_roundtrips():
    reg = MetricsRegistry()
    reg.counter("train_steps_total", "steps").inc(42)
    reg.gauge("train_loss", "loss").set(0.125)
    h = reg.histogram("req_s", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    g = reg.gauge("hb_age", "age", labelnames=("rank",))
    g.labels(rank="0").set(1.5)
    g.labels(rank="1").set(250.0)

    samples, types = _parse_exposition(reg.render())
    assert types["train_steps_total"] == "counter"
    assert types["train_loss"] == "gauge"
    assert types["req_s"] == "histogram"
    assert samples[("train_steps_total", frozenset())] == 42
    assert samples[("train_loss", frozenset())] == 0.125
    # Histogram: cumulative buckets, +Inf == count, sum adds up.
    assert samples[("req_s_bucket", frozenset({('le', '0.1')}))] == 1
    assert samples[("req_s_bucket", frozenset({('le', '1')}))] == 2
    assert samples[("req_s_bucket", frozenset({('le', '+Inf')}))] == 3
    assert samples[("req_s_count", frozenset())] == 3
    assert samples[("req_s_sum", frozenset())] == pytest.approx(5.55)
    assert samples[("hb_age", frozenset({('rank', '1')}))] == 250.0


def test_exporter_serves_metrics_and_healthz():
    reg = MetricsRegistry()
    reg.counter("train_steps_total", "steps").inc(3)
    stats = ServingStats()
    stats.record_admission(queue_s=0.01, prompt_len=8)
    stats.record_first_token(ttft_s=0.02)
    stats.record_step(2, 4)
    bridge.serving_collector(reg, stats)

    exp = MetricsExporter(reg, host="127.0.0.1", port=0).start()
    try:
        base = f"http://127.0.0.1:{exp.port}"
        body = urllib.request.urlopen(f"{base}/metrics").read().decode()
        samples, types = _parse_exposition(body)
        assert samples[("train_steps_total", frozenset())] == 3
        # The pull-time ServingStats bridge populated the serve gauges.
        assert samples[("serve_requests_admitted", frozenset())] == 1
        assert samples[("serve_total_tokens", frozenset())] == 3
        hz = json.loads(urllib.request.urlopen(f"{base}/healthz").read())
        assert hz["ok"] is True
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        exp.stop()


# --------------------------------------------------- heartbeats + watch

def test_heartbeat_roundtrip_and_stall_detection(tmp_path):
    d = str(tmp_path)
    HeartbeatWriter(d, 0, clock=lambda: 1000.0).beat(50, last_span="step")
    HeartbeatWriter(d, 1, clock=lambda: 700.0).beat(31, last_span="data_wait")
    stalls = detect_stalls(d, stale_after_s=120.0, now=1010.0)
    assert [s.rank for s in stalls] == [1]
    s = stalls[0]
    assert s.step == 31 and s.last_span == "data_wait"
    assert s.age_s == pytest.approx(310.0)
    assert "rank 1" in s.describe() and "data_wait" in s.describe()
    # Torn/garbage files are skipped, not fatal.
    (tmp_path / "rank-9.json").write_text("{not json")
    assert [s.rank for s in detect_stalls(d, 120.0, now=1010.0)] == [1]


def test_watch_flags_stalled_rank(tmp_path):
    """A hung rank (stale heartbeat) is reported BY RANK ID with its last
    span while healthy ranks stay unreported — and the stall is emitted
    once, not once per poll."""
    from tests.test_watch import FakeCluster

    d = str(tmp_path)
    now = {"t": 1000.0}
    HeartbeatWriter(d, 0, clock=lambda: now["t"]).beat(50, last_span="step")
    HeartbeatWriter(d, 1, clock=lambda: 500.0).beat(12,
                                                    last_span="data_wait")

    cfg = JobConfig(num_workers=2)
    cluster = FakeCluster([
        {"active": 2, "succeeded": 0},
        {"active": 2, "succeeded": 0},
        {"active": 0, "succeeded": 2},
    ])
    events = []
    fake = {"t": 0.0}
    result = watch_mod.watch(
        cfg, kubectl=watch_mod.Kubectl(runner=cluster.runner),
        clock=lambda: fake["t"],
        sleep=lambda dt: fake.__setitem__("t", fake["t"] + dt),
        poll_interval=1.0, attempt_timeout=100.0,
        on_event=events.append,
        heartbeat_dir=d, heartbeat_stale_after=120.0,
        heartbeat_clock=lambda: now["t"])
    assert result.status.succeeded == 2
    stall_events = [e for e in events if "stalled" in e]
    assert len(stall_events) == 1, events       # reported once, not per poll
    assert "rank 1" in stall_events[0]
    assert "data_wait" in stall_events[0]       # last-completed span named
    assert not any("rank 0" in e for e in stall_events)


# ------------------------------------------- tracing changes no result

def test_tracing_changes_no_loss_and_no_dispatch():
    """The loop's built-in spans (JSONL emit included) are host
    bookkeeping: a traced and an untraced ``fit`` over the same seed give
    bit-identical losses and dispatch the step the same number of times,
    and the traced run emits its four spans a step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from k8s_distributed_deeplearning_tpu.models import mnist
    from k8s_distributed_deeplearning_tpu.train import data as data_lib
    from k8s_distributed_deeplearning_tpu.train import loop as train_loop

    steps = 12
    model = mnist.MNISTConvNet(dtype=jnp.float32)
    rng = jax.random.key(0)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)), train=False)["params"]
    opt = optax.adam(1e-3)

    @jax.jit
    def step(state, batch, step_rng):
        p, opt_state = state
        (loss, aux), grads = jax.value_and_grad(
            lambda q: mnist.loss_fn(model, q, batch, step_rng),
            has_aux=True)(p)
        updates, opt_state = opt.update(grads, opt_state, p)
        return (optax.apply_updates(p, updates), opt_state), loss, aux

    x, y = data_lib.synthetic_mnist(64, seed=0)

    def run(tracer):
        losses = []

        def counted(state, batch, step_rng):
            state, loss, aux = step(state, batch, step_rng)
            losses.append(loss)
            return state, loss, aux

        batches = iter([{"image": x, "label": y}] * steps)
        train_loop.fit(counted, (params, opt.init(params)), batches, steps,
                       rng, log_every=0, tracer=tracer)
        return np.asarray(losses)

    plain = run(None)
    tracer, _ = _tracer()
    traced = run(tracer)
    assert len(plain) == len(traced) == steps
    assert plain.tobytes() == traced.tobytes()
    # data_wait, rng, step, hooks a step (log_every=0: no sync spans)
    assert tracer.spans_emitted == 4 * steps


def test_heartbeat_beat_never_raises_on_broken_target(tmp_path, capsys):
    """Liveness reporting must never kill the step it reports on: a writer
    whose target directory turns unwritable (volume yanked mid-run)
    swallows every failure after one warning."""
    marker = tmp_path / "regular-file"
    marker.write_text("not a directory")
    writer = HeartbeatWriter(str(tmp_path / "hb"), rank=0)
    # Break the target AFTER construction: the open() inside beat() now
    # raises NotADirectoryError (chmod tricks don't apply — tests run as
    # root, for whom mode bits are advisory).
    writer.directory = str(marker / "sub")
    for step in range(3):
        writer.beat(step)            # must not raise
    err = capsys.readouterr().err
    assert err.count("heartbeat write failed") == 1
    # a healthy writer alongside is unaffected
    ok = HeartbeatWriter(str(tmp_path / "hb2"), rank=1)
    ok.beat(7)
    from k8s_distributed_deeplearning_tpu.telemetry.heartbeat import (
        read_heartbeats)
    assert read_heartbeats(str(tmp_path / "hb2"))[0]["step"] == 7


def test_tracer_emit_failure_never_raises(capsys):
    """A tracer whose logger dies (full disk, closed stream) times spans,
    warns once, and never propagates into the traced work."""
    class _DeadLogger:
        def emit(self, *a, **kw):
            raise OSError("disk full")

    tr = Tracer(logger=_DeadLogger(), rank=0)
    for i in range(3):
        with tr.span("step", step=i):
            pass
    assert tr.last_span == "step"    # spans still recorded
    err = capsys.readouterr().err
    assert err.count("span emit failed") == 1


def test_metrics_logger_emit_failure_never_raises(capsys):
    """MetricsLogger.emit with a dead stream warns once and drops the
    event instead of killing the caller."""
    class _DeadStream:
        def write(self, *_a):
            raise OSError("broken pipe")

        def flush(self):
            raise OSError("broken pipe")

    log = MetricsLogger(stream=_DeadStream(), job="t")
    for i in range(3):
        log.emit("checkpoint", step=i)   # must not raise
    err = capsys.readouterr().err
    assert err.count("metrics emit failed") == 1


# ------------------------------------------- the loop's spans, compile log

def test_trace_module_imports_without_jax():
    """Control-plane processes import the tracer; it must not pull jax in
    (the profiler switch is flipped by utils.profiling, which has jax)."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from k8s_distributed_deeplearning_tpu.telemetry import trace\n"
            "assert trace._annotation is None\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_ring_records_carry_both_clocks():
    tr = Tracer(ring_size=8)
    with tr.span("outer", step=1):
        with tr.span("inner"):
            pass
    inner, outer = tr.recent_spans()
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    assert abs((outer["t1"] - outer["t0"]) * 1e3 - outer["dur_ms"]) < 1e-2
    assert outer["ts"] > 1e9 and outer["step"] == 1


def _fit_spans(num_steps, log_every, **fit_kw):
    import jax

    from k8s_distributed_deeplearning_tpu import backend
    from k8s_distributed_deeplearning_tpu.train import loop as train_loop
    backend.compile_log()      # entry points start it (use_compile_cache)
    tr = Tracer(ring_size=4096)
    buf = io.StringIO()
    log = MetricsLogger(stream=buf, job="t")
    train_loop.fit(lambda state, batch, rng: (state, 0.5, {"acc": 1.0}),
                   state=None, batches=iter(range(num_steps)),
                   num_steps=num_steps, rng=jax.random.key(0), tracer=tr,
                   metrics=log, log_every=log_every, **fit_kw)
    return tr.recent_spans(), _events(buf)


def test_fit_spans_are_flat_siblings_per_step_and_per_sync():
    """``fit`` for 2 x log_every steps: ``data_wait``/``rng``/``step``/
    ``hooks`` once a step in that order, ``log_sync`` then ``log`` once a
    sync, every one at depth 0 with its step (graftscope sums depth-0
    spans into a step's components: no span may wrap the iteration)."""
    log_every = 3
    spans, events = _fit_spans(2 * log_every, log_every)
    assert all(s["depth"] == 0 and s["parent"] is None for s in spans)
    for step in range(2 * log_every):
        names = [s["name"] for s in spans if s["step"] == step]
        want = ["data_wait", "rng", "step", "hooks"]
        if (step + 1) % log_every == 0:
            want += ["log_sync", "log"]
        assert names == want, (step, names)
    sync, logged = [[s for s in spans if s["name"] == n]
                    for n in ("log_sync", "log")]
    assert [s["step"] for s in sync] == [2, 5]
    assert all(a["t1"] <= b["t0"] for a, b in zip(sync, logged))
    # the run's last events: device_memory, then the compile summary
    assert [e["event"] for e in events[-2:]] == ["device_memory", "compile"]
    assert events[-1]["step"] == 2 * log_every


def test_fit_eval_and_profiler_hooks_get_spans(tmp_path):
    """``eval`` wraps the eval call at its cadence; a run with a profiler
    has a second ``hooks`` span a step (before ``data_wait``), and the
    steps inside the profiler's window are on its clock as ``program:``
    annotations."""
    from benchmarks.harness import trace_reduce
    from k8s_distributed_deeplearning_tpu.utils.profiling import StepProfiler
    d = str(tmp_path / "prof")
    spans, _ = _fit_spans(4, 2, eval_every=2, eval_fn=lambda st: {"m": 1.0},
                          profiler=StepProfiler(d, start_step=1, num_steps=2))
    assert [s["step"] for s in spans if s["name"] == "eval"] == [1, 3]
    assert [s["name"] for s in spans if s["step"] == 0][:2] == [
        "hooks", "data_wait"]
    assert sum(s["name"] == "hooks" for s in spans) == 2 * 4
    ex = trace_reduce.extract(trace_reduce.find_xplane(d))
    names = [e[0] for p in ex["planes"] for ln in p["lines"]
             for e in ln["events"] if e[0].startswith("program:")]
    assert names.count("program:step") == 2          # steps 1 and 2
    assert names.count("program:log_sync") == 1      # the sync at step 1


def test_compile_log_counts_a_fresh_jit_once_and_a_second_call_not_at_all():
    import jax
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu import backend
    log = backend.compile_log()
    assert backend.compile_log() is log        # one listener per process

    def fresh_fn_for_the_compile_log(x):
        return x * 3 + 1
    f = jax.jit(fresh_fn_for_the_compile_log)

    def mine():
        return [(p, s) for _, p, s, name in log.events()
                if name and "fresh_fn_for_the_compile_log" in name]
    x = jnp.ones((5,))
    t0 = __import__("time").perf_counter()
    jax.block_until_ready(f(x))
    once = mine()
    assert sorted(p for p, _ in once) == ["backend_compile", "lower", "trace"]
    assert all(s >= 0 for _, s in once)
    jax.block_until_ready(f(x))
    assert mine() == once
    # the window arithmetic: everything of this jit arrived after t0
    assert log.count("lower", t0) >= 1 and log.seconds("lower", t0) > 0
    assert log.count("lower", t_hi=t0) == log.count("lower") - log.count("lower", t0)
    summ = log.summary()
    assert summ["trace"]["count"] >= 1 and summ["trace"]["seconds"] > 0


def test_compile_log_seconds_are_wall_time_not_a_sum_of_nested_events():
    """JAX reports a jit traced inside another's trace as its own event,
    inside the outer one's seconds: a phase's seconds are the union of the
    events' intervals, so nothing is counted twice."""
    from k8s_distributed_deeplearning_tpu import backend
    log = backend.CompileLog()
    log._events = [
        (10.2, "trace", 0.2, "inner"),         # [10.0, 10.2]
        (10.3, "trace", 0.3, "outer"),         # [10.0, 10.3] holds inner
        (10.4, "lower", 0.1, "jit(outer)"),    # [10.3, 10.4]
        (12.0, "trace", 0.5, "later"),         # [11.5, 12.0]
        (12.5, "cache_saved", -0.25, None)]
    assert log.count("trace") == 3
    assert log.seconds("trace") == pytest.approx(0.8)
    assert log.seconds("trace", t_hi=11.0) == pytest.approx(0.3)
    assert log.seconds(("trace", "lower"), t_hi=11.0) == pytest.approx(0.4)
    assert log.seconds("trace", 11.0, 13.0) == pytest.approx(0.5)
    assert log.seconds("cache_saved") == -0.25
    assert log.summary()["trace"] == {"count": 3, "seconds": 0.8}
    assert "backend_compile" not in log.summary()
    # and through jax.monitoring itself: the listener keeps what it is sent
    for name, phase in backend.COMPILE_PHASES.items():
        log._on_duration(name, 0.5, fun_name="f")
        assert log.events()[-1][1:] == (phase, 0.5, "f")
    log._on_duration("/jax/some/other/event", 1.0)
    assert len(log.events()) == 5 + len(backend.COMPILE_PHASES)


def test_compile_collector_exports_seconds_by_phase():
    import jax
    import jax.numpy as jnp
    reg = MetricsRegistry()
    bridge.compile_collector(reg)
    jax.block_until_ready(jax.jit(lambda x: x - 7)(jnp.ones((3,))))
    def rows():
        samples, types = _parse_exposition(reg.render())
        assert types["xla_compile_seconds_total"] == "counter"
        return {dict(labels)["phase"]: v for (name, labels), v in
                samples.items() if name == "xla_compile_seconds_total"}
    first = rows()
    assert first["lower"] > 0 and first["trace"] > 0
    assert "cache_saved" not in first
    assert rows() == first                     # a scrape adds only new events
