"""The five readers of the program's host spans and compile log, on
hand-made records with a known answer — and ``None`` (never 0) on records
that lack the spans, as a parent commit's do."""
import pytest

from benchmarks.harness import manifest as M
from benchmarks.harness import span_math
from benchmarks.harness.spans import BenchTracer

MS = 1e-3


def _run(records, t_open=100.0, t_close=101.0, **win):
    tr = BenchTracer()
    tr.records = list(records)
    return {"win": {"tracer": tr, "t_open": t_open, "t_close": t_close, **win}}


def _engine_step(t0, *, wait_first=None, pages=(10, 100)):
    """One 50 ms engine step at *t0* in closing order: sweep 1 ms,
    [prefill 6 ms holding a 2 ms first-token wait], decode 30 ms holding a
    26 ms wait, emit 3 ms, epilogue 1 ms."""
    t = t0
    out = [("sweep", t, t + 1 * MS, {})]
    t += 2 * MS
    if wait_first:
        out += [("device_wait", t + 3 * MS, t + 5 * MS, {"kind": "first_token"}),
                ("prefill", t, t + 6 * MS, {"bucket": 128})]
    t += 8 * MS
    out += [("device_wait", t + 3 * MS, t + 29 * MS, {"kind": "decode"}),
            ("decode", t, t + 30 * MS, {"active": 2}),
            ("emit", t + 31 * MS, t + 34 * MS, {}),
            ("epilogue", t + 35 * MS, t + 36 * MS,
             {"pages_used": pages[0], "pages_total": pages[1], "active": 2}),
            ("engine_step", t0, t0 + 50 * MS, {"step": 0})]
    return out


SERVE = (_engine_step(99.98, pages=(20, 100))          # straddles t_open: out
         + _engine_step(100.10, wait_first=True, pages=(30, 100))
         + _engine_step(100.20, pages=(42, 100))
         + _engine_step(100.30, pages=(41, 100))
         + _engine_step(100.97, pages=(98, 100)))       # straddles t_close: out


def test_engine_host_ms_per_step_known_answer():
    # three steps of 50 ms; waits 26 + 2, 26, 26 ms: (150 - 80) / 3
    got = M.load_reader("engine_host_ms_per_step")(_run(SERVE))
    assert got == pytest.approx(70.0 / 3)


def test_engine_emit_ms_per_step_known_answer():
    # emit 3 ms + epilogue 1 ms in each of the three steps inside
    got = M.load_reader("engine_emit_ms_per_step")(_run(SERVE))
    assert got == pytest.approx(4.0)


def test_kv_pool_peak_fill_known_answer():
    # epilogues inside the window read 20 (of the step that straddles the
    # opening: the span itself is inside), 30, 42, 41 of 100; the one after
    # the close (98) does not count
    got = M.load_reader("kv_pool_peak_fill")(_run(SERVE))
    assert got == pytest.approx(42.0)


def _train(n_steps=30, log_every=10, step_s=0.020, restart=(0.004, 0.005, 0.200)):
    """Steps of 20 ms (dispatch 1 ms); at every log_every-th step a
    log_sync, after which the next step's dispatch closes ``restart`` later."""
    out, t, k = [], 100.0, 0
    for step in range(n_steps):
        out += [("data_wait", t, t + 0.1 * MS, {"step": step}),
                ("rng", t + 0.1 * MS, t + 0.2 * MS, {"step": step}),
                ("step", t + 0.2 * MS, t + 1.2 * MS, {"step": step}),
                ("hooks", t + 1.2 * MS, t + 1.3 * MS, {"step": step})]
        t += step_s
        if (step + 1) % log_every == 0 and step + 1 < n_steps:
            sync_close = t
            out += [("log_sync", t - 15 * MS, sync_close, {"step": step}),
                    ("log", sync_close, sync_close + 0.3 * MS, {"step": step})]
            # the next step span closes `restart` after the fence returned
            t = sync_close + restart[k] - 1.2 * MS
            k += 1
    return out


def test_train_sync_restart_ms_is_the_median_over_the_windows_syncs():
    rec = _train(n_steps=40, log_every=10)
    assert [round(1e3 * g, 6) for g in
            span_math.sync_restarts(rec, 100.0, 102.0)] == [4.0, 5.0, 200.0]
    got = M.load_reader("train_sync_restart_ms")(_run(rec, 100.0, 102.0))
    assert got == pytest.approx(5.0)           # the 200 ms outlier moves nothing
    # a window that closes before the third sync's next step sees two syncs
    t_third = max(t1 for n, _, t1, _ in rec if n == "log_sync")
    got = M.load_reader("train_sync_restart_ms")(_run(rec, 100.0, t_third + 0.01))
    assert got == pytest.approx(4.5)


def test_setup_trace_lower_s_reads_the_compile_log_before_the_window(monkeypatch):
    from k8s_distributed_deeplearning_tpu import backend
    log = backend.CompileLog()
    log._events = [(50.0, "trace", 2.0, "inner"),          # inside the next
                   (51.0, "trace", 4.0, "_decode_program"),  # [47, 51]
                   (60.0, "lower", 6.0, "jit(_decode_program)"),
                   (70.0, "backend_compile", 9.0, "jit(_decode_program)"),
                   (70.0, "cache_retrieval", 1.0, None),
                   (100.5, "trace", 0.25, "late")]            # after t_open
    monkeypatch.setattr(backend, "compile_log", lambda: log)
    read = M.load_reader("setup_trace_lower_s")
    assert read(_run([], 100.0, 101.0)) == pytest.approx(10.0)
    # nothing traced or lowered before the window: nothing to read, not 0
    log._events = log._events[-1:]
    assert read(_run([], 100.0, 101.0)) is None
    # a program without a compile log (a parent commit)
    monkeypatch.delattr(backend, "compile_log")
    assert read(_run([], 100.0, 101.0)) is None


PARENT_SERVE = [r for r in SERVE if r[0] in ("prefill", "decode")]
PARENT_TRAIN = [r for r in _train() if r[0] in ("data_wait", "step")]


@pytest.mark.parametrize("metric,records", [
    ("engine_host_ms_per_step", PARENT_SERVE),
    ("engine_emit_ms_per_step", PARENT_SERVE),
    ("kv_pool_peak_fill", PARENT_SERVE),
    ("train_sync_restart_ms", PARENT_TRAIN),
    ("engine_host_ms_per_step", []),
    ("train_sync_restart_ms", []),
])
def test_readers_return_none_without_the_new_spans(metric, records):
    """A parent commit's records (only the spans it had), an empty window,
    a run without a tracer: nothing to read is ``None``, and the line
    leaves the metric out."""
    read = M.load_reader(metric)
    assert read(_run(records)) is None
    assert read({"win": {}}) is None


@pytest.mark.parametrize("metric", ["engine_host_ms_per_step", "engine_emit_ms_per_step",
                                    "kv_pool_peak_fill", "train_sync_restart_ms",
                                    "setup_trace_lower_s"])
def test_new_metrics_are_in_the_manifest_with_their_cells(metric):
    man = M.load_manifest()
    m = next(x for x in man["per_layer"] if x["name"] == metric)
    serving = metric.startswith(("engine_", "kv_"))
    cells = {"mistral-7b-d16.chat-backlog"} if serving else {
        "bert-base.mlm-s512", "bert-base.mlm-s512-dp4"}
    if metric == "setup_trace_lower_s":
        cells = {w["name"] for w in man["workloads"]}
    assert set(m["workloads"]) == cells
    assert m["source"] in ("program_span", "program_counter")
