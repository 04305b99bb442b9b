"""The three readers of the engine's inner spans (PR 35: the calls, operands,
key and fetches inside ``prefill``, ``decode`` and ``device_wait``) on
hand-made records and a hand-made reduction with a known answer — and
``None`` (never 0) on a parent commit's records, without a trace where one is
needed, and on a training cell; ``trace_reduce.reduce`` naming an idle gap by
the innermost of the new spans; and the fixture manifest the readers wait in."""
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest as M
from benchmarks.harness import trace_reduce as T
from benchmarks.harness.spans import BenchTracer

MS = 1e-3
MAN = M.load_manifest()
SERVING = ["mistral-7b-d16.chat-backlog", "sarvam-105b-ep4-d6.docs-backlog",
           "lfm2-8b-a1b-d14.chat-backlog-wide",
           "nemotron-3-super-ep4-d11.chat-backlog-wide"]
RATE_CELLS = [c for c in SERVING if c != "sarvam-105b-ep4-d6.docs-backlog"]
NEW_READERS = ("engine_dispatch_ms_per_step", "engine_dispatch_idle_share",
               "engine_fence_idle_ms_per_decode")
FIXTURE_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                                "dispatch_spans", "manifest.json")


def _engine_step(t0, *, final=False, chunk=True):
    """One 40 ms engine step at *t0*, in closing order: ``decode`` from 1 ms
    (``decode_call`` 2 ms), a ``prefill`` at 4 ms — operands 0.25 ms, on a
    final chunk the key 5 ms, the call 1.5 ms — then the fence from 20 ms:
    tokens 15 ms, one chunk's counts 0.5 ms, keys 1 ms."""
    t, out = t0 + 1 * MS, []
    out.append(("decode_call", t, t + 2 * MS, {"in_flight": 1}))
    if chunk:
        p = t0 + 4 * MS
        out.append(("chunk_operands", p, p + 0.25 * MS, {}))
        c = p + 0.5 * MS
        if final:
            out.append(("first_key", c, c + 5 * MS, {"in_flight": 1}))
            c += 5.5 * MS
        out.append(("chunk_call", c, c + 1.5 * MS,
                    {"program": "final_chunk_512" if final else "chunk_512", "in_flight": 1}))
        out.append(("prefill", p, c + 2 * MS, {"bucket" if final else "chunk": 512}))
    w = t0 + 20 * MS
    out += [("fetch_tokens", w, w + 15 * MS, {}),
            ("fetch_counts", w + 15.5 * MS, w + 16 * MS, {}),
            ("prefill_counts", w + 16 * MS, w + 16 * MS, {"chunk": 512}),
            ("fetch_keys", w + 16.5 * MS, w + 17.5 * MS, {}),
            ("device_wait", w, w + 18 * MS, {"kind": "decode", "covered": int(chunk)}),
            ("decode", t, w + 18 * MS, {"active": 2, "rows": 2}),
            ("emit", t0 + 38.2 * MS, t0 + 39 * MS, {}),
            ("engine_step", t0, t0 + 40 * MS, {"step": 0})]
    return out


SERVE = (_engine_step(99.98)                            # straddles t_open: out
         + _engine_step(100.10)
         + _engine_step(100.20, final=True)
         + _engine_step(100.30, chunk=False)
         + _engine_step(100.97, final=True))            # straddles t_close: out
INNER = {"decode_call", "chunk_operands", "first_key", "chunk_call", "trie_adopt",
         "fetch_tokens", "fetch_counts", "fetch_keys"}
PARENT_SERVE = [r for r in SERVE if r[0] not in INNER]
TRAIN = [(n, 100.0 + 0.02 * i, 100.001 + 0.02 * i, {"step": i})
         for i in range(30) for n in ("data_wait", "step")]

# a reduction of a 4 s traced stretch, as ``trace_reduce.reduce`` returns it
RED = {"window_s": 4.0, "busy_s": 3.7,
       "programs": {"jit__decode_program": {"calls": 100, "seconds": 1.5},
                    "jit__chunk_program": {"calls": 60, "seconds": 1.2}},
       "idle_gaps": [["program:first_key", 0.120], ["program:chunk_call", 0.060],
                     ["program:fetch_tokens", 0.050], ["program:prefill", 0.016],
                     ["program:emit", 0.020], ["program:decode_call", 0.004],
                     ["program:device_wait", 0.006], ["program:fetch_keys", 0.004],
                     ["within_jit__decode_program", 0.012], ["unattributed", 0.008]]}


def _run(records, red=RED, **extra):
    tr = BenchTracer()
    tr.records = list(records)
    return {"win": {"tracer": tr, "t_open": 100.0, "t_close": 101.0}, "trace": red,
            "rehearsal": False, **extra}


def test_engine_dispatch_ms_per_step_known_answer():
    # three steps inside the window: decode_call 2 ms each; operands 0.25 ms
    # and a call of 1.5 ms in two of them; one key of 5 ms: (6 + 3.5 + 5) / 3
    got = M.load_reader("engine_dispatch_ms_per_step")(_run(SERVE, red=None))
    assert got == pytest.approx(14.5 / 3)


def test_engine_dispatch_idle_share_known_answer():
    # first_key 0.120 + chunk_call 0.060 + prefill 0.016 + decode_call 0.004
    # of 4.0 s; emit, the fetches and the unlabelled gaps are not dispatch
    got = M.load_reader("engine_dispatch_idle_share")(_run(SERVE))
    assert got == pytest.approx(100.0 * 0.200 / 4.0)


def test_engine_fence_idle_ms_per_decode_known_answer():
    # fetch_tokens 0.050 + device_wait 0.006 + fetch_keys 0.004 over the 100
    # traced calls of the decode program (the chunk program's do not count)
    got = M.load_reader("engine_fence_idle_ms_per_decode")(_run(SERVE))
    assert got == pytest.approx(1e3 * 0.060 / 100)
    no_decode = dict(RED, programs={"jit__chunk_program": RED["programs"]["jit__chunk_program"]})
    assert M.load_reader("engine_fence_idle_ms_per_decode")(_run(SERVE, red=no_decode)) is None


@pytest.mark.parametrize("metric", NEW_READERS)
@pytest.mark.parametrize("what", ["parent", "no_trace", "rehearsal", "bert", "no_window"])
def test_readers_return_none_where_there_is_nothing_to_read(metric, what):
    """A parent commit's records carry ``prefill``, ``decode`` and
    ``device_wait`` — and its reduction their labels — but none of the inner
    spans: every reader answers ``None`` there, not the outer labels' sum.
    The two trace readers need a reduction and a chip; the span reader needs
    neither. A training cell opens no engine span."""
    read = M.load_reader(metric)
    parent_red = dict(RED, idle_gaps=[["program:prefill", 0.21], ["program:device_wait", 0.002]])
    run = {"parent": _run(PARENT_SERVE, red=parent_red),
           "no_trace": _run(SERVE, red=None),
           "rehearsal": dict(_run(SERVE), rehearsal=True),
           "bert": _run(TRAIN, red=dict(RED, idle_gaps=[["program:log_sync", 0.02]],
                                        programs={"jit_step": {"calls": 50, "seconds": 3.9}})),
           "no_window": {"win": {}, "trace": RED, "rehearsal": False}}[what]
    got = read(run)
    if metric == "engine_dispatch_ms_per_step" and what in ("no_trace", "rehearsal"):
        assert got == pytest.approx(14.5 / 3)
    else:
        assert got is None


# ------------------------------ the reduction names a gap by the inner span

def _extraction(host, busy):
    """One device whose operation line is busy over *busy* (ns intervals)
    inside one program's run, and one host line of (name, start, end)."""
    ops = [["%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)", a, b - a, {}] for a, b in busy]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": T.OPS_LINE, "events": ops},
            {"name": T.MODULES_LINE,
             "events": [["jit__chunk_program(7)", busy[0][0], busy[-1][1] - busy[0][0], {}]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[n, a, b - a, {}] for n, a, b in host]}]}]}


NEST = [("bench:engine_step", 0, 10_000), ("program:engine_step", 100, 9_900),
        ("program:decode", 200, 9_000), ("program:prefill", 1_000, 5_000)]


def test_a_gap_under_the_call_is_labelled_chunk_call_not_prefill():
    """``program:chunk_call`` nests in ``program:prefill`` nests in
    ``program:decode`` and ``program:engine_step``: every one of them covers
    the gap [3000, 4000], and the shortest names it."""
    host = NEST + [("program:chunk_operands", 1_050, 1_200), ("program:first_key", 1_300, 2_800),
                   ("program:chunk_call", 2_900, 4_400)]
    red = T.reduce(_extraction(host, [(500, 3_000), (4_000, 8_000)]))
    assert dict(map(tuple, red["idle_gaps"])) == {"program:chunk_call": pytest.approx(1000e-9)}


def test_a_gap_split_between_key_and_call_falls_to_prefill():
    """Neither ``first_key`` (45 % of the gap) nor ``chunk_call`` (45 %)
    covers half of [2000, 4000]: the shortest span that does is the
    ``prefill`` around both — the remainder a reading may leave there."""
    host = NEST + [("program:first_key", 1_300, 2_900), ("program:chunk_call", 3_100, 4_400)]
    red = T.reduce(_extraction(host, [(500, 2_000), (4_000, 8_000)]))
    assert dict(map(tuple, red["idle_gaps"])) == {"program:prefill": pytest.approx(2000e-9)}


def test_a_gap_under_a_fetch_is_labelled_with_the_fetch_not_the_wait():
    host = [("program:engine_step", 100, 9_900), ("program:decode", 200, 9_000),
            ("program:device_wait", 5_000, 8_900), ("program:fetch_tokens", 5_050, 8_000),
            ("program:fetch_keys", 8_100, 8_800)]
    red = T.reduce(_extraction(host, [(500, 5_500), (7_900, 8_200), (8_700, 9_500)]))
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps == {"program:fetch_tokens": pytest.approx(2400e-9),
                    "program:fetch_keys": pytest.approx(500e-9)}
    run = _run(SERVE, red=dict(red, programs={"jit__decode_program": {"calls": 2, "seconds": 1e-6}}))
    assert M.load_reader("engine_fence_idle_ms_per_decode")(run) == pytest.approx(1e3 * 2900e-9 / 2)


# ------------------------------------------------------ the fixture manifest

def test_the_three_readers_wait_in_a_fixture_manifest_for_a_benchmark_pr():
    """``test_perfbench_flash_roofline.py:75`` holds ``flash_attn_roofline`` to
    the LAST place of ``per_layer`` and a new entry may only be appended, so
    the three readers are files beside the others, listed in
    ``fixtures/dispatch_spans/manifest.json`` — the four serving cells'
    accepted entries, each list of cells cut to those four and nothing else
    altered, plus theirs — which ``run.py --manifest`` takes as it is."""
    assert not {m["name"] for m in MAN["per_layer"]} & set(NEW_READERS)
    fx = M.load_manifest(FIXTURE_MANIFEST)
    assert [w["name"] for w in fx["workloads"]] == SERVING
    assert fx["workloads"] == [w for w in MAN["workloads"] if w["name"] in SERVING]
    held = {w["config"] for w in fx["workloads"]}
    assert fx["configs"] == [c for c in MAN["configs"] if c["name"] in held]
    assert (fx["command"], fx["paths"], fx["run_seconds"]) == (
        MAN["command"], MAN["paths"], MAN["run_seconds"])

    def cut(ms):
        out = []
        for m in ms:
            if "workloads" in m:
                m = dict(m, workloads=[w for w in m["workloads"] if w in SERVING])
            if m.get("workloads", True):
                out.append(m)
        return out
    assert fx["end_to_end"] == cut(MAN["end_to_end"])
    assert fx["per_layer"][:-3] == cut(MAN["per_layer"])
    for cell in SERVING:
        theirs = [m["name"] for m in M.Cell(fx, cell).per_layer()]
        accepted = [m["name"] for m in M.Cell(MAN, cell).per_layer()]
        assert theirs == accepted + (list(NEW_READERS) if cell in RATE_CELLS else [])
        assert M.Cell(fx, cell).options == M.Cell(MAN, cell).options
    assert [m["name"] for m in fx["per_layer"][-3:]] == list(NEW_READERS)
    for m in fx["per_layer"][-3:]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["layer"], m["moves"], m["better"]) == (
            "serving engine", "serve_out_tokens_per_s", "lower")
        assert m["workloads"] == RATE_CELLS and callable(M.load_reader(m["name"]))
    assert [(m["unit"], m["source"]) for m in fx["per_layer"][-3:]] == [
        ("ms", "program_span"), ("%", "device_trace"), ("ms", "device_trace")]


def test_a_rehearsal_through_the_fixture_manifest_reads_the_engines_own_spans(capsys):
    """The Mistral cell at its tiny size on the CPU, through the fixture
    manifest: the engine's own ``decode_call`` / ``chunk_call`` records reach
    the span reader (its name is among those reported); the two trace readers
    have no chip to read and stay out of the line."""
    rc = bench_run.main(["--workload", "mistral-7b-d16.chat-backlog", "--seed", "3500000019",
                         "--seconds", "1", "--trace", "1", "--rehearsal",
                         "--manifest", FIXTURE_MANIFEST])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["rehearsal"] and line["correct"] and line["failed"] == 0
    reported = set(line["metrics_reported"])
    assert {"engine_dispatch_ms_per_step", "engine_host_ms_per_step"} <= reported
    assert not reported & {"engine_dispatch_idle_share", "engine_fence_idle_ms_per_decode"}
