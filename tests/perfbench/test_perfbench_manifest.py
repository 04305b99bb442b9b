"""The manifest and the data-driven layout: every cell's files exist, every
per-layer metric has a reader, every ``moves`` names an end-to-end metric each
of its cells reports, names and units keep to the allowed characters — and a
cell made of nothing but fixture files loads with no edit to the harness."""
import json
import os
import re

import pytest

from benchmarks import run as bench_run
from benchmarks.harness import manifest as M

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = M.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51 and isinstance(MAN["run_seconds"], int)
    assert len(json.dumps(MAN)) < 64 * 1024
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in MAN["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_load(cell):
    c = M.Cell(MAN, cell)
    assert c.traffic["driver"] in ("train", "serve")
    assert os.path.exists(os.path.join(M.BENCH_DIR, "harness", c.driver + "_window.py"))
    assert os.path.exists(os.path.join(
        M.BENCH_DIR, "harness", "family_" + c.config["family"].replace("-", "_") + ".py"))
    assert c.config_entry["file"].startswith(tuple(p + "/" for p in MAN["paths"]))
    assert sorted(c.config["reduced"]) == sorted(c.config_entry["reduced"])
    assert c.options.get("limits"), "every cell states the limits of `correct`"
    e2e = [m["name"] for m in c.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(c.per_layer()) >= 1
    assert len(c.entry["why"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(M.load_reader(metric))


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_moves_names_an_end_to_end_metric_its_cells_report(metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    target = next(x for x in MAN["end_to_end"] if x["name"] == m["moves"])
    cells = m.get("workloads", CELLS)
    for c in cells:
        assert c in CELLS
        assert "workloads" not in target or c in target["workloads"], (metric, c)
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_names_units_and_sources_use_allowed_characters(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    if "bound" in m:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_of_cells_configs_and_traffic():
    for w in MAN["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["paths"]:
        for d, _, files in os.walk(os.path.join(M.ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), M.ROOT)), f


def test_a_cell_made_of_fixture_files_loads_without_touching_the_harness():
    man = M.load_manifest(os.path.join(HERE, "fixtures", "manifest.json"))
    bench = os.path.join(HERE, "fixtures", "bench")
    cell = M.Cell(man, "toy-config.toy-mix", bench_dir=bench)
    assert cell.driver == "toy" and cell.config["hidden_size"] == 8
    assert cell.options["limits"] == {"toy_gap": 0.5}
    assert [m["name"] for m in cell.end_to_end()] == ["toy_rate", "setup_s"]
    run = {"hits": 3, "lookups": 4}
    got = {}
    for m in cell.per_layer():
        v = M.load_reader(m["name"], bench_dir=bench)(run)
        if v is not None:
            got[m["name"]] = v
    assert got == {"toy_layer_metric": 75.0}      # the silent one is left out


def test_last_line_key_set_and_order():
    line = bench_run.result_line(True, 10, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
                                 {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                                  "memory_peak_bytes": 1}, None, {"x": [0.1, 0.2]})
    assert tuple(line)[:5] == bench_run.RESULT_KEYS and tuple(line)[-1] == "compared"
    traced = bench_run.result_line(True, 10, 0, {}, {}, {"device_ops": [], "idle_gaps": []}, {})
    assert set(traced) == set(bench_run.RESULT_KEYS) | {"breakdown", "compared"}
    json.dumps(line)


def test_unknown_device_kind_is_an_error_not_a_default():
    from benchmarks.harness import peaks
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
