"""The bench regression gate (bench.check_regression) as a pure function.

Round 2: a 2-3% headline slide shipped silently because bench.py
had no stored baseline. These tests prove the gate fires exactly when a
metric drops below baseline*(1-band) — including for metrics nested in
``extra`` — without touching a TPU.
"""
import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "BASELINE_FILE", str(tmp_path / "baseline.json"))
    return mod


def write_baseline(mod, spec):
    with open(mod.BASELINE_FILE, "w") as f:
        json.dump(spec, f)


def test_pass_within_band(bench):
    write_baseline(bench, {"m": {"value": 100.0, "band_pct": 3.0}})
    assert bench.check_regression({"metric": "m", "value": 98.0}) == []


def test_fail_below_band(bench):
    write_baseline(bench, {"m": {"value": 100.0, "band_pct": 3.0}})
    msgs = bench.check_regression({"metric": "m", "value": 96.9})
    assert len(msgs) == 1 and "REGRESSION m" in msgs[0]


def test_extra_metrics_gated(bench):
    # The r2 dip was in extra["llama_small_tokens_per_sec_per_chip"] of the
    # "all" suite record — the gate must see nested extras.
    write_baseline(bench, {
        "llama_small_tokens_per_sec_per_chip":
            {"value": 85173, "band_pct": 3.0}})
    rec = {"metric": "mnist_conv_dp_images_per_sec_per_chip", "value": 5e5,
           "extra": {"llama_small_tokens_per_sec_per_chip": 83121.7}}
    assert bench.check_regression(rec) == []  # 83121 > 85173*0.97=82618
    rec["extra"]["llama_small_tokens_per_sec_per_chip"] = 82000.0
    assert len(bench.check_regression(rec)) == 1


def test_would_have_caught_r2_dip_at_measured_band(bench):
    # With the band at the measured ~1% spread the r2 dip (85173 -> 83121,
    # -2.4%) fails the gate — the round-2 acceptance criterion.
    write_baseline(bench, {
        "llama_small_tokens_per_sec_per_chip":
            {"value": 85173, "band_pct": 1.5}})
    rec = {"metric": "llama_small_tokens_per_sec_per_chip", "value": 83121.7,
           "extra": {}}
    assert len(bench.check_regression(rec)) == 1


def test_missing_baseline_file_passes(bench):
    assert bench.check_regression({"metric": "m", "value": 1.0}) == []


def test_unknown_and_non_numeric_keys_ignored(bench):
    write_baseline(bench, {"m": {"value": 100.0}, "other": {"value": 5.0}})
    rec = {"metric": "m", "value": 100.0, "extra": {"cfg": {"a": 1}}}
    assert bench.check_regression(rec) == []
