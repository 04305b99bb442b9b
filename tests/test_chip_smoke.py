"""The parts of chip_smoke.py's contract that need no chip, and the two
rules it leans on (where the compile cache lives; no made-up peaks).

The smoke itself passes only on a TPU. What tier-1 can hold it to here:
without an accelerator it exits non-zero, prints no result and names the
backend error (no CPU fallback); alone in a directory it fails the same way;
its parent — like the launcher's — never imports jax.
"""
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from k8s_distributed_deeplearning_tpu import backend
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "k8s_distributed_deeplearning_tpu"


def _smoke_in(tmp_path, *, with_package: bool):
    """Run a copy of chip_smoke.py from *tmp_path* (its output directory is
    beside the script, so the checkout's chiprun_out/ stays untouched)."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    if with_package:
        os.symlink(os.path.join(REPO, PKG), tmp_path / PKG)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)


def test_smoke_without_accelerator_fails_and_names_the_backend(tmp_path):
    out = _smoke_in(tmp_path, with_package=True)
    assert out.returncode != 0
    assert out.stdout.strip() == "", "a failed smoke prints no result line"
    # The children are pinned to JAX_PLATFORMS=tpu whatever this process
    # runs on (conftest pins cpu): a missing chip is an error, not a CPU run.
    assert "Unable to initialize backend 'tpu'" in out.stderr
    assert '"ok": false' in out.stderr


def test_smoke_alone_in_a_directory_fails(tmp_path):
    out = _smoke_in(tmp_path, with_package=False)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "No module named" in out.stderr


def test_smoke_and_launcher_parents_stay_off_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke, %s.launch;"
            " assert 'jax' not in sys.modules, 'jax imported'" % (REPO, PKG))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_result_line_has_exactly_the_contract_keys():
    """What the driver reads off the last line of stdout: ``ok`` and
    ``device``, the device ``platform``/``kind`` (text) and ``count`` (a whole
    number) — and nothing else; the per-phase report is a line of its own."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.fixture()
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_compile_cache_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(backend.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert backend.use_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch,
                                                        config_updates):
    monkeypatch.setenv(backend.CACHE_ENV, "/somewhere/else")
    assert backend.use_compile_cache() == "/somewhere/else"
    assert config_updates == []


def _fake_devices(monkeypatch, platform: str, kind: str):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_peak_flops_unknown_accelerator_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        mesh_lib.peak_flops_per_device()
    with pytest.raises(ValueError, match="TPU v99"):
        mesh_lib.interconnect_bandwidth_estimate()


def test_peak_flops_known_kind_and_cpu(monkeypatch):
    assert mesh_lib.peak_flops_per_device() is None      # the CPU has no peak
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite")
    assert mesh_lib.peak_flops_per_device("bfloat16") == 197e12
