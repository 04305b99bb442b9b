"""Deployment smoke: the L2/L3 layer verified by validation + execution,
not string-matching (SURVEY.md §4). Tier 1: offline structural validation.
Tier 2 (gated): kubectl server dry-run against a live cluster/kind. Tier 3:
the rendered Job EXECUTED locally — the Indexed-Job controller emulated, env
taken from the manifest itself."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from k8s_distributed_deeplearning_tpu.config import JobConfig
from k8s_distributed_deeplearning_tpu.launch import (
    local_executor,
    render,
    validate,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rendered_manifests_validate_clean():
    for workers in (1, 2, 8):
        docs = render.render_all(JobConfig(num_workers=workers,
                                           tpu_topology="2x8"))
        assert validate.validate(docs) == [], workers


def test_validator_catches_seeded_faults():
    """Each fault class the validator claims to catch, caught."""
    cfg = JobConfig(num_workers=2)

    docs = render.render_all(JobConfig(num_workers=2, name="Bad_Name"))
    assert any("RFC-1123" in e for e in validate.validate(docs))

    docs = render.render_all(JobConfig(num_workers=2, memory="4GiB"))  # typo
    assert any("quantity" in e for e in validate.validate(docs))

    docs = render.render_all(cfg)
    docs[-1]["spec"]["completions"] = 3          # gang broken
    assert any("parallelism" in e for e in validate.validate(docs))

    docs = render.render_all(cfg)
    env = docs[-1]["spec"]["template"]["spec"]["containers"][0]["env"]
    env[1]["value"] = "7"                        # NUM_PROCESSES lies
    assert any("TPUJOB_NUM_PROCESSES" in e for e in validate.validate(docs))

    docs = render.render_all(cfg)
    docs[-1]["spec"]["template"]["spec"]["subdomain"] = "elsewhere"
    errs = validate.validate(docs)
    assert any("coordinator host" in e or "Service" in e for e in errs)

    # Job rendered without its headless Service: pod DNS would not resolve.
    docs = [d for d in render.render_all(cfg) if d["kind"] != "Service"]
    assert any("headless Service" in e for e in validate.validate(docs))


def test_validate_cli_ok():
    out = subprocess.run(
        [sys.executable, "-m", "k8s_distributed_deeplearning_tpu.launch",
         "validate", "--workers", "4"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert "offline validation: OK" in out.stdout


@pytest.mark.skipif(shutil.which("kubectl") is None,
                    reason="kubectl not installed")
def test_kubectl_server_dry_run():
    """Gated: server-side schema validation when a cluster (e.g. kind)
    answers; skips when the API server is unreachable."""
    docs = render.render_all(JobConfig(num_workers=2))
    try:
        ok, out = validate.kubectl_validate(render.to_yaml(docs))
    except Exception as e:  # no cluster behind kubectl
        pytest.skip(f"no reachable cluster: {e}")
    if "connection refused" in out or "Unable to connect" in out:
        pytest.skip("no reachable cluster")
    assert ok, out


@pytest.mark.slow
def test_rendered_job_executes_locally(tmp_path):
    """Execute the manifest: 2 workers spawned per the rendered Job (env,
    fieldRefs, command all from the manifest) form a real 2-process JAX
    world, train MNIST, and rank-0 discipline holds. A rendering bug in the
    env contract fails this test the way it would fail the real Job."""
    cfg = JobConfig(
        num_workers=2,
        script="examples/train_mnist.py",
        script_args=["--num-steps", "80", "--batch-size", "8", "--no-eval",
                     "--checkpoint-dir", str(tmp_path / "ck"),
                     "--checkpoint-every", "1000", "--log-every", "10",
                     "--prefetch", "0"],
    )
    results = local_executor.run_local(
        cfg, timeout=420, cwd=REPO,
        extra_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        })
    assert [r.returncode for r in results] == [0, 0], \
        results[0].stderr[-2000:] + results[1].stderr[-2000:]
    # Rank-0 discipline straight from the manifest-injected identity.
    ev0 = [json.loads(l) for l in results[0].stdout.splitlines()
           if l.startswith("{")]
    ev1 = [json.loads(l) for l in results[1].stdout.splitlines()
           if l.startswith("{")]
    assert any(e.get("event") == "train_step" for e in ev0)
    assert not ev1, "non-primary worker must not emit metrics"
    start = next(e for e in ev0 if e.get("event") == "start")
    assert start["world_size"] == 4  # 2 processes x 2 virtual devices


def test_run_local_rejects_invalid_manifest():
    with pytest.raises(ValueError, match="validation failed"):
        local_executor.run_local(JobConfig(num_workers=2, name="Bad_Name"))
