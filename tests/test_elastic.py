"""Elastic DP through the control plane: re-render + restart + checkpoint
resume. Proves the two elastic behaviors the reference only links to
(horovod/README.md:20-22) — crash recovery and world-resize — by EXECUTING
the rendered job, not by unit-testing the checkpoint layer (that's
tests/test_checkpoint.py)."""
import json
import os
import sys
import textwrap

import pytest

from k8s_distributed_deeplearning_tpu.config import JobConfig
from k8s_distributed_deeplearning_tpu.launch import elastic, local_executor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
}


def _mnist_cfg(tmp_path, workers, num_steps):
    return JobConfig(
        num_workers=workers,
        script="examples/train_mnist.py",
        script_args=["--num-steps", str(num_steps), "--batch-size", "8",
                     "--no-eval", "--checkpoint-dir", str(tmp_path / "ck"),
                     "--checkpoint-every", "10", "--log-every", "10",
                     "--prefetch", "0"],
    )


def _events(result):
    return [json.loads(l) for l in result.stdout.splitlines()
            if l.startswith("{")]


@pytest.mark.slow
def test_elastic_resize_resumes_from_checkpoint(tmp_path):
    """World resize 2 -> 1 through the rendered-job path: phase B restores
    phase A's step instead of starting over."""
    # Phase A: 2 workers x 2 devices = world 4; 160 global steps -> 40 local.
    res, restarts = elastic.run_elastic(
        _mnist_cfg(tmp_path, 2, 160), extra_env=CPU_ENV, cwd=REPO,
        timeout=420)
    assert restarts == 0 and len(res) == 2
    # Phase B: "scaled down" to 1 worker (world 2; 160 -> 80 local steps),
    # same checkpoint dir: must restore at 40, finish at 80.
    res, restarts = elastic.run_elastic(
        _mnist_cfg(tmp_path, 1, 160), extra_env=CPU_ENV, cwd=REPO,
        timeout=420)
    assert restarts == 0 and len(res) == 1
    events = _events(res[0])
    restore = next(e for e in events if e.get("event") == "restore")
    assert restore["step"] == 40
    assert any(e.get("event") == "checkpoint" and e.get("step") == 80
               for e in events)


@pytest.mark.slow
def test_elastic_restarts_crashed_gang(tmp_path):
    """A worker that dies on the first attempt: the reconcile loop restarts
    the gang and the retry succeeds (K8s-eviction recovery, locally)."""
    crash_flag = tmp_path / "crashed_once"
    script = tmp_path / "flaky_worker.py"
    script.write_text(textwrap.dedent(f"""
        import json, os, sys
        if os.environ["TPUJOB_PROCESS_ID"] == "1" \\
                and not os.path.exists({str(crash_flag)!r}):
            open({str(crash_flag)!r}, "w").close()
            sys.exit(17)   # simulated eviction, first attempt only
        print(json.dumps({{"event": "worker_ok",
                           "pid": os.environ["TPUJOB_PROCESS_ID"],
                           "world": os.environ["TPUJOB_NUM_PROCESSES"]}}))
    """))
    cfg = JobConfig(num_workers=2, script=str(script), script_args=[])
    seen = []
    res, restarts = elastic.run_elastic(
        cfg, cwd=REPO, timeout=120,
        on_restart=lambda n, c: seen.append((n, c.num_workers)))
    assert restarts == 1 and seen == [(1, 2)]
    assert all(r.returncode == 0 for r in res)
    assert crash_flag.exists()


def test_elastic_resize_on_failure(tmp_path):
    """The failure->resize branch: worker 1 of 2 dies, the resize policy
    shrinks the world to 1, and the retried 1-worker gang succeeds."""
    script = tmp_path / "needs_small_world.py"
    script.write_text(textwrap.dedent("""
        import json, os, sys
        if os.environ["TPUJOB_NUM_PROCESSES"] != "1" \\
                and os.environ["TPUJOB_PROCESS_ID"] == "1":
            sys.exit(23)   # dies until the world shrinks to 1
        print(json.dumps({"event": "worker_ok",
                          "world": os.environ["TPUJOB_NUM_PROCESSES"]}))
    """))
    cfg = JobConfig(num_workers=2, script=str(script), script_args=[])
    seen = []
    res, restarts = elastic.run_elastic(
        cfg, cwd=REPO, timeout=120, resize=elastic.resize_to(1),
        on_restart=lambda n, c: seen.append((n, c.num_workers)))
    assert restarts == 1 and seen == [(1, 1)]
    assert len(res) == 1 and res[0].returncode == 0
    assert _events(res[0])[0]["world"] == "1"


def test_elastic_gives_up_after_max_restarts(tmp_path):
    script = tmp_path / "always_fails.py"
    script.write_text("import sys; sys.exit(3)\n")
    cfg = JobConfig(num_workers=1, script=str(script), script_args=[])
    with pytest.raises(RuntimeError, match="gang failed"):
        elastic.run_elastic(cfg, cwd=REPO, max_restarts=1, timeout=60)


def test_resize_policy():
    cfg = JobConfig(num_workers=4)
    new = elastic.resize_to(2)(cfg, [])
    assert new.num_workers == 2 and cfg.num_workers == 4
