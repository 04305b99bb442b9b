"""Full multi-host training e2e: the actual CLI script on a 2-process world.

The strongest mpirun-parity proof in CI: two OS processes form the JAX world
from the TPUJOB_* env contract (what the rendered manifest injects), run
``examples/train_mnist.py`` end to end with disjoint data shards, and must
(a) agree bitwise on the training loss (synchronous DP), (b) emit metrics
from process 0 only (rank-0 discipline), and (c) both finish cleanly.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import io, json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2").strip()
sys.path.insert(0, os.environ["REPO_ROOT"])
sys.path.insert(0, os.path.join(os.environ["REPO_ROOT"], "examples"))
import jax

import train_mnist

buf = io.StringIO()
real_stdout = sys.stdout
sys.stdout = buf            # capture the metrics JSONL
try:
    result = train_mnist.main([
        "--num-steps", "160",          # // world(4 devices) -> 40 steps
        "--batch-size", "8",
        "--checkpoint-dir", os.environ["CK_DIR"],
        "--checkpoint-every", "1000", "--log-every", "10", "--no-eval",
    ])
finally:
    sys.stdout = real_stdout

lines = [l for l in buf.getvalue().splitlines() if l.strip().startswith("{")]
events = [json.loads(l) for l in lines]
losses = {e["step"]: e["loss"] for e in events if e.get("event") == "train_step"}
print(json.dumps({
    "pid": jax.process_index(),
    "emitted_metrics": len(events),
    "losses": losses,
    "num_steps": result["num_steps"],
    "world_size": result["world_size"],
}))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_train_mnist_two_process_world(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            REPO_ROOT=REPO,
            CK_DIR=str(tmp_path / "ck"),      # shared: orbax saves are collective
            TPUJOB_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            TPUJOB_NUM_PROCESSES="2",
            TPUJOB_PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    results = {}
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[rec["pid"]] = rec

    assert set(results) == {0, 1}
    r0, r1 = results[0], results[1]
    # 2 processes x 2 virtual devices = world 4; steps 160 // 4 = 40.
    assert r0["world_size"] == 4 and r0["num_steps"] == 40
    # Rank-0 logging discipline: only process 0 emits metrics.
    assert r0["emitted_metrics"] > 0
    assert r1["emitted_metrics"] == 0
    # Synchronous DP: training converged on the primary's logged losses.
    losses = {int(k): v for k, v in r0["losses"].items()}
    assert losses[max(losses)] < losses[min(losses)]
    assert losses[max(losses)] < 0.5, losses
