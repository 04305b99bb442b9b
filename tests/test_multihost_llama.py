"""Multi-host LLAMA training e2e: the flagship CLI on a 2-process world.

Complements ``test_multihost_train.py`` (mnist): two OS processes form the
JAX world from the TPUJOB_* env contract and run ``train_llama.py`` with an
FSDP axis spanning BOTH processes — the collectives (param all-gather +
grad reduce-scatter) really cross the process boundary over the
coordinator-established transport, which no single-process virtual-mesh
test exercises.
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

import train_llama

buf = io.StringIO()
real_stdout = sys.stdout
sys.stdout = buf
try:
    result = train_llama.main([
        "--preset", "tiny", "--dp", "2", "--fsdp", "2",
        "--num-steps", "12", "--batch-size", "8", "--seq-len", "64",
        "--log-every", "4", "--no-eval", "--prefetch", "0",
        "--checkpoint-dir", os.environ["CK_DIR"],
        "--checkpoint-every", "1000",
    ])
finally:
    sys.stdout = real_stdout

events = [json.loads(l) for l in buf.getvalue().splitlines()
          if l.strip().startswith("{")]
print(json.dumps({
    "pid": jax.process_index(),
    "emitted_metrics": len(events),
    "losses": {e["step"]: e["loss"] for e in events
               if e.get("event") == "train_step"},
    "num_steps": result["num_steps"],
    "world_size": result["world_size"],
}))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_train_llama_two_process_fsdp(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            REPO_ROOT=REPO,
            CK_DIR=str(tmp_path / "ck"),
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
        results_line = out.strip().splitlines()[-1]
        rec = json.loads(results_line)
        results[rec["pid"]] = rec

    assert set(results) == {0, 1}
    r0, r1 = results[0], results[1]
    # 2 processes x 2 virtual devices = 4 chips: mesh dp2 x fsdp2 — the
    # fsdp axis spans the process boundary.
    assert r0["world_size"] == 4 and r0["num_steps"] == 12
    assert r0["emitted_metrics"] > 0
    assert r1["emitted_metrics"] == 0     # rank-0 logging discipline
    losses = {int(k): v for k, v in r0["losses"].items()}
    assert losses[max(losses)] < losses[min(losses)], losses
