"""Multi-process world formation over the coordinator (DCN path), on CPU.

SURVEY.md §4's "Multi-process" tier: spawn two real OS processes that form a
JAX distributed world via ``distributed.initialize_from_env`` (the same env
contract the TPUJob manifest injects, ``launch/render.py``), then run a
global-batch computation whose result requires both processes' data — the
CI analog of two pods bootstrapping over DCN.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2").strip()
sys.path.insert(0, os.environ["REPO_ROOT"])
import jax

from k8s_distributed_deeplearning_tpu.parallel import distributed

assert distributed.initialize_from_env(), "world must form from env"
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib

pid = distributed.process_index()
world = distributed.process_count()
mesh = mesh_lib.make_mesh({"data": -1})          # all global devices
sh = NamedSharding(mesh, P("data"))

# Each process contributes a distinct local slice; the jitted global sum can
# only be right if cross-process data movement works.
local = jnp.full((2, 4), float(pid + 1))          # 2 local devices x rows
garr = jax.make_array_from_process_local_data(sh, local)
total = jax.jit(lambda x: x.sum(),
                out_shardings=NamedSharding(mesh, P()))(garr)
expected = 4.0 * sum(2 * (i + 1) for i in range(world))

print(json.dumps({
    "pid": pid, "world": world,
    "global_devices": len(jax.devices()),
    "local_devices": len(jax.local_devices()),
    "is_primary": distributed.is_primary(),
    "total": float(total), "expected": expected,
}))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_world_and_global_computation(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            REPO_ROOT=REPO,
            TPUJOB_COORDINATOR_ADDRESS=f"127.0.0.1:{port}",
            TPUJOB_NUM_PROCESSES="2",
            TPUJOB_PROCESS_ID=str(pid),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))

    results = {}
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        rec = json.loads(out.strip().splitlines()[-1])
        results[rec["pid"]] = rec

    assert set(results) == {0, 1}
    for pid, rec in results.items():
        assert rec["world"] == 2
        assert rec["global_devices"] == 4      # 2 procs x 2 virtual devices
        assert rec["local_devices"] == 2
        assert rec["is_primary"] == (pid == 0)
        assert rec["total"] == rec["expected"], rec
