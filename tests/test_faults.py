"""Fault-injection harness + crash-safe recovery — the chaos matrix.

Each fault type the harness can inject (hard kill, SIGTERM, external
executor kill, data stall, transient shard-read IO error, corrupt/truncated
checkpoint, silenced heartbeat) is driven against the REAL recovery path —
``run_elastic`` over the rendered gang, ``train.loop.fit`` restore-on-start,
the manifest-verified checkpoint fallback chain — and recovery is asserted
*deterministically*: the faulted run's final parameters must be
bit-identical to an unfaulted run's (replay-free resume makes that an
equality check, not a tolerance check).
"""
import json
import os
import textwrap

import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu import faults
from k8s_distributed_deeplearning_tpu.config import JobConfig
from k8s_distributed_deeplearning_tpu.faults.plan import Fault, FaultPlan
from k8s_distributed_deeplearning_tpu.launch import elastic
from k8s_distributed_deeplearning_tpu.launch import watch as watch_mod
from k8s_distributed_deeplearning_tpu.utils import ckpt as ckpt_paths
from k8s_distributed_deeplearning_tpu.utils.retry import retry_transient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    # worker scripts live in tmp dirs, so the package isn't on sys.path[0]
    "PYTHONPATH": REPO,
}


@pytest.fixture(autouse=True)
def _fault_isolation(monkeypatch):
    """No plan leaks between tests: clear the env and the process-global
    injector cache on both sides of every test in this module."""
    monkeypatch.delenv(faults.FAULT_PLAN_ENV, raising=False)
    faults.deactivate()
    yield
    faults.deactivate()


# --------------------------------------------------------------- plan layer


def test_plan_json_roundtrip():
    plan = FaultPlan(faults=(
        Fault(site="step", action="exit", rank=1, step=5, exit_code=43),
        Fault(site="shard_read", action="ioerror", after=2, count=3),
        Fault(site="data_wait", action="stall", step=2, seconds=1.5),
    ))
    plan.validate_or_raise()
    again = FaultPlan.from_json(plan.to_json())
    assert again == plan


def test_plan_rejects_bad_shapes():
    with pytest.raises(ValueError, match="unknown"):
        FaultPlan.from_json('{"faults": [{"site": "step", "action": "exit",'
                            ' "bogus_field": 1}]}')
    with pytest.raises(ValueError):
        FaultPlan.from_json('{"faults": [17]}')
    # site/action combination validity
    assert FaultPlan((Fault(site="heartbeat", action="exit"),)).problems()
    assert FaultPlan((Fault(site="step", action="truncate"),)).problems()
    # stall needs a duration; executor faults need a named rank
    assert FaultPlan((Fault(site="step", action="stall"),)).problems()
    assert FaultPlan((Fault(site="executor", action="exit"),)).problems()


def test_injector_rank_attempt_and_window_scoping():
    plan = FaultPlan(faults=(
        Fault(site="shard_read", action="ioerror", rank=0, attempt=0,
              after=1, count=2),
    ))
    inj = faults.FaultInjector(plan, rank=0, attempt=0)
    inj.fire("shard_read")                       # visit 1: before the window
    for _ in range(2):                           # visits 2, 3: inside it
        with pytest.raises(OSError, match="injected"):
            inj.fire("shard_read")
    inj.fire("shard_read")                       # visit 4: window exhausted
    assert len(inj.fired) == 2
    # Wrong rank or wrong attempt: the same plan never fires.
    for kw in ({"rank": 1, "attempt": 0}, {"rank": 0, "attempt": 1}):
        quiet = faults.FaultInjector(plan, **kw)
        for _ in range(5):
            quiet.fire("shard_read")
        assert quiet.fired == []


def test_active_reads_env_once(monkeypatch):
    assert faults.active() is None
    # Setting the env AFTER resolution must not resurrect a plan mid-run.
    monkeypatch.setenv(faults.FAULT_PLAN_ENV, json.dumps(
        {"faults": [{"site": "step", "action": "stall", "step": 0,
                     "seconds": 1.0}]}))
    assert faults.active() is None
    faults.deactivate()                          # re-resolve
    inj = faults.active()
    assert inj is not None and len(inj.plan.faults) == 1


# -------------------------------------------------------------- utils.retry


def test_retry_transient_backoff_schedule():
    sleeps, calls = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("blip")
        return "ok"

    assert retry_transient(flaky, retries=2, backoff_s=0.5,
                           sleep=sleeps.append) == "ok"
    assert sleeps == [0.5, 1.0]


def test_retry_transient_permanent_error_surfaces_first_attempt():
    sleeps = []

    def broken():
        raise ValueError("config error")

    with pytest.raises(ValueError):
        retry_transient(broken, retries=5, sleep=sleeps.append)
    assert sleeps == []


def test_retry_transient_exhaustion_propagates():
    sleeps = []

    def always():
        raise OSError("still down")

    with pytest.raises(OSError):
        retry_transient(always, retries=2, backoff_s=0.1,
                        sleep=sleeps.append)
    assert sleeps == [0.1, 0.2]


# ------------------------------------------- in-process training-loop chaos

def _tiny_fit(num_steps=6, checkpointer=None, checkpoint_every=0,
              heartbeat=None):
    """Minimal deterministic fit() run: stateless batch schedule + fold_in
    RNG, so two runs (or a faulted run that restores) agree bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.train import loop as train_loop

    @jax.jit
    def step(state, batch, rng):
        w = state["w"]
        loss = jnp.sum((w - batch["target"]) ** 2)
        noise = jax.random.normal(rng, w.shape) * 1e-3
        return {"w": w - 0.2 * (w - batch["target"]) + noise}, loss, {}

    def batches(start):
        def gen():
            s = start
            while True:
                yield {"target": jnp.full((4,), 0.01 * s, jnp.float32)}
                s += 1
        return gen()

    return train_loop.fit(step, {"w": jnp.zeros((4,), jnp.float32)}, batches,
                          num_steps, jax.random.key(7), log_every=0,
                          checkpointer=checkpointer,
                          checkpoint_every=checkpoint_every,
                          heartbeat=heartbeat)


def test_data_stall_fault_delays_but_never_diverges():
    """Chaos type: data-iterator stall. The stall costs wall-clock only —
    the trained parameters are bit-identical to an unfaulted run."""
    sleeps = []
    faults.activate(FaultPlan((
        Fault(site="data_wait", action="stall", step=2, seconds=7.5),)),
        sleep=sleeps.append)
    faulted = _tiny_fit()
    faults.deactivate()
    clean = _tiny_fit()
    assert sleeps == [7.5]
    np.testing.assert_array_equal(np.asarray(faulted["w"]),
                                  np.asarray(clean["w"]))


def test_heartbeat_stop_fault_is_detected_as_stall(tmp_path):
    """Chaos type: heartbeat writer silenced mid-run. Training itself is
    unaffected, and the watch-side stall detector names the silent rank."""
    from k8s_distributed_deeplearning_tpu.telemetry import heartbeat as hb

    writer = hb.HeartbeatWriter(str(tmp_path / "hb"), rank=0,
                                clock=lambda: 100.0)
    faults.activate(FaultPlan((
        Fault(site="heartbeat", action="stop", step=3),)))
    faulted = _tiny_fit(heartbeat=writer)
    faults.deactivate()
    clean = _tiny_fit()
    np.testing.assert_array_equal(np.asarray(faulted["w"]),
                                  np.asarray(clean["w"]))
    recs = hb.read_heartbeats(str(tmp_path / "hb"))
    assert len(recs) == 1 and recs[0]["step"] == 2   # beats 1, 2 then silence
    stalls = hb.detect_stalls(str(tmp_path / "hb"), 5.0, now=200.0)
    assert [s.rank for s in stalls] == [0]


def test_shard_read_transient_ioerror_is_retried(tmp_path):
    """Chaos type: transient IO errors from shard reads. Two injected
    failures cost two backoff sleeps; the delivered batch is identical to
    an unfaulted read. A failure outlasting the retry budget surfaces."""
    from k8s_distributed_deeplearning_tpu.train.data import TokenShardBatcher

    np.save(tmp_path / "shard.npy",
            np.arange(500, dtype=np.int32))
    ref = TokenShardBatcher(str(tmp_path), batch_size=2,
                            seq_len=8).batch_at(0)

    sleeps = []
    faults.activate(FaultPlan((
        Fault(site="shard_read", action="ioerror", count=2),)))
    out = TokenShardBatcher(str(tmp_path), batch_size=2, seq_len=8,
                            io_backoff_s=0.05,
                            sleep=sleeps.append).batch_at(0)
    np.testing.assert_array_equal(out["tokens"], ref["tokens"])
    assert sleeps == [0.05, 0.1]

    faults.activate(FaultPlan((
        Fault(site="shard_read", action="ioerror", count=10),)))
    with pytest.raises(OSError, match="injected"):
        TokenShardBatcher(str(tmp_path), batch_size=2, seq_len=8,
                          io_backoff_s=0.01,
                          sleep=lambda _s: None).batch_at(0)


# --------------------------------------- checkpoint integrity + quarantine


class _RecordingMetrics:
    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append((event, fields))


def _make_ckpt(directory, metrics=None):
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.train.checkpoint import Checkpointer

    ck = Checkpointer(str(directory), metrics=metrics)
    for step in (2, 4):
        ck.save(step, {"w": jnp.full((64,), float(step), jnp.float32)})
    return ck


@pytest.mark.parametrize("mode,marker", [("truncate", "truncated"),
                                         ("corrupt", "corrupt bytes")])
def test_damaged_newest_checkpoint_quarantined_and_older_restored(
        tmp_path, mode, marker):
    """Chaos type: corrupt checkpoint — BOTH damage shapes (torn write
    that changes the size, bitrot that preserves it). Restore must verify
    the manifest, quarantine the bad step with an event, and fall back to
    the previous good step instead of bricking the job."""
    import jax.numpy as jnp

    metrics = _RecordingMetrics()
    ck = _make_ckpt(tmp_path / "ck", metrics=metrics)
    victim = faults.inject.damage_newest_checkpoint(ck.directory, mode=mode)
    assert victim is not None

    state, step = ck.restore_latest({"w": jnp.zeros((64,), jnp.float32)})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.full((64,), 2.0, np.float32))
    assert ck.quarantined and ck.quarantined[0][0] == 4
    assert marker in ck.quarantined[0][1]
    names = os.listdir(ck.directory)
    qdirs = [n for n in names if n.startswith("quarantined-4")]
    assert len(qdirs) == 1
    # evidence preserved: manifest + reason ride inside the quarantine dir
    qfiles = os.listdir(os.path.join(ck.directory, qdirs[0]))
    assert "manifest.json" in qfiles and "reason.txt" in qfiles
    assert [e for e, _ in metrics.events if e == "ckpt_quarantined"]
    ck.close()


def test_all_steps_damaged_restores_none(tmp_path):
    """Every step bad: the fallback chain quarantines each in turn and
    restore_latest reports "nothing restorable" instead of raising."""
    import jax.numpy as jnp

    ck = _make_ckpt(tmp_path / "ck")
    faults.inject.damage_newest_checkpoint(ck.directory, mode="truncate")
    # damage_newest only targets the newest step (4); tear step 2 directly
    root2 = os.path.join(ck.directory, "2")
    victim2 = max((os.path.join(dp, n)
                   for dp, _, ns in os.walk(root2) for n in ns),
                  key=os.path.getsize)
    with open(victim2, "r+b") as f:
        f.truncate(1)
    assert ck.restore_latest({"w": jnp.zeros((64,), jnp.float32)}) is None
    assert ckpt_paths.steps_on_disk(ck.directory) == []
    assert sorted(s for s, _ in ck.quarantined) == [2, 4]
    ck.close()


def test_manifest_verify_and_gc(tmp_path):
    d = tmp_path / "ck"
    (d / "3").mkdir(parents=True)
    (d / "3" / "data.bin").write_bytes(b"x" * 1024)
    ckpt_paths.write_manifest(str(d), 3)
    assert ckpt_paths.verify_manifest(str(d), 3) is None
    # a step with NO manifest verifies OK (pre-scheme checkpoints)
    (d / "5").mkdir()
    assert ckpt_paths.verify_manifest(str(d), 5) is None
    # orphaned manifests are GC'd once the step dir is gone
    import shutil
    shutil.rmtree(d / "3")
    ckpt_paths.gc_manifests(str(d))
    assert not os.path.exists(ckpt_paths.manifest_path(str(d), 3))


# ------------------------------------------------- gang-level chaos matrix

_WORKER = textwrap.dedent('''
    import hashlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from k8s_distributed_deeplearning_tpu.train import loop as train_loop
    from k8s_distributed_deeplearning_tpu.train.checkpoint import Checkpointer
    from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger

    ckdir, num_steps = sys.argv[1], int(sys.argv[2])

    @jax.jit
    def step(state, batch, rng):
        w = state["w"]
        loss = jnp.sum((w - batch["target"]) ** 2)
        noise = jax.random.normal(rng, w.shape) * 1e-3
        return {"w": w - 0.2 * (w - batch["target"]) + noise}, loss, {}

    def batches(start):
        def gen():
            s = start
            while True:
                yield {"target": jnp.full((4,), 0.01 * s, jnp.float32)}
                s += 1
        return gen()

    metrics = MetricsLogger(job="chaos")
    ck = Checkpointer(ckdir, metrics=metrics)
    state = train_loop.fit(step, {"w": jnp.zeros((4,), jnp.float32)},
                           batches, num_steps, jax.random.key(7),
                           metrics=metrics, checkpointer=ck,
                           checkpoint_every=2, log_every=0)
    digest = hashlib.md5(np.asarray(state["w"]).tobytes()).hexdigest()
    metrics.emit("final", digest=digest)
    ck.close()
''')


def _events(result):
    return [json.loads(l) for l in result.stdout.splitlines()
            if l.startswith("{")]


def _run_gang(script, ckdir, plan=None, num_steps=8, max_restarts=3):
    cfg = JobConfig(num_workers=1, script=str(script),
                    script_args=[str(ckdir), str(num_steps)])
    env = dict(CPU_ENV)
    if plan is not None:
        env[faults.FAULT_PLAN_ENV] = json.dumps(plan)
    res, restarts = elastic.run_elastic(
        cfg, extra_env=env, cwd=REPO, timeout=240,
        max_restarts=max_restarts, checkpoint_dir=str(ckdir))
    events = _events(res[0])
    digest = next(e["digest"] for e in events if e.get("event") == "final")
    return restarts, events, digest


@pytest.fixture(scope="module")
def gang(tmp_path_factory):
    """The chaos worker script plus the UNFAULTED reference digest every
    kill-type test compares against (one clean gang run, shared)."""
    root = tmp_path_factory.mktemp("chaos")
    script = root / "worker.py"
    script.write_text(_WORKER)
    restarts, _, digest = _run_gang(script, root / "ck-ref")
    assert restarts == 0
    return script, digest


def test_gang_hard_kill_recovers_step_for_step(gang, tmp_path):
    """Chaos type: hard kill (os._exit — no atexit, no signal handlers, no
    flushing; the closest local analog of an OOM kill). The restarted gang
    restores from the last checkpoint and finishes with parameters
    IDENTICAL to the unfaulted run."""
    script, ref = gang
    plan = {"faults": [{"site": "step", "action": "exit", "step": 5,
                        "attempt": 0, "exit_code": 43}]}
    restarts, events, digest = _run_gang(script, tmp_path / "ck", plan)
    assert restarts == 1
    restore = next(e for e in events if e.get("event") == "restore")
    assert restore["step"] == 4
    assert digest == ref


def test_gang_sigterm_recovers_step_for_step(gang, tmp_path):
    """Chaos type: SIGTERM (K8s eviction without a preemption handler —
    the default-disposition death). Same step-for-step recovery bar."""
    script, ref = gang
    plan = {"faults": [{"site": "step", "action": "sigterm", "step": 5,
                        "attempt": 0}]}
    restarts, events, digest = _run_gang(script, tmp_path / "ck", plan)
    assert restarts == 1
    assert any(e.get("event") == "restore" for e in events)
    assert digest == ref


def test_executor_kill_fault_restarts_gang(tmp_path):
    """Chaos type: EXTERNAL kill — the executor (standing in for the
    kubelet) SIGKILLs a worker from outside after a delay; the fault is
    attempt-scoped so the restarted gang runs clean."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import json, time
        time.sleep(1.0)
        print(json.dumps({"event": "worker_ok"}))
    """))
    plan = {"faults": [{"site": "executor", "action": "exit", "rank": 0,
                        "seconds": 0.2, "attempt": 0}]}
    cfg = JobConfig(num_workers=1, script=str(script), script_args=[])
    env = {faults.FAULT_PLAN_ENV: json.dumps(plan)}
    res, restarts = elastic.run_elastic(cfg, extra_env=env, cwd=REPO,
                                        timeout=60, max_restarts=2)
    assert restarts == 1
    assert res[0].returncode == 0
    assert any(e.get("event") == "worker_ok" for e in _events(res[0]))


# --------------------------------------------------- crash-loop detection


def test_crash_loop_stops_restarting_early(tmp_path):
    """A deterministic death with zero checkpoint progress must NOT burn
    the whole restart budget: the loop stops after crash_loop_after
    no-progress attempts, naming each attempt's exit codes."""
    script = tmp_path / "dies.py"
    script.write_text("import sys; sys.exit(7)\n")
    ckdir = tmp_path / "ck"
    ckdir.mkdir()
    metrics = _RecordingMetrics()
    cfg = JobConfig(num_workers=1, script=str(script), script_args=[])
    with pytest.raises(elastic.CrashLoopError) as ei:
        elastic.run_elastic(cfg, cwd=REPO, timeout=60, max_restarts=10,
                            checkpoint_dir=str(ckdir), crash_loop_after=2,
                            metrics=metrics)
    assert ei.value.exit_codes == [[7], [7]]
    ev = [f for e, f in metrics.events if e == "crash_loop"]
    assert ev and ev[0]["attempts"] == 2 and ev[0]["exit_codes"] == [[7], [7]]


def test_checkpoint_progress_resets_crash_loop_counter(tmp_path):
    """Failures WITH progress are ordinary crash recovery, not a loop:
    each attempt advances the checkpoint stream, so the run is allowed its
    full restart budget and eventually completes."""
    script = tmp_path / "slow_progress.py"
    ckdir = tmp_path / "ck"
    ckdir.mkdir()
    script.write_text(textwrap.dedent(f"""
        import json, os, sys
        att = int(os.environ.get("TPUJOB_ATTEMPT", "0"))
        os.makedirs(os.path.join({str(ckdir)!r}, str(att + 1)),
                    exist_ok=True)
        if att < 3:
            sys.exit(9)
        print(json.dumps({{"event": "worker_ok"}}))
    """))
    cfg = JobConfig(num_workers=1, script=str(script), script_args=[])
    res, restarts = elastic.run_elastic(
        cfg, cwd=REPO, timeout=60, max_restarts=5,
        checkpoint_dir=str(ckdir), crash_loop_after=2, min_progress_steps=1)
    assert restarts == 3 and res[0].returncode == 0


def test_watch_crash_loop_detection(tmp_path):
    """The on-cluster reconcile loop applies the same contract: repeated
    Job failures with no checkpoint progress abort with a crash_loop
    event instead of re-applying forever."""
    class _FakeKubectl:
        def apply(self, text):
            pass

        def delete_job(self, cfg):
            pass

        def job_status(self, cfg):
            return watch_mod.GangStatus(exists=True, active=0, succeeded=0,
                                        failed=1, job_failed=True)

    ckdir = tmp_path / "ck"
    ckdir.mkdir()
    events = []
    with pytest.raises(RuntimeError, match="crash_loop"):
        watch_mod.watch(JobConfig(num_workers=1), kubectl=_FakeKubectl(),
                        max_restarts=10, poll_interval=0.0,
                        sleep=lambda _s: None, on_event=events.append,
                        checkpoint_dir=str(ckdir), crash_loop_after=2)
    assert any("crash_loop" in m for m in events)


# --------------------------------------------------------- hook cheapness


def test_hooks_are_noop_without_plan():
    """The steady-state contract: with no plan configured, every hook site
    resolves to a single cached None check."""
    assert faults.active() is None
    assert faults.active() is None   # cached, not re-read
    state = _tiny_fit(num_steps=3)
    assert state["w"].shape == (4,)
