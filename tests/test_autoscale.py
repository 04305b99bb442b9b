"""graftpilot chaos matrix (serve/autoscale.py): SLO-driven elastic
fleet control over the gateway's dynamic membership — scale up on
sustained fast-window burn or queue pressure, drain-safe scale down
(migration-backed, zero lost requests), sick-replica replacement, and
the reversible brownout ladder at max scale.

The matrix the issue demands: actuation ioerror/stall at the
``autoscale_actuate`` fault site, a replica CRASHING mid-scale-down,
and oscillating load — in every case the controller converges, never
exceeds ``max_replicas``, never flaps faster than its cooldowns, and
every brownout escalation is eventually followed by
``autoscale_restored``.

Also here: the gateway dynamic-membership unit tests (add under load,
remove mid-decode bit-identical to drain+migrate, breaker retired with
the member) and the stale-heartbeat discovery regression (a killed
replica's beacon is filtered by ``stale_after_s``; a cleanly shut down
replica removes its own)."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu import faults
from k8s_distributed_deeplearning_tpu.faults.plan import Fault, FaultPlan
from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.serve import (QueueFull, Request,
                                                    ServeEngine,
                                                    ServeGateway)
from k8s_distributed_deeplearning_tpu.serve.autoscale import (
    BROWNOUT_STAGE_NAMES, FleetController, K8sParallelismBackend,
    default_brownout_stages, heartbeat_discoverer)
from k8s_distributed_deeplearning_tpu.telemetry import heartbeat
from k8s_distributed_deeplearning_tpu.telemetry.fleet import (
    discover_endpoints)
from k8s_distributed_deeplearning_tpu.telemetry.slo import (SLOEngine,
                                                            SLOTarget)
from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.deactivate()
    yield
    faults.deactivate()


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _ref_greedy(model, params, prompt, max_new):
    return np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :],
        max_new_tokens=max_new))[0]


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _Events:
    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append((event, fields))

    def names(self):
        return [e for e, _ in self.events]

    def fields(self, name):
        return [f for e, f in self.events if e == name]


class _FakePool:
    def counters(self):
        return {"pages_total": 8, "pages_used": 0, "pages_shared": 0}


class _ClassedQueue(list):
    """Plain-list queue that also knows tenant priority classes — the
    surface ``ServeGateway._tenant_class`` duck-types against."""

    def priority_of(self, tenant):
        return {"bulk": "batch", "chat": "interactive"}.get(tenant)


class _FakeEngine:
    """Enough ServeEngine surface for controller/breaker state tests —
    no jax, instant steps, settable load, latched drain."""

    def __init__(self, replica_id=None, occupied=0, slots=2,
                 auto_drain=True, queue=None):
        self.replica_id = replica_id
        self.queue = queue if queue is not None else []
        self.num_slots = slots
        self.pool = _FakePool()
        self.steps = 0
        self.submitted = []
        self.shutdowns = 0
        self._occupied = occupied
        self._auto_drain = auto_drain
        self._draining = False
        self._drained = False

    def busy(self):
        return False

    def occupied_slots(self):
        return self._occupied

    def load(self):
        return self._occupied + len(self.queue)

    def step(self):
        self.steps += 1
        return []

    def submit(self, req, *, requeue=False):
        self.submitted.append(req)

    def cancel(self, request_id, reason="aborted"):
        return None

    def drain(self, *, flush=False):
        self._draining = True
        if self._auto_drain:
            self._drained = True
        return []

    def finish_drain(self):
        self._drained = True

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        return self._drained

    def shutdown(self):
        self.shutdowns += 1
        self._draining = True
        self._drained = True
        return []


class _Backend:
    """EngineFactoryBackend shape with start/stop bookkeeping."""

    def __init__(self, factory=None):
        self.factory = factory if factory is not None else _FakeEngine
        self.started = []
        self.stopped = []

    def start_replica(self):
        e = self.factory()
        self.started.append(e)
        return e

    def stop_replica(self, rid, engine):
        self.stopped.append(rid)
        engine.shutdown()


def _fleet(n=1, *, occupied=0, logger=None, clk=None, **gw_kw):
    engines = [_FakeEngine(replica_id=f"r{i}", occupied=occupied)
               for i in range(n)]
    kw = dict(stats=ServingStats(), logger=logger)
    if clk is not None:
        kw["clock"] = clk
    gw = ServeGateway(engines, **kw, **gw_kw)
    return gw, engines


def _ctl(gw, backend, clk, **kw):
    kw.setdefault("interval_s", 0.0)
    kw.setdefault("up_cooldown_s", 1.0)
    kw.setdefault("down_cooldown_s", 1.0)
    kw.setdefault("sustain_rounds", 2)
    return FleetController(gw, backend, clock=clk, **kw)


def _set_load(gw, occ):
    for rid in gw.replica_ids():
        gw.replica_engine(rid)._occupied = occ


def _actuation_fault(action, *, step=None, seconds=None):
    return FaultPlan((Fault(site="autoscale_actuate", action=action,
                            step=step, seconds=seconds),))


def _kill_replica_plan(index):
    return FaultPlan((Fault(site="gateway_dispatch", action="ioerror",
                            step=index, attempt=None),))


# ------------------------------------------------------------ validation


def test_controller_and_stage_validation():
    gw, _ = _fleet(1)
    be = _Backend()
    with pytest.raises(ValueError, match="min_replicas"):
        FleetController(gw, be, min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        FleetController(gw, be, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="sustain_rounds"):
        FleetController(gw, be, sustain_rounds=0)
    with pytest.raises(ValueError, match="load_low"):
        FleetController(gw, be, load_low=2.0, load_high=1.0)
    with pytest.raises(ValueError, match="cooldowns"):
        FleetController(gw, be, up_cooldown_s=-1.0)
    with pytest.raises(ValueError, match="unknown brownout stage"):
        default_brownout_stages(("shed_batch", "nope"))
    # The ladder subsets and reorders by name.
    names = [s.name for s in default_brownout_stages(
        ("no_hedge", "shed_batch"))]
    assert names == ["no_hedge", "shed_batch"]


def test_autoscale_fault_site_plan_validation():
    assert not _actuation_fault("ioerror", step=2).problems()
    assert not _actuation_fault("stall", seconds=0.01).problems()
    assert not FaultPlan((Fault(site="autoscale_actuate",
                                action="exit"),)).problems()
    # Checkpoint-damage actions make no sense at an actuation site.
    assert FaultPlan((Fault(site="autoscale_actuate",
                            action="truncate"),)).problems()


# -------------------------------------------------------------- scale up


def test_scale_up_on_sustained_load_respects_cooldown():
    clk = _Clock()
    ev = _Events()
    gw, _ = _fleet(1, occupied=4, logger=ev)     # 4 load / 2 slots = 2.0
    ctl = _ctl(gw, _Backend(), clk, max_replicas=3, logger=ev)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "hold"               # sustain_rounds=2
    clk.advance(0.1)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "up" and d["started"]
    assert ctl.desired == 2
    assert len(gw.replica_ids()) == 2
    assert ev.names().count("autoscale_up") == 1
    # Still overloaded but inside the up cooldown: the next round holds.
    clk.advance(0.2)
    _set_load(gw, 4)                             # keep every slot saturated
    assert ctl.control_round(clk.t)["decision"] == "hold"
    clk.advance(1.0)                             # past the cooldown
    assert ctl.control_round(clk.t)["decision"] == "up"
    assert ctl.desired == 3
    # At max_replicas "up" is off the table forever after.
    for _ in range(5):
        clk.advance(1.1)
        _set_load(gw, 4)
        d = ctl.control_round(clk.t)
        assert d["decision"] in ("hold", "brownout")
        assert len(gw.replica_ids()) <= 3
    assert ctl.snapshot()["desired_replicas"] == 3


def test_scale_up_on_slo_fast_burn():
    clk = _Clock()
    gw, _ = _fleet(1)                            # idle: load is no signal
    slo = SLOEngine({"default": SLOTarget(availability=0.99,
                                          window_s=60.0)}, clock=clk)
    ctl = _ctl(gw, _Backend(), clk, slo=slo)
    # 40 timeouts, zero successes: fast-window burn = 1.0/0.01 >> 14.4.
    slo.observe(finished={"default": {"timeout": 40}}, now=clk.t)
    assert ctl.control_round(clk.t)["decision"] == "hold"
    clk.advance(0.1)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "up" and d["fast_burn"] > 14.4
    assert any(a.window == "fast" for a in slo.active_alerts())
    assert len(gw.replica_ids()) == 2
    # Burn decays out of the fast window -> calm -> eventual scale-down.
    clk.advance(30.0)
    slo.observe(finished={"default": {"timeout": 40, "eos": 500}},
                now=clk.t)
    for _ in range(4):
        clk.advance(1.1)
        d = ctl.control_round(clk.t)
    assert not any(a.window == "fast" for a in slo.active_alerts())
    assert d["decision"] in ("down", "hold")
    assert ctl.snapshot()["actual_replicas"] >= 1


# --------------------------------------------------------- chaos: faults


def test_actuation_ioerror_counts_failure_and_reconciles():
    clk = _Clock()
    gw, _ = _fleet(1, occupied=4)
    be = _Backend()
    ctl = _ctl(gw, be, clk, max_replicas=2)
    faults.activate(_actuation_fault("ioerror"))  # every actuation fails
    try:
        ctl.control_round(clk.t)
        clk.advance(0.1)
        d = ctl.control_round(clk.t)
    finally:
        faults.deactivate()
    assert d["decision"] == "up" and not d["started"]
    assert ctl.desired == 2
    assert len(gw.replica_ids()) == 1            # actuation failed
    assert ctl.snapshot()["actuation_failures"] == 1
    # Fault cleared: the reconcile term (actual < desired) retries the
    # start after the up cooldown without re-raising desired.
    clk.advance(1.1)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "up" and d["started"]
    assert ctl.desired == 2 and len(gw.replica_ids()) == 2
    assert len(be.started) == 1


def test_actuation_stall_slows_but_does_not_fail():
    clk = _Clock()
    gw, _ = _fleet(1, occupied=4)
    ctl = _ctl(gw, _Backend(), clk)
    faults.activate(_actuation_fault("stall", seconds=0.01))
    try:
        ctl.control_round(clk.t)
        clk.advance(0.1)
        d = ctl.control_round(clk.t)
    finally:
        faults.deactivate()
    assert d["decision"] == "up" and d["started"]
    assert ctl.snapshot()["actuation_failures"] == 0
    assert len(gw.replica_ids()) == 2


# ------------------------------------------------------------ scale down


def test_scale_down_drains_then_stops_backend():
    clk = _Clock()
    ev = _Events()
    gw, engines = _fleet(2, logger=ev)
    be = _Backend()
    ctl = _ctl(gw, be, clk, sustain_rounds=1, logger=ev)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "down" and d["victim"] == "r0"
    assert engines[0].draining                   # drain-backed removal
    assert len(gw.replica_ids()) == 2            # membership not yet cut
    clk.advance(0.1)
    ctl.control_round(clk.t)                     # finalizes: drained victim
    assert gw.replica_ids() == ["r1"]
    assert be.stopped == ["r0"]
    assert engines[0].shutdowns == 1
    assert "autoscale_down" in ev.names()
    assert "gateway_replica_removed" in ev.names()
    # Never below min_replicas, no matter how long the idle runs.
    for _ in range(5):
        clk.advance(1.1)
        assert ctl.control_round(clk.t)["decision"] == "hold"
    assert gw.replica_ids() == ["r1"]
    assert ctl.snapshot()["pending_removals"] == 0


def test_replica_crash_during_scale_down_converges():
    clk = _Clock()
    gw, engines = _fleet(2, clk=clk, failures_to_trip=1)
    victim = engines[0]
    victim._auto_drain = False                   # drain never completes...
    be = _Backend()
    ctl = _ctl(gw, be, clk, sustain_rounds=1)
    assert ctl.control_round(clk.t)["decision"] == "down"
    assert victim.draining and not victim.drained
    clk.advance(0.1)
    ctl.control_round(clk.t)
    assert len(gw.replica_ids()) == 2            # stuck mid-drain
    # ...because the victim CRASHES: its dispatch faults, the breaker
    # trips and evacuates (engine shutdown -> empty + draining =
    # drained), and the next round finalizes the removal anyway.
    faults.activate(_kill_replica_plan(0))
    try:
        gw.step()
    finally:
        faults.deactivate()
    assert victim.drained
    clk.advance(0.1)
    ctl.control_round(clk.t)
    assert gw.replica_ids() == ["r1"]
    assert be.stopped == ["r0"]
    snap = ctl.snapshot()
    assert snap["pending_removals"] == 0
    assert snap["desired_replicas"] == 1 == snap["actual_replicas"]


def test_stop_failure_retries_next_round():
    clk = _Clock()
    gw, _ = _fleet(2)
    be = _Backend()
    ctl = _ctl(gw, be, clk, sustain_rounds=1)
    assert ctl.control_round(clk.t)["decision"] == "down"
    faults.activate(_actuation_fault("ioerror"))
    try:
        clk.advance(0.1)
        ctl.control_round(clk.t)                 # membership cut, stop fails
    finally:
        faults.deactivate()
    assert gw.replica_ids() == ["r1"]
    assert be.stopped == []
    assert ctl.snapshot()["pending_removals"] == 1
    assert ctl.snapshot()["actuation_failures"] == 1
    clk.advance(0.1)
    ctl.control_round(clk.t)                     # retried, succeeds
    assert be.stopped == ["r0"]
    assert ctl.snapshot()["pending_removals"] == 0


# --------------------------------------------------------------- replace


def test_replace_sick_replica_repairs_in_place():
    clk = _Clock()
    ev = _Events()
    gw, engines = _fleet(2, logger=ev, clk=clk, failures_to_trip=1)
    be = _Backend()
    ctl = _ctl(gw, be, clk, unhealthy_rounds=2, sustain_rounds=50,
               logger=ev)
    faults.activate(_kill_replica_plan(0))
    try:
        gw.step()                                # r0 trips OPEN
    finally:
        faults.deactivate()
    assert gw.breaker_state("r0") == "open"
    assert ctl.control_round(clk.t)["decision"] == "hold"   # streak = 1
    clk.advance(0.1)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "replace" and d["replica"] == "r0"
    clk.advance(0.1)
    ctl.control_round(clk.t)                     # finalize + owed start
    rids = gw.replica_ids()
    assert "r0" not in rids and len(rids) == 2   # repaired, not shrunk
    assert be.stopped == ["r0"] and len(be.started) == 1
    assert ctl.desired == 2                      # replace never moves desired
    assert ev.names().count("autoscale_replace") == 1
    with pytest.raises(KeyError):
        gw.breaker_state("r0")                   # breaker retired with it


# -------------------------------------------------------------- brownout


def test_brownout_ladder_escalates_and_restores():
    clk = _Clock()
    ev = _Events()
    q = _ClassedQueue()
    eng = _FakeEngine(replica_id="r0", occupied=4, queue=q)
    gw = ServeGateway([eng], logger=ev, hedge_after_s=0.5)
    ctl = _ctl(gw, _Backend(), clk, min_replicas=1, max_replicas=1,
               sustain_rounds=1, logger=ev)
    d = ctl.control_round(clk.t)
    assert d["decision"] == "brownout" and d["stage"] == "shed_batch"
    assert gw.shed_classes == frozenset({"batch"})
    # The lever actually sheds: batch-class tenants bounce at the door,
    # interactive traffic keeps flowing.
    with pytest.raises(QueueFull, match="shed"):
        gw.submit(Request(prompt=[1, 2], max_new_tokens=2, tenant="bulk"))
    gw.submit(Request(prompt=[1, 2], max_new_tokens=2, tenant="chat"))
    clk.advance(1.1)
    d = ctl.control_round(clk.t)
    assert d["stage"] == "no_hedge" and gw.hedge_after_s is None
    clk.advance(1.1)
    d = ctl.control_round(clk.t)
    assert d["stage"] == "tight_admission"
    assert gw.max_live_requests == 2             # fleet slot capacity
    assert ctl.brownout_level() == 3
    # Ladder exhausted: still over, never exceeds max_replicas.
    clk.advance(1.1)
    assert ctl.control_round(clk.t)["decision"] == "hold"
    assert len(gw.replica_ids()) == 1
    # Burn clears: unwind stage by stage; restored fires as the LAST
    # lever lifts, and every lever is back to its pre-brownout value.
    eng._occupied = 0
    for _ in range(3):
        clk.advance(1.1)
        assert ctl.control_round(clk.t)["decision"] == "restore"
    assert ctl.brownout_level() == 0
    assert gw.shed_classes == frozenset()
    assert gw.hedge_after_s == 0.5
    assert gw.max_live_requests is None
    assert ev.names().count("autoscale_brownout") == 3
    assert ev.names().count("autoscale_restored") == 1
    # Every escalation was eventually followed by the restore marker.
    assert (ev.names().index("autoscale_restored")
            > max(i for i, n in enumerate(ev.names())
                  if n == "autoscale_brownout"))


# ------------------------------------------------------ oscillating load


def test_oscillating_load_is_damped_and_converges():
    clk = _Clock()
    ev = _Events()
    gw, _ = _fleet(1, logger=ev)
    ctl = _ctl(gw, _Backend(), clk, min_replicas=1, max_replicas=3,
               sustain_rounds=1, flap_window_s=100.0,
               max_flips_per_window=4)
    decision_times = {"up": [], "down": []}
    for i in range(40):
        clk.advance(1.1)
        _set_load(gw, 4 if i % 2 == 0 else 0)
        d = ctl.control_round(clk.t)
        if d["decision"] in decision_times:
            decision_times[d["decision"]].append(clk.t)
        n = len([r for r in gw.snapshot()["replicas"].values()
                 if not r["draining"]])
        assert 1 <= n <= 3
    # The damper kicked in: inside one flap window the fleet never
    # changed size more than max_flips_per_window times (the whole test
    # spans < one window), and some rounds were explicitly held.
    flips = len(decision_times["up"]) + len(decision_times["down"])
    assert flips <= 4
    assert ctl.snapshot()["flap_damped_rounds"] > 0
    # Per-direction cooldowns held even while thrashing.
    for kind, cd in (("up", ctl.up_cooldown_s), ("down",
                                                 ctl.down_cooldown_s)):
        ts = decision_times[kind]
        assert all(b - a >= cd for a, b in zip(ts, ts[1:]))
    # Oscillation ends, the damper window drains, the fleet converges
    # back to min_replicas and stays there.
    clk.advance(200.0)
    _set_load(gw, 0)
    for _ in range(12):
        clk.advance(1.1)
        ctl.control_round(clk.t)
        _set_load(gw, 0)
    assert gw.replica_ids() == [gw.replica_ids()[0]]
    assert ctl.snapshot()["actual_replicas"] == 1
    assert ctl.snapshot()["desired_replicas"] == 1


def test_maybe_round_rate_limits_to_interval():
    clk = _Clock()
    gw, _ = _fleet(1)
    ctl = _ctl(gw, _Backend(), clk, interval_s=0.5)
    assert ctl.maybe_round(clk.t) is not None
    clk.advance(0.1)
    assert ctl.maybe_round(clk.t) is None        # inside the interval
    clk.advance(0.5)
    assert ctl.maybe_round(clk.t) is not None
    assert ctl.snapshot()["rounds"] == 2


# -------------------------------------- gateway dynamic membership units


def test_add_replica_routes_within_one_step():
    ev = _Events()
    busy = _FakeEngine(occupied=2)
    gw = ServeGateway([busy], logger=ev)
    gw.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert len(busy.submitted) == 1
    fresh = _FakeEngine()
    rid = gw.add_replica(fresh)
    assert rid == "r1" and fresh.replica_id == "r1"
    assert gw.breaker_state("r1") == "closed"
    assert "gateway_replica_added" in ev.names()
    # The VERY next submission prefers the less-loaded newcomer.
    gw.submit(Request(prompt=[1, 2], max_new_tokens=2))
    assert len(fresh.submitted) == 1 and len(busy.submitted) == 1
    with pytest.raises(ValueError, match="duplicate replica_id"):
        gw.add_replica(_FakeEngine(replica_id="r1"))
    # Indexes stay monotonic across churn: remove r1, the next unnamed
    # replica is r2 — step-scoped fault plans keep naming stable slots.
    gw.remove_replica("r1")
    assert gw.add_replica(_FakeEngine()) == "r2"


def test_remove_replica_guards_and_force():
    ev = _Events()
    gw, engines = _fleet(2, logger=ev)
    with pytest.raises(ValueError, match="unknown replica"):
        gw.remove_replica("r9")
    stuck = engines[0]
    stuck._auto_drain = False
    with pytest.raises(RuntimeError, match="drain"):
        gw.remove_replica("r0")                  # drain begun, not done
    assert stuck.draining
    gw.remove_replica("r0", force=True)
    assert gw.replica_ids() == ["r1"]
    assert ev.names().count("gateway_replica_removed") == 1
    with pytest.raises(ValueError, match="last replica"):
        gw.remove_replica("r1")


def test_remove_replica_mid_decode_bit_identical(tiny):
    """Satellite acceptance: ``remove_replica`` on a replica holding
    live decodes IS drain+migrate — every stream (including the moved
    ones) matches the one-shot oracle bit-for-bit, zero lost requests,
    and the member's breaker state is retired with it."""
    model, params, cfg = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(4, 12))).astype(np.int32)
               for _ in range(4)]
    max_news = [int(rng.integers(8, 12)) for _ in range(4)]
    stats = ServingStats()
    engines = [ServeEngine(model, params, num_slots=2, eos_id=None,
                           stats=stats, replica_id=f"r{i}")
               for i in range(2)]
    gw = ServeGateway(engines, stats=stats)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    for r in reqs:
        gw.submit(r)
    assert engines[0].load() == 2 and engines[1].load() == 2
    outs = []
    for _ in range(3):                           # both replicas mid-decode
        outs.extend(gw.step())
    assert engines[0].occupied_slots() == 2
    gw.remove_replica("r0")                      # drain -> migrate -> retire
    assert gw.replica_ids() == ["r1"]
    assert stats.gateway_migrations == 2         # both live streams moved
    with pytest.raises(KeyError):
        gw.breaker_state("r0")
    for _ in range(200):
        if not gw.busy():
            break
        outs.extend(gw.step())
    outd = {o.request_id: o for o in outs}
    assert len(outd) == len(reqs)                # zero lost requests
    for r, p, m in zip(reqs, prompts, max_news):
        assert outd[r.request_id].finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(outd[r.request_id].tokens),
            _ref_greedy(model, params, p, m))


def test_remove_replica_retires_breaker_state():
    gw, _ = _fleet(2, failures_to_trip=1)
    faults.activate(_kill_replica_plan(0))
    try:
        gw.step()
    finally:
        faults.deactivate()
    assert gw.breaker_state("r0") == "open"
    gw.remove_replica("r0")                      # trip already drained it
    with pytest.raises(KeyError):
        gw.breaker_state("r0")
    assert "r0" not in gw.snapshot()["replicas"]


# ----------------------------------- stale-beacon discovery (regression)


def _beacon(directory, rank, ts, addr):
    heartbeat.HeartbeatWriter(directory, rank,
                              clock=lambda: ts).beat(
        step=1, metrics_addr=addr)


def test_discovery_filters_stale_beacons(tmp_path):
    d = str(tmp_path)
    _beacon(d, 0, ts=100.0, addr="127.0.0.1:1111")   # long dead
    _beacon(d, 1, ts=195.0, addr="127.0.0.1:2222")   # fresh
    assert discover_endpoints(d) == ["127.0.0.1:1111", "127.0.0.1:2222"]
    assert discover_endpoints(d, stale_after_s=10.0,
                              now=200.0) == ["127.0.0.1:2222"]
    # Clean shutdown removes the beacon outright — no staleness window
    # during which discovery could hand back a deliberately-gone rank.
    w = heartbeat.HeartbeatWriter(d, 1, clock=lambda: 195.0)
    w.remove()
    w.remove()                                   # idempotent
    assert discover_endpoints(d, stale_after_s=10.0, now=200.0) == []
    assert not os.path.exists(os.path.join(d, "rank-1.json"))


def test_heartbeat_discoverer_hook_yields_each_endpoint_once(tmp_path):
    d = str(tmp_path)
    import time as _t
    now = _t.time()
    _beacon(d, 0, ts=now, addr="127.0.0.1:1111")
    _beacon(d, 1, ts=now - 60.0, addr="127.0.0.1:2222")  # stale
    hook = heartbeat_discoverer(d, stale_after_s=10.0)
    new = hook(known_rids=[])
    assert [c.endpoint for c in new] == ["http://127.0.0.1:1111"]
    assert hook(known_rids=[]) == []             # seen: not re-offered
    _beacon(d, 2, ts=now, addr="127.0.0.1:3333")
    assert [c.endpoint for c in hook([])] == ["http://127.0.0.1:3333"]


def test_k8s_backend_patches_parallelism_and_names_victim():
    calls = []

    class _Kubectl:
        def patch_job(self, name, namespace, patch):
            calls.append((name, namespace, json.loads(patch)))

    be = K8sParallelismBackend(
        _Kubectl(), "svc-replica", "prod", initial_replicas=2,
        endpoint_template="svc-replica-{i}.svc-replica.prod:9100")
    client = be.start_replica()
    assert calls == [("svc-replica", "prod",
                      {"spec": {"parallelism": 3, "completions": 3}})]
    assert client.replica_id == "r2"
    assert client.endpoint == \
        "http://svc-replica-2.svc-replica.prod:9100"
    be.stop_replica("r2", _FakeEngine())
    assert calls[-1][2]["spec"]["parallelism"] == 2
    # The Job controller reaps the highest completion index: the victim
    # override steers the controller's drain at exactly that replica.
    assert be.victim_rid(["r0", "r2", "r1"]) == "r2"
    assert be.victim_rid([]) is None


def test_cli_brownout_literal_matches_ladder():
    """The CLI validates --autoscale-brownout against a pre-import
    literal copy of BROWNOUT_STAGE_NAMES; keep the two in lockstep."""
    import ast
    import inspect

    from k8s_distributed_deeplearning_tpu.serve import cli
    m = re.search(r"known = (\([^)]*\))", inspect.getsource(cli))
    assert m, "cli.py lost its literal brownout tuple"
    assert ast.literal_eval(m.group(1)) == BROWNOUT_STAGE_NAMES
