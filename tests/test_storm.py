"""graftstorm (serve/storm.py) + the probabilistic fault trigger
(faults/plan.py ``p:`` / plan ``seed``) + gateway poison quarantine.

The contract under test, in one line: a chaos soak is a PURE FUNCTION of
its seed — same seed → identical fault firing sequence and identical
invariant report — and the invariant monitor actually catches the bug
classes it claims to (lost/duplicated requests, leaked KV pages, oracle
parity breaks, counter/event divergence), each with a replayable repro.

Most tests run on scripted jax-free engines (instant steps, deterministic
"autoregressive" token function), mirroring tests/test_gateway.py's fake
idiom; one end-to-end test drives real tiny CPU engines through the
disagg topology so the in-process ``transport_pages`` hook is exercised
for real.
"""
import json

import pytest

from k8s_distributed_deeplearning_tpu import faults
from k8s_distributed_deeplearning_tpu.faults.inject import FaultInjector
from k8s_distributed_deeplearning_tpu.faults.plan import Fault, FaultPlan
from k8s_distributed_deeplearning_tpu.serve.gateway import ServeGateway
from k8s_distributed_deeplearning_tpu.serve.request import (EngineDraining,
                                                            QueueFull,
                                                            Request,
                                                            RequestOutput)
from k8s_distributed_deeplearning_tpu.serve.storm import (InvariantMonitor,
                                                          StormConfig,
                                                          VirtualClock,
                                                          build_fault_plan,
                                                          generate_traffic,
                                                          run_storm)
from k8s_distributed_deeplearning_tpu.telemetry.events import known_events


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    faults.deactivate()
    yield
    faults.deactivate()


# --------------------------------------------------- jax-free fakes


class _ScriptPool:
    def __init__(self):
        self.used = 0

    def counters(self):
        return {"pages_total": 64, "pages_used": self.used,
                "pages_shared": 0, "pages_reserved": 0}

    def owners_summary(self):
        return {"slot": self.used}


def _out(rid, tokens, reason="length"):
    return RequestOutput(request_id=rid, prompt_len=0,
                         tokens=list(tokens), finish_reason=reason,
                         queue_s=0.0, ttft_s=None, latency_s=0.0)


def _next_tok(history):
    """The fake model: next token is a pure function of the FULL token
    history (prompt + generated), so a migrated continuation decoding
    from ``prompt + emitted`` produces the identical stream — the same
    autoregressive property the splice contract relies on for real
    engines."""
    return (sum(history) * 31 + len(history) * 7) % 997


class _ScriptEngine:
    """Deterministic instant-decode engine with the surface run_storm /
    ServeGateway / FleetController touch. ``leak`` keeps one KV page
    held through shutdown — the intentionally-broken fixture the monitor
    must catch."""

    def __init__(self, i=None, *, num_slots=4, leak=False):
        self.replica_id = None if i is None else (
            f"s{i}" if i >= 0 else "oracle")
        self.num_slots = num_slots
        self.queue = []
        self.pool = _ScriptPool()
        self.leak = leak
        self._live = {}      # request_id -> [req, history, emitted]
        self._draining = False
        self._dead = False

    # -- engine surface -------------------------------------------------

    def busy(self):
        return bool(self._live or self.queue)

    def occupied_slots(self):
        return len(self._live)

    def load(self):
        return len(self._live) + len(self.queue)

    def submit(self, req, *, requeue=False):
        if self._draining:
            raise EngineDraining("draining")
        if self.load() >= self.num_slots + 16:
            raise QueueFull("scripted queue bound")
        if requeue:
            self.queue.insert(0, req)
        else:
            self.queue.append(req)

    def cancel(self, request_id, reason="aborted"):
        if self._live.pop(request_id, None) is not None:
            self.pool.used -= 1
        self.queue = [r for r in self.queue if r.request_id != request_id]

    def step(self):
        inj = faults.active()
        if inj is not None:
            inj.fire("serve_decode")   # stall-only in soak plans
        while self.queue and len(self._live) < self.num_slots:
            r = self.queue.pop(0)
            self._live[r.request_id] = [r, list(r.prompt), []]
            self.pool.used += 1
        outs = []
        for rid, (r, history, emitted) in list(self._live.items()):
            tok = _next_tok(history)
            history.append(tok)
            emitted.append(tok)
            if r.on_token is not None:
                r.on_token(tok)
            if len(emitted) >= r.max_new_tokens:
                del self._live[rid]
                self.pool.used -= 1
                if r.on_finish is not None:
                    r.on_finish("length")
                outs.append(_out(rid, emitted))
        return outs

    def run(self, reqs):
        # Batch path (the oracle): no admission bound, like the real
        # engine's run() which feeds the queue as slots free up.
        self.queue.extend(reqs)
        outs = []
        while self.busy():
            outs.extend(self.step())
        return outs

    def drain(self, *, flush=False):
        self._draining = True
        return []

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        return self._draining and not self.busy()

    def shutdown(self):
        self._live.clear()
        self.queue.clear()
        self.pool.used = 1 if self.leak else 0
        self._dead = True
        return []


def _cfg(**kw):
    base = dict(seed=3, steps=30, replicas=2, arrival_rate=1.0,
                prompt_len=(2, 6), out_len=(2, 6), vocab=997,
                oracle=True)
    base.update(kw)
    return StormConfig(**base)


# ------------------------------------------- traffic & plan determinism


def test_traffic_is_a_pure_function_of_the_seed():
    a, b = generate_traffic(_cfg()), generate_traffic(_cfg())
    assert a == b and len(a) > 0
    assert generate_traffic(_cfg(seed=4)) != a
    tenants = {s["tenant"] for s in a}
    assert tenants <= {"default", "tenant-a", "tenant-b"}


def test_fault_plan_seeded_and_valid():
    p1, p2 = build_fault_plan(_cfg()), build_fault_plan(_cfg())
    assert p1.to_json() == p2.to_json()
    assert p1.seed == 3
    assert build_fault_plan(_cfg(seed=9)).to_json() != p1.to_json()
    assert p1.problems() == []
    assert all(f.p is not None and 0.0 < f.p <= 1.0 for f in p1.faults)


# ----------------------------------- satellite: p trigger + plan seed


def test_p_trigger_domain_validation():
    assert any("p must be in (0, 1]" in e for e in
               Fault(site="serve_decode", action="stall", p=0.0).problems())
    assert any("p must be in (0, 1]" in e for e in
               Fault(site="serve_decode", action="stall", p=1.5).problems())
    assert any("mutually exclusive" in e for e in
               Fault(site="serve_decode", action="stall",
                     p=0.5, step=3).problems())
    # p without a plan-level seed cannot replay → rejected at plan level.
    plan = FaultPlan(faults=(
        Fault(site="serve_decode", action="stall", p=0.5, seconds=0.1),))
    assert any("needs a plan-level seed" in e for e in plan.problems())
    seeded = FaultPlan(faults=plan.faults, seed=7)
    assert seeded.problems() == []


def test_plan_seed_json_round_trip():
    plan = FaultPlan(faults=(
        Fault(site="serve_decode", action="stall", p=0.25, count=3,
              seconds=0.1),), seed=5)
    back = FaultPlan.from_json(plan.to_json())
    assert back == plan and back.seed == 5 and back.faults[0].p == 0.25
    # Pre-storm plans (no seed, no p) keep their exact wire shape.
    old = FaultPlan(faults=(
        Fault(site="serve_decode", action="stall", seconds=0.1),))
    assert "seed" not in json.loads(old.to_json())
    assert FaultPlan.from_json(old.to_json()) == old


def test_injector_p_firing_sequence_replays():
    """Same plan seed → the faults fire on the SAME visit indices, not
    just the same number of times; a different seed moves them."""
    faults_ = (Fault(site="serve_decode", action="stall", p=0.3, count=4,
                     seconds=0.01),)

    def fired_visits(seed):
        inj = FaultInjector(FaultPlan(faults=faults_, seed=seed),
                            sleep=lambda s: None)
        hits = []
        for v in range(200):
            before = len(inj.fired)
            inj.fire("serve_decode")
            if len(inj.fired) > before:
                hits.append(v)
        return hits

    a = fired_visits(13)
    assert fired_visits(13) == a and 0 < len(a) <= 4
    assert any(fired_visits(s) != a for s in range(14, 20))


# -------------------------------------------------- the soak replays


def test_storm_same_seed_identical_report_and_firing():
    cfg = _cfg()
    a = run_storm(cfg, make_engine=_ScriptEngine)
    b = run_storm(cfg, make_engine=_ScriptEngine)
    assert a.violations == [] and b.violations == []
    assert a.fired == b.fired
    assert a.to_dict() == b.to_dict()      # wall-clock-free by design
    assert a.submitted == a.finished > 0
    assert a.parity_checked > 0


def test_storm_different_seed_different_schedule():
    a = run_storm(_cfg(), make_engine=_ScriptEngine)
    c = run_storm(_cfg(seed=4), make_engine=_ScriptEngine)
    assert c.plan_json != a.plan_json
    assert c.fired != a.fired or c.submitted != a.submitted


def test_storm_autoscale_topology_conserves_under_fire():
    cfg = _cfg(seed=6, steps=50, replicas=1, arrival_rate=2.5,
               autoscale=True, autoscale_max=3)
    rep = run_storm(cfg, make_engine=_ScriptEngine)
    assert rep.violations == []
    assert rep.submitted == rep.finished > 0
    # under real pressure: three fault sites fired, at half load or more
    assert len(rep.distinct_sites) >= 3 and "serve_decode" in rep.distinct_sites
    assert rep.peak_load_frac >= 0.5


# --------------------------------- the monitor catches what it claims


def test_storm_kv_leak_fixture_is_caught():
    """The intentionally-broken engine: one page deref skipped on
    shutdown. The teardown sweep must flag it and carry the repro."""
    rep = run_storm(_cfg(), make_engine=lambda i: _ScriptEngine(i, leak=True))
    kinds = {v["kind"] for v in rep.violations}
    assert "kv_page_leak" in kinds
    assert "--seed 3" in rep.repro


def test_monitor_duplicate_finish_and_lost_request():
    mon = InvariantMonitor()
    r1 = Request(prompt=[1, 2], max_new_tokens=2)
    mon.wrap_request(r1, widx=0, deterministic=True)
    r1.on_finish("length")
    r1.on_finish("length")                  # exactly-once broken
    r2 = Request(prompt=[3], max_new_tokens=2)
    mon.wrap_request(r2, widx=1, deterministic=True)  # never finishes
    mon.finalize([])
    kinds = [v["kind"] for v in mon.violations]
    assert "duplicate_finish" in kinds
    assert "lost_request" in kinds


def test_monitor_token_parity_divergence():
    mon = InvariantMonitor(oracle={0: [5, 6, 7]})
    r = Request(prompt=[1], max_new_tokens=3)
    mon.wrap_request(r, widx=0, deterministic=True)
    for t in (5, 6, 99):                    # diverges at position 2
        r.on_token(t)
    r.on_finish("length")
    mon.on_output(_out(r.request_id, [5, 6, 99]))
    mon.finalize([])
    assert any(v["kind"] == "token_parity" and "token 2" in v["detail"]
               for v in mon.violations)


def test_monitor_counter_event_coherence():
    from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats
    mon = InvariantMonitor()
    stats = ServingStats()
    stats.gateway_migrations = 3            # counters say 3 ...
    mon.finalize([], stats=stats, events={"gateway_migrated": 2})  # events 2
    assert any(v["kind"] == "counter_event_divergence"
               for v in mon.violations)


def test_monitor_violations_dedupe_and_dump_once():
    dumps = []

    class _Flight:
        def dump(self, reason, extra=None):
            dumps.append((reason, extra["kind"]))

    mon = InvariantMonitor(flight=_Flight(), repro="replay-me")
    for _ in range(5):
        mon.violation("kv_page_leak", "replica s0: 1 page after drain")
    assert len(mon.violations) == 1
    assert dumps == [("storm_invariant", "kv_page_leak")]


# ------------------------------- satellite: gateway poison quarantine


def test_gateway_poison_quarantine_caps_migrations():
    """A request whose replicas keep dying under it: after
    ``max_migrations`` laps the gateway finishes it terminally as
    "poisoned" (exactly once) instead of migrating forever."""

    class _Ev:
        def __init__(self):
            self.events = []

        def emit(self, event, **fields):
            self.events.append((event, fields))

    ev = _Ev()
    finishes = []
    engines = [_ScriptEngine(0, num_slots=1), _ScriptEngine(1, num_slots=1)]
    gw = ServeGateway(engines, max_migrations=1, logger=ev)
    req = Request(prompt=[1, 2, 3], max_new_tokens=50,
                  on_finish=finishes.append)
    gw.submit(req)
    gw.step()                                # some tokens flow
    gw.drain_replica("s0")                   # 1st migration: within budget
    assert gw.stats.gateway_migrations == 1
    gw.drain_replica("s1")                   # budget exhausted → poisoned
    assert gw.stats.gateway_poisoned == 1
    assert finishes == ["poisoned"]          # terminal, exactly once
    names = [e for e, _ in ev.events]
    assert names.count("gateway_poisoned") == 1
    f = dict(ev.events)[("gateway_poisoned")]
    assert f["migrations"] == 1 and f["request_id"] == req.request_id
    with pytest.raises(ValueError, match="max_migrations"):
        ServeGateway([_ScriptEngine(9)], max_migrations=0)


def test_storm_poisoned_is_conserved_not_a_violation():
    """Quarantine is a TERMINAL outcome: a poisoned request counts as
    finished in the conservation sweep, not lost."""
    mon = InvariantMonitor()
    r = Request(prompt=[1], max_new_tokens=4)
    mon.wrap_request(r, widx=0, deterministic=True)
    r.on_finish("poisoned")
    mon.on_output(_out(r.request_id, [], "poisoned"))
    mon.finalize([])
    assert mon.violations == []
    assert mon.finish_reasons == {"poisoned": 1}


# ----------------------------------------- events / manifests / clock


def test_storm_events_registered():
    evs = known_events()
    for name in ("storm_invariant_violation", "storm_summary",
                 "gateway_poisoned"):
        assert name in evs


def test_virtual_clock_is_the_sleep():
    vc = VirtualClock()
    vc.sleep(2.5)
    vc.advance(0.5)
    assert vc.now() == vc() == 3.0


def test_storm_job_renders_and_validates():
    from k8s_distributed_deeplearning_tpu.config import JobConfig
    from k8s_distributed_deeplearning_tpu.launch import render, validate

    cfg = JobConfig(storm_steps=200, storm_seed=4, storm_fault_rate=0.3)
    docs = render.render_all(cfg)
    roles = [(d["metadata"].get("labels") or {}).get("role")
             for d in docs if d.get("kind") == "Job"]
    assert "serve-storm" in roles
    assert validate.validate(docs) == []

    job = render.render_storm_job(cfg)
    cmd = job["spec"]["template"]["spec"]["containers"][0]["command"]
    assert "storm" in cmd and "--seed" in cmd
    assert job["spec"]["backoffLimit"] == 0

    # Broken domains must be caught OFFLINE, not inside the pod.
    bad = render.render_storm_job(JobConfig(storm_steps=0))
    errs = validate.validate(render.render_all(cfg)[:1] + [bad])
    assert any("--steps" in e for e in errs)
    tampered = render.render_storm_job(cfg)
    tampered["spec"]["backoffLimit"] = 3
    errs = validate.validate(render.render_all(cfg)[:1] + [tampered])
    assert any("backoffLimit 0" in e for e in errs)


# ------------------------------------------- end-to-end on real engines


def test_storm_disagg_real_engines_clean():
    """One real pass: tiny CPU engines, disagg topology (prefill tier +
    in-process KV shipping under the new ``transport_pages`` hook), a
    short seeded soak — zero violations, everything conserved."""
    import jax
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.models import llama
    from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine

    mcfg = llama.config_tiny(max_seq_len=64, dtype=jnp.float32)
    model = llama.LlamaLM(mcfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = _cfg(seed=5, steps=16, replicas=1, arrival_rate=0.8,
               prefill=1, vocab=mcfg.vocab_size,
               prompt_len=(2, 6), out_len=(2, 5))

    def mk(i):
        return ServeEngine(model, params, num_slots=4, max_queue=64,
                           tenants=cfg.tenant_configs(),
                           replica_id=f"s{i}" if i >= 0 else "oracle")

    def mk_pre(i):
        return ServeEngine(model, params, num_slots=4, max_queue=64,
                           tenants=cfg.tenant_configs(),
                           replica_id=f"p{i}", prefill_only=True)

    rep = run_storm(cfg, make_engine=mk, make_prefill_engine=mk_pre)
    assert rep.violations == []
    assert rep.submitted == rep.finished > 0
    assert rep.parity_checked > 0


# ---------------------------------------------------------------------------
# live metrics wiring: run_storm's on_monitor hook + bridge.storm_collector
# ---------------------------------------------------------------------------


def test_storm_collector_scrapes_live_soak():
    """The CLI exposes a running soak through late-bound proxies: the
    monitor/injector only exist inside run_storm, so the collector reads
    through boxes that the on_monitor hook fills. A scrape before the
    hook fires must render zeros (not crash); a scrape after the soak
    must report the real submitted/violation/fired numbers."""
    from k8s_distributed_deeplearning_tpu.telemetry import bridge
    from k8s_distributed_deeplearning_tpu.telemetry.registry import (
        MetricsRegistry)

    mon_box: list = []
    inj_box: list = []

    class _LazyMon:
        violations = property(
            lambda self: mon_box[0].violations if mon_box else [])

        def in_flight(self):
            return mon_box[0].in_flight() if mon_box else 0

        def submitted_total(self):
            return mon_box[0].submitted_total() if mon_box else 0

    class _LazyInj:
        fired = property(
            lambda self: inj_box[0].fired if inj_box else [])

    reg = MetricsRegistry()
    bridge.storm_collector(reg, _LazyMon(), injector=_LazyInj())

    def _value(text, name):
        line = [ln for ln in text.splitlines()
                if ln.startswith(name + " ")][0]
        return float(line.split()[-1])

    before = reg.render()
    assert _value(before, "serve_storm_requests_submitted_total") == 0
    assert _value(before, "serve_storm_faults_fired_total") == 0

    rep = run_storm(
        _cfg(), make_engine=_ScriptEngine,
        on_monitor=lambda m, i: (mon_box.append(m), inj_box.append(i)))

    after = reg.render()
    assert rep.submitted > 0
    assert _value(after, "serve_storm_requests_submitted_total") == \
        rep.submitted
    assert _value(after, "serve_storm_faults_fired_total") == len(rep.fired)
    assert _value(after, "serve_storm_invariant_violations_total") == 0
    assert _value(after, "serve_storm_requests_in_flight") == 0


def test_queue_bound_is_global_across_tenants():
    """The engine's max_queue bounds EACH tenant (engine.py admission
    contract), so a healthy engine under open-loop overload can reach
    tenants x max_queue queued requests. The monitor's bound must be the
    GLOBAL one — a sustained-overload soak at 12k steps regressed on
    this (depth 298 with per-tenant bound 256 and 3 tenants: legal)."""
    cfg = _cfg(max_queue=10)          # default mix has 3 tenants
    assert cfg.global_queue_bound() == 30

    class _E:
        replica_id = "s0"
        num_slots = 4
        occupied_slots = 0
        queue = list(range(25))

    mon = InvariantMonitor(repro="r", max_queue=cfg.global_queue_bound())
    mon.check_step([_E()])
    assert mon.violations == []       # over per-tenant, under global: legal
    _E.queue = list(range(31))
    mon.check_step([_E()])
    assert [v["kind"] for v in mon.violations] == ["queue_overflow"]
