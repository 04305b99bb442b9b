"""Failover gateway chaos matrix (serve/gateway.py): health-routed
dispatch, per-replica circuit breakers (trip / half-open probe / doubled
backoff), in-flight migration with bit-exact stream splicing, bounded
hedging, replica drain, exactly-once ``on_finish`` across every terminal
path, and the requeue-at-head scheduler contract migration rides on.

The headline acceptance criterion: kill one of two in-process replicas
mid-decode and every migrated greedy stream is IDENTICAL to an unfaulted
single-replica run — failover is invisible in the tokens."""
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu import faults
from k8s_distributed_deeplearning_tpu.faults.plan import Fault, FaultPlan
from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.serve import (QueueFull, Request,
                                                    ServeEngine,
                                                    ServeGateway,
                                                    TenantConfig,
                                                    TenantScheduler)
from k8s_distributed_deeplearning_tpu.serve.gateway import (CLOSED,
                                                            HALF_OPEN, OPEN)
from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.deactivate()
    yield
    faults.deactivate()


def _workload(cfg, n, seed=0, p_lo=4, p_hi=17, m_lo=3, m_hi=16):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(p_lo, p_hi))).astype(
                                np.int32) for _ in range(n)]
    max_news = [int(rng.integers(m_lo, m_hi)) for _ in range(n)]
    return prompts, max_news


def _ref_greedy(model, params, prompt, max_new):
    return np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :],
        max_new_tokens=max_new))[0]


def _fleet(tiny, n=2, *, stats=None, num_slots=2, **kw):
    """N replica engines sharing one ServingStats (the CLI wiring)."""
    model, params, _ = tiny
    stats = stats if stats is not None else ServingStats()
    engines = [ServeEngine(model, params, num_slots=num_slots, eos_id=None,
                           stats=stats, replica_id=f"r{i}", **kw)
               for i in range(n)]
    return engines, stats


def _drive(gw, outs, max_steps=600):
    """Step the gateway to quiescence (bounded — a hang fails loudly)."""
    for _ in range(max_steps):
        if not gw.busy():
            return
        outs.extend(gw.step())
    raise AssertionError(f"gateway did not finish in {max_steps} steps")


def _kill_replica_plan(index):
    """Step-scoped ioerror at the gateway_dispatch site: ``step`` carries
    the replica INDEX, so this fails exactly one replica's dispatch on
    every gateway iteration while the plan is active."""
    return FaultPlan((Fault(site="gateway_dispatch", action="ioerror",
                            step=index, attempt=None),))


class _Events:
    """Duck-typed MetricsLogger capturing emitted events for assertions."""

    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append((event, fields))

    def names(self):
        return [e for e, _ in self.events]

    def fields(self, name):
        return [f for e, f in self.events if e == name]


# --------------------------------------------------- jax-free: fakes


class _FakePool:
    def counters(self):
        return {"pages_total": 8, "pages_used": 0, "pages_shared": 0}


class _FakeEngine:
    """Just enough ServeEngine surface for breaker/routing state tests —
    no jax, no model, instant steps."""

    def __init__(self, replica_id=None, occupied=0):
        self.replica_id = replica_id
        self.queue = []
        self.num_slots = 2
        self.pool = _FakePool()
        self.steps = 0
        self.submitted = []
        self._occupied = occupied
        self._draining = False

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
        return []

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        return self._draining

    def shutdown(self):
        return []


def test_gateway_constructor_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        ServeGateway([])
    with pytest.raises(ValueError, match="failures_to_trip"):
        ServeGateway([_FakeEngine()], failures_to_trip=0)
    with pytest.raises(ValueError, match="probe_backoff_s"):
        ServeGateway([_FakeEngine()], probe_backoff_s=0.0)
    with pytest.raises(ValueError, match="probe_backoff_s"):
        ServeGateway([_FakeEngine()], probe_backoff_s=2.0,
                     max_probe_backoff_s=1.0)
    with pytest.raises(ValueError, match="hedge_after_s"):
        ServeGateway([_FakeEngine()], hedge_after_s=0.0)
    with pytest.raises(ValueError, match="duplicate replica_id"):
        ServeGateway([_FakeEngine(replica_id="x"),
                      _FakeEngine(replica_id="x")])
    # Unnamed replicas get positional ids, written back for traces.
    engines = [_FakeEngine(), _FakeEngine()]
    gw = ServeGateway(engines)
    assert [e.replica_id for e in engines] == ["r0", "r1"]
    assert gw.breaker_state("r0") == CLOSED


def test_routing_prefers_less_loaded_and_skips_draining():
    busy, idle = _FakeEngine(occupied=2), _FakeEngine()
    gw = ServeGateway([busy, idle])
    gw.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    assert len(idle.submitted) == 1 and not busy.submitted
    # A draining replica leaves the routable set: its live request is
    # migrated onto the peer and new submissions follow it there.
    gw.drain_replica("r1")
    assert len(busy.submitted) == 1          # the migrated resubmission
    assert gw.stats.gateway_migrations == 1
    gw.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    assert len(busy.submitted) == 2
    gw.drain_replica("r0")
    with pytest.raises(QueueFull, match="no healthy replica"):
        gw.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))
    with pytest.raises(ValueError, match="unknown replica"):
        gw.drain_replica("r9")


def test_breaker_trip_probe_backoff_recovery():
    """The full breaker lifecycle on an injected clock: consecutive
    failures trip it OPEN, the open window rejects stepping, a failed
    half-open probe re-opens with the backoff doubled (bounded), and a
    healthy probe closes it and resets the schedule."""
    t = [1000.0]
    ev = _Events()
    gw = ServeGateway([_FakeEngine(), _FakeEngine()], failures_to_trip=2,
                      probe_backoff_s=1.0, max_probe_backoff_s=4.0,
                      clock=lambda: t[0], logger=ev)
    faults.activate(_kill_replica_plan(0))
    gw.step()
    assert gw.breaker_state("r0") == CLOSED      # 1 failure: below trip
    gw.step()
    assert gw.breaker_state("r0") == OPEN
    assert gw.breaker_state("r1") == CLOSED      # peer unaffected
    assert gw.stats.gateway_breaker_trips == 1
    gw.step()                                    # probe timer not expired
    assert gw.breaker_state("r0") == OPEN
    t[0] += 1.1                                  # past next_probe_t
    gw.step()                                    # half-open probe fails
    assert gw.breaker_state("r0") == OPEN
    assert gw.stats.gateway_breaker_trips == 2
    snap = gw.snapshot()["replicas"]["r0"]
    assert 1.9 <= snap["next_probe_in_s"] <= 2.0  # backoff doubled
    t[0] += 1.1                                  # doubled window still runs
    gw.step()
    assert gw.breaker_state("r0") == OPEN
    faults.deactivate()
    t[0] += 1.0
    gw.step()                                    # healthy probe closes it
    assert gw.breaker_state("r0") == CLOSED
    assert gw._by_rid["r0"].backoff == 1.0       # schedule reset
    assert ev.names().count("gateway_breaker_open") == 2
    assert ev.names().count("gateway_breaker_closed") == 1


def test_open_breaker_goes_half_open_at_probe_time():
    t = [0.0]
    gw = ServeGateway([_FakeEngine()], failures_to_trip=1,
                      probe_backoff_s=5.0, clock=lambda: t[0])
    faults.activate(_kill_replica_plan(0))
    gw.step()
    assert gw.breaker_state("r0") == OPEN
    faults.deactivate()
    t[0] += 5.1
    # The transition is visible mid-step via the submitted probe state;
    # after a clean step it has already closed again.
    eng = gw._replicas[0]
    gw.step()
    assert eng.state == CLOSED and gw._replicas[0].engine.steps == 1


# -------------------------------------------------- real-model matrix


def test_routing_spreads_load_and_unfaulted_parity(tiny):
    """Baseline sanity: submissions alternate across equally-healthy
    replicas, and a 2-replica gateway run is bit-identical per request to
    the isolated one-shot generate() oracle."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 6, seed=4)
    engines, _ = _fleet(tiny, 2)
    gw = ServeGateway(engines)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    for r in reqs[:4]:
        gw.submit(r)
    assert engines[0].load() == 2 and engines[1].load() == 2
    outs = list(gw.run(reqs[4:]))
    outd = {o.request_id: o for o in outs}
    assert len(outd) == len(reqs)
    for r, p, m in zip(reqs, prompts, max_news):
        assert outd[r.request_id].finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(outd[r.request_id].tokens),
            _ref_greedy(model, params, p, m))


def test_replica_kill_migrates_bit_identically(tiny):
    """THE acceptance criterion: r0 dies mid-decode (injected dispatch
    ioerror -> breaker trip -> engine teardown), its live requests are
    resubmitted to r1 as prompt + streamed cursor, and every greedy
    stream — including the migrated ones — is bit-identical to the
    unfaulted oracle. on_finish fires exactly once per request and the
    migration counter matches the emitted gateway_migrated events."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 4, seed=5, m_lo=10, m_hi=14)
    engines, stats = _fleet(tiny, 2, prefix_cache_mb=4, kv_pool_pages=16)
    ev = _Events()
    gw = ServeGateway(engines, stats=stats, logger=ev, failures_to_trip=1)
    finishes = {}
    reqs = []
    for p, m in zip(prompts, max_news):
        r = Request(prompt=p, max_new_tokens=m)
        r.on_finish = (lambda reason, rid=r.request_id:
                       finishes.setdefault(rid, []).append(reason))
        reqs.append(r)
        gw.submit(r)
    assert engines[0].load() == 2 and engines[1].load() == 2
    outs = []
    for _ in range(3):                       # both replicas mid-decode
        outs.extend(gw.step())
    assert engines[0].occupied_slots() == 2
    faults.activate(_kill_replica_plan(0))
    try:
        outs.extend(gw.step())               # r0 trips; its work migrates
    finally:
        faults.deactivate()
    assert gw.breaker_state("r0") == OPEN
    assert stats.gateway_breaker_trips == 1
    assert stats.gateway_migrations == 2     # both of r0's live requests
    _drive(gw, outs)
    outd = {o.request_id: o for o in outs}
    assert len(outd) == len(reqs)
    for r, p, m in zip(reqs, prompts, max_news):
        o = outd[r.request_id]
        assert o.finish_reason == "length"
        np.testing.assert_array_equal(np.asarray(o.tokens),
                                      _ref_greedy(model, params, p, m))
        assert finishes[r.request_id] == ["length"]
    migrated = ev.fields("gateway_migrated")
    assert len(migrated) == stats.gateway_migrations
    assert all(m["from_replica"] == "r0" and m["to_replica"] == "r1"
               for m in migrated)
    # Mid-decode migration, not a queued reshuffle: the cursor moved.
    assert any(m["tokens_emitted"] > 0 for m in migrated)
    assert ev.names().count("gateway_breaker_open") == 1


def test_hedge_covers_straggling_replica_and_cancels_loser(tiny):
    """A request stuck behind a sick replica's prefill gets one duplicate
    dispatch after hedge_after_s; the peer's stream wins (bit-exact) and
    the loser is cancelled on the sick replica with reason hedge_lost."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 1, seed=7, m_lo=8, m_hi=9)
    engines, stats = _fleet(tiny, 2)
    t = [0.0]
    ev = _Events()
    # failures_to_trip is huge: the sick replica must straggle, not trip —
    # hedging (not migration) has to win this one.
    gw = ServeGateway(engines, stats=stats, logger=ev, hedge_after_s=0.5,
                      failures_to_trip=10_000, clock=lambda: t[0])
    reasons = []
    req = Request(prompt=prompts[0], max_new_tokens=max_news[0])
    req.on_finish = reasons.append
    faults.activate(_kill_replica_plan(0))
    outs = []
    try:
        gw.submit(req)                       # ties route to r0 — the sick one
        assert engines[0].load() == 1
        gw.step()
        assert stats.gateway_hedges == 0     # within the hedge window
        t[0] += 1.0
        _drive(gw, outs)
    finally:
        faults.deactivate()
    assert stats.gateway_hedges == 1
    assert "gateway_breaker_open" not in ev.names()
    (out,) = outs
    assert out.finish_reason == "length"
    np.testing.assert_array_equal(
        np.asarray(out.tokens),
        _ref_greedy(model, params, prompts[0], max_news[0]))
    assert reasons == ["length"]
    # The losing shadow was cancelled off the sick replica's queue.
    assert stats.finish_reasons.get("hedge_lost") == 1
    assert engines[0].load() == 0


def test_drain_replica_migrates_work_and_excludes_routing(tiny):
    """Cooperative drain: r0's in-flight work moves to r1 (engine reason
    ``migrated``), r0 reports drained, routing never touches it again —
    and every stream still matches the oracle."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 4, seed=6, m_lo=8, m_hi=12)
    engines, stats = _fleet(tiny, 2, kv_pool_pages=16)
    ev = _Events()
    gw = ServeGateway(engines, stats=stats, logger=ev)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    for r in reqs:
        gw.submit(r)
    outs = []
    for _ in range(2):
        outs.extend(gw.step())
    gw.drain_replica("r0")
    gw.drain_replica("r0")                   # idempotent
    assert engines[0].draining
    assert stats.gateway_migrations >= 1
    assert "replica_drained" in ev.names()
    # Post-drain submissions only ever land on r1.
    extra = Request(prompt=prompts[0], max_new_tokens=max_news[0])
    gw.submit(extra)
    _drive(gw, outs)
    outd = {o.request_id: o for o in outs}
    assert len(outd) == len(reqs) + 1
    for r, p, m in zip(reqs + [extra], prompts + [prompts[0]],
                       max_news + [max_news[0]]):
        o = outd[r.request_id]
        assert o.finish_reason == "length"
        np.testing.assert_array_equal(np.asarray(o.tokens),
                                      _ref_greedy(model, params, p, m))
    assert engines[0].drained and engines[0].load() == 0
    assert stats.finish_reasons.get("migrated", 0) >= 1


def test_migration_preserves_deadline_anchor_timeout_once(tiny):
    """Terminal-path matrix, migration x deadline: the resubmission keeps
    the ORIGINAL _t_submit, so deadline_abs never resets — the request
    times out relative to its first submit even though it moved replicas
    mid-flight. on_finish fires exactly once, with "timeout"."""
    model, params, cfg = tiny
    engines, stats = _fleet(tiny, 2)
    gw = ServeGateway(engines, stats=stats, failures_to_trip=1)
    rng = np.random.default_rng(11)
    reasons = []
    req = Request(prompt=rng.integers(0, cfg.vocab_size, size=8).astype(
                      np.int32),
                  max_new_tokens=40, deadline_s=0.5)
    req.on_finish = reasons.append
    gw.submit(req)
    outs = []
    for _ in range(2):
        outs.extend(gw.step())
    time.sleep(0.35)                         # burn most of the deadline
    faults.activate(_kill_replica_plan(0))
    try:
        outs.extend(gw.step())               # migrate to r1 mid-flight
    finally:
        faults.deactivate()
    assert stats.gateway_migrations == 1
    # < deadline_s has elapsed SINCE migration; > deadline_s since submit.
    time.sleep(0.25)
    _drive(gw, outs)
    (out,) = outs
    assert out.finish_reason == "timeout"
    assert 0 < len(out.tokens) < req.max_new_tokens
    assert reasons == ["timeout"]


def test_shutdown_after_migration_finishes_once(tiny):
    """Terminal-path matrix, migration x shutdown: tearing the whole
    gateway down right after a migration aborts the request exactly once
    (the muted victim shadow and the live one can't both finish it)."""
    model, params, cfg = tiny
    engines, stats = _fleet(tiny, 2)
    gw = ServeGateway(engines, stats=stats, failures_to_trip=1)
    rng = np.random.default_rng(12)
    reasons = []
    req = Request(prompt=rng.integers(0, cfg.vocab_size, size=6).astype(
                      np.int32),
                  max_new_tokens=30)
    req.on_finish = reasons.append
    gw.submit(req)
    for _ in range(2):
        gw.step()
    faults.activate(_kill_replica_plan(0))
    try:
        gw.step()
    finally:
        faults.deactivate()
    assert stats.gateway_migrations == 1
    outs = gw.shutdown()
    (out,) = outs
    assert out.finish_reason == "aborted"
    assert reasons == ["aborted"]
    assert not gw.busy()
    assert gw.step() == []                   # quiesced, not wedged


def test_engine_cancel_migrated_terminal_path(tiny):
    """Engine-level surface the gateway drains through: cancel a decoding
    request with reason "migrated" -> partial tokens, exactly-once
    on_finish, freed slot immediately reusable with bit-exact decode."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 3, seed=9, m_lo=8, m_hi=12)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    reasons = []
    victim = Request(prompt=prompts[0], max_new_tokens=max_news[0])
    victim.on_finish = reasons.append
    eng.submit(victim)
    for _ in range(3):
        eng.step()
    out = eng.cancel(victim.request_id, "migrated")
    assert out is not None and out.finish_reason == "migrated"
    assert 0 < len(out.tokens) < max_news[0]
    assert reasons == ["migrated"]
    assert eng.cancel(victim.request_id, "migrated") is None   # idempotent
    # The freed slot serves the next request exactly.
    follow = Request(prompt=prompts[1], max_new_tokens=max_news[1])
    outs = {o.request_id: o for o in eng.run([follow])}
    np.testing.assert_array_equal(
        np.asarray(outs[follow.request_id].tokens),
        _ref_greedy(model, params, prompts[1], max_news[1]))
    assert reasons == ["migrated"]           # cancel never double-fires


# ------------------------------------- scheduler requeue-at-head contract


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _req(prompt_len=8, max_new=8, tenant="default", deadline_s=None):
    return Request(prompt=np.zeros(prompt_len, np.int32),
                   max_new_tokens=max_new, tenant=tenant,
                   deadline_s=deadline_s)


def test_tenant_requeue_pops_first_without_rebilling():
    """A migrated request re-enters at its deadline class's head and its
    tenant's token bucket is NOT charged a second time — the first pop
    already paid the full prompt+decode cost."""
    clk = _Clock()
    ts = TenantScheduler([TenantConfig("t", rate_tokens_per_s=100.0)],
                         clock=clk)
    first = _req(tenant="t")                 # cost 16
    ts.submit(first)
    tokens0 = ts._tenants["t"].tokens
    assert ts.pop() is first
    assert ts._tenants["t"].tokens == tokens0 - 16
    ts.requeue(first)
    ts.submit(_req(tenant="t"))              # later arrival, same deadline
    assert ts.pop() is first                 # head re-entry wins the tie
    assert ts._tenants["t"].tokens == tokens0 - 16   # no second charge
    assert not first._requeued               # latch consumed at the pop


def test_tenant_requeue_bypasses_rate_block():
    """An empty token bucket must not strand a migrated request: its cost
    is prepaid, so the head requeue pops through the rate gate."""
    clk = _Clock()
    ts = TenantScheduler([TenantConfig("t", rate_tokens_per_s=1.0)],
                         clock=clk)
    req = _req(tenant="t")                   # cost 16 >> burst 1.0
    ts.submit(req)
    assert ts.pop() is req                   # oversized: admits on full bucket
    assert ts._tenants["t"].tokens < 0       # bucket deep in debt
    ts.requeue(req)
    assert ts.pop() is req                   # prepaid: not rate-blocked
    ts.release(req)
    ts.release(req)
    ts.submit(_req(tenant="t"))
    assert ts.pop() is None                  # fresh work IS rate-blocked


def test_tenant_requeue_preserves_deadline_abs():
    """deadline_abs anchors to the FIRST submit: after 3s elapse and a
    requeue, a 5s-deadline request expires at t0+5, not t_requeue+5."""
    clk = _Clock()
    ts = TenantScheduler([TenantConfig("t")], clock=clk)
    req = _req(tenant="t", deadline_s=5.0)
    ts.submit(req)
    assert ts.pop() is req
    clk.advance(3.0)
    ts.requeue(req)
    clk.advance(2.5)                         # t0+5.5: expired iff anchored
    expired = ts.sweep_expired()
    assert [r.request_id for r in expired] == [req.request_id]


def test_tenant_remove_and_fifo_requeue():
    clk = _Clock()
    ts = TenantScheduler([TenantConfig("t")], clock=clk)
    a, b = _req(tenant="t"), _req(tenant="t")
    ts.submit(a)
    ts.submit(b)
    assert ts.remove(a.request_id) is a
    assert ts.remove("nope") is None
    assert ts.pop() is b and len(ts) == 0


def test_gateway_dispatch_fault_site_plan_validation():
    assert not _kill_replica_plan(0).problems()
    assert FaultPlan((Fault(site="gateway_dispatch", action="stall",
                            seconds=0.1),)).problems() == []
    # Checkpoint-damage actions make no sense at a dispatch site.
    assert FaultPlan((Fault(site="gateway_dispatch",
                            action="truncate"),)).problems()


# ------------------------------------------------------ SIGTERM drain


@pytest.mark.slow
def test_cli_sigterm_drains_replicas_and_exits_zero(tmp_path):
    """The k8s eviction handshake end-to-end: SIGTERM to a running
    2-replica serve CLI flips drain mode, the gang finishes what it
    holds, emits replica_drained per replica, and exits 0."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "k8s_distributed_deeplearning_tpu.launch",
         "serve", "--preset", "tiny", "--max-seq-len", "64",
         "--replicas", "2", "--slots", "2", "--requests", "64",
         # Small queues keep most of the workload UNSUBMITTED (fed under
         # back-pressure) when SIGTERM lands, so the drain has a tail to
         # shed — that's what the < 64 completion assert measures.
         "--max-queue", "4",
         "--prompt-len", "4", "12", "--out-len", "8", "16"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        # Wait for the loop to be live (first completion on stdout) so
        # the handler is installed and work is genuinely in flight.
        deadline = time.time() + 420
        saw_request = False
        lines = []
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            lines.append(line)
            if '"serve_request"' in line:
                saw_request = True
                break
        assert saw_request, "".join(lines)[-2000:]
        proc.send_signal(signal.SIGTERM)
        rest, err = proc.communicate(timeout=300)
    except Exception:
        proc.kill()
        raise
    assert proc.returncode == 0, err[-2000:]
    out = "".join(lines) + rest
    assert out.count('"replica_drained"') >= 2     # one per replica
    assert '"serve_summary"' in out
    # Drain sheds the unsubmitted tail: strictly fewer completions than
    # the requested workload proves SIGTERM actually cut the run short.
    assert out.count('"serve_request"') < 64
