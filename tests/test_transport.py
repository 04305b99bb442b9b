"""graftwire chaos matrix (serve/transport.py): cross-process replica
transport with network fault tolerance.

Two tiers, mirroring test_gateway.py:

- jax-free wire tests against a deterministic fake engine behind a REAL
  ReplicaServer (real stdlib HTTP, real fault injection): idempotent
  submit across ambiguous failures, exactly-once stream splicing over
  lost poll responses, typed rejection mapping, partition windows,
  drain-retry accumulation, probe split, heartbeat discovery.
- real-model integration: a ServeGateway over ReplicaClients to two
  live ReplicaServers — bit parity against the one-shot generate()
  oracle through remote dispatch, wire drain/migration, and a replica
  process kill.

The headline acceptance criterion: a retried submit after a dropped
response admits EXACTLY once, and every migrated/reconnected stream is
bit-identical to the unfaulted oracle."""
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu import faults
from k8s_distributed_deeplearning_tpu.faults.plan import Fault, FaultPlan
from k8s_distributed_deeplearning_tpu.serve.request import (EngineDraining,
                                                            QueueFull,
                                                            Request,
                                                            SamplingParams)
from k8s_distributed_deeplearning_tpu.serve.transport import (
    ReplicaClient, ReplicaServer, discover_replica_clients,
    request_from_wire, request_to_wire)
from k8s_distributed_deeplearning_tpu.telemetry.registry import (
    MetricsRegistry)
from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats
from k8s_distributed_deeplearning_tpu.utils.retry import retry_transient


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.deactivate()
    yield
    faults.deactivate()


class _Events:
    def __init__(self):
        self.events = []

    def emit(self, event, **fields):
        self.events.append((event, fields))

    def names(self):
        return [e for e, _ in self.events]


# ------------------------------------------------- satellite: full jitter


def test_retry_full_jitter_schedule_is_rng_times_doubling_ceiling():
    """jitter=True draws each wait uniformly from [0, ceiling) with the
    ceiling doubling (AWS full jitter); injectable rng makes the exact
    schedule assertable."""
    sleeps, seq = [], iter([0.5, 0.25, 0.125])
    calls = [0]

    def fn():
        calls[0] += 1
        if calls[0] <= 3:
            raise OSError("blip")
        return "ok"

    observed = []
    assert retry_transient(
        fn, retries=3, backoff_s=1.0, sleep=sleeps.append,
        jitter=True, rng=lambda: next(seq),
        on_retry=lambda n, e, d: observed.append((n, d))) == "ok"
    assert sleeps == [0.5 * 1.0, 0.25 * 2.0, 0.125 * 4.0]
    # on_retry sees the ACTUAL post-jitter delay, not the ceiling.
    assert observed == [(1, 0.5), (2, 0.5), (3, 0.5)]


def test_retry_without_jitter_keeps_pure_doubling():
    sleeps, calls = [], [0]

    def fn():
        calls[0] += 1
        if calls[0] <= 2:
            raise OSError("blip")
        return calls[0]

    assert retry_transient(fn, retries=2, backoff_s=0.5,
                           sleep=sleeps.append) == 3
    assert sleeps == [0.5, 1.0]


def test_retry_permanent_error_never_sleeps():
    sleeps = []
    with pytest.raises(ValueError):
        retry_transient(lambda: (_ for _ in ()).throw(ValueError("bad")),
                        retries=5, sleep=sleeps.append, jitter=True,
                        rng=lambda: 1.0)
    assert sleeps == []


# ------------------------------------------- fault-site / plan registry


def test_transport_fault_sites_accept_network_actions():
    for site in ("transport_send", "transport_recv"):
        for action in ("ioerror", "stall", "drop"):
            seconds = 0.1 if action == "stall" else 0.0
            assert not FaultPlan((Fault(site=site, action=action,
                                        seconds=seconds),)).problems()
        assert not FaultPlan((Fault(site=site, action="partition",
                                    seconds=0.5),)).problems()
        # A zero-length partition is a no-op masquerading as chaos.
        assert FaultPlan((Fault(site=site, action="partition"),)).problems()
        # Checkpoint-damage actions make no sense on the wire.
        assert FaultPlan((Fault(site=site, action="truncate"),)).problems()


# --------------------------------------------------- wire serialization


def test_wire_request_roundtrip_preserves_decode_inputs():
    req = Request(prompt=np.arange(3, 8, dtype=np.int32), max_new_tokens=7,
                  sampling=SamplingParams(temperature=0.5, top_k=3,
                                          top_p=0.9),
                  tenant="t1", seed=9, deadline_s=4.0)
    msg = json.loads(json.dumps(request_to_wire(req, deadline_s=2.5)))
    back = request_from_wire(msg)
    assert list(back.prompt) == [3, 4, 5, 6, 7]
    assert back.max_new_tokens == 7
    assert (back.sampling.temperature, back.sampling.top_k,
            back.sampling.top_p) == (0.5, 3, 0.9)
    assert back.request_id == req.request_id
    assert back.trace_id == req.trace_id      # graftscope stitching key
    assert back.tenant == "t1" and back.seed == 9
    # The wire carries REMAINING budget, re-anchored server-side.
    assert back.deadline_s == 2.5
    with pytest.raises((KeyError, ValueError, TypeError)):
        request_from_wire({"prompt": [1, 2]})   # no max_new_tokens


# -------------------------------------------------- fake wire engine


class _WirePool:
    def counters(self):
        return {"pages_total": 16, "pages_used": 1, "pages_shared": 0}


class _WireEngine:
    """Deterministic jax-free engine behind a real ReplicaServer: each
    step emits ``prompt[-1] + n + 1`` per live request — the expected
    stream for prompt p, budget m is ``[p[-1]+1, ..., p[-1]+m]``, so
    exactly-once delivery is assertable token by token."""

    def __init__(self, replica_id=None, num_slots=2, max_queue=4):
        self.replica_id = replica_id
        self.stats = ServingStats()
        self.pool = _WirePool()
        self.num_slots = num_slots
        self.max_queue = max_queue
        self.live = []                 # [request, emitted]
        self.queue = []                # queued beyond the slots
        self.submits = []
        self.fail_submit = None
        self._draining = False

    def busy(self):
        return bool(self.live or self.queue)

    def occupied_slots(self):
        return len(self.live)

    def load(self):
        return len(self.live) + len(self.queue)

    def submit(self, req, *, requeue=False):
        if self.fail_submit is not None:
            raise self.fail_submit
        if self._draining and not requeue:
            raise EngineDraining("draining")
        if self.load() >= self.num_slots + self.max_queue:
            raise QueueFull("queue full")
        self.submits.append(req.request_id)
        if len(self.live) < self.num_slots:
            self.live.append([req, 0])
        else:
            self.queue.append(req)

    def step(self):
        for entry in list(self.live):
            req, n = entry
            entry[1] += 1
            tok = int(req.prompt[-1]) + n + 1
            if req.on_token is not None:
                req.on_token(tok)
            if entry[1] >= req.max_new_tokens:
                self.live.remove(entry)
                if req.on_finish is not None:
                    req.on_finish("length")
        while self.queue and len(self.live) < self.num_slots:
            self.live.append([self.queue.pop(0), 0])
        return []

    def cancel(self, request_id, reason="aborted"):
        for entry in list(self.live):
            if entry[0].request_id == request_id:
                self.live.remove(entry)
                if entry[0].on_finish is not None:
                    entry[0].on_finish(reason)
                return entry[0]
        for req in list(self.queue):
            if req.request_id == request_id:
                self.queue.remove(req)
                if req.on_finish is not None:
                    req.on_finish(reason)
                return req
        return None

    def drain(self, *, flush=False):
        self._draining = True
        if flush:
            out, self.queue = list(self.queue), []
            return out
        return []

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        return self._draining and not self.live and not self.queue

    def shutdown(self):
        self.live.clear()
        self.queue.clear()
        return []


@pytest.fixture
def wire():
    eng = _WireEngine(replica_id="r0")
    srv = ReplicaServer(eng, registry=MetricsRegistry(),
                        idle_wait_s=0.002).start()
    yield eng, srv
    srv.close()


def _client(srv, **kw):
    kw.setdefault("replica_id", "r0")
    kw.setdefault("timeout_s", 5.0)
    kw.setdefault("backoff_s", 0.001)
    kw.setdefault("rng", lambda: 1.0)
    return ReplicaClient(srv.address, **kw)


def _wait(pred, deadline_s=5.0, msg="condition"):
    t0 = time.time()
    while not pred():
        if time.time() - t0 > deadline_s:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.002)


def _drive_client(client, deadline_s=10.0):
    t0 = time.time()
    while client.busy():
        client.step()
        if time.time() - t0 > deadline_s:
            raise AssertionError("client did not quiesce")
        time.sleep(0.002)


def _expected(req):
    base = int(req.prompt[-1])
    return [base + i + 1 for i in range(req.max_new_tokens)]


# -------------------------------------------------- wire happy path


def test_wire_stream_end_to_end(wire):
    eng, srv = wire
    client = _client(srv)
    toks, fins = [], []
    req = Request(prompt=[5, 6, 7], max_new_tokens=4)
    req.on_token = toks.append
    req.on_finish = fins.append
    client.submit(req)
    assert client.busy()
    _drive_client(client)
    assert toks == _expected(req)
    assert fins == ["length"]
    assert eng.submits == [req.request_id]
    assert not client._streams


def test_ambiguous_submit_retry_admits_exactly_once(wire):
    """THE idempotency criterion: the first /submit lands server-side but
    its response is dropped on the wire (transport_recv after the
    handler). The client's retry of the SAME dispatch key hits the
    dedup ledger — one admission, one stream, one on_finish."""
    eng, srv = wire
    client = _client(srv)
    toks, fins = [], []
    req = Request(prompt=[10], max_new_tokens=5)
    req.on_token = toks.append
    req.on_finish = fins.append
    faults.activate(FaultPlan((Fault(site="transport_recv", action="drop",
                                     count=1),)))
    try:
        client.submit(req)
    finally:
        faults.deactivate()
    assert eng.submits == [req.request_id]        # admitted ONCE
    assert client.stats.transport_retries == 1
    assert eng.stats.transport_dedup_hits == 1    # retry answered duplicate
    _drive_client(client)
    assert toks == _expected(req)                 # stream intact
    assert fins == ["length"]                     # exactly-once terminal


def test_lost_poll_response_splices_exactly_once(wire):
    """A poll whose response is severed after the handler ran must not
    double-deliver on retry: the client never advanced its cursor, the
    server re-answers tokens[cursor:] — the splice is bit-exact."""
    eng, srv = wire
    client = _client(srv)
    toks, fins = [], []
    req = Request(prompt=[20], max_new_tokens=6)
    req.on_token = toks.append
    req.on_finish = fins.append
    client.submit(req)
    _wait(lambda: not eng.busy(), msg="server-side generation")
    faults.activate(FaultPlan((Fault(site="transport_recv", action="drop",
                                     count=1),)))
    try:
        client.step()
    finally:
        faults.deactivate()
    assert toks == _expected(req)
    assert fins == ["length"]
    assert client.stats.transport_retries == 1


def test_poll_exhaustion_raises_then_reconnect_is_counted(wire):
    """Transport exhaustion surfaces to the gateway's breaker as a raise;
    the first successful poll after failures records a reconnect (the
    stream resumed from its cursor, nothing lost)."""
    eng, srv = wire
    ev = _Events()
    client = _client(srv, retries=1, logger=ev)
    toks, fins = [], []
    req = Request(prompt=[30], max_new_tokens=3)
    req.on_token = toks.append
    req.on_finish = fins.append
    client.submit(req)
    faults.activate(FaultPlan((Fault(site="transport_send", action="ioerror",
                                     count=2),)))
    try:
        with pytest.raises(OSError):
            client.step()
    finally:
        faults.deactivate()
    assert client.stats.transport_retries == 1
    _drive_client(client)
    assert client.stats.transport_reconnects == 1
    assert toks == _expected(req) and fins == ["length"]
    assert "transport_retry" in ev.names()
    assert "transport_reconnect" in ev.names()


def test_partition_window_severs_both_attempts_then_heals(wire):
    """partition is stateful: the first fire opens a window and every
    subsequent attempt at the site fails until it closes — a submit
    caught inside maps to EngineDraining (route elsewhere), and its
    abandoned dispatch key can never double-admit."""
    eng, srv = wire
    client = _client(srv, retries=1)
    req = Request(prompt=[40], max_new_tokens=2)
    inj = faults.activate(FaultPlan((Fault(site="transport_send",
                                           action="partition",
                                           seconds=30.0),)))
    try:
        with pytest.raises(EngineDraining, match="unreachable"):
            client.submit(req)
    finally:
        faults.deactivate()
    assert ("transport_send", "partition") in inj.fired
    assert eng.submits == []                      # never left the client
    assert not client._streams                    # no orphan stream
    # Network healed (plan cleared): the same request admits cleanly.
    fins = []
    req.on_finish = fins.append
    client.submit(req)
    _drive_client(client)
    assert eng.submits == [req.request_id] and fins == ["length"]


def test_typed_rejections_map_without_retries(wire):
    """Server-mapped statuses surface as their typed exceptions and are
    never retried — HTTPError is an OSError subclass, so this guards the
    map-before-transient-predicate ordering."""
    eng, srv = wire
    sleeps = []
    client = _client(srv, sleep=sleeps.append)
    for exc, expect in ((QueueFull("full"), QueueFull),
                        (EngineDraining("draining"), EngineDraining),
                        (ValueError("too long"), ValueError)):
        eng.fail_submit = exc
        with pytest.raises(expect, match="replica answered"):
            client.submit(Request(prompt=[1], max_new_tokens=1))
    eng.fail_submit = None
    assert sleeps == []                           # zero retry sleeps


def test_replica_restart_lost_streams_raise_for_breaker(wire):
    eng, srv = wire
    client = _client(srv)
    req = Request(prompt=[50], max_new_tokens=4)
    client.submit(req)
    with srv._cond:                               # simulate process restart
        srv._records.clear()
        eng.live.clear()
    with pytest.raises(RuntimeError, match="lost 1 dispatched stream"):
        client.step()


def test_readyz_flips_503_on_drain_while_healthz_stays_200(wire):
    """The probe split the k8s render depends on: readiness gates routing
    (503 while draining), liveness gates restart (200 while draining —
    restarting a draining pod loses the work the drain protects)."""
    eng, srv = wire

    def _get(path):
        with urllib.request.urlopen(f"http://{srv.address}{path}",
                                    timeout=5.0) as resp:
            return resp.status, json.loads(resp.read().decode())

    assert _get("/healthz")[0] == 200
    code, body = _get("/readyz")
    assert code == 200 and body["ready"] is True
    client = _client(srv)
    client.drain()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get("/readyz")
    assert ei.value.code == 503
    code, body = _get("/healthz")                 # still alive, draining
    assert code == 200 and body["draining"] is True
    assert client.draining                        # piggybacked to the client


def test_drain_retry_returns_accumulated_flush_list(wire):
    """A drain whose response was lost must be retryable without the
    flushed requests falling through: the server returns the FULL
    accumulated flush list, not the call's delta."""
    eng, srv = wire
    client = _client(srv)
    # A budget no step loop can finish inside the test: the third
    # request must still be QUEUED when the drain flushes it.
    reqs = [Request(prompt=[60 + i], max_new_tokens=10_000_000)
            for i in range(3)]
    for r in reqs:
        client.submit(r)
    _wait(lambda: len(eng.queue) == 1, msg="third request queued")
    faults.activate(FaultPlan((Fault(site="transport_recv", action="drop",
                                     count=1),)))
    try:
        flushed = client.drain(flush=True)
    finally:
        faults.deactivate()
    # First drain's flush landed server-side, its response died; the
    # retried call's engine flush is empty — the ledger still reports it.
    assert [r.request_id for r in flushed] == [reqs[2].request_id]
    assert client.stats.transport_retries == 1
    assert eng.draining
    # The flushed request left the client's streams (gateway remigrates
    # it); the live two keep streaming to completion.
    assert len(client._streams) == 2


def test_heartbeat_discovery_builds_clients(tmp_path):
    eng = _WireEngine(replica_id="r0")
    srv = ReplicaServer(eng, registry=MetricsRegistry(),
                        heartbeat_dir=str(tmp_path), rank=0).start()
    try:
        clients = discover_replica_clients(str(tmp_path), backoff_s=0.001)
        assert [c.endpoint for c in clients] == [f"http://{srv.address}"]
        fins = []
        req = Request(prompt=[70], max_new_tokens=2)
        req.on_finish = fins.append
        clients[0].submit(req)
        _drive_client(clients[0])
        assert fins == ["length"]
    finally:
        srv.close()


def test_health_snapshot_piggybacks_and_scrapes():
    eng = _WireEngine(replica_id="r0")
    registry = MetricsRegistry()
    srv = ReplicaServer(eng, registry=registry, idle_wait_s=0.002)
    # The instantaneous slot/load gauges the client's scrape path reads
    # (the default registry wires these; the fake-engine fixture opts out
    # of the full collectors, so register just the gauges here).
    srv._register_engine_gauges(registry)
    srv.start()
    try:
        client = _client(srv, health_refresh_s=0.0)
        req = Request(prompt=[80], max_new_tokens=10_000_000)
        client.submit(req)
        _wait(lambda: eng.occupied_slots() == 1, msg="slot occupied")
        # /metrics scrape path (the same exposition the fleet plane
        # reads).
        assert client.num_slots == eng.num_slots
        assert client.occupied_slots() == 1
        # The poll piggyback path carries the KV counters.
        client.step()
        assert client.pool.counters()["pages_total"] == 16
        client.cancel(req.request_id, "aborted")
        _wait(lambda: not eng.busy(), msg="cancel to land")
    finally:
        srv.close()


# ---------------------------------------------- real-model integration


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.models import llama
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _ref_greedy(model, params, prompt, max_new):
    import jax.numpy as jnp

    from k8s_distributed_deeplearning_tpu.models import generate
    return np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :],
        max_new_tokens=max_new))[0]


def _remote_fleet(tiny, n=2):
    from k8s_distributed_deeplearning_tpu.serve import ServeEngine
    model, params, _ = tiny
    stats = ServingStats()
    engines = [ServeEngine(model, params, num_slots=2, eos_id=None,
                           replica_id=f"r{i}") for i in range(n)]
    # Default registry: the real serving/sched collectors + slot gauges,
    # so routing reads live load through the /metrics scrape path.
    servers = [ReplicaServer(e, handler_timeout=120.0).start()
               for e in engines]
    clients = [ReplicaClient(s.address, replica_id=f"r{i}", stats=stats,
                             timeout_s=120.0, backoff_s=0.05,
                             health_refresh_s=0.0)
               for i, s in enumerate(servers)]
    return engines, servers, clients, stats


def _drive_remote(gw, outs, deadline_s=300.0):
    t0 = time.time()
    while gw.busy():
        outs.extend(gw.step())
        if time.time() - t0 > deadline_s:
            raise AssertionError("remote gateway did not quiesce")
        time.sleep(0.005)


def _tracked_requests(cfg, n, seed, p_lo=4, p_hi=12, m_lo=6, m_hi=12):
    rng = np.random.default_rng(seed)
    reqs, streams, finishes = [], {}, {}
    for _ in range(n):
        p = rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(p_lo, p_hi))).astype(np.int32)
        r = Request(prompt=p, max_new_tokens=int(rng.integers(m_lo, m_hi)))
        r.on_token = (lambda t, rid=r.request_id:
                      streams.setdefault(rid, []).append(t))
        r.on_finish = (lambda reason, rid=r.request_id:
                       finishes.setdefault(rid, []).append(reason))
        reqs.append(r)
    return reqs, streams, finishes


_NETWORK_FAULTS = {
    "drop": Fault(site="transport_send", action="drop", count=3),
    "latency": Fault(site="transport_send", action="stall", seconds=0.25,
                     count=3),
    "partition": Fault(site="transport_send", action="partition",
                       seconds=0.5),
}


@pytest.mark.parametrize("fault", ["none", *_NETWORK_FAULTS])
def test_remote_gateway_bit_parity_and_wire_drain(tiny, fault):
    """The tentpole end-to-end: a gateway over two replica-server
    processes-worth of HTTP (in-process servers, real sockets) serves
    every stream bit-identically to the oracle with exactly-once
    on_finish — also with calls dropped, stalled or cut off for a window
    at 50 % fleet load: the client's retries and the server's dispatch
    ledger absorb all three, nothing is lost; then a wire drain empties r0
    and routing excludes it."""
    from k8s_distributed_deeplearning_tpu.serve import ServeGateway
    model, params, cfg = tiny
    engines, servers, clients, stats = _remote_fleet(tiny)
    plan = None
    if fault != "none":
        plan = FaultPlan((_NETWORK_FAULTS[fault],))
        for c in clients:           # the retries must outlast the window
            c.retries, c.backoff_s = 6, 0.15
    try:
        gw = ServeGateway(clients, stats=stats)
        reqs, streams, finishes = _tracked_requests(cfg, 4, seed=3)
        # drop and latency are armed before admission (a submit whose
        # answer is lost must not admit twice); the partition opens once
        # polling is under way, or admission would sit the window out
        try:
            if plan is not None and fault != "partition":
                inj = faults.activate(plan)
            for r in reqs:
                gw.submit(r)
            if fault == "partition":
                inj = faults.activate(plan)
            outs = []
            _drive_remote(gw, outs)
        finally:
            faults.deactivate()
        if plan is not None:
            assert inj.fired and stats.gateway_breaker_trips == 0
            assert fault == "latency" or stats.transport_retries >= 1
        assert {o.request_id for o in outs} == {r.request_id for r in reqs}
        for r in reqs:
            assert finishes[r.request_id] == ["length"]
            np.testing.assert_array_equal(
                np.asarray(streams[r.request_id]),
                _ref_greedy(model, params, r.prompt, r.max_new_tokens))
        # Wire drain: the handshake crosses the transport, the client's
        # cached health flips, routing excludes the replica.
        gw.drain_replica("r0")
        assert clients[0].draining
        _wait(lambda: servers[0].drained, deadline_s=30.0,
              msg="replica drain over the wire")
        extra, estreams, efin = _tracked_requests(cfg, 1, seed=9)
        gw.submit(extra[0])
        _drive_remote(gw, outs)
        assert engines[0].load() == 0             # r0 never touched again
        assert efin[extra[0].request_id] == ["length"]
        np.testing.assert_array_equal(
            np.asarray(estreams[extra[0].request_id]),
            _ref_greedy(model, params, extra[0].prompt,
                        extra[0].max_new_tokens))
    finally:
        for s in servers:
            s.close()


def test_remote_replica_kill_migrates_bit_identically(tiny):
    """Replica-process kill mid-decode: the server's socket goes away,
    the client's poll exhausts its retries and raises, the breaker
    trips, and the gateway resubmits from ITS cursor onto the survivor
    — the spliced streams match the oracle bit for bit."""
    from k8s_distributed_deeplearning_tpu.serve import ServeGateway
    model, params, cfg = tiny
    engines, servers, clients, stats = _remote_fleet(tiny)
    for c in clients:
        c.timeout_s = 10.0                        # dead-socket calls fail fast
        c.retries = 1
    try:
        gw = ServeGateway(clients, stats=stats, failures_to_trip=1)
        # Long streams: the replica's background step loop must not be
        # able to FINISH them before the kill lands.
        reqs, streams, finishes = _tracked_requests(cfg, 4, seed=5,
                                                    p_lo=4, p_hi=8,
                                                    m_lo=40, m_hi=50)
        # Until the kill every decode iteration stalls: a replica steps on
        # its own thread and, on a loaded machine, could otherwise finish
        # its streams between two polls of this loop.
        faults.activate(FaultPlan((Fault(site="serve_decode", action="stall",
                                         seconds=0.05, count=10_000),)))
        try:
            for r in reqs:
                gw.submit(r)
            assert clients[0].busy() and clients[1].busy()
            outs = []
            t0 = time.time()
            while True:
                outs.extend(gw.step())
                live0 = {st.req.request_id
                         for st in clients[0]._streams.values()}
                if live0 and any(streams.get(rid) for rid in live0):
                    break                         # r0 provably mid-stream
                assert clients[0]._streams, "r0 finished before the kill"
                assert time.time() - t0 < 300.0, "no tokens before kill"
                time.sleep(0.005)
            servers[0].close()                    # kill the replica process
        finally:
            faults.deactivate()
        _drive_remote(gw, outs)
        assert stats.gateway_breaker_trips >= 1
        assert stats.gateway_migrations >= 1
        assert {o.request_id for o in outs} == {r.request_id for r in reqs}
        for r in reqs:
            assert finishes[r.request_id] == ["length"]   # exactly once
            np.testing.assert_array_equal(
                np.asarray(streams[r.request_id]),
                _ref_greedy(model, params, r.prompt, r.max_new_tokens))
    finally:
        for s in servers[1:]:
            s.close()


# ------------------------------------------------------ subprocess e2e


def _wait_port_file(path, deadline):
    while time.time() < deadline:
        if os.path.exists(path):
            txt = open(path).read().strip()
            if txt:
                return int(txt)
        time.sleep(0.2)
    raise AssertionError(f"port file {path} never appeared")


@pytest.mark.slow
def test_cli_replica_server_gateway_sigterm_drains_and_exits_zero(tmp_path):
    """The k8s handshake end-to-end across REAL process boundaries: two
    replica-server processes (ephemeral ports via --port-file), a remote
    gateway feeding them, SIGTERM to the gateway mid-run (drain through
    the wire, exit 0), then SIGTERM to each replica server (drain, emit
    replica_drained, exit 0)."""
    replica_cmd = [sys.executable, "-m",
                   "k8s_distributed_deeplearning_tpu.launch", "serve",
                   "--replica-server", "--preset", "tiny",
                   "--max-seq-len", "64", "--slots", "2",
                   "--metrics-port", "0"]
    replicas = []
    try:
        for i in range(2):
            pf = str(tmp_path / f"port-{i}")
            replicas.append((pf, subprocess.Popen(
                replica_cmd + ["--port-file", pf, "--replica-rank", str(i)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)))
        deadline = time.time() + 420
        ports = [_wait_port_file(pf, deadline) for pf, _ in replicas]
        endpoints = ",".join(f"127.0.0.1:{p}" for p in ports)
        # -u: the gateway's completion lines must reach us unbuffered so
        # SIGTERM lands while most of the (256-request, long-output)
        # workload is still unsubmitted — that's the tail the drain
        # sheds and the < 256 assert measures.
        gw = subprocess.Popen(
            [sys.executable, "-u", "-m",
             "k8s_distributed_deeplearning_tpu.launch", "serve",
             "--replica-endpoints", endpoints, "--requests", "256",
             "--max-queue", "4", "--prompt-len", "4", "12",
             "--out-len", "24", "40"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            lines, saw = [], False
            while time.time() < deadline:
                line = gw.stdout.readline()
                if not line:
                    break
                lines.append(line)
                if '"serve_request"' in line:
                    saw = True
                    break
            assert saw, "".join(lines)[-2000:]
            gw.send_signal(signal.SIGTERM)
            rest, gerr = gw.communicate(timeout=300)
        except Exception:
            gw.kill()
            raise
        assert gw.returncode == 0, gerr[-2000:]
        gout = "".join(lines) + rest
        assert '"serve_summary"' in gout
        assert gout.count('"serve_request"') < 256  # drain shed the tail
        for _, proc in replicas:
            proc.send_signal(signal.SIGTERM)
        for _, proc in replicas:
            out, err = proc.communicate(timeout=300)
            assert proc.returncode == 0, err[-2000:]
            assert '"replica_drained"' in out
            assert '"serve_summary"' in out
    finally:
        for _, proc in replicas:
            if proc.poll() is None:
                proc.kill()
