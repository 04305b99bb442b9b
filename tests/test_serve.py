"""Continuous-batching serving engine: per-request parity with one-shot
generate(), slot reuse, in-flight admission, compile-once discipline,
back-pressure, streaming, and shutdown semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.serve import (QueueFull, Request,
                                                    RequestOutput,
                                                    SamplingParams,
                                                    ServeEngine)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _workload(cfg, n, seed=0, p_lo=4, p_hi=17, m_lo=3, m_hi=16):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(p_lo, p_hi))).astype(
                                np.int32) for _ in range(n)]
    max_news = [int(rng.integers(m_lo, m_hi)) for _ in range(n)]
    return prompts, max_news


def _ref_greedy(model, params, prompt, max_new, eos_id=None):
    """Isolated one-shot generate() for one prompt, trimmed after EOS."""
    row = np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :], max_new_tokens=max_new,
        eos_id=eos_id))[0]
    if eos_id is not None:
        hits = np.flatnonzero(row == eos_id)
        if hits.size:
            row = row[:hits[0] + 1]   # generate() pads after emitting EOS
    return row


def test_greedy_parity_with_slot_reuse_and_midstream_admission(tiny):
    """More requests than slots, mixed lengths: every slot is reused and
    most admissions happen while other slots are mid-decode — each
    request's greedy tokens must be IDENTICAL to an isolated generate()
    (the per-request correctness acceptance criterion)."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 10)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    eng = ServeEngine(model, params, num_slots=3, eos_id=None)
    outs = {o.request_id: o for o in eng.run(reqs)}
    assert len(outs) == len(reqs)
    for r, p, m in zip(reqs, prompts, max_news):
        out = outs[r.request_id]
        assert out.finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(out.tokens), _ref_greedy(model, params, p, m))


def test_slot_reuse_after_eos(tiny):
    """EOS frees a slot mid-stream; the next queued request admitted into
    that slot must decode exactly as an isolated run (stale KV from the
    previous occupant is never attended). EOS id is chosen from an actual
    greedy rollout so terminations really happen."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 6, seed=1, m_lo=6, m_hi=12)
    # Pick the token the first request emits mid-rollout as the global EOS:
    # at least that request terminates early; others may too.
    probe = _ref_greedy(model, params, prompts[0], max_news[0])
    eos_id = int(probe[2])
    eng = ServeEngine(model, params, num_slots=2, eos_id=eos_id)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    outs = {o.request_id: o for o in eng.run(reqs)}
    assert len(outs) == len(reqs)
    n_eos = 0
    for r, p, m in zip(reqs, prompts, max_news):
        ref = _ref_greedy(model, params, p, m, eos_id=eos_id)
        out = outs[r.request_id]
        np.testing.assert_array_equal(np.asarray(out.tokens), ref)
        if out.finish_reason == "eos":
            n_eos += 1
            assert out.tokens[-1] == eos_id
    assert n_eos >= 1   # the probe request terminates by construction


def test_decode_compiles_once_across_admissions(tiny):
    """The compile-once acceptance criterion: a whole workload — slot
    reuse, EOS completions, in-flight admissions — adds exactly ONE
    compiled decode program, and a second engine/workload with the same
    shape adds zero. num_slots is unique to this test so prior tests'
    cached programs can't mask a recompile."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 9, seed=2)
    eng = ServeEngine(model, params, num_slots=5, eos_id=None)
    d0 = eng.decode_cache_size()
    p0 = ServeEngine.prefill_cache_size()
    eng.run([Request(prompt=p, max_new_tokens=m)
             for p, m in zip(prompts, max_news)])
    assert eng.decode_cache_size() - d0 == 1
    # Prefill compiles at most once per power-of-two bucket (32, 64 here).
    assert ServeEngine.prefill_cache_size() - p0 <= 2
    eng2 = ServeEngine(model, params, num_slots=5, eos_id=None)
    prompts2, max_news2 = _workload(cfg, 7, seed=3)
    eng2.run([Request(prompt=p, max_new_tokens=m)
              for p, m in zip(prompts2, max_news2)])
    assert eng2.decode_cache_size() - d0 == 1   # still the same program


def test_queue_backpressure(tiny):
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 3)
    eng = ServeEngine(model, params, num_slots=2, max_queue=2)
    eng.submit(Request(prompt=prompts[0], max_new_tokens=max_news[0]))
    eng.submit(Request(prompt=prompts[1], max_new_tokens=max_news[1]))
    with pytest.raises(QueueFull):
        eng.submit(Request(prompt=prompts[2], max_new_tokens=max_news[2]))
    # Draining the queue restores capacity.
    eng.run()
    eng.submit(Request(prompt=prompts[2], max_new_tokens=max_news[2]))
    assert len(eng.run()) == 1


def test_streaming_callback_ordering(tiny):
    """on_token fires once per emitted token, in emission order, and the
    streamed sequence equals the final output — including the first
    (prefill-sampled) token."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 5, seed=4)
    streams = {}
    reqs = []
    for p, m in zip(prompts, max_news):
        r = Request(prompt=p, max_new_tokens=m)
        streams[r.request_id] = []
        r.on_token = streams[r.request_id].append
        reqs.append(r)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r in reqs:
        assert streams[r.request_id] == outs[r.request_id].tokens


def test_shutdown_with_requests_in_flight(tiny):
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 5, seed=5, m_lo=8, m_hi=16)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    reqs = [Request(prompt=p, max_new_tokens=m)
            for p, m in zip(prompts, max_news)]
    for r in reqs:
        eng.submit(r)
    done = eng.step() + eng.step()   # 2 slots decoding, 3 queued
    aborted = eng.shutdown()
    assert all(o.finish_reason == "aborted" for o in aborted)
    assert len(done) + len(aborted) == len(reqs)
    in_flight = [o for o in aborted if o.tokens]
    queued = [o for o in aborted if not o.tokens]
    assert len(in_flight) == 2 and len(queued) == 3
    assert all(o.ttft_s is None for o in queued)
    # Engine is reusable after shutdown.
    out = eng.run([Request(prompt=prompts[0], max_new_tokens=3)])
    assert len(out) == 1 and out[0].finish_reason == "length"
    np.testing.assert_array_equal(
        np.asarray(out[0].tokens), _ref_greedy(model, params, prompts[0], 3))


def test_topk1_sampling_matches_greedy(tiny):
    """top_k=1 with temperature > 0 collapses the categorical to the
    argmax — the sampled slot path agrees with greedy token-for-token."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 4, seed=6)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    sp = SamplingParams(temperature=0.7, top_k=1)
    reqs = [Request(prompt=p, max_new_tokens=m, sampling=sp, seed=i)
            for i, (p, m) in enumerate(zip(prompts, max_news))]
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r, p, m in zip(reqs, prompts, max_news):
        np.testing.assert_array_equal(
            np.asarray(outs[r.request_id].tokens),
            _ref_greedy(model, params, p, m))


def test_sampled_output_is_seed_deterministic_and_placement_free(tiny):
    """A sampled request's tokens depend on its seed, not on which slot it
    lands in or what else is running: each slot carries its own PRNG key
    chain. Run the same request alone and inside a busy engine."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 6, seed=7)
    sp = SamplingParams(temperature=0.9, top_k=12, top_p=0.9)
    target = Request(prompt=prompts[0], max_new_tokens=10, sampling=sp,
                     seed=123)
    alone = ServeEngine(model, params, num_slots=2, eos_id=None)
    ref = alone.run([target])[0].tokens

    busy = ServeEngine(model, params, num_slots=2, eos_id=None)
    again = Request(prompt=prompts[0], max_new_tokens=10, sampling=sp,
                    seed=123)
    others = [Request(prompt=p, max_new_tokens=m, sampling=sp, seed=50 + i)
              for i, (p, m) in enumerate(zip(prompts[1:], max_news[1:]))]
    outs = {o.request_id: o for o in busy.run(others[:2] + [again]
                                              + others[2:])}
    assert outs[again.request_id].tokens == ref
    assert all(0 <= t < cfg.vocab_size
               for o in outs.values() for t in o.tokens)


@pytest.mark.parametrize("seed", [0, 1, 2**31 - 1, 2**31, 2**32 - 1,
                                  2**32 + 5, -1])
@pytest.mark.parametrize("x64", [False, True])
def test_first_key_is_the_prng_key_of_the_seed(seed, x64):
    """The key a final chunk takes is made on the host, bit for bit what
    ``jax.random.PRNGKey`` gives in this process — with ``jax_enable_x64``
    too, where the seed's high word is kept."""
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        got = ServeEngine._first_key(seed)
    assert got.dtype == np.uint32 and got.shape == (2,)
    np.testing.assert_array_equal(got, want)


def test_first_key_refuses_what_prng_key_refuses(tiny):
    """A seed beyond int64 overflows as it does in ``PRNGKey``; an engine is
    not built where the default PRNG is not the ``threefry2x32`` its
    programs' ``uint32[2]`` keys are."""
    model, params, _ = tiny
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(2**63)
    with pytest.raises(OverflowError):
        ServeEngine._first_key(2**63)
    with jax.default_prng_impl("rbg"):
        with pytest.raises(ValueError, match="threefry2x32"):
            ServeEngine(model, params, num_slots=2)


def _ref_sampled(model, params, prompt, max_new, sp, seed):
    """generate()'s two halves — ``prefill``, then ``decode_step`` a token
    at a time — with the engine's sampler between them and its key chain
    begun at ``jax.random.PRNGKey(seed)``: one request served alone."""
    from k8s_distributed_deeplearning_tpu.serve.engine import _sample_slots
    regs = (jnp.float32([sp.temperature]), jnp.int32([sp.top_k]),
            jnp.float32([sp.top_p]))
    keys = jax.random.PRNGKey(seed)[None]
    logits, cache = generate.prefill(model, params,
                                     jnp.asarray(prompt)[None, :])
    last, toks = logits[:, -1, :], []
    for _ in range(max_new):
        keys, tok = _sample_slots(last, *regs, keys)
        toks.append(int(tok[0]))
        last, cache = generate.decode_step(model, params, cache, tok)
    return toks


@pytest.mark.parametrize("seed", [123, 2**32 + 5, -1])
def test_sampled_stream_is_the_one_begun_at_the_seeds_prng_key(tiny, seed):
    """Temperature > 0 and a fixed seed: the served stream is token for
    token the sampler's key chain begun at ``jax.random.PRNGKey(seed)``
    over generate()'s logits — through a one-call admission and through
    chunks behind another request's decodes alike."""
    model, params, cfg = tiny
    prompts, _ = _workload(cfg, 2, seed=11, p_lo=18, p_hi=24)
    sp = SamplingParams(temperature=0.9, top_k=12, top_p=0.9)
    want = _ref_sampled(model, params, prompts[0], 10, sp, seed)
    assert want != _ref_sampled(model, params, prompts[0], 10, sp, seed + 1)
    for kw in ({}, {"min_bucket": 8, "prefill_chunk_tokens": 8}):
        eng = ServeEngine(model, params, num_slots=2, eos_id=None, **kw)
        other = Request(prompt=prompts[1], max_new_tokens=16)
        target = Request(prompt=prompts[0], max_new_tokens=10, sampling=sp,
                         seed=seed)
        outs = {o.request_id: o for o in eng.run([other, target])}
        assert outs[target.request_id].tokens == want, kw


def test_submit_validation_and_sampling_params(tiny):
    model, params, cfg = tiny
    eng = ServeEngine(model, params, num_slots=2)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(Request(prompt=np.zeros(40, np.int32),
                           max_new_tokens=cfg.max_seq_len))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(prompt=np.zeros(0, np.int32), max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=0))
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(temperature=0.5, top_p=0.0)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=0.0, top_k=5)
    with pytest.raises(ValueError, match="num_slots"):
        ServeEngine(model, params, num_slots=1)


def test_max_new_tokens_one_finishes_at_admission(tiny):
    """A 1-token budget completes during admission (the prefill-sampled
    token IS the output) and the slot immediately serves the next
    request."""
    model, params, cfg = tiny
    prompts, _ = _workload(cfg, 4, seed=8)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    reqs = [Request(prompt=p, max_new_tokens=1) for p in prompts]
    outs = {o.request_id: o for o in eng.run(reqs)}
    assert len(outs) == 4
    for r, p in zip(reqs, prompts):
        assert outs[r.request_id].finish_reason == "length"
        np.testing.assert_array_equal(
            np.asarray(outs[r.request_id].tokens),
            _ref_greedy(model, params, p, 1))


def test_serving_stats_accounting(tiny):
    """ServingStats totals reconcile with the outputs: every emitted token
    is counted once, occupancy is in (0, 1], and completion reasons sum."""
    model, params, cfg = tiny
    prompts, max_news = _workload(cfg, 6, seed=9)
    eng = ServeEngine(model, params, num_slots=3, eos_id=None)
    outs = eng.run([Request(prompt=p, max_new_tokens=m)
                    for p, m in zip(prompts, max_news)])
    s = eng.stats.summary()
    assert s["requests_admitted"] == s["requests_completed"] == 6
    assert s["total_tokens"] == sum(len(o.tokens) for o in outs)
    assert s["finish_reasons"] == {"length": 6}
    assert 0.0 < s["mean_slot_occupancy"] <= 1.0
    assert s["ttft_p50_ms"] is not None and s["latency_p95_ms"] is not None


def test_deadline_expired_mid_flight_cancels_at_decode_boundary(tiny):
    """A request whose deadline passes mid-decode is cancelled at the next
    step() boundary: finish_reason "timeout", partial tokens delivered, the
    on_finish callback told, and the freed slot immediately reusable."""
    import time

    model, params, cfg = tiny
    prompts, _ = _workload(cfg, 3, seed=11)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    eng.run([Request(prompt=prompts[0], max_new_tokens=2)])   # warm compile

    reasons = []
    victim = Request(prompt=prompts[1], max_new_tokens=40, deadline_s=0.2,
                     on_finish=reasons.append)
    eng.submit(victim)
    outs = eng.step()                      # admission + first decode
    assert outs == []
    time.sleep(0.25)                       # let the deadline lapse
    outs = eng.step()
    timed = next(o for o in outs if o.request_id == victim.request_id)
    assert timed.finish_reason == "timeout"
    assert 1 <= len(timed.tokens) < 40     # partial stream, not a full run
    assert reasons == ["timeout"]
    assert eng.stats.summary()["finish_reasons"]["timeout"] == 1
    # the slot is clean: the next request through it has exact parity
    after = Request(prompt=prompts[2], max_new_tokens=6)
    outs = {o.request_id: o for o in eng.run([after])}
    np.testing.assert_array_equal(
        np.asarray(outs[after.request_id].tokens),
        _ref_greedy(model, params, prompts[2], 6))


def test_deadline_expired_in_queue_never_prefills(tiny):
    """A request already past its deadline when popped completes as
    "timeout" with zero tokens and no ttft — no prefill is spent on it —
    and requests behind it in the queue are unaffected."""
    model, params, cfg = tiny
    prompts, _ = _workload(cfg, 2, seed=12)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None)
    reasons = []
    hung = Request(prompt=prompts[0], max_new_tokens=30, deadline_s=1e-9,
                   on_finish=reasons.append)
    live = Request(prompt=prompts[1], max_new_tokens=5)
    outs = {o.request_id: o for o in eng.run([hung, live])}
    timed = outs[hung.request_id]
    assert timed.finish_reason == "timeout"
    assert timed.tokens == [] and timed.ttft_s is None
    assert reasons == ["timeout"]
    # the hung client never stalled the other slot
    np.testing.assert_array_equal(
        np.asarray(outs[live.request_id].tokens),
        _ref_greedy(model, params, prompts[1], 5))


# ------------------------------------------------ the spans of one step()

def _step_spans(tiny, **engine_kw):
    """A record-only tracer (no logger, a ring) around one engine: returns
    (engine, take) where take() hands back the spans closed since the last
    call, oldest first."""
    from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
    model, params, _ = tiny
    tr = Tracer(ring_size=4096)
    eng = ServeEngine(model, params, num_slots=2, eos_id=None, tracer=tr,
                      **engine_kw)
    seen = [0]

    def take():
        spans = tr.recent_spans()[seen[0]:]
        seen[0] += len(spans)
        return spans
    return eng, take


def _named(spans, name, **fields):
    return [s for s in spans if s["name"] == name
            and all(s.get(k) == v for k, v in fields.items())]


def _assert_nested(spans):
    """Every span with a parent lies inside a span of that name one level
    up (same thread; the ring is in closing order, so the parent closes
    later)."""
    for i, s in enumerate(spans):
        if s["parent"] is None:
            assert s["depth"] == 0
            continue
        parents = [p for p in spans[i + 1:]
                   if p["name"] == s["parent"] and p["depth"] == s["depth"] - 1
                   and p["t0"] <= s["t0"] and s["t1"] <= p["t1"]]
        assert parents, (s, [p["name"] for p in spans])


def test_one_step_names_every_phase_once_and_nests(tiny):
    """The first request on an idle engine: its ``step()`` admits and
    prefills (a final chunk) and, with no slot occupied, takes the first
    token at once (``device_wait`` kind=first_token, not covered, directly
    under ``engine_step``); nothing decodes yet. The next ``step()`` decodes
    only: exactly one ``engine_step`` (the only depth-0 span), ``sweep``,
    ``grow``, ``decode``, ``emit`` and ``epilogue``; the fence is a
    ``device_wait`` child of ``decode`` (kind=decode); ``emit`` follows the
    fence."""
    _, _, cfg = tiny
    eng, take = _step_spans(tiny)
    req = Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
                  max_new_tokens=4)
    eng.submit(req)
    eng.step()
    spans = take()
    _assert_nested(spans)
    for name in ("engine_step", "sweep", "admission", "prefill", "epilogue"):
        assert len(_named(spans, name)) == 1, (name, spans)
    for name in ("grow", "decode", "emit"):
        assert not _named(spans, name), (name, spans)
    step = _named(spans, "engine_step")[0]
    assert step["depth"] == 0 and step["step"] == 0
    assert [s["name"] for s in spans if s["depth"] == 0] == ["engine_step"]
    assert _named(spans, "admission")[0]["request_id"] == req.request_id
    assert _named(spans, "prefill")[0]["request_id"] == req.request_id
    waits = _named(spans, "device_wait")
    assert [(w["kind"], w["parent"], w["covered"]) for w in waits] == [
        ("first_token", "engine_step", 0)]
    assert waits[0]["t0"] >= _named(spans, "prefill")[0]["t1"]
    ep = _named(spans, "epilogue")[0]
    assert ep["parent"] == "engine_step"
    assert (ep["active"], ep["queued"], ep["prefill_tokens"]) == (1, 0, 5)
    assert 0 < ep["pages_used"] <= ep["pages_total"]
    # the next step decodes only: single spans, no admission/prefill
    eng.step()
    spans = take()
    _assert_nested(spans)
    for name in ("engine_step", "sweep", "grow", "decode", "emit",
                 "epilogue"):
        assert len(_named(spans, name)) == 1, (name, spans)
    assert _named(spans, "engine_step")[0]["step"] == 0   # no decode yet ran
    assert not _named(spans, "prefill") and not _named(spans, "admission")
    waits = _named(spans, "device_wait")
    assert [(w["kind"], w["parent"], w["covered"]) for w in waits] == [
        ("decode", "decode", 0)]
    decode, emit = _named(spans, "decode")[0], _named(spans, "emit")[0]
    assert decode["active"] == 1 and decode["parent"] == "engine_step"
    assert emit["t0"] >= waits[0]["t1"] and emit["t0"] >= decode["t1"]
    ep = _named(spans, "epilogue")[0]
    assert ep["t0"] >= emit["t1"] and ep["parent"] == "engine_step"
    eng.step()
    assert _named(take(), "engine_step")[0]["step"] == 1


@pytest.mark.parametrize("path", ["idle", "prefill_only", "spec_k"])
def test_every_return_path_closes_engine_step_and_epilogue(tiny, path):
    """The idle (no active slot), prefill-only (disaggregated role: the
    first token is read at once, not covered) and speculative return paths
    each close one ``engine_step`` around one ``epilogue``; the speculative
    fence is ``device_wait(kind=spec)`` inside ``decode``, with ``emit``
    after it."""
    model, params, cfg = tiny
    kw = {}
    if path == "prefill_only":
        kw = {"prefill_only": True}
    elif path == "spec_k":
        dmodel = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32,
                                                 max_seq_len=64))
        dparams = dmodel.init(jax.random.key(7),
                              jnp.zeros((1, 8), jnp.int32))["params"]
        kw = {"draft_model": dmodel, "draft_params": dparams, "spec_k": 2}
    eng, take = _step_spans(tiny, **kw)
    if path != "idle":
        eng.submit(Request(prompt=np.arange(6, dtype=np.int32),
                           max_new_tokens=6))
    eng.step()
    if path == "spec_k":
        take()          # the idle engine's first step admits; the next decodes
        eng.step()
    spans = take()
    _assert_nested(spans)
    assert len(_named(spans, "engine_step")) == 1
    assert len(_named(spans, "epilogue")) == 1
    assert len(_named(spans, "sweep")) == 1
    assert [s["name"] for s in spans if s["depth"] == 0] == ["engine_step"]
    if path == "idle":
        assert {s["name"] for s in spans} == {"engine_step", "sweep",
                                              "epilogue"}
    elif path == "prefill_only":
        assert not _named(spans, "decode") and not _named(spans, "emit")
        assert [(w["kind"], w["covered"])
                for w in _named(spans, "device_wait")] == [("first_token", 0)]
    else:
        decode = _named(spans, "decode")[0]
        assert decode["spec_k"] == 2
        spec = _named(spans, "device_wait", kind="spec")
        assert len(spec) == 1 and spec[0]["parent"] == "decode"
        assert _named(spans, "emit")[0]["t0"] >= decode["t1"]


def test_disabled_tracer_costs_the_engine_the_shared_null_span(tiny):
    """No tracer: every phase — and every call, operand set, key and fetch
    inside ``decode``, ``prefill`` and ``device_wait`` (PR 35) — gets the one
    shared no-op span object, and a run opens nothing else."""
    from k8s_distributed_deeplearning_tpu.telemetry import trace as trace_lib
    model, params, cfg = tiny
    eng = ServeEngine(model, params, num_slots=2, eos_id=None, min_bucket=8,
                      prefill_chunk_tokens=8, prefix_cache_mb=1)
    assert eng.tracer.span("engine_step", step=0) is trace_lib._NULL_SPAN
    assert eng.tracer.span("emit") is trace_lib._NULL_SPAN
    opened = []
    span = eng.tracer.span
    eng.tracer.span = lambda name, **f: opened.append(name) or span(name, **f)
    try:
        eng.run([Request(prompt=np.arange(n, dtype=np.int32) % cfg.vocab_size,
                         max_new_tokens=3) for n in (20, 5)])
    finally:
        del eng.tracer.span         # the shared null tracer: leave it as it was
    assert set(opened) >= set(_INNER_SPANS) - {"fetch_counts"}
    assert all(span(name) is trace_lib._NULL_SPAN for name in set(opened))


# ------- the calls, operands, key and fetches inside the three boxes (PR 35)

_INNER_SPANS = ("decode_call", "chunk_operands", "first_key", "chunk_call",
                "trie_adopt", "fetch_tokens", "fetch_counts", "fetch_keys")


def _children(spans, parent):
    """The spans that lie inside the span *parent* and name it as their
    parent, in opening order."""
    return [s for s in sorted(spans, key=lambda s: s["t0"])
            if s["parent"] == parent["name"] and s["depth"] == parent["depth"] + 1
            and parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"]]


def _chunky(tiny, b_kw=None, **kw):
    """A two-slot engine with 8-token chunks and buckets, request A decoding
    and a 20-token request B (further fields: *b_kw*) submitted: B takes two
    intermediate chunks, a final one of 4 tokens, then its first token — one
    a step, each behind A's decode. Returns (engine, take)."""
    _, _, cfg = tiny
    eng, take = _step_spans(tiny, min_bucket=8, prefill_chunk_tokens=8, **kw)
    eng.submit(Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
                       max_new_tokens=24, request_id="A"))
    eng.step()
    eng.step()                          # a decode-only step: its fence returns
    eng.submit(Request(prompt=(np.arange(20, dtype=np.int32) * 3)
                       % cfg.vocab_size, max_new_tokens=6, request_id="B",
                       **(b_kw or {})))
    take()
    return eng, take


def test_a_decode_only_step_has_one_call_and_two_fetches(tiny):
    """``decode_call`` is the first child of ``decode`` (``in_flight`` 0: the
    step before was decode-only and its fence has returned); the fence holds
    ``fetch_tokens`` then ``fetch_keys`` and nothing else; no chunk span."""
    _, _, cfg = tiny
    eng, take = _step_spans(tiny)
    eng.submit(Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
                       max_new_tokens=6))
    eng.step()
    eng.step()
    take()
    eng.step()
    spans = take()
    _assert_nested(spans)
    decode = _named(spans, "decode")[0]
    assert [(s["name"], s.get("in_flight")) for s in _children(spans, decode)] == [
        ("decode_call", 0), ("device_wait", None)]
    wait = _named(spans, "device_wait")[0]
    assert [s["name"] for s in _children(spans, wait)] == ["fetch_tokens",
                                                          "fetch_keys"]
    assert {s["name"] for s in spans} & set(_INNER_SPANS) == {
        "decode_call", "fetch_tokens", "fetch_keys"}


def test_a_chunk_step_splits_prefill_into_operands_and_call(tiny):
    """An intermediate chunk dispatched behind a decode: ``decode_call``
    comes before ``admission``; inside ``prefill`` ``chunk_operands`` then
    ``chunk_call`` (``program`` = the ``attention_impls`` key, ``in_flight``
    1: the decode) and no ``first_key``; the step after it dispatches its
    decode with the chunk ahead of it (``in_flight`` 1) and its own chunk
    behind both (2)."""
    eng, take = _chunky(tiny)
    eng.step()
    spans = take()
    _assert_nested(spans)
    decode = _named(spans, "decode")[0]
    assert [s["name"] for s in _children(spans, decode)] == [
        "decode_call", "admission", "prefill", "device_wait"]
    assert _named(spans, "decode_call")[0]["in_flight"] == 0
    prefill = _named(spans, "prefill")[0]
    assert prefill["chunk"] == 8 and prefill["start"] == 0
    inner = _children(spans, prefill)
    assert [s["name"] for s in inner] == ["chunk_operands", "chunk_call"]
    assert (inner[1]["program"], inner[1]["in_flight"]) == ("chunk_8", 1)
    assert "draft" not in inner[1] and inner[1]["program"] in eng.attention_impls()
    assert not _named(spans, "first_key") and not _named(spans, "trie_adopt")
    eng.step()                                          # B's second chunk
    spans = take()
    assert _named(spans, "decode_call")[0]["in_flight"] == 1
    # no wait has been made for the first chunk: an upper bound counts it
    assert _named(spans, "chunk_call")[0]["in_flight"] == 2
    assert _named(spans, "prefill")[0]["start"] == 8


def test_a_final_chunk_step_makes_its_key_between_operands_and_call(tiny):
    """The final chunk: ``chunk_operands`` → ``first_key`` → ``chunk_call``
    (``final_chunk_8``) inside ``prefill``, key and call both with the
    decode and the chunk before it in flight; the next step takes the first
    token right behind its ``decode_call`` — ``fetch_tokens`` →
    ``fetch_keys`` inside the ``first_token`` wait — and then its own tokens
    the same way."""
    eng, take = _chunky(tiny)
    eng.step()
    eng.step()
    take()
    eng.step()
    spans = take()
    _assert_nested(spans)
    prefill = _named(spans, "prefill")[0]
    assert (prefill["bucket"], prefill["tokens"], prefill["start"]) == (8, 4, 16)
    inner = _children(spans, prefill)
    assert [(s["name"], s["in_flight"] if "in_flight" in s else None)
            for s in inner] == [("chunk_operands", None), ("first_key", 2),
                                ("chunk_call", 2)]
    assert inner[2]["program"] == "final_chunk_8"
    eng.step()
    spans = take()
    _assert_nested(spans)
    decode = _named(spans, "decode")[0]
    kids = _children(spans, decode)
    assert [(s["name"], s.get("kind")) for s in kids] == [
        ("decode_call", None), ("device_wait", "first_token"),
        ("device_wait", "decode")]
    assert kids[0]["in_flight"] == 1                    # the final chunk
    for wait in kids[1:]:
        assert [s["name"] for s in _children(spans, wait)] == [
            "fetch_tokens", "fetch_keys"]
    assert eng.occupied_slots() == 2


def test_a_final_chunk_behind_a_decode_asks_the_device_for_no_key(
        tiny, monkeypatch):
    """A sampling request's final chunk, dispatched behind a running decode:
    the step calls neither ``jax.random.PRNGKey`` nor ``jax.random.key`` (a
    device program each, and a blocking read ahead of the chunk's call);
    ``first_key`` still lies between ``chunk_operands`` and ``chunk_call``,
    and the slot's chained key is what the seed's ``PRNGKey`` splits into."""
    eng, take = _chunky(tiny, b_kw=dict(
        sampling=SamplingParams(temperature=0.8, top_k=12), seed=2**32 + 5))
    split = np.asarray(jax.random.split(jax.random.PRNGKey(2**32 + 5))[0])

    def refuse(*a, **kw):
        raise AssertionError("a key was asked of the device inside step()")
    monkeypatch.setattr(jax.random, "PRNGKey", refuse)
    monkeypatch.setattr(jax.random, "key", refuse)
    eng.step()
    eng.step()
    take()
    eng.step()                                          # B's final chunk
    spans = take()
    _assert_nested(spans)
    inner = _children(spans, _named(spans, "prefill")[0])
    assert [s["name"] for s in inner] == ["chunk_operands", "first_key",
                                          "chunk_call"]
    assert inner[2]["program"] == "final_chunk_8"
    assert _named(spans, "decode_call")[0]["t0"] < inner[1]["t0"]
    eng.step()                                          # B's first token
    slot = next(i for i, fl in enumerate(eng._slots)
                if fl is not None and fl.req.request_id == "B")
    # the step's decode was dispatched before the activation: one split
    assert eng._temps[slot] > 0 and eng.occupied_slots() == 2
    np.testing.assert_array_equal(eng._keys[slot], split)


@pytest.mark.parametrize("trie", [False, True])
def test_trie_adopt_is_opened_only_with_a_trie(tiny, trie):
    """With a prefix trie a final chunk's ``prefill`` ends in ``trie_adopt``
    (the ``insert`` / ``release`` block); without one the span is never
    opened. The idle engine's first step takes the token at once: ``prefill``
    and the ``first_token`` wait with its two fetches, no ``decode_call``."""
    _, _, cfg = tiny
    eng, take = _step_spans(tiny, **({"prefix_cache_mb": 1,
                                      "prefix_block_tokens": 4} if trie else {}))
    eng.submit(Request(prompt=np.arange(9, dtype=np.int32) % cfg.vocab_size,
                       max_new_tokens=3))
    eng.step()
    spans = take()
    _assert_nested(spans)
    inner = [(s["name"], s.get("in_flight"))
             for s in _children(spans, _named(spans, "prefill")[0])]
    want = [("chunk_operands", None), ("first_key", 0), ("chunk_call", 0)]
    assert inner == want + ([("trie_adopt", None)] if trie else [])
    assert not _named(spans, "decode_call")
    assert [s["name"] for s in _children(spans, _named(spans, "device_wait")[0])
            ] == ["fetch_tokens", "fetch_keys"]
    eng.run()
    assert len(_named(take(), "trie_adopt")) == 0      # no other final chunk


def test_a_spec_engines_calls_and_fetches(tiny):
    """A speculative step: ONE ``decode_call`` around the draft and verify
    dispatches, ``fetch_tokens`` (window, selections, accepts) then
    ``fetch_keys`` inside the ``spec`` wait; every chunk is mirrored into the
    draft's arena by a second ``chunk_call`` with ``draft=1`` (a final
    chunk's mirror is the plain chunk program at the bucket's width)."""
    model, params, cfg = tiny
    eng, take = _chunky(tiny, draft_model=model, draft_params=params, spec_k=2)
    eng.step()
    spans = take()
    _assert_nested(spans)
    decode = _named(spans, "decode")[0]
    assert decode["spec_k"] == 2
    assert [s["name"] for s in _children(spans, decode)] == [
        "decode_call", "admission", "prefill", "device_wait"]
    calls = _children(spans, _named(spans, "prefill")[0])
    assert [(s["name"], s.get("program"), s.get("draft"), s.get("in_flight"))
            for s in calls] == [("chunk_operands", None, None, None),
                                ("chunk_call", "chunk_8", None, 2),
                                ("chunk_call", "chunk_8", 1, 3)]
    wait = _named(spans, "device_wait", kind="spec")[0]
    assert [s["name"] for s in _children(spans, wait)] == ["fetch_tokens",
                                                          "fetch_keys"]
    eng.step()
    take()
    eng.step()                                          # B's final chunk
    calls = [(s["program"], s.get("draft")) for s in _named(take(), "chunk_call")]
    assert calls == [("final_chunk_8", None), ("chunk_8", 1)]


def test_fetch_counts_is_one_copy_a_chunk_and_the_record_stays_outside_it():
    """A model with expert layers: an intermediate chunk's counts are read at
    a later step's fence, between ``fetch_tokens`` and ``fetch_keys``, in a
    ``fetch_counts`` of their own; the ``prefill_counts`` record that follows
    is a child of ``device_wait``, not of the fetch."""
    from k8s_distributed_deeplearning_tpu.models import moe
    from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
    cfg, latent, mo = moe.config_tiny_latent_moe()
    model = moe.LatentMoELM(cfg, latent, mo)
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    tr = Tracer(ring_size=4096)
    eng = ServeEngine(model, params, num_slots=2, min_bucket=8,
                      prefill_chunk_tokens=8, tracer=tr)
    eng.submit(Request(prompt=list(range(5)), max_new_tokens=12))
    eng.step()
    eng.submit(Request(prompt=list(range(30, 50)), max_new_tokens=4))
    eng.step()                  # decode + chunk 0 behind it
    seen = len(tr.recent_spans())
    eng.step()                  # decode + chunk 1; chunk 0's counts are due
    spans = tr.recent_spans()[seen:]
    _assert_nested(spans)
    wait = _named(spans, "device_wait", kind="decode")[0]
    assert [s["name"] for s in _children(spans, wait)] == [
        "fetch_tokens", "fetch_counts", "prefill_counts", "fetch_keys"]
    record = _named(spans, "prefill_counts")[0]
    assert record["parent"] == "device_wait" and record["start"] == 0
    assert record["t0"] >= _named(spans, "fetch_counts")[0]["t1"]
    eng.run()
    assert not eng._chunk_counts


# --------------------- no wait with an empty device queue behind it (PR 28)

def _busy_then_one_more(tiny, *, first_budget=24, **engine_kw):
    """An engine with request A decoding in a slot and request B just
    submitted: the next ``step()`` dispatches A's decode, then B's (final)
    prefill chunk behind it. Returns (engine, take, A, B)."""
    _, _, cfg = tiny
    eng, take = _step_spans(tiny, **engine_kw)
    a = Request(prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
                max_new_tokens=first_budget, request_id="A")
    eng.submit(a)
    eng.step()                                  # idle engine: A's token at once
    assert eng.occupied_slots() == 1
    b = Request(prompt=(np.arange(7, dtype=np.int32) * 3) % cfg.vocab_size,
                max_new_tokens=6, request_id="B")
    eng.submit(b)
    take()
    return eng, take, a, b


def _owing(eng):
    """Slots whose final chunk has been dispatched and whose first token is
    still on the device."""
    return [slot for slot, p in eng._pending.items() if p.first is not None]


def test_decode_is_dispatched_before_the_steps_prefill_and_awaited_after(tiny):
    """A step with an occupied slot and a pending prefill opens its spans in
    the order ``decode`` (the dispatch) → ``admission`` → ``prefill`` →
    ``device_wait(kind=decode)``: the chunk lies behind the decode in the
    device's queue when the host blocks, so the wait is covered. The final
    chunk's first token is NOT waited for in that step: the slot owes it
    (``busy()`` stays true), and the next step takes it right after its own
    decode's dispatch — ``device_wait(kind=first_token, covered=1)`` — before
    that decode is awaited. The slot joins the decode after that."""
    eng, take, a, b = _busy_then_one_more(tiny)
    got_b = []
    b.on_token = got_b.append
    eng.step()
    spans = sorted(take(), key=lambda s: s["t0"])
    _assert_nested(sorted(spans, key=lambda s: s["t1"]))
    order = [(s["name"], s.get("kind")) for s in spans
             if s["name"] in ("decode", "admission", "prefill", "device_wait")]
    assert order == [("decode", None), ("admission", None), ("prefill", None),
                     ("device_wait", "decode")]
    wait = _named(spans, "device_wait")[0]
    assert wait["covered"] == 1 and wait["parent"] == "decode"
    assert _named(spans, "prefill")[0]["parent"] == "decode"
    assert _named(spans, "decode")[0]["rows"] == 1
    assert _owing(eng) and eng.busy() and eng.occupied_slots() == 1
    assert got_b == [] and eng.stats.summary()["requests_admitted"] == 2
    eng.step()
    spans = sorted(take(), key=lambda s: s["t0"])
    order = [(s["name"], s.get("kind"), s.get("covered")) for s in spans
             if s["name"] in ("decode", "prefill", "device_wait")]
    assert order == [("decode", None, None), ("device_wait", "first_token", 1),
                     ("device_wait", "decode", 0)]
    assert _named(spans, "decode")[0]["rows"] == 1        # B is not a row yet
    assert len(got_b) == 1 and not _owing(eng) and eng.occupied_slots() == 2
    eng.step()
    assert _named(take(), "decode")[0]["rows"] == 2 and len(got_b) == 2


def test_busy_while_a_first_token_is_owed_and_nothing_else_is_left(tiny):
    """A's last token and B's final chunk fall into one step: afterwards no
    slot is occupied and the queue is empty, and only the owed first token
    keeps ``busy()`` true. The next step has no decode to dispatch: the token
    is read with nothing behind it (``covered=0``)."""
    eng, take, a, b = _busy_then_one_more(tiny, first_budget=2)
    outs = eng.step()
    assert [o.request_id for o in outs] == ["A"]
    assert eng.occupied_slots() == 0 and len(eng.queue) == 0
    assert _owing(eng) and eng.busy() and eng.load() == 1
    take()
    eng.step()
    spans = take()
    assert not _named(spans, "decode")
    assert [(w["kind"], w["covered"], w["parent"])
            for w in _named(spans, "device_wait")] == [
                ("first_token", 0, "engine_step")]
    assert eng.occupied_slots() == 1 and not _owing(eng)
    assert [o.request_id for o in eng.run()] == ["B"] and not eng.busy()


def test_fence_covered_share_counts_every_wait(tiny):
    """``serve_fence_covered_share`` = covered ÷ all blocking reads, counted
    where the ``device_wait`` spans are opened."""
    from k8s_distributed_deeplearning_tpu.telemetry import bridge
    from k8s_distributed_deeplearning_tpu.telemetry.registry import (
        MetricsRegistry)
    model, params, cfg = tiny
    eng, take = _step_spans(tiny, min_bucket=8, prefill_chunk_tokens=8)
    assert eng.stats.summary()["fence_covered_share"] is None
    reg = MetricsRegistry()
    bridge.serving_collector(reg, eng.stats)
    prompts, max_news = _workload(cfg, 7, seed=21)
    eng.run([Request(prompt=p, max_new_tokens=m)
             for p, m in zip(prompts, max_news)])
    waits = _named(take(), "device_wait")
    covered = sum(w["covered"] for w in waits)
    s = eng.stats.summary()
    assert (s["fences"], s["fences_covered"]) == (len(waits), covered)
    assert 0 < covered < len(waits)
    assert s["fence_covered_share"] == round(covered / len(waits), 4)
    assert (f"serve_fence_covered_share {s['fence_covered_share']}"
            in reg.render())


@pytest.mark.parametrize("sampling_budget", [0, 5])
def test_sampler_sort_share_counts_the_steps_with_a_sampling_row(
        tiny, sampling_budget):
    """``sampled_rows`` on a ``decode`` span = rows with a temperature > 0
    in the register file at that dispatch (what the program's own predicate
    sees); ``serve_sampler_sort_share`` = the share of such dispatches. An
    all-greedy run has none. A sampling request of n tokens beside a longer
    greedy one is live for n - 1 decode steps in a row (its first token
    comes from its final chunk), and for no step after its slot is freed."""
    from k8s_distributed_deeplearning_tpu.telemetry import bridge
    from k8s_distributed_deeplearning_tpu.telemetry.registry import (
        MetricsRegistry)
    _, _, cfg = tiny
    eng, take = _step_spans(tiny)
    assert eng.stats.summary()["sampler_sort_share"] is None
    reg = MetricsRegistry()
    bridge.serving_collector(reg, eng.stats)
    prompts, _ = _workload(cfg, 3, seed=5)
    reqs = [Request(prompt=prompts[0], max_new_tokens=14),
            Request(prompt=prompts[1], max_new_tokens=3)]
    if sampling_budget:
        reqs.insert(1, Request(
            prompt=prompts[2], max_new_tokens=sampling_budget,
            sampling=SamplingParams(temperature=0.8, top_k=12), seed=3))
    outs = eng.run(reqs)
    assert sorted(len(o.tokens) for o in outs) == sorted(
        r.max_new_tokens for r in reqs)
    rows = [d["sampled_rows"] for d in _named(take(), "decode")]
    live = max(sampling_budget - 1, 0)
    assert sum(rows) == live and set(rows) <= {0, 1}
    first = rows.index(1) if live else 0
    assert rows[first:first + live] == [1] * live
    s = eng.stats.summary()
    assert (s["sampler_steps"], s["sampler_sort_steps"]) == (len(rows), live)
    assert s["sampler_sort_share"] == round(live / len(rows), 4)
    (line,) = [ln for ln in reg.render().splitlines()
               if ln.startswith("serve_sampler_sort_share ")]
    assert float(line.split()[1]) == s["sampler_sort_share"]
    assert not (eng._temps > 0).any()


# One engine, requests arriving while others decode and prefill in chunks,
# against the same requests served one at a time (where every first token is
# read at once, as the engine did before it deferred them).

_FIELDS = ("request_id", "prompt_len", "tokens", "finish_reason",
           "cached_prompt_tokens", "prefill_chunks", "spec_proposed",
           "spec_accepted")


def _fields(out, skip=()):
    return {k: getattr(out, k) for k in _FIELDS if k not in skip}


def _draft(cfg):
    dmodel = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32,
                                             max_seq_len=cfg.max_seq_len))
    return dmodel, dmodel.init(jax.random.key(7),
                               jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(tiny, **kw):
    model, params, _ = tiny
    kw.setdefault("eos_id", None)
    return ServeEngine(model, params, num_slots=3, min_bucket=8,
                       prefill_chunk_tokens=8, **kw)


def _mixed_run(eng, reqs):
    """Two requests first, the rest while those decode: most final chunks
    are dispatched behind a decode and owe their first token for a step."""
    streams = {r.request_id: [] for r in reqs}
    for r in reqs:
        r.on_token = streams[r.request_id].append
    owed = 0
    outs = []
    for r in reqs[:2]:
        eng.submit(r)
    outs += eng.step() + eng.step()
    for r in reqs[2:]:
        eng.submit(r)
    while eng.busy():
        outs += eng.step()
        owed += bool(_owing(eng))
    return {o.request_id: o for o in outs}, streams, owed


@pytest.mark.parametrize("case", [
    "greedy", "sampled", "spec_k", "prefix_hits", "eos_first_token",
    "one_token_budgets", "tp1"])
def test_streams_equal_one_at_a_time_serving(tiny, case):
    """Token streams, finish reasons and the other fields of every
    ``RequestOutput`` of a mixed batch are those of the same requests served
    one at a time, and no page is leaked."""
    model, params, cfg = tiny
    n = 7
    prompts, max_news = _workload(cfg, n, seed=31, p_lo=3, p_hi=25)
    sampling = [SamplingParams()] * n
    kw, skip = {}, ()
    if case == "sampled":
        sampling = [SamplingParams(temperature=0.8 + 0.1 * (i % 3),
                                   top_k=(0, 12, 40)[i % 3],
                                   top_p=(1.0, 0.9, 0.7)[i % 3])
                    for i in range(n)]
    elif case == "spec_k":
        dmodel, dparams = _draft(cfg)
        kw = {"draft_model": dmodel, "draft_params": dparams, "spec_k": 2}
        sampling = [SamplingParams(), SamplingParams(temperature=0.9,
                                                     top_k=20)] * 4
    elif case == "prefix_hits":
        shared = prompts[0][:16] if len(prompts[0]) >= 16 else np.resize(
            prompts[0], 16)
        prompts = [np.concatenate([shared, p[:9]]) for p in prompts]
        kw = {"prefix_cache_mb": 4, "prefix_block_tokens": 8}
        skip = ("cached_prompt_tokens", "prefill_chunks")   # who hits differs
    elif case == "eos_first_token":
        # the first token request 3 samples ends it, where its final chunk
        # was dispatched behind a decode
        kw = {"eos_id": int(_ref_greedy(model, params, prompts[3], 1)[0])}
    elif case == "one_token_budgets":
        max_news = [1 if i % 2 else m for i, m in enumerate(max_news)]
    elif case == "tp1":
        kw = {"tp": 1}
    mk = lambda: [Request(prompt=p, max_new_tokens=m, sampling=sp, seed=40 + i,
                          request_id=f"{case}-{i}")
                  for i, (p, m, sp) in enumerate(zip(prompts, max_news, sampling))]
    alone_eng = _engine(tiny, **kw)
    alone = {}
    for r in mk():
        (out,) = alone_eng.run([r])
        alone[out.request_id] = out
    eng = _engine(tiny, **kw)
    mixed, streams, owed = _mixed_run(eng, mk())
    assert owed >= 3                       # the deferred path really ran
    assert sorted(mixed) == sorted(alone)
    for rid, want in alone.items():
        assert _fields(mixed[rid], skip) == _fields(want, skip), rid
        assert streams[rid] == want.tokens
    if case == "prefix_hits":
        assert eng.stats.summary()["prefix_cache_hits"] >= 1
    if case == "eos_first_token":
        assert mixed[f"{case}-3"].finish_reason == "eos"
        assert len(mixed[f"{case}-3"].tokens) == 1
    assert eng._check_page_leaks("test") is None
    assert alone_eng._check_page_leaks("test") is None


def _program_counts(eng):
    return (eng.decode_cache_size(), ServeEngine.prefill_cache_size(),
            ServeEngine.chunk_cache_size())


@pytest.mark.parametrize("case", [
    "prefix_cache_below_one_block", "request_trace_sample_1", "flight_ring",
    "explicit_default_tenant", "jsonl_tracer", "fleet_scrape",
    "one_replica_gateway", "idle_autoscaler"])
def test_feature_idle_adds_no_device_work(tiny, case):
    """A feature that is attached and has nothing to do is host bookkeeping:
    the same seeded requests through a plain engine and through one with the
    feature on give the same ``RequestOutput`` fields (token streams, finish
    reasons, chunk counts), dispatch the same number of programs, compile
    none, and leak no page."""
    import io

    from k8s_distributed_deeplearning_tpu.serve import (
        DEFAULT_TENANT, EngineFactoryBackend, FleetController, ServeGateway,
        TenantConfig)
    from k8s_distributed_deeplearning_tpu.telemetry import (
        MetricsExporter, MetricsRegistry, Tracer, bridge, fleet)
    from k8s_distributed_deeplearning_tpu.telemetry.flight import (
        FlightRecorder)
    from k8s_distributed_deeplearning_tpu.utils.metrics import MetricsLogger

    _, _, cfg = tiny
    n = 7
    prompts, max_news = _workload(cfg, n, seed=47, p_lo=3, p_hi=25)
    mk = lambda: [Request(prompt=p, max_new_tokens=m, request_id=f"{case}-{i}")
                  for i, (p, m) in enumerate(zip(prompts, max_news))]
    # the gateway's own RequestOutput carries no per-engine chunk count
    skip = (("prefill_chunks",) if case in ("one_replica_gateway",
                                            "idle_autoscaler") else ())
    plain = _engine(tiny)
    want = {o.request_id: _fields(o, skip) for o in plain.run(mk())}
    assert len(want) == n
    programs = _program_counts(plain)

    sink = MetricsLogger(stream=io.StringIO(), job="test")
    kw = {
        "prefix_cache_below_one_block": {"prefix_cache_mb": 1 / 1024},
        "request_trace_sample_1": {"request_trace_sample": 1.0,
                                   "request_log": sink},
        "flight_ring": {"flight": FlightRecorder(256)},
        "explicit_default_tenant": {"tenants": [TenantConfig(DEFAULT_TENANT)]},
        "jsonl_tracer": {"tracer": Tracer(sink)},
    }.get(case, {})
    eng = _engine(tiny, **kw)
    if case == "fleet_scrape":
        registry = MetricsRegistry()
        bridge.serving_collector(registry, eng.stats)
        exporter = MetricsExporter(registry, host="127.0.0.1", port=0).start()
        scraper = fleet.FleetScraper([f"127.0.0.1:{exporter.port}"])
        try:
            scraper.poll()
            outs = eng.run(mk())
            (state,) = scraper.poll().values()
        finally:
            exporter.stop()
        assert state.consecutive_failures == 0 and state.families
    elif case == "one_replica_gateway":
        outs = ServeGateway([eng]).run(mk())
    elif case == "idle_autoscaler":
        def never():
            raise AssertionError("an idle controller started a replica")
        gw = ServeGateway([eng])
        # thresholds out of reach: every round senses, decides, holds
        ctl = FleetController(gw, EngineFactoryBackend(never), min_replicas=1,
                              max_replicas=1, interval_s=0.0, load_high=1e9,
                              load_low=0.0)
        for r in mk():
            gw.submit(r)
        outs = []
        while len(outs) < n:
            outs += gw.step()
            assert ctl.control_round()["decision"] == "hold"
    else:
        outs = eng.run(mk())
    assert {o.request_id: _fields(o, skip) for o in outs} == want
    assert eng._dispatches == plain._dispatches
    assert _program_counts(eng) == programs
    assert eng._check_page_leaks("test") is None
    if case == "prefix_cache_below_one_block":
        assert len(eng.prefix_cache) == 0
        assert eng.prefix_cache.inserted_blocks == 0
        assert eng.stats.prefix_misses == n
    elif case == "request_trace_sample_1":
        assert eng.stats.request_traces == n
    elif case == "flight_ring":
        assert len(kw["flight"].ring) >= eng.stats.steps > 0
    elif case == "jsonl_tracer":
        assert kw["tracer"].spans_emitted > eng.stats.steps


@pytest.mark.parametrize("what", ["cancel", "cancel_as_it_finishes", "export",
                                  "timeout", "drain", "shutdown"])
def test_a_slot_owing_its_first_token_is_seen_by(tiny, what):
    """``cancel``, ``export_request_kv``, the deadline sweep, ``drain`` and
    ``shutdown`` meet a slot whose first token is still on the device: they
    take the token first (a plain read), so each sees the decoding request
    the engine would have had a step earlier — its first token emitted, then
    cancelled / shipped / timed out / finished / aborted."""
    model, params, cfg = tiny
    budget = 1 if what == "cancel_as_it_finishes" else 6
    eng, take, a, b = _busy_then_one_more(tiny)
    b.max_new_tokens = budget
    b.deadline_s = 5.0 if what == "timeout" else None
    got, reasons = [], []
    b.on_token, b.on_finish = got.append, reasons.append
    ref = _ref_greedy(model, params, b.prompt, 6).tolist()
    outs = eng.step()
    assert outs == [] and _owing(eng) and got == []
    if what == "cancel":
        out = eng.cancel("B", "migrated")
        assert (out.finish_reason, out.tokens, got) == ("migrated", ref[:1],
                                                        ref[:1])
        assert out.ttft_s is not None and out.prefill_chunks == 1
    elif what == "cancel_as_it_finishes":
        assert eng.cancel("B") is None          # it had finished: unknown here
        assert (got, reasons) == (ref[:1], ["length"]) and eng.busy()
        outs = eng.step()                       # … and its output is not lost
        assert [(o.request_id, o.finish_reason, o.tokens) for o in outs] == [
            ("B", "length", ref[:1])]
    elif what == "export":
        blob = eng.export_request_kv("B")
        assert (blob["emitted"], got) == (ref[:1], ref[:1])
        dst = ServeEngine(model, params, num_slots=2, eos_id=None)
        dst.import_request_kv(blob)
        (out,) = dst.run()
        assert out.tokens == ref and dst._check_page_leaks("test") is None
    elif what == "timeout":
        b._t_submit -= 10.0                     # its deadline has passed
        outs = eng.step()
        (out,) = [o for o in outs if o.request_id == "B"]
        assert (out.finish_reason, out.tokens) == ("timeout", ref[:1])
        assert reasons == ["timeout"]
    elif what == "drain":
        assert eng.drain() == [] and not eng.drained
        outs = []
        while not eng.drained:
            outs += eng.step()
        assert {o.request_id: o.tokens for o in outs}["B"] == ref
    elif what == "shutdown":
        outs = {o.request_id: o for o in eng.shutdown()}
        assert (outs["B"].finish_reason, outs["B"].tokens) == ("aborted",
                                                               ref[:1])
        assert outs["A"].finish_reason == "aborted" and len(outs["A"].tokens) == 2
    assert not _owing(eng)
    if what != "shutdown":
        eng.run()
    assert not eng.busy() and eng._check_page_leaks("test") is None
