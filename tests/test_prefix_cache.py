"""Prefix-reuse KV caching + chunked prefill: greedy parity on the
cache-hit and chunked paths vs one-shot generate(), hit/eviction/refcount
accounting, per-iteration prefill work bounds and compile-once discipline
with both features on. (An enabled-but-empty cache adds no device work:
tests/test_serve.py::test_feature_idle_adds_no_device_work.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.serve import (PrefixCache, Request,
                                                    ServeEngine)

BLOCK = 32  # the engine's min_bucket == default prefix block granularity


@pytest.fixture(scope="module")
def med():
    # Longer sequences than test_serve's fixture: prefix hits need whole
    # 32-token blocks below the prompt, chunked prefill needs prompts
    # spanning several chunks.
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=256)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _ref_greedy(model, params, prompt, max_new):
    """Isolated one-shot generate() for one prompt — the parity oracle."""
    return np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :],
        max_new_tokens=max_new))[0]


def _shared_prefix_prompts(cfg, n, prefix_len, tail_lo, tail_hi, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, size=prefix_len)
    return [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, size=int(rng.integers(tail_lo, tail_hi)))]
        ).astype(np.int32) for _ in range(n)]


# ------------------------------------------------------------ parity paths


def test_prefix_hit_greedy_parity_and_accounting(med):
    """Shared-prefix workload through a cache-enabled engine: every request
    decodes bit-identical to an isolated generate(), later admissions reuse
    the shared prefix's cached KV, and the hit shows up in RequestOutput,
    the trie counters AND ServingStats."""
    model, params, cfg = med
    prompts = _shared_prefix_prompts(cfg, 4, prefix_len=40, tail_lo=8,
                                     tail_hi=24)
    reqs = [Request(prompt=p, max_new_tokens=6) for p in prompts]
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64)
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            np.asarray(outs[r.request_id].tokens),
            _ref_greedy(model, params, p, 6))
    # Slots 1+2 admit before any insert (cold); 3+4 admit after and must
    # reuse the shared 40-token prefix's first whole block.
    hits = [outs[r.request_id].cached_prompt_tokens for r in reqs]
    assert hits[0] == 0 and hits[1] == 0
    assert hits[2] >= BLOCK and hits[3] >= BLOCK
    c = eng.prefix_cache.counters()
    assert c["hits"] == 2 and c["misses"] == 2
    assert c["hit_tokens"] == sum(hits)
    # Prompts are 48-63 tokens: exactly one whole block each, and all four
    # share it — one device copy-out serves the whole workload.
    assert c["inserted_blocks"] == 1 and c["evictions"] == 0
    summ = eng.stats.summary()
    assert summ["prefix_cache_hits"] == 2
    assert summ["prefix_cache_misses"] == 2
    assert 0.0 < summ["prefix_hit_rate"] < 1.0


def test_fully_cached_prompt_still_samples_first_token(med):
    """Re-serving an identical prompt: the hit is capped at one block below
    the prompt end — at least one real token must prefill so the first
    output token is sampled from real logits, not a stale cache."""
    model, params, cfg = med
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, size=2 * BLOCK).astype(np.int32)
    ref = _ref_greedy(model, params, prompt, 5)
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64)
    out1 = eng.run([Request(prompt=prompt, max_new_tokens=5)])[0]
    out2 = eng.run([Request(prompt=prompt, max_new_tokens=5)])[0]
    np.testing.assert_array_equal(np.asarray(out1.tokens), ref)
    np.testing.assert_array_equal(np.asarray(out2.tokens), ref)
    assert out1.cached_prompt_tokens == 0
    # Both blocks are in the trie, but only the first is reusable: block 2
    # ends exactly at the prompt end.
    assert out2.cached_prompt_tokens == BLOCK


def test_chunked_prefill_parity_and_per_step_budget(med):
    """A long prompt admitted while another slot is mid-decode: prefill is
    carved into C-token chunks across iterations, each iteration's prefill
    work stays <= C, the in-flight slot emits exactly one token per
    iteration throughout (no multi-step freeze), and both requests match
    their isolated references bit-for-bit."""
    model, params, cfg = med
    rng = np.random.default_rng(3)
    victim_p = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    long_p = rng.integers(0, cfg.vocab_size, size=3 * BLOCK + 7).astype(
        np.int32)
    victim_toks = []
    victim = Request(prompt=victim_p, max_new_tokens=24,
                     on_token=victim_toks.append)
    eng = ServeEngine(model, params, num_slots=2,
                      prefill_chunk_tokens=BLOCK)
    eng.submit(victim)
    eng.step()
    assert len(victim_toks) >= 1
    long_req = Request(prompt=long_p, max_new_tokens=6)
    eng.submit(long_req)
    pending_steps = 0
    while True:
        before = len(victim_toks)
        eng.step()          # admission happens inside step()
        pending_steps += 1
        assert eng.last_step_prefill_tokens <= BLOCK
        # The victim's stream never stalls while the long prompt prefills.
        assert len(victim_toks) == before + 1
        if not eng._pending:
            break
    # 103 tokens at C=32: three intermediate chunks + the 7-token final
    # chunk, each on its own iteration (the budget admits one per step);
    # the slot stays pending one step more, until its first token is taken
    # behind that step's decode.
    assert pending_steps == 5
    outs = {o.request_id: o for o in eng.run()}
    np.testing.assert_array_equal(
        np.asarray(victim_toks), _ref_greedy(model, params, victim_p, 24))
    np.testing.assert_array_equal(
        np.asarray(outs[long_req.request_id].tokens),
        _ref_greedy(model, params, long_p, 6))


def test_concurrent_cold_chunked_prefills_parity(med):
    """Several cold prompts admitted in the SAME step, chunk-prefilling
    across iterations while the first finisher decodes: every decode
    iteration writes a rider KV row for EVERY slot at that slot's cursor,
    and a pending slot's cursor is stale (pre-admission). Its block-table
    row must stay all-scratch until admission completes, or the rider
    write lands inside the freshly prefilled prompt pages — regression
    test: requests admitted later decoded from corrupted prompt KV."""
    model, params, cfg = med
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(2 * BLOCK + 2,
                                                  3 * BLOCK))).astype(
                                np.int32) for _ in range(6)]
    reqs = [Request(prompt=p, max_new_tokens=7) for p in prompts]
    eng = ServeEngine(model, params, num_slots=3,
                      prefill_chunk_tokens=BLOCK)
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            np.asarray(outs[r.request_id].tokens),
            _ref_greedy(model, params, p, 7))


def test_chunked_plus_prefix_cache_parity(med):
    """Both features on at once: pasted prefix blocks advance the chunk
    cursor, chunks resume after them, and greedy output still matches the
    isolated reference for every request."""
    model, params, cfg = med
    prompts = _shared_prefix_prompts(cfg, 3, prefix_len=2 * BLOCK,
                                     tail_lo=20, tail_hi=60, seed=11)
    reqs = [Request(prompt=p, max_new_tokens=5) for p in prompts]
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64,
                      prefill_chunk_tokens=BLOCK)
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            np.asarray(outs[r.request_id].tokens),
            _ref_greedy(model, params, p, 5))
    # The last-admitted request rides the full shared prefix from cache.
    assert outs[reqs[2].request_id].cached_prompt_tokens == 2 * BLOCK


def test_cache_disabled_passthrough(med):
    """Default construction: no trie, no hit accounting, outputs report
    zero cached tokens — the legacy admission path verbatim."""
    model, params, cfg = med
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
               for _ in range(2)]
    eng = ServeEngine(model, params, num_slots=2)
    assert eng.prefix_cache is None
    outs = eng.run([Request(prompt=p, max_new_tokens=4) for p in prompts])
    assert all(o.cached_prompt_tokens == 0 for o in outs)
    summ = eng.stats.summary()
    assert summ["prefix_cache_hits"] == 0
    assert summ["prefix_hit_rate"] is None
    for o, p in zip(outs, prompts):
        np.testing.assert_array_equal(
            np.asarray(o.tokens), _ref_greedy(model, params, p, 4))


# --------------------------------------------------- eviction and refcounts


def test_eviction_respects_byte_budget(med):
    """Budget for exactly two blocks, three distinct one-block prompts:
    the third insert evicts the LRU block, used_bytes never exceeds the
    budget, and decoding stays bit-correct throughout."""
    model, params, cfg = med
    probe = ServeEngine(model, params, num_slots=2, prefix_cache_mb=1)
    bn = probe.prefix_cache.block_nbytes
    eng = ServeEngine(model, params, num_slots=2,
                      prefix_cache_mb=2 * bn / 2 ** 20)
    rng = np.random.default_rng(9)
    for _ in range(3):
        p = rng.integers(0, cfg.vocab_size, size=40).astype(np.int32)
        out = eng.run([Request(prompt=p, max_new_tokens=4)])[0]
        np.testing.assert_array_equal(
            np.asarray(out.tokens), _ref_greedy(model, params, p, 4))
        c = eng.prefix_cache.counters()
        assert c["used_bytes"] <= c["capacity_bytes"]
    c = eng.prefix_cache.counters()
    assert c["inserted_blocks"] == 3
    assert c["evictions"] == 1
    assert c["blocks"] == 2
    # Accounting is exact and lives in one place: the running used_bytes
    # always equals the sum of the surviving nodes' charges.
    assert c["used_bytes"] == sum(
        nd.nbytes for nd in eng.prefix_cache._nodes)
    assert eng.stats.summary()["prefix_cache_evictions"] == 1


def test_refcount_pins_blocks_under_insert_pressure():
    """An acquired (in-flight) path is never evicted: insert pressure that
    would need its bytes is skipped instead; after release the same blocks
    are evictable (returning their pool pages via release_page).
    Unit-level on PrefixCache with synthetic page ids."""
    released: list[int] = []
    pc = PrefixCache(capacity_bytes=64, block_tokens=4, block_nbytes=32,
                     release_page=released.append)
    pages = iter(range(1, 100))
    page_for = lambda i: next(pages)
    t1 = list(range(8))
    assert pc.insert(t1, page_for) == (2, 0)
    hit, nodes = pc.acquire(t1 + [99])
    assert hit == 8 and len(nodes) == 2
    # Full + every block protected (leaf pinned, interior has a child):
    # the insert must skip, not evict under a pending admission.
    t2 = list(range(100, 108))
    assert pc.insert(t2, page_for) == (0, 0)
    assert pc.skipped_blocks == 1
    assert all(nd.page is not None for nd in nodes)
    assert released == []            # pinned pages never released
    pc.release(nodes)
    new, evicted = pc.insert(t2, page_for)
    assert (new, evicted) == (2, 2)
    assert sorted(released) == [1, 2]    # evicted nodes returned their pages
    with pytest.raises(RuntimeError):
        pc.release(nodes)       # refs already at zero — unbalanced release


def test_acquire_touches_lru_order():
    """A re-acquired block becomes most-recently-used: eviction picks the
    other, untouched entry."""
    pages = iter(range(1, 100))
    page_for = lambda i: next(pages)
    pc = PrefixCache(capacity_bytes=64, block_tokens=4, block_nbytes=32)
    a, b = [1] * 4, [2] * 4
    pc.insert(a, page_for)
    pc.insert(b, page_for)
    hit, nodes = pc.acquire(a + [0])     # touch a — b becomes LRU
    pc.release(nodes)
    pc.insert([3] * 4, page_for)         # needs room: must evict b, not a
    assert pc.acquire(a + [0])[0] == 4
    assert pc.acquire(b + [0])[0] == 0


# ------------------------------------------------- compile-once + overhead


def test_compile_once_with_cache_and_chunking(med):
    """Both features on, mixed prompt lengths: still exactly ONE decode
    program, one intermediate-chunk program per C, and final-chunk
    programs bounded by the bucket count — admissions never recompile."""
    model, params, cfg = med
    prompts = _shared_prefix_prompts(cfg, 6, prefix_len=BLOCK, tail_lo=4,
                                     tail_hi=80, seed=13)
    eng = ServeEngine(model, params, num_slots=4, prefix_cache_mb=64,
                      prefill_chunk_tokens=BLOCK)
    d0 = eng.decode_cache_size()
    c0 = ServeEngine.chunk_cache_size()
    p0 = ServeEngine.prefill_cache_size()
    eng.run([Request(prompt=p, max_new_tokens=4) for p in prompts])
    assert eng.decode_cache_size() - d0 == 1
    assert ServeEngine.chunk_cache_size() - c0 <= 1
    # With C == min_bucket every final chunk is a 32-bucket program.
    assert ServeEngine.prefill_cache_size() - p0 <= 1
    eng2 = ServeEngine(model, params, num_slots=4, prefix_cache_mb=64,
                       prefill_chunk_tokens=BLOCK)
    eng2.run([Request(prompt=p, max_new_tokens=3) for p in prompts[:3]])
    assert eng2.decode_cache_size() - d0 == 1   # same shape: zero new


def test_engine_flag_validation(med):
    model, params, _ = med
    with pytest.raises(ValueError):
        ServeEngine(model, params, prefill_chunk_tokens=40)   # not multiple
    with pytest.raises(ValueError):
        ServeEngine(model, params, prefill_chunk_tokens=16)   # < min_bucket
    with pytest.raises(ValueError):
        ServeEngine(model, params, prefix_cache_mb=-1.0)
    with pytest.raises(ValueError):
        ServeEngine(model, params, prefix_cache_mb=1.0,
                    prefix_block_tokens=0)
    with pytest.raises(ValueError):
        ServeEngine(model, params, kv_pool_pages=0)
    with pytest.raises(ValueError):
        PrefixCache(capacity_bytes=1 << 20, block_tokens=0)
    with pytest.raises(ValueError):
        # block_nbytes is required: fit tests must never touch arrays.
        PrefixCache(capacity_bytes=1 << 20, block_tokens=4)


def test_cli_rejects_bad_serving_flags():
    """The CLI re-validates before the model build: bad flags exit with
    usage text (argparse SystemExit), not an engine traceback."""
    from k8s_distributed_deeplearning_tpu.serve import cli
    for argv in (["--prefill-chunk-tokens", "40"],
                 ["--prefill-chunk-tokens", "16"],
                 ["--prefix-cache-mb", "-1"],
                 ["--shared-prefix-len", "-8"]):
        with pytest.raises(SystemExit) as e:
            cli.main(argv)
        assert e.value.code == 2
