"""Paged KV arena: PagePool refcount/reservation invariants, pool-
exhaustion back-pressure at admission, copy-free prefix sharing
(pages_shared mid-run, no paste/splice/copy-out programs left), greedy
parity on the combined hit+chunked+growth path, compile-once discipline
across page-boundary growth, and the kv gauge plumbing through
ServingStats and the telemetry bridge."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import generate, llama
from k8s_distributed_deeplearning_tpu.serve import (PagePool, Request,
                                                    ServeEngine)
from k8s_distributed_deeplearning_tpu.serve import engine as engine_mod


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=96)
    model = llama.LlamaLM(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params, cfg


def _ref_greedy(model, params, prompt, max_new):
    """Isolated one-shot generate() for one prompt — the parity oracle."""
    return np.asarray(generate.generate(
        model, params, jnp.asarray(prompt)[None, :],
        max_new_tokens=max_new))[0]


# ------------------------------------------------------------- PagePool


def test_pool_alloc_deref_roundtrip_and_counters():
    pool = PagePool(num_pages=5, page_tokens=8)
    assert pool.counters() == {"pages_total": 4, "pages_used": 0,
                               "pages_shared": 0, "pages_reserved": 0}
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and all(p > 0 for p in pages)
    assert pool.available() == 1
    assert pool.counters()["pages_used"] == 3
    for p in pages:
        pool.deref(p)
    assert pool.available() == 4
    assert pool.counters()["pages_used"] == 0
    # LIFO: the most recently freed page comes back first (cache warmth).
    assert pool.alloc(1) == [pages[-1]]


def test_pool_scratch_page_is_untouchable():
    pool = PagePool(num_pages=4, page_tokens=8)
    assert 0 not in pool.alloc(3)          # scratch never handed out
    with pytest.raises(RuntimeError):
        pool.ref(0)
    with pytest.raises(RuntimeError):
        pool.deref(0)


def test_pool_exhaustion_and_dead_page_raise():
    pool = PagePool(num_pages=4, page_tokens=8)
    (page,) = pool.alloc(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(3)                      # only 2 free remain
    pool.deref(page)
    with pytest.raises(RuntimeError, match="dead"):
        pool.ref(page)                     # refcount hit 0 — page is dead
    with pytest.raises(RuntimeError, match="dead"):
        pool.deref(page)


def test_pool_sharing_refcounts():
    pool = PagePool(num_pages=4, page_tokens=8)
    (page,) = pool.alloc(1)
    pool.ref(page)                         # second holder (e.g. the trie)
    assert pool.counters()["pages_shared"] == 1
    pool.deref(page)
    assert pool.counters()["pages_shared"] == 0
    assert pool.counters()["pages_used"] == 1      # first holder remains
    assert pool.available() == 2                   # not freed yet
    pool.deref(page)
    assert pool.available() == 3


def test_pool_reservations_gate_alloc_but_not_growth():
    pool = PagePool(num_pages=6, page_tokens=8)    # 5 usable
    pool.reserve(3)
    assert pool.available() == 2
    with pytest.raises(RuntimeError):
        pool.alloc(3)                      # reserved pages are off-limits
    with pytest.raises(RuntimeError):
        pool.reserve(3)                    # can't promise what isn't free
    grown = pool.alloc_reserved(2)         # growth draws on the promise
    assert len(grown) == 2 and pool.reserved == 1
    with pytest.raises(RuntimeError):
        pool.alloc_reserved(2)             # only 1 still promised
    pool.unreserve(1)
    with pytest.raises(RuntimeError):
        pool.unreserve(1)                  # nothing left to return
    assert pool.available() == 3


def test_pool_validation():
    with pytest.raises(ValueError, match="pages"):
        PagePool(num_pages=1, page_tokens=8)
    with pytest.raises(ValueError, match="page_tokens"):
        PagePool(num_pages=4, page_tokens=0)


# ----------------------------------------------- engine: back-pressure


@pytest.mark.parametrize("case", ["pages_cap_residency",
                                  "dense_arena_budget", "int8_same_bytes"])
def test_pool_exhaustion_backpressure_defers_admission(tiny, case):
    """Residency follows the pool's pages, not the slot count: admission
    back-pressure (the scheduler's fits probe) admits what fits, nothing
    crashes, every request completes (with full greedy parity where the
    pool is not quantized), and the pool drains back to zero used pages.

    - ``pages_cap_residency``: a pool sized for 2 concurrent requests
      under a 6-request load holds at most 2.
    - ``dense_arena_budget``: the pages a dense arena would spend on TWO
      slots of ``max_seq_len`` hold, at requests a quarter that long, at
      least twice as many residents.
    - ``int8_same_bytes``: the first case's byte budget spent on int8
      pages holds at least 1.8x its residents."""
    model, params, cfg = tiny
    rng = np.random.default_rng(0)
    # 8 tokens/page; a request of 6 + 12 tokens needs ceil((6 + 12 - 1)/8)
    # = 3 pages — growth crosses two page boundaries mid-decode.
    bt, slots, max_new, kw = 8, 4, 12, {}
    if case == "pages_cap_residency":
        pages, lo, hi = 6, 1, 2     # 6 usable pages => at most 2 resident
    elif case == "dense_arena_budget":
        # two dense slots = 2 * (96 / 8) pages; 6 + 18 tokens = max_seq / 4
        pages, slots, max_new = 2 * (cfg.max_seq_len // bt), 8, 18
        lo, hi = 4, 8
    else:
        probe = ServeEngine(model, params, num_slots=2, kv_quant="int8",
                            prefix_block_tokens=bt)
        pages = (6 * probe._block_nbytes(bt, kv_quant=None)
                 // probe._block_nbytes(bt))
        slots, kw = 8, {"kv_quant": "int8"}
        lo, hi = 4, pages // 3      # >= 1.8 x the fp pool's 2
    prompts = [rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
               for _ in range(3 * slots // 2)]
    eng = ServeEngine(model, params, num_slots=slots, eos_id=None,
                      prefix_block_tokens=bt, kv_pool_pages=int(pages), **kw)
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        eng.submit(r)
    outs, peak = [], 0
    while eng.busy():
        outs.extend(eng.step())
        resident = (sum(s is not None for s in eng._slots)
                    + len(eng._pending))
        peak = max(peak, resident)
    assert lo <= peak <= hi         # capped by pages, not by the slots
    outs = {o.request_id: o for o in outs}
    assert len(outs) == len(reqs)
    for r, p in zip(reqs, prompts):
        assert outs[r.request_id].finish_reason == "length"
        if not kw:
            np.testing.assert_array_equal(
                np.asarray(outs[r.request_id].tokens),
                _ref_greedy(model, params, p, max_new))
    c = eng.pool.counters()
    assert c["pages_used"] == 0 and c["pages_reserved"] == 0


def test_submit_rejects_request_larger_than_pool(tiny):
    model, params, cfg = tiny
    eng = ServeEngine(model, params, num_slots=2,
                      prefix_block_tokens=8, kv_pool_pages=2)
    with pytest.raises(ValueError, match="kv_pool_pages"):
        eng.submit(Request(prompt=np.zeros(20, np.int32), max_new_tokens=8))


def test_engine_flag_validation(tiny):
    model, params, cfg = tiny
    with pytest.raises(ValueError, match="kv_pool_pages"):
        ServeEngine(model, params, kv_pool_pages=0)
    with pytest.raises(ValueError, match="prefix_block_tokens"):
        ServeEngine(model, params,
                    prefix_block_tokens=cfg.max_seq_len + 1)


# ------------------------------------------- copy-free prefix sharing


def test_copy_programs_are_gone():
    """The paged arena's zero-copy claim, enforced structurally: the
    per-block device-copy programs the dense arena needed (prefix paste,
    chunk splice, trie copy-out) must not exist at all."""
    for name in ("_paste_program", "_splice_program", "_copyout_program"):
        assert not hasattr(engine_mod, name), name


def test_prefix_hit_shares_pages_mid_run(tiny):
    """While a cache-hit request is decoding, the prefix pages are held by
    BOTH the trie and the slot's block table — pages_shared >= 1 with no
    device copy; after completion the trie keeps them alive (used > 0)."""
    model, params, cfg = tiny
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab_size, size=32)
    p1 = np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, size=8)]).astype(np.int32)
    p2 = np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, size=8)]).astype(np.int32)
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64)
    eng.run([Request(prompt=p1, max_new_tokens=4)])     # populate the trie
    assert eng.stats.summary()["kv_pages_shared"] == 0
    hit = Request(prompt=p2, max_new_tokens=6)
    eng.submit(hit)
    eng.step()                     # admission maps the trie's prefix page
    mid = eng.stats.summary()
    assert mid["kv_pages_shared"] >= 1
    assert mid["kv_pages_used"] <= mid["kv_pages_total"]
    out = eng.run()[0]
    assert out.cached_prompt_tokens >= 32
    np.testing.assert_array_equal(
        np.asarray(out.tokens), _ref_greedy(model, params, p2, 6))
    end = eng.stats.summary()
    assert end["kv_pages_shared"] == 0     # slot released its references
    assert end["kv_pages_used"] >= 1       # trie still holds the prefix


def test_combined_hit_chunked_growth_parity(tiny):
    """All three paged paths in one request: a chunked-prefill admission
    whose prefix is already in the trie and whose decode grows across a
    page boundary — bit-identical to an isolated generate()."""
    model, params, cfg = tiny
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, size=32)
    mk = lambda n: np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, size=n)]).astype(np.int32)
    p1, p2 = mk(34), mk(38)        # 66- and 70-token prompts, 3 chunks
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64,
                      prefill_chunk_tokens=32)
    out1 = eng.run([Request(prompt=p1, max_new_tokens=16)])[0]
    out2 = eng.run([Request(prompt=p2, max_new_tokens=16)])[0]
    assert out1.cached_prompt_tokens == 0
    assert out2.cached_prompt_tokens == 32
    np.testing.assert_array_equal(
        np.asarray(out1.tokens), _ref_greedy(model, params, p1, 16))
    np.testing.assert_array_equal(
        np.asarray(out2.tokens), _ref_greedy(model, params, p2, 16))


# ------------------------------------------------- compile-once + gauges


def test_decode_compiles_once_across_page_growth(tiny):
    """Block tables are traced operands: decode steps that cross page
    boundaries (table rows changing values) reuse the ONE compiled decode
    program. num_slots is unique to this test so prior tests' cached
    programs can't mask a recompile."""
    model, params, cfg = tiny
    rng = np.random.default_rng(3)
    eng = ServeEngine(model, params, num_slots=7, eos_id=None,
                      prefix_block_tokens=8)
    d0 = eng.decode_cache_size()
    prompts = [rng.integers(0, cfg.vocab_size, size=int(
        rng.integers(4, 20))).astype(np.int32) for _ in range(5)]
    eng.run([Request(prompt=p, max_new_tokens=14) for p in prompts])
    assert eng.decode_cache_size() - d0 == 1


def test_kv_gauges_flow_through_stats_and_bridge(tiny):
    """Pool utilization reaches both surfaces: ServingStats.summary() keys
    and the telemetry bridge's serve_kv_* gauges at scrape time."""
    from k8s_distributed_deeplearning_tpu.telemetry import bridge
    from k8s_distributed_deeplearning_tpu.telemetry.registry import (
        MetricsRegistry)

    model, params, cfg = tiny
    eng = ServeEngine(model, params, num_slots=2, prefix_cache_mb=64)
    reg = MetricsRegistry()
    bridge.serving_collector(reg, eng.stats)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=40).astype(np.int32)
    eng.run([Request(prompt=prompt, max_new_tokens=4)])
    summ = eng.stats.summary()
    assert summ["kv_pages_total"] == eng.pool.num_pages - 1
    assert summ["kv_pages_used"] >= 1      # the trie's cached prefix
    body = reg.render()
    for name in ("serve_kv_pages_total", "serve_kv_pages_used",
                 "serve_kv_pages_shared"):
        assert f"\n{name} " in body or body.startswith(f"{name} "), name
    assert f"serve_kv_pages_total {summ['kv_pages_total']}\n" in body
