"""The window-and-global attention + sparse-expert family (K-EXAONE layout) at
a tiny float32 size on the CPU (window 8, layers ``L L L G L``, 8 routed
experts of which 4 are held): the model on every cache path against the plain
reference; the ring itself — chunks longer and shorter than the window, a
right-padded final chunk, contexts shorter than the window, a reused slot,
idle and mid-prefill rows left bit for bit; the expert layer's two shares
adding up to the uncut layer; ``ServeEngine``'s pool holding the global layer
alone; the spans and what the engine says it runs; and every existing
family's parameter and cache trees as the parent built them."""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_window_moe as fam
from benchmarks.harness import lowp
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_window_moe as ref
from benchmarks.harness import weights
from k8s_distributed_deeplearning_tpu.models import generate, llama, moe
from k8s_distributed_deeplearning_tpu.models import transformer as T
from k8s_distributed_deeplearning_tpu.serve import engine as E
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.request import Request
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer

CELL = "k-exaone-ep8-d5.docs-backlog-wide"
SEED = 3_900_000_019
# float32 end to end: what is left is the order of the sums (the engine's
# banded blocks and rings against the reference's padded keys)
TOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    """(cfg dict, model, params) of the cell's rehearsal size: hidden 64, 4
    query heads on 2 KV heads of 16, window 8, layers L/dense L L G L, 4 of 8
    experts held, top-2, one shared expert; weights from the seed as the
    benchmark makes them."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    return cell.config, model, params


def _ref_logits(cfg, toks, **kw):
    return ref.forward_logits(cfg, SEED, [np.asarray(toks)], [np.arange(len(toks))], **kw)[0][0]


def test_model_forward_and_row_cache_equal_the_reference(tiny):
    """The plain forward, and a prefill of 11 tokens then single steps through
    ``generate()``'s row cache (the rings made by the model itself)."""
    cfg, model, params = tiny
    toks = np.random.default_rng(0).integers(0, 256, size=37).astype(np.int32)
    want = _ref_logits(cfg, toks)
    full = np.asarray(model.apply({"params": params}, jnp.asarray(toks)[None])[0])
    lg, cache = generate.prefill(model, params, jnp.asarray(toks[:11])[None])
    steps = [np.asarray(lg[0])]
    for t in toks[11:]:
        l1, cache = generate.decode_step(model, params, cache, jnp.asarray([t]))
        steps.append(np.asarray(l1))
    assert np.abs(full - want).max() < TOL
    assert np.abs(np.concatenate(steps, axis=0) - want).max() < TOL
    assert np.abs(want).max() > 0.1
    ring = cache["transformer"]["block_0"]["attn"]["window_key"]
    assert ring.shape == (1, 8, 2 * 16) and "cached_key" not in cache["transformer"]["block_0"]["attn"]
    assert cache["transformer"]["block_3"]["attn"]["cached_key"].shape == (1, 128, 32)


def test_the_window_faults_move_the_logits(tiny):
    """Each window fault the readings plant through the reference (the window
    layers attending the whole context; RoPE in the global layer) moves the
    logits far more than the program lies from the reference."""
    cfg, _, _ = tiny
    toks = np.random.default_rng(9).integers(0, 256, size=40).astype(np.int32)
    want = _ref_logits(cfg, toks)
    full = _ref_logits(cfg, toks, fault="window_full")
    assert np.abs(full[:8] - want[:8]).max() < TOL      # inside the window: no difference
    assert np.abs(full[8:] - want[8:]).max() > 1e-2
    assert np.abs(_ref_logits(cfg, toks, fault="rope_global") - want).max() > 1e-2


def _arena(model, cfg, pages, page_tokens, slots):
    lanes = model.cfg.resolved_kv_heads * model.cfg.resolved_head_dim
    w = cfg["sliding_window"]
    out = {}
    for i, (mixer, _) in enumerate(ref.layer_kinds(cfg)):
        out[f"block_{i}"] = {"attn": (
            {"window_key": jnp.zeros((slots, w, lanes), jnp.float32),
             "window_value": jnp.zeros((slots, w, lanes), jnp.float32)}
            if mixer == "sliding_attention" else
            {"cached_key": jnp.zeros((pages, page_tokens, lanes), jnp.float32),
             "cached_value": jnp.zeros((pages, page_tokens, lanes), jnp.float32)})}
    return {"transformer": out}


@pytest.mark.parametrize("chunk,impl", [(16, "xla"), (5, "xla"), (16, "paged_flash")],
                         ids=["longer-than-the-window", "shorter-than-the-window", "paged-kernel"])
def test_paged_chunks_then_slot_decode_equal_reference_logits(tiny, chunk, impl):
    """Prefill in chunks through the block table — each longer (16) or shorter
    (5) than the window of 8, the last one right-padded to the chunk — then
    slot decode beside an idle row whose rings must stay as they were, bit for
    bit — against ALL the logits of the reference's ONE full forward."""
    cfg, model, params = tiny
    model = model.clone(cfg=dataclasses.replace(model.cfg, attention_impl=impl))
    toks = np.random.default_rng(1).integers(0, 256, size=51).astype(np.int32)
    want = _ref_logits(cfg, toks)
    pt, n_blocks = 8, 8
    arena = _arena(model, cfg, 20, pt, slots=1)
    table = np.zeros((2, n_blocks), np.int32)
    table[1, :7] = [7, 3, 11, 5, 2, 9, 14]         # row 1 is the request, row 0 idle
    got, a = [], 0
    while a < 38:
        n = min(chunk, 38 - a)                     # the last chunk: padded to `chunk`
        piece = np.zeros(chunk, np.int32)
        piece[:n] = toks[a:a + n]
        pos = (a + jnp.arange(chunk, dtype=jnp.int32))[None]
        lg, arena, _ = generate.prefill_chunk(
            model, params, arena, jnp.asarray(piece)[None], positions=pos,
            block_tables=jnp.asarray(table[1:2]), lengths=jnp.asarray([n], jnp.int32))
        got.append(np.asarray(lg[0, :n]))
        a += n
    junk = lambda leaf: jnp.concatenate([jnp.full_like(leaf, 7.0), leaf], axis=0)
    arena = jax.tree_util.tree_map_with_path(
        lambda p, leaf: junk(leaf) if p[-1].key in E._STATE_LEAVES else leaf, arena)
    for i in range(38, 51):
        lg, arena, _ = generate.slot_decode_step(
            model, params, arena, jnp.asarray([0, toks[i]], jnp.int32),
            jnp.asarray([0, i], jnp.int32), block_tables=jnp.asarray(table))
        got.append(np.asarray(lg[1:2]))
    assert np.abs(np.concatenate(got, axis=0) - want).max() < TOL
    for b in (0, 1, 2, 4):
        idle = arena["transformer"][f"block_{b}"]["attn"]["window_key"][0]
        assert (np.asarray(idle) == 7.0).all()     # dropped in the scatter, not selected


def test_the_ring_holds_the_last_window_of_real_tokens_in_place(tiny):
    """After a right-padded chunk the ring holds the keys of the last
    ``window`` REAL positions, position p in entry p % window; a decode step
    writes its token at entry ``pos % window`` and nothing else."""
    cfg, model, params = tiny
    w = cfg["sliding_window"]
    toks = np.random.default_rng(4).integers(0, 256, size=13).astype(np.int32)
    _, row = generate.prefill(model, params, jnp.asarray(toks)[None])   # the row cache's ring
    arena = _arena(model, cfg, 8, 8, slots=1)
    piece = np.zeros(16, np.int32)
    piece[:13] = toks
    _, arena, _ = generate.prefill_chunk(
        model, params, arena, jnp.asarray(piece)[None],
        positions=jnp.arange(16, dtype=jnp.int32)[None],
        block_tables=jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32),
        lengths=jnp.asarray([13], jnp.int32))
    got = np.asarray(arena["transformer"]["block_1"]["attn"]["window_key"][0])
    np.testing.assert_allclose(got, np.asarray(row["transformer"]["block_1"]["attn"]["window_key"][0]),
                               atol=1e-6)
    before = got.copy()
    _, arena, _ = generate.slot_decode_step(
        model, params, arena, jnp.asarray([5], jnp.int32), jnp.asarray([13], jnp.int32),
        block_tables=jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32))
    after = np.asarray(arena["transformer"]["block_1"]["attn"]["window_key"][0])
    changed = [j for j in range(w) if not np.array_equal(before[j], after[j])]
    assert changed == [13 % w]


def _engine(model, params, **kw):
    kw = {"num_slots": 3, "min_bucket": 16, "prefill_chunk_tokens": 16,
          "prefix_block_tokens": 8, "kv_pool_pages": 64, **kw}
    return ServeEngine(model, params, **kw)


def test_engine_equals_the_reference_across_slot_reuse(tiny):
    """Eight requests through three slots — prompts shorter than the window
    (masked ring entries: 1, 3 and 6 tokens), of exactly a chunk, of several
    chunks and a padded tail: every request was served, token for token, what
    one-shot ``generate()`` serves and the reference's ONE full forward puts
    first; a slot's next occupant never reads the last one's ring."""
    cfg, model, params = tiny
    tracer = Tracer(ring_size=8192)
    eng = _engine(model, params, tracer=tracer)
    rng = np.random.default_rng(2)
    sizes = [(40, 6), (3, 9), (17, 5), (6, 4), (33, 4), (16, 5), (50, 3), (1, 10)]
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=k,
                    request_id=f"r{i}") for i, (n, k) in enumerate(sizes)]
    outs = {o.request_id: o for o in eng.run(reqs)}
    for r in reqs:
        assert outs[r.request_id].finish_reason == "length"
        want = generate.generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                                 max_new_tokens=r.max_new_tokens)
        assert outs[r.request_id].tokens == np.asarray(want[0]).tolist()
    sample = [{"prompt": np.asarray(r.prompt), "tokens": np.asarray(outs[r.request_id].tokens)}
              for r in reqs]
    sc = ref.score_served(cfg, SEED, sample)
    assert sc["gaps"]["all_max"] < 1e-4 and sc["not_reference_best"] == 0
    attn = eng._cache["transformer"]["block_0"]["attn"]
    assert set(attn) == {"window_key", "window_value"} and attn["window_key"].shape == (3, 8, 32)
    assert eng._cache["transformer"]["block_3"]["attn"]["cached_key"].shape == (65, 8, 32)
    assert eng._state_names == ["window_key", "window_value"]
    assert eng._slot_state_nbytes == 4 * 2 * 8 * 32 * 4
    assert eng.stats.summary()["window_ring_bytes"] == 3 * eng._slot_state_nbytes
    assert eng.shutdown() == []


def test_the_pool_holds_the_global_layer_alone(tiny):
    """A page holds what ONE layer of five caches: ``_block_nbytes`` and what
    ``_need_pages`` reserves count the global layer's K/V alone."""
    _, model, params = tiny
    eng = _engine(model, params)
    assert eng._block_nbytes(8) == 1 * 2 * 8 * 32 * 4           # K and V, 2 heads x 16, f32
    assert [E._leaf_name(p) for p, _ in eng._pool_rows(eng._row_shapes)] == [
        "cached_key", "cached_value"]
    req = Request(prompt=list(range(30)), max_new_tokens=11)
    assert eng._need_pages(req) == -(-40 // 8)


def test_idle_and_mid_prefill_rows_keep_their_rings_bit_for_bit(tiny):
    """One long prompt admitted while another request decodes: between two of
    its chunks the slot's rings are, to the last bit, what the chunk left
    (decode steps ride every slot and the scatter drops its row); a free
    slot's rings are never written; once it decodes its rings ARE what the
    same tokens leave in ``generate()``'s row cache."""
    _, model, params = tiny
    eng = _engine(model, params, num_slots=3)
    rng = np.random.default_rng(6)
    short = Request(prompt=rng.integers(0, 256, size=5).tolist(), max_new_tokens=14,
                    request_id="short")
    long_ = Request(prompt=rng.integers(0, 256, size=70).tolist(), max_new_tokens=9,
                    request_id="long")
    eng.submit(short)
    outs = eng.step() + eng.step()                          # `short` is decoding
    eng.submit(long_)
    outs += eng.step()                                      # `long`: its first chunk
    slot = next(iter(eng._pending))
    free = next(j for j in range(3) if eng._slots[j] is None and j != slot)
    blocks = (0, 1, 2, 4)
    rows = lambda i: [np.asarray(eng._cache["transformer"][f"block_{b}"]["attn"][leaf][i])
                      for b in blocks for leaf in ("window_key", "window_value")]
    held, idle = rows(slot), rows(free)
    for _ in range(3):
        nxt, _, eng._cache = eng._decode_step()
        jax.block_until_ready(nxt)
    for before, after in zip(held + idle, rows(slot) + rows(free)):
        np.testing.assert_array_equal(before, after)
    while not any(fl is not None and fl.req.request_id == "long" and len(fl.tokens) >= 4
                  for fl in eng._slots):
        outs += eng.step()
    i = next(j for j, fl in enumerate(eng._slots) if fl is not None
             and fl.req.request_id == "long")
    consumed = long_.prompt + eng._slots[i].tokens[:-1]
    _, full = generate.prefill(model, params, jnp.asarray([consumed], jnp.int32))
    # keys after their norm are of unit size: 1e-5 is float32's order of the
    # sums over 70+ positions, chunked against one pass
    for got, (b, leaf) in zip(rows(i), [(b, leaf) for b in blocks
                                        for leaf in ("window_key", "window_value")]):
        np.testing.assert_allclose(
            got, np.asarray(full["transformer"][f"block_{b}"]["attn"][leaf][0]), atol=1e-5)
    assert {o.request_id for o in outs + eng.run()} >= {"long", "short"}


def test_the_spans_count_the_window_and_the_engine_names_its_path(tiny):
    """``window_tokens`` on every ``decode`` span is the ring entries the live
    rows attend (min(cursor + 1, window) a row); ``window_pairs`` on every
    ``prefill`` span the banded pairs of its real tokens; each program's
    ``attention_impls`` entry ends in ``window=ring-xla``."""
    cfg, model, params = tiny
    w = cfg["sliding_window"]
    tracer = Tracer(ring_size=8192)
    eng = _engine(model, params, tracer=tracer)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=4)
            for n in (3, 37, 20)]
    eng.run(reqs)
    spans = tracer.recent_spans()
    dec = [s for s in spans if s["name"] == "decode"]
    pre = [s for s in spans if s["name"] == "prefill"]
    assert dec and all(0 < s["window_tokens"] <= w * s["rows"] for s in dec)
    assert any(s["window_tokens"] < w * s["rows"] for s in dec)      # a context under 8
    pairs = lambda s, n: sum(min(p + 1, w) for p in range(s, s + n))
    assert pre and all(s["window_pairs"] == pairs(s["start"], s["tokens"]) for s in pre)
    assert {s["start"] for s in pre} >= {0, 16, 32}
    assert all(v.endswith(" window=ring-xla") for v in eng.attention_impls().values())   # the CPU


@pytest.mark.parametrize("what, kw", [
    ("prefix_cache_mb", {"prefix_cache_mb": 1}),
    ("spec_k", {"spec_k": 2, "draft": True}),
    ("tp=1", {"tp": 1}),
    ("kv_quant", {"kv_quant": "int8"})])
def test_engine_refuses_every_pages_only_path_by_name(tiny, what, kw):
    _, model, params = tiny
    kw = dict(kw)
    if kw.pop("draft", False):
        draft = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
        kw.update(draft_model=draft, draft_params=draft.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    with pytest.raises(ValueError,
                       match=r"per-slot state \(\['window_key', 'window_value'\] leaves\).*"
                       + what.split("=")[0]):
        _engine(model, params, **kw)


def test_the_two_shares_add_up_to_the_uncut_expert_layer(tiny):
    """ONE CHIP'S SHARE: the held experts' part of a layer of 8 routed experts,
    computed on two chips of 4 (the shared expert on one of them), adds up to
    the uncut layer's feed-forward — the part the post-norm takes after the
    exchange."""
    cfg, _, _ = tiny
    uncut = dict(cfg, num_experts=8)
    w = ref.make_layer(uncut, SEED, 1)
    x = jax.random.normal(jax.random.key(5), (21, cfg["hidden_size"]))
    mm = functools.partial(lowp.einsum, precision="f32")
    kw = dict(top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"],
              served_dtype=jnp.float32)
    share = lambda lo, shared: ref.feed_forward(
        x, {**w, **{k: w[k][lo:lo + 4] for k in ("mlp/w_gate", "mlp/w_up", "mlp/w_down")}},
        mm, offset=lo, shared=shared, **kw)[0]
    whole = ref.feed_forward(x, w, mm, offset=0, shared=True, **kw)[0]
    np.testing.assert_allclose(np.asarray(share(0, True) + share(4, False)), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)
    assert np.abs(np.asarray(share(4, False))).max() > 1e-3        # both shares do work


def test_the_pattern_is_one_object_a_kind():
    m = moe.MoEConfig(num_experts=8, top_k=2)
    lt = ("sliding_attention",) * 3 + ("full_attention",)
    pat = moe.exaone_pattern(lt * 2, ("dense",) + ("sparse",) * 7, m, 128)
    assert len({id(k) for k in pat}) == 3 and moe.exaone_pattern(
        lt * 2, ("dense",) + ("sparse",) * 7, m, 128) is pat
    assert all(k.post_norm and k.solo is None for k in pat)
    assert pat[0].attention.keywords == {"window": 128} and pat[0].mlp is None
    assert pat[3].attention.keywords == {"rope": False} and pat[3].mlp.keywords["moe"] == m
    with pytest.raises(ValueError, match="chunked"):
        moe.exaone_pattern(("chunked",), ("dense",), m, 128)


FAMILY_TREES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                            "family_trees.json")


def _tree(t):
    return {weights.path_name(p): [list(a.shape), str(a.dtype)]
            for p, a in jax.tree_util.tree_flatten_with_path(t)[0]}


@pytest.mark.parametrize("cell", [
    "mistral-7b-d16.chat-backlog", "sarvam-105b-ep4-d6.docs-backlog",
    "lfm2-8b-a1b-d14.chat-backlog-wide", "nemotron-3-super-ep4-d11.chat-backlog-wide",
    "jamba2-3b.chat-backlog-wide", "bert-base.mlm-s512"])
def test_every_existing_family_keeps_its_parameter_and_cache_trees(cell):
    """The rehearsal models of the five served configurations and of BERT,
    and their engines' caches, have the parameter and cache trees (names,
    shapes, types) the tree before the window layers built —
    ``fixtures/family_trees.json`` was written from that tree."""
    want = json.load(open(FAMILY_TREES))[cell]
    c = M.Cell(M.load_manifest(), cell)
    c.apply_rehearsal()
    if c.driver == "train":
        from k8s_distributed_deeplearning_tpu.models import bert
        model = bert.BertMLM(c.family().program_config(c.config))
        params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
        assert (_tree(params), {}) == (want["params"], want["cache"])
        return
    eo = c.options["engine"]
    model, params = c.family().build_model_and_params(c.config, eo["max_seq_len"], 7)
    eng = ServeEngine(model, params, num_slots=eo["num_slots"], min_bucket=eo["min_bucket"],
                      prefill_chunk_tokens=eo["prefill_chunk_tokens"],
                      prefix_cache_mb=eo.get("prefix_cache_mb"),
                      prefix_block_tokens=eo.get("page_tokens"),
                      kv_pool_pages=eo.get("kv_pool_pages"))
    assert _tree(params) == want["params"]
    assert _tree(eng._cache) == want["cache"]
    assert eng.stats.summary()["window_ring_bytes"] == 0
    assert eng.shutdown() == []


def test_a_window_layer_refuses_what_it_cannot_serve(tiny):
    cfg, model, params = tiny
    x = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="packed positions"):
        model.apply({"params": params}, x, positions=jnp.arange(4)[None])
    assert T._window_query_block(2048) == 128 and T._window_query_block(100) == 100
    assert T._window_query_block(1) == 1 and T._window_query_block(200) == 100


@pytest.mark.parametrize("start,real", [(0, 16), (8, 16), (3, 11), (40, 5)],
                         ids=["first-chunk", "after-one-window", "off-grid", "padded"])
def test_the_window_kernel_equals_the_jax_numpy_blocks(start, real):
    """``window_attn`` (interpret mode) against the ``jax.numpy`` band on the
    same extended keys: a first chunk (the ring all masked), chunks after one
    window and off the window's grid, a right-padded one — every real query's
    output, and the rings it leaves, as the XLA form's."""
    w, b, length, h, kv, hd = 8, 2, 16, 4, 2, 16
    ks = jax.random.split(jax.random.key(start + 1), 5)
    q = jax.random.normal(ks[0], (b, length, h, hd))
    k = jax.random.normal(ks[1], (b, length, kv, hd))
    v = jax.random.normal(ks[2], (b, length, kv, hd))
    ring_k = jax.random.normal(ks[3], (b, w, kv * hd))
    ring_v = jax.random.normal(ks[4], (b, w, kv * hd))
    st = jnp.asarray([start, start + 1], jnp.int32)
    lengths = jnp.asarray([real, real], jnp.int32)
    want = T.window_chunk_attention(q, k, v, ring_k, ring_v, st, w, lengths, impl="xla")
    got = T.window_chunk_attention(q, k, v, ring_k, ring_v, st, w, lengths, impl="kernel")
    np.testing.assert_allclose(np.asarray(got[0][:, :real]), np.asarray(want[0][:, :real]),
                               atol=1e-5, rtol=1e-5)
    for a, c in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_the_engine_names_the_window_path_each_program_takes(tiny):
    """A chunk of whole windows takes the kernel where it is asked for (here:
    forced, interpret mode) and serves what the XLA form serves; decode reads
    its ring in XLA."""
    cfg, model, params = tiny
    forced = model.clone(cfg=dataclasses.replace(model.cfg, attention_impl="paged_flash"))
    impls = _engine(forced, params).attention_impls()
    assert impls["decode"].endswith(" window=ring-xla")
    assert impls["chunk_16"].endswith(" window=kernel")
    assert T.window_attention_impl(model.cfg, 2048, 128) == "xla"          # off the TPU
    assert T.window_attention_impl(forced.cfg, 12, 8) == "xla"             # not whole windows
    prompt = np.random.default_rng(3).integers(0, 256, size=45).tolist()
    want = _engine(model, params).run([Request(prompt=prompt, max_new_tokens=6)])[0].tokens
    got = _engine(forced, params).run([Request(prompt=prompt, max_new_tokens=6)])[0].tokens
    assert got == want
