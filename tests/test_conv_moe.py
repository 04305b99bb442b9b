"""The gated-short-convolution + sparse-expert family (LFM2-MoE layout) at a
tiny float32 size on the CPU: the convolution mixer fed whole, in uneven
chunks and token by token; a padded final chunk leaving the last REAL
token's state; the model on every cache path against the plain reference;
``ServeEngine``'s pages and state arena across slot reuse, a cancel, a
migration and a deadline; and every pages-only path refused by name."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_conv_moe as fam
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_conv_moe as ref
from k8s_distributed_deeplearning_tpu.models import generate, llama, moe
from k8s_distributed_deeplearning_tpu.models import transformer as T
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.request import Request
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer

CELL = "lfm2-8b-a1b-d14.chat-backlog-wide"
SEED = 3_100_000_019
# float32 end to end; what is left is the order of the sums (a chunk's
# attention against one pass's, the experts' gated sum)
TOL = 5e-5


@pytest.fixture(scope="module")
def tiny():
    """(cfg dict, model, params) of the cell's rehearsal size (6 layers:
    c c A c c c, two dense then four expert layers), weights from the seed as
    the benchmark makes them."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    return cell.config, model, params


def _ref_logits(cfg, toks):
    lg, _, _ = ref.forward_logits(cfg, SEED, [np.asarray(toks)], [np.arange(len(toks))])
    return lg[0]


def _conv(width=3):
    cfg = T.TransformerConfig(dim=32, n_heads=2, dtype=jnp.float32, max_seq_len=64)
    layer = T.ShortConv(cfg, width=width)
    x = jax.random.normal(jax.random.key(0), (2, 23, 32))
    params = nn.meta.unbox(layer.init(jax.random.key(1), x)["params"])
    return layer, params, x


@pytest.mark.parametrize("splits", [(23,), (1, 7, 2, 13), (5, 18), (1,) * 23],
                         ids=["whole", "uneven", "two", "token-by-token"])
@pytest.mark.parametrize("width", [3, 4])
def test_short_conv_whole_equals_chunked_equals_token_by_token(splits, width):
    layer, params, x = _conv(width)
    want = layer.apply({"params": params}, x)               # zeros before the sequence
    got, cache, at = [], None, 0
    for n in splits:
        variables = {"params": params} if cache is None else {"params": params, "cache": cache}
        y, new = layer.apply(variables, x[:, at:at + n], decode=True, mutable=["cache"])
        cache, at = new["cache"], at + n
        got.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want),
                               atol=1e-6)
    assert cache["conv_state"].shape == (2, width - 1, 32)


def test_short_conv_against_the_equation_position_by_position():
    layer, params, x = _conv()
    got = np.asarray(layer.apply({"params": params}, x))
    w_in, taps, w_out = (np.asarray(params["in_proj"]["kernel"]), np.asarray(params["conv"]),
                         np.asarray(params["out_proj"]["kernel"]))
    xs = np.asarray(x)
    for b in range(2):
        bcu = xs[b] @ w_in
        z = bcu[:, :32] * bcu[:, 64:]
        for t in (0, 1, 2, 11, 22):
            v = sum(taps[j] * (z[t - 2 + j] if t - 2 + j >= 0 else 0.0) for j in range(3))
            np.testing.assert_allclose(got[b, t], (bcu[t, 32:64] * v) @ w_out, atol=1e-5)


@pytest.mark.parametrize("real", [1, 2, 5, 16])
def test_a_padded_final_chunk_leaves_the_last_real_tokens_state(real):
    """A chunk right-padded to its bucket of 16: with ``lengths`` the state is
    what the ``real`` tokens alone leave (the carried-in tail where fewer
    than two are real); without, the pad's."""
    layer, params, x = _conv()
    _, before = layer.apply({"params": params}, x[:, :7], decode=True, mutable=["cache"])
    padded = x[:, 7:23].at[:, real:].set(9.0)
    run = lambda chunk, **kw: layer.apply(
        {"params": params, "cache": before["cache"]}, chunk, decode=True, mutable=["cache"], **kw)
    want_y, want = run(x[:, 7:7 + real])
    got_y, got = run(padded, lengths=jnp.full((2,), real, jnp.int32))
    np.testing.assert_allclose(np.asarray(got["cache"]["conv_state"]),
                               np.asarray(want["cache"]["conv_state"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y[:, :real]), np.asarray(want_y), atol=1e-6)
    if real < 16:
        _, pads = run(padded)
        assert not np.allclose(np.asarray(pads["cache"]["conv_state"]),
                               np.asarray(want["cache"]["conv_state"]))


def test_model_forward_and_row_cache_equal_the_reference(tiny):
    """The plain forward, and a prefill of 9 tokens then single steps through
    ``generate()``'s row cache (``conv_state`` made by the model itself)."""
    cfg, model, params = tiny
    toks = np.random.default_rng(0).integers(0, 256, size=37).astype(np.int32)
    want = _ref_logits(cfg, toks)
    full = np.asarray(model.apply({"params": params}, jnp.asarray(toks)[None])[0])
    lg, cache = generate.prefill(model, params, jnp.asarray(toks[:9])[None])
    steps = [np.asarray(lg[0])]
    for t in toks[9:]:
        l1, cache = generate.decode_step(model, params, cache, jnp.asarray([t]))
        steps.append(np.asarray(l1))
    assert np.abs(full - want).max() < TOL
    assert np.abs(np.concatenate(steps, axis=0) - want).max() < TOL
    assert model.cfg.norm_eps == 1e-5 and model.cfg.qk_norm and model.cfg.tie_embeddings
    assert "head" not in params                                 # the embedding is the head


def _arena_for(model, pages, page_tokens, slots):
    cfg = model.cfg
    lanes = cfg.resolved_kv_heads * cfg.resolved_head_dim
    pool = lambda: jnp.zeros((pages, page_tokens, lanes), jnp.float32)
    return {"transformer": {f"block_{i}": {"attn": (
        {"conv_state": jnp.zeros((slots, 2, cfg.dim), jnp.float32)} if kind.attention is not None
        else {"cached_key": pool(), "cached_value": pool()})}
        for i, kind in enumerate(model.pattern)}}


@pytest.mark.parametrize("impl", ["xla", "paged_flash"])
def test_paged_chunks_then_slot_decode_equal_reference_logits(tiny, impl):
    """Prefill in chunks of 16 through the block table (the per-head q/k norms
    on the chunk path), the last one padded, then slot decode beside an idle
    row — through the XLA gather and through the paged kernel (interpret
    mode) — against the logits of the reference's ONE full forward."""
    cfg, model, params = tiny
    model = model.clone(cfg=dataclasses.replace(model.cfg, attention_impl=impl))
    toks = np.random.default_rng(1).integers(0, 256, size=51).astype(np.int32)
    want = _ref_logits(cfg, toks)
    pt, n_blocks = 8, 8
    arena = _arena_for(model, 20, pt, slots=1)
    table = np.zeros((2, n_blocks), np.int32)
    table[1, :7] = [7, 3, 11, 5, 2, 9, 14]         # row 1 is the request, row 0 idle
    got = []
    for a, n in ((0, 16), (16, 16), (32, 6)):      # the last chunk: 6 real of a bucket of 16
        chunk = np.zeros(16, np.int32)
        chunk[:n] = toks[a:a + n]
        pos = (a + jnp.arange(16, dtype=jnp.int32))[None]
        lg, arena, counts = generate.prefill_chunk(
            model, params, arena, jnp.asarray(chunk)[None], positions=pos,
            block_tables=jnp.asarray(table[1:2]), lengths=jnp.asarray([n], jnp.int32))
        got.append(np.asarray(lg[0, :n]))
        assert counts.shape == (4, cfg["num_experts"]) and int(counts.sum()) == 16 * 2 * 4
    # the decode call's batch is the slots: row 1 carries the request's state
    state = lambda leaf: jnp.concatenate([jnp.zeros_like(leaf), leaf], axis=0)
    arena = jax.tree_util.tree_map_with_path(
        lambda p, leaf: state(leaf) if p[-1].key == "conv_state" else leaf, arena)
    for i in range(38, 51):
        lg, arena, _ = generate.slot_decode_step(
            model, params, arena, jnp.asarray([0, toks[i]], jnp.int32),
            jnp.asarray([0, i], jnp.int32), block_tables=jnp.asarray(table))
        got.append(np.asarray(lg[1:2]))
    assert np.abs(np.concatenate(got, axis=0) - want).max() < TOL


def _engine(model, params, **kw):
    kw = {"num_slots": 3, "min_bucket": 16, "prefill_chunk_tokens": 16,
          "prefix_block_tokens": 8, "kv_pool_pages": 64, **kw}
    return ServeEngine(model, params, **kw)


def test_engine_pages_and_state_arena_equal_the_reference_across_slot_reuse(tiny):
    """Ten requests through three slots — prompts of one token, of exactly a
    chunk, of several chunks and a padded tail — with a cancel mid-prefill, a
    migration (a decoding request's slot taken away) and an expired deadline
    between them: every request that ran to its end was served, token for
    token, what the reference's ONE full forward pass puts first (every gap
    under the float32 tolerance), and equals one-shot ``generate()``. The three
    programs compile once; nothing is left behind."""
    cfg, model, params = tiny
    tracer = Tracer(ring_size=8192)
    eng = _engine(model, params, tracer=tracer)
    rng = np.random.default_rng(2)
    sizes = [(40, 6), (17, 5), (9, 8), (33, 4), (50, 7), (21, 3), (16, 5), (32, 5), (1, 4),
             (47, 6)]
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=k,
                    request_id=f"r{i}") for i, (n, k) in enumerate(sizes)]
    reqs[3].deadline_s = 1e-9                                  # expires in the queue or a slot
    for r in reqs:
        eng.submit(r)
    done, cancelled = [], {}
    done.extend(eng.step())                                     # r0-r2 admitted, first chunks
    sizes_before = None
    cancelled["r0"] = eng.cancel("r0")                          # mid-prefill (40 tokens: 3 chunks)
    assert cancelled["r0"].finish_reason == "aborted" and not cancelled["r0"].tokens
    while eng.busy():
        done.extend(eng.step())
        if "r4" not in cancelled and any(
                fl is not None and fl.req.request_id == "r4" and len(fl.tokens) >= 2
                for fl in eng._slots):
            cancelled["r4"] = eng.cancel("r4", reason="migrated")   # decoding: slot taken away
            sizes_before = (eng.decode_cache_size(), eng.chunk_cache_size(),
                            eng.prefill_cache_size())
    assert (eng.decode_cache_size(), eng.chunk_cache_size(),
            eng.prefill_cache_size()) == sizes_before
    outs = {o.request_id: o for o in done}
    assert outs["r3"].finish_reason == "timeout" and 2 <= len(cancelled["r4"].tokens) < 7
    served = [r for r in reqs if r.request_id not in ("r0", "r3", "r4")]
    assert len(served) == 7 and all(outs[r.request_id].finish_reason == "length" for r in served)
    for r in served:
        want = generate.generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                                 max_new_tokens=r.max_new_tokens)
        assert outs[r.request_id].tokens == np.asarray(want[0]).tolist()
    sample = [{"prompt": np.asarray(r.prompt), "tokens": np.asarray(outs[r.request_id].tokens)}
              for r in served] + [{"prompt": np.asarray(reqs[4].prompt),
                                   "tokens": np.asarray(cancelled["r4"].tokens)}]
    sc = ref.score_served(cfg, SEED, sample)
    assert sc["gaps"]["all_max"] < 1e-4 and sc["not_reference_best"] == 0
    assert sc["tokens"] == sum(len(s["tokens"]) for s in sample)
    # the arena: one row a slot beside the pages, and nothing held at the end
    leaf = eng._cache["transformer"]["block_0"]["attn"]["conv_state"]
    assert leaf.shape == (3, 2, 64) and eng._slot_state_nbytes == 5 * 2 * 64 * 4
    assert eng._cache["transformer"]["block_2"]["attn"]["cached_key"].shape == (65, 8, 32)
    spans = tracer.recent_spans()
    ep = [s for s in spans if s["name"] == "epilogue"]
    assert max(s["state_slots"] for s in ep) == 3 and ep[-1]["state_slots"] == 0
    assert all(s["state_bytes"] == s["state_slots"] * eng._slot_state_nbytes for s in ep)
    summ = eng.stats.summary()
    assert summ["state_slots"] == 0 and summ["state_bytes"] == 0 and summ["kv_pages_used"] == 0
    calls = [s for s in spans if s["name"] in ("prefill", "prefill_counts")]
    assert {s["state_from"] for s in calls} == {"zero", "carried"}
    assert all((s["state_from"] == "zero") == (s["start"] == 0) for s in calls)
    dec = [s for s in spans if s["name"] == "decode"]
    assert all(s["moe_assignments"] == 3 * 2 * 4 for s in dec)   # rows x top-2 x 4 expert layers
    assert eng.shutdown() == []


def test_a_reused_slot_starts_from_zeros_whatever_its_last_occupant_left(tiny):
    """No host-side clear: the arena row of a freed slot keeps its garbage, and
    the next request's first chunk (``start == 0``) never reads it."""
    _, model, params = tiny
    eng = _engine(model, params, num_slots=2)
    prompt = np.random.default_rng(5).integers(0, 256, size=21).tolist()
    first = eng.run([Request(prompt=prompt, max_new_tokens=5, request_id="a")])[0].tokens
    eng._cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.full_like(leaf, 1e3) if p[-1].key == "conv_state" else leaf,
        eng._cache)
    again = eng.run([Request(prompt=prompt, max_new_tokens=5, request_id="b")])[0].tokens
    assert again == first


def test_a_slot_mid_prefill_keeps_its_state_under_other_slots_decodes(tiny):
    """The decode program rides every slot; a prompt's state carried from
    chunk to chunk must not be advanced by the rider's pad token. One long
    prompt admitted while another request decodes: its chunks interleave with
    decode steps. Mid-decode, the slot's arena row IS the tail the same tokens
    leave in ``generate()``'s own row cache, to the last bit of float32 — the
    arena's plumbing held to the state itself, not to an argmax."""
    _, model, params = tiny
    eng = _engine(model, params, num_slots=2)
    rng = np.random.default_rng(6)
    short = Request(prompt=rng.integers(0, 256, size=5).tolist(), max_new_tokens=12,
                    request_id="short")
    long_ = Request(prompt=rng.integers(0, 256, size=90).tolist(), max_new_tokens=9,
                    request_id="long")
    eng.submit(short)
    outs = eng.step() + eng.step()                          # `short` is decoding
    eng.submit(long_)
    slot = lambda: next((i for i, fl in enumerate(eng._slots) if fl is not None
                         and fl.req.request_id == "long" and len(fl.tokens) >= 4), None)
    while slot() is None:
        outs += eng.step()
    i = slot()
    consumed = long_.prompt + eng._slots[i].tokens[:-1]     # all but the token not yet fed
    assert int(eng._kv_lens[i]) == len(consumed)
    _, rows = generate.prefill(model, params, jnp.asarray([consumed], jnp.int32))
    for b in (0, 1, 3, 4, 5):
        np.testing.assert_allclose(
            np.asarray(eng._cache["transformer"][f"block_{b}"]["attn"]["conv_state"][i]),
            np.asarray(rows["transformer"][f"block_{b}"]["attn"]["conv_state"][0]), atol=1e-6)
    outs = {o.request_id: o for o in outs + eng.run()}
    assert outs["long"].prefill_chunks == 6
    for r in (short, long_):
        want = generate.generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                                 max_new_tokens=r.max_new_tokens)
        assert outs[r.request_id].tokens == np.asarray(want[0]).tolist()


@pytest.mark.parametrize("what, kw", [
    ("prefix_cache_mb", {"prefix_cache_mb": 1}),
    ("spec_k", {"spec_k": 2, "draft": True}),
    ("tp=1", {"tp": 1}),
    ("kv_quant", {"kv_quant": "int8"}),
    ("prefill_only", {"prefill_only": True})])
def test_engine_refuses_every_pages_only_path_by_name(tiny, what, kw):
    _, model, params = tiny
    kw = dict(kw)
    if kw.pop("draft", False):
        draft = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
        kw.update(draft_model=draft, draft_params=draft.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    with pytest.raises(ValueError, match=r"per-slot state \(\['conv_state'\] leaves\).*"
                       + what.split("=")[0]):
        _engine(model, params, **kw)


@pytest.mark.parametrize("call", ["export_request_kv", "import_request_kv", "can_import"])
def test_kv_shipping_is_refused_by_name_for_a_model_with_state(tiny, call):
    _, model, params = tiny
    eng = _engine(model, params)
    eng.submit(Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=6, request_id="x"))
    for _ in range(3):
        eng.step()
    blob = {"request_id": "y", "page_tokens": 8, "kv_quant": None, "prompt": [1, 2, 3],
            "max_new_tokens": 4, "n_pages": 1, "emitted": [7], "kv_len": 3}
    if call == "can_import":
        assert eng.can_import(blob) is False
        return
    with pytest.raises(ValueError, match=call + r".*per-slot state \(\['conv_state'\]"):
        eng.export_request_kv("x") if call == "export_request_kv" else eng.import_request_kv(blob)
    assert eng.occupied_slots() == 1                        # nothing was released or adopted
    eng.shutdown()


def test_a_draft_with_state_is_refused(tiny):
    _, model, params = tiny
    target = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
    tparams = target.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="draft model with per-slot state"):
        ServeEngine(target, tparams, num_slots=2, draft_model=model, draft_params=params,
                    spec_k=2)


def test_attention_impls_names_each_programs_expert_dispatch(tiny):
    _, model, params = tiny
    eng = _engine(model, params, num_slots=4, prefill_chunk_tokens=64, min_bucket=32)
    # 8 experts top-2: a held expert expects rows / 4 — 32 from 128 rows on
    assert eng.attention_impls() == {
        "decode": "xla experts=dense", "chunk_64": "xla experts=dense",
        "final_chunk_32": "xla experts=dense", "final_chunk_64": "xla experts=dense"}
    assert moe.moe_config_of(model).num_experts == 8
    assert moe.moe_config_of(llama.LlamaLM(llama.config_tiny())) is None
    # from 128 rows the grouped kernel, which makes the hidden activation itself
    wide = _engine(model, params, num_slots=4, prefill_chunk_tokens=128, min_bucket=64)
    assert wide.attention_impls() == {
        "decode": "xla experts=dense", "chunk_128": "xla experts=grouped hidden=fused",
        "final_chunk_64": "xla experts=dense",
        "final_chunk_128": "xla experts=grouped hidden=fused"}


def test_wide_chunks_through_the_kernel_serve_the_xla_paths_tokens():
    """256-token chunks, wider than one grid row of the paged kernel: the
    engine forced to ``paged_flash`` (the kernel in interpret mode, each chunk
    attending in two blocks of 128 queries) serves token for token what the
    XLA gather-attend serves, over prompts of a chunk and a padded tail, of
    several chunks, and of less than one; and every ``prefill`` span and
    ``prefill_counts`` record says which of the two its program took."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 1024, SEED)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (300, 700, 90)]

    def run(impl):
        tracer = Tracer(ring_size=4096)
        eng = ServeEngine(
            model.clone(cfg=dataclasses.replace(model.cfg, attention_impl=impl)), params,
            num_slots=2, min_bucket=256, prefill_chunk_tokens=256, prefix_block_tokens=32,
            kv_pool_pages=64, tracer=tracer)
        reqs = [Request(prompt=p, max_new_tokens=4, request_id=f"r{i}")
                for i, p in enumerate(prompts)]
        outs = {o.request_id: o.tokens for o in eng.run(reqs)}
        calls = [s for s in tracer.recent_spans() if s["name"] in ("prefill", "prefill_counts")]
        assert {s["name"] for s in calls} == {"prefill", "prefill_counts"}
        assert {s["attn"] for s in calls} == {impl} and len(calls) == 2 * 6
        return [outs[r.request_id] for r in reqs], eng.attention_impls()

    served, impls = run("paged_flash")
    assert impls["chunk_256"] == impls["final_chunk_256"] == (
        "paged_flash q_block=128 pages_per_cell=1 cells=64 experts=grouped hidden=fused")
    assert impls["decode"] == "paged_flash pages_per_cell=1 cells=64 experts=dense"
    assert served == run("xla")[0] and all(len(t) == 4 for t in served)


def test_the_pattern_is_one_object_a_kind_and_models_built_twice_are_equal(tiny):
    cfg, model, _ = tiny
    kinds = model.pattern
    assert [k.attention is None for k in kinds] == [False, False, True, False, False, False]
    assert [k.mlp is None for k in kinds] == [True, True, False, False, False, False]
    assert kinds[0] is kinds[1] and kinds[3] is kinds[4] is kinds[5] and kinds[0] != kinds[3]
    again = T.PatternLM(*fam.program_config(cfg, 128))
    assert again == model and hash(again) == hash(model)
    with pytest.raises(ValueError, match="layer_types names"):
        moe.conv_moe_pattern(("conv", "window"), moe.MoEConfig(), 0)
    with pytest.raises(ValueError, match="cannot be scanned"):
        T.PatternLM(dataclasses.replace(model.cfg, scan_layers=True), kinds).init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("eps", [1e-6, 1e-5, 1e-2])
def test_every_norm_takes_its_epsilon_from_the_config(eps):
    x = 1e-2 * jax.random.normal(jax.random.key(0), (2, 5, 16))
    scale = jnp.ones((16,))
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    cfg = T.TransformerConfig(dim=16, n_heads=2, norm_eps=eps, dtype=jnp.float32)
    got = T.make_norm(cfg, "n").apply({"params": {"scale": scale}}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    ln = T.make_norm(dataclasses.replace(cfg, norm="layernorm"), "n")
    assert ln.epsilon == eps and T.TransformerConfig().norm_eps == 1e-6
    # the per-head q/k norms too
    attn = T.Attention(dataclasses.replace(cfg, qk_norm=True, position="none"))
    params = nn.meta.unbox(attn.init(jax.random.key(1), x)["params"])
    assert params["q_norm"]["scale"].shape == params["k_norm"]["scale"].shape == (8,)
    q = jnp.einsum("bsd,dhk->bshk", x, params["q_proj"]["kernel"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["k_proj"]["kernel"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["v_proj"]["kernel"])
    rms = lambda a: a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
    s = jnp.einsum("bqhk,bthk->bhqt", rms(q), rms(k)) * 8 ** -0.5
    s = jnp.where(jnp.arange(5)[None, :] <= jnp.arange(5)[:, None], s, -jnp.inf)
    o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(s, -1), v)
    want = jnp.einsum("bqhk,hkd->bqd", o, params["o_proj"]["kernel"])
    np.testing.assert_allclose(np.asarray(attn.apply({"params": params}, x)), np.asarray(want),
                               atol=1e-6)
