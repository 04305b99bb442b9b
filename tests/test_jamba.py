"""The Mamba-1 + multi-query attention family (Jamba layout) at a tiny float32
size on the CPU: the ``Mamba1`` mixer fed whole, in uneven chunks and token by
token against the reference's scan over tokens (its output AND its state); a
padded final chunk leaving the last REAL token's state; both kernels
(interpret mode) against their ``jax.numpy`` references on the arena's
layout (the paged kernel at a group of 20 query heads a KV head is a case of
``test_pallas_paged_attn.py``); the model on every cache path against the
plain reference;
``ServeEngine``'s pages and state arena — the STATE itself held to
``generate()``'s row cache, every pages-only path refused by name — and what
the engine says each program runs."""
import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_jamba as fam
from benchmarks.harness import lowp
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_jamba as ref
from k8s_distributed_deeplearning_tpu.models import generate, llama, moe
from k8s_distributed_deeplearning_tpu.models import transformer as T
from k8s_distributed_deeplearning_tpu.ops import pallas_selective_scan as S
from k8s_distributed_deeplearning_tpu.serve import engine as E
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.request import Request
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer

CELL = "jamba2-3b.chat-backlog-wide"
SEED = 3_700_000_019
TOL = 5e-5          # float32 end to end; what is left is the order of the sums


@pytest.fixture(scope="module")
def tiny():
    """(cfg dict, model, params) of the cell's rehearsal size (4 layers, the
    third attention: M M A M; 4 query heads on one KV head; 128 channels of
    16 state columns), weights from the seed as the benchmark makes them."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    return cell.config, model, params


def _ref_logits(cfg, toks, **kw):
    return ref.forward_logits(cfg, SEED, [np.asarray(toks)], [np.arange(len(toks))], **kw)[0]


MAMBA = T.Mamba1Config(inner=64, state_size=16, dt_rank=4)


def _mixer(seq=29):
    cfg = T.TransformerConfig(dim=32, n_heads=2, dtype=jnp.float32, max_seq_len=64,
                              norm_eps=1e-6, position="none")
    layer = T.Mamba1(cfg, MAMBA)
    x = jax.random.normal(jax.random.key(0), (2, seq, 32))
    params = nn.meta.unbox(layer.init(jax.random.key(1), x)["params"])
    return layer, params, x


def _reference_mixer(params, x):
    """The reference's ``mamba`` (a ``lax.scan`` over tokens on the published
    ``[E, N]`` state) on the module's own parameters, row by row: (outputs,
    final states ``[B, E, N]``)."""
    w = {"attn/" + k: v for k, v in {
        "in_proj/kernel": params["in_proj"]["kernel"], "conv": params["conv"],
        "conv_bias": params["conv_bias"], "x_proj/kernel": params["x_proj"]["kernel"],
        "dt_norm/scale": params["dt_norm"]["scale"], "b_norm/scale": params["b_norm"]["scale"],
        "c_norm/scale": params["c_norm"]["scale"], "dt_proj/kernel": params["dt_proj"]["kernel"],
        "dt_proj/bias": params["dt_proj"]["bias"], "A_log": params["A_log"], "D": params["D"],
        "out_proj/kernel": params["out_proj"]["kernel"]}.items()}
    mm = functools.partial(lowp.einsum, precision="f32")
    outs = [ref.mamba(row, w, mm, sizes=(64, 16, 4, 4), eps=1e-6, return_state=True)
            for row in x]
    return jnp.stack([o for o, _ in outs]), jnp.stack([s for _, s in outs])


@pytest.mark.parametrize("seq", [8, 29, 1], ids=["eight", "ragged", "one"])
def test_mamba1_whole_equals_the_references_scan_output_and_state(seq):
    layer, params, x = _mixer(seq)
    want, state = _reference_mixer(params, x)
    got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=TOL)
    # the state a row cache is left with: [B, N, E], the reference's transposed
    out, cache = layer.apply({"params": params}, x, decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(np.asarray(cache["cache"]["ssm_state"]),
                               np.asarray(jnp.swapaxes(state, 1, 2)), atol=TOL, rtol=TOL)
    assert cache["cache"]["ssm_state"].dtype == jnp.float32
    assert cache["cache"]["conv_state"].shape == (2, 3, 64)
    assert np.abs(np.asarray(state)).max() > 1e-2          # a state worth carrying


@pytest.mark.parametrize("splits", [(13, 16), (1,) * 29, (4, 9, 16)],
                         ids=["two-chunks", "token-by-token", "uneven"])
def test_mamba1_whole_equals_chunked_equals_token_by_token(splits):
    layer, params, x = _mixer(29)
    want = layer.apply({"params": params}, x)
    cache, outs, at = None, [], 0
    for n in splits:
        v = {"params": params} if cache is None else {"params": params, "cache": cache}
        out, mut = layer.apply(v, x[:, at:at + n], decode=True, mutable=["cache"])
        cache, at = mut["cache"], at + n
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(want),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("real", [5, 16], ids=["padded", "full"])
def test_a_padded_final_chunk_leaves_the_last_real_tokens_state(real):
    layer, params, x = _mixer(16)
    _, exact = layer.apply({"params": params}, x[:, :real], decode=True, mutable=["cache"])
    padded = x.at[:, real:].set(3.0)                      # junk where the pads are
    _, got = layer.apply({"params": params}, padded, decode=True, mutable=["cache"],
                         lengths=jnp.full((2,), real, jnp.int32))
    for leaf in ("conv_state", "ssm_state"):
        np.testing.assert_allclose(np.asarray(got["cache"][leaf]),
                                   np.asarray(exact["cache"][leaf]), atol=1e-6)


def _scan_case(b=2, length=21, n=16, e=256, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, length, e)),
            jax.nn.softplus(jax.random.normal(k[1], (b, length, e)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (n, e))),
            jax.random.normal(k[3], (b, length, n)), jax.random.normal(k[4], (b, length, n)),
            jax.random.normal(k[5], (b, n, e)))


@pytest.mark.parametrize("length,segment", [(21, 512), (40, 16), (8, 512)],
                         ids=["ragged", "three-segments", "one-group"])
def test_selective_scan_kernel_equals_the_scan_over_tokens(length, segment, monkeypatch):
    """The kernel (interpret mode) against ``lax.scan`` over tokens on the
    arena's ``[N, E]`` layout: the outputs, and the state it leaves — through
    a call cut into segments too (the state carried between them)."""
    monkeypatch.setattr(S, "SCAN_TOKENS", segment)
    case = _scan_case(length=length)
    want_y, want_h = S.selective_scan_reference(*case)
    y, h = S.selective_scan(*case, interpret=True)
    assert y.shape == want_y.shape and h.shape == want_h.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-5, rtol=2e-5)


def test_a_pad_past_the_real_tokens_moves_no_state():
    """dt = 0 at a pad (what the mixer makes of ``lengths``): the state after
    the real tokens and any pads is the state after the real tokens."""
    u, dt, a, bm, cm, h0 = _scan_case(length=24)
    _, exact = S.selective_scan(u[:, :13], dt[:, :13], a, bm[:, :13], cm[:, :13], h0,
                                interpret=True)
    _, padded = S.selective_scan(u, dt.at[:, 13:].set(0.0), a, bm, cm, h0, interpret=True)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(exact))


@pytest.mark.parametrize("live", [[True, False, True, False], [False] * 4, [True] * 4,
                                  [False, False, False, True]],
                         ids=["half", "none", "all", "last"])
def test_selective_update_kernel_equals_jax_numpy_and_touches_live_rows_only(live):
    u, dt, a, bm, cm, _ = _scan_case(b=4, length=1, e=384)
    state = jax.random.normal(jax.random.key(9), (4, 16, 384))
    live = jnp.asarray(live)
    args = (u[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], live)
    want_s, want_y = S.selective_update_reference(state, *args)
    got_s, got_y = S.selective_update(state, *args, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y), atol=2e-5, rtol=2e-5)
    idle = ~np.asarray(live)
    # a row at cursor 0 comes back bit for bit, and its y is 0
    np.testing.assert_array_equal(np.asarray(got_s)[idle], np.asarray(state)[idle])
    assert not np.asarray(got_y)[idle].any()
    with pytest.raises(ValueError, match="float32 arena"):
        S.selective_update(state.astype(jnp.bfloat16), *args, interpret=True)


def test_model_forward_and_row_cache_equal_the_reference(tiny):
    """The plain forward, and a prefill of 9 tokens then single steps through
    ``generate()``'s row cache (both state leaves made by the model itself)."""
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
    assert model.cfg.position == "none" and model.cfg.activation == "swiglu"
    assert "head" not in params and model.cfg.tie_embeddings        # tied
    assert [ref.is_attention(cfg, i) for i in range(4)] == [False, False, True, False]


def _arena_for(model, cfg, pages, page_tokens, slots):
    lanes = model.cfg.resolved_kv_heads * model.cfg.resolved_head_dim
    e, n, _, k = ref.mamba_sizes(cfg)
    pool = lambda: jnp.zeros((pages, page_tokens, lanes), jnp.float32)
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        out[f"block_{i}"] = {"attn": (
            {"cached_key": pool(), "cached_value": pool()} if ref.is_attention(cfg, i) else
            {"conv_state": jnp.zeros((slots, k - 1, e), jnp.float32),
             "ssm_state": jnp.zeros((slots, n, e), jnp.float32)})}
    return {"transformer": out}


@pytest.mark.parametrize("impl", ["xla", "paged_flash"])
def test_paged_chunks_then_slot_decode_equal_reference_logits(tiny, impl):
    """Prefill in chunks of 16 through the block table, the last one padded,
    then slot decode beside an idle row (whose state must stay zero) — through
    the XLA gather and through the paged kernel (interpret mode, 4 query heads
    on the one KV head) — against ALL the logits of the reference's ONE full
    forward."""
    cfg, model, params = tiny
    model = model.clone(cfg=dataclasses.replace(model.cfg, attention_impl=impl))
    toks = np.random.default_rng(1).integers(0, 256, size=51).astype(np.int32)
    want = _ref_logits(cfg, toks)
    pt, n_blocks = 8, 8
    arena = _arena_for(model, cfg, 20, pt, slots=1)
    table = np.zeros((2, n_blocks), np.int32)
    table[1, :7] = [7, 3, 11, 5, 2, 9, 14]         # row 1 is the request, row 0 idle
    got = []
    for a, n in ((0, 16), (16, 16), (32, 6)):      # the last chunk: 6 real of a bucket of 16
        chunk = np.zeros(16, np.int32)
        chunk[:n] = toks[a:a + n]
        pos = (a + jnp.arange(16, dtype=jnp.int32))[None]
        lg, arena, _ = generate.prefill_chunk(
            model, params, arena, jnp.asarray(chunk)[None], positions=pos,
            block_tables=jnp.asarray(table[1:2]), lengths=jnp.asarray([n], jnp.int32))
        got.append(np.asarray(lg[0, :n]))
    state = lambda leaf: jnp.concatenate([jnp.zeros_like(leaf), leaf], axis=0)
    arena = jax.tree_util.tree_map_with_path(
        lambda p, leaf: state(leaf) if p[-1].key in E._STATE_LEAVES else leaf, arena)
    for i in range(38, 51):
        lg, arena, _ = generate.slot_decode_step(
            model, params, arena, jnp.asarray([0, toks[i]], jnp.int32),
            jnp.asarray([0, i], jnp.int32), block_tables=jnp.asarray(table))
        got.append(np.asarray(lg[1:2]))
    assert np.abs(np.concatenate(got, axis=0) - want).max() < TOL
    idle = arena["transformer"]["block_0"]["attn"]["ssm_state"][0]
    assert not np.asarray(idle).any()              # masked inside the update, not by the engine


def _engine(model, params, **kw):
    kw = {"num_slots": 3, "min_bucket": 16, "prefill_chunk_tokens": 16,
          "prefix_block_tokens": 8, "kv_pool_pages": 64, **kw}
    return ServeEngine(model, params, **kw)


def test_engine_pages_and_state_arena_equal_the_reference_across_slot_reuse(tiny):
    """Seven requests through three slots — prompts of one token, of exactly a
    chunk, of several chunks and a padded tail: every request was served,
    token for token, what one-shot ``generate()`` serves and the reference's
    ONE full forward pass puts first; the arena is one row a slot beside the
    pages, found by name; the decode spans count the rows the update moved."""
    cfg, model, params = tiny
    tracer = Tracer(ring_size=8192)
    eng = _engine(model, params, tracer=tracer)
    rng = np.random.default_rng(2)
    sizes = [(40, 6), (17, 5), (9, 8), (33, 4), (16, 5), (50, 3), (1, 4)]
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
    assert attn["ssm_state"].shape == (3, 16, 128) and attn["ssm_state"].dtype == jnp.float32
    assert attn["conv_state"].shape == (3, 3, 128)
    assert eng._state_names == ["conv_state", "ssm_state"]
    assert eng._slot_state_nbytes == 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert eng._cache["transformer"]["block_2"]["attn"]["cached_key"].shape == (65, 8, 16)
    dec = [s for s in tracer.recent_spans() if s["name"] == "decode"]
    assert dec and all(1 <= s["state_rows"] <= 3 for s in dec)
    assert all(s["state_bytes_moved"] == 2 * s["state_rows"] * eng._slot_state_nbytes
               for s in dec)
    assert eng.shutdown() == []


def test_a_slot_mid_prefill_keeps_its_state_and_a_decoding_slot_holds_the_row_caches(tiny):
    """One long prompt admitted while another request decodes: between two of
    its chunks the slot's rows are, to the last bit, what the chunk left
    (decode steps ride every slot and the update masks the row out); once it
    decodes, its rows ARE the state the same tokens leave in ``generate()``'s
    own row cache — the STATE, not a token, is held to the reference path."""
    _, model, params = tiny
    eng = _engine(model, params, num_slots=2)
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
    mamba_blocks = (0, 1, 3)
    rows = lambda i: [np.asarray(eng._cache["transformer"][f"block_{b}"]["attn"][leaf][i])
                      for b in mamba_blocks for leaf in ("conv_state", "ssm_state")]
    held = rows(slot)
    for _ in range(3):
        nxt, _, eng._cache = eng._decode_step()
        jax.block_until_ready(nxt)
    for before, after in zip(held, rows(slot)):
        np.testing.assert_array_equal(before, after)
    while not any(fl is not None and fl.req.request_id == "long" and len(fl.tokens) >= 4
                  for fl in eng._slots):
        outs += eng.step()
    i = next(j for j, fl in enumerate(eng._slots) if fl is not None
             and fl.req.request_id == "long")
    consumed = long_.prompt + eng._slots[i].tokens[:-1]
    _, full = generate.prefill(model, params, jnp.asarray([consumed], jnp.int32))
    for got, (b, leaf) in zip(rows(i), [(b, leaf) for b in mamba_blocks
                                        for leaf in ("conv_state", "ssm_state")]):
        np.testing.assert_allclose(
            got, np.asarray(full["transformer"][f"block_{b}"]["attn"][leaf][0]), atol=2e-6)
    assert {o.request_id for o in outs + eng.run()} >= {"long", "short"}


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
                       match=r"per-slot state \(\['conv_state', 'ssm_state'\] leaves\).*"
                       + what.split("=")[0]):
        _engine(model, params, **kw)


def test_attention_impls_names_the_scan_and_the_update_each_program_runs(tiny):
    """What a server says it runs is what it runs: for Mamba-1 mixers the
    one-token update (``ssm=``) AND the scan over a chunk (``scan=``), each
    as the model's own rule resolves it; a Mamba-2 model's entries keep their
    one ``ssm=`` word."""
    cfg, model, params = tiny
    eng = _engine(model, params, num_slots=4, prefill_chunk_tokens=64, min_bucket=32)
    impls = eng.attention_impls()
    assert set(impls) == {"decode", "chunk_64", "final_chunk_32", "final_chunk_64"}
    assert all(v == "xla ssm=xla scan=xla" for v in impls.values())
    base, pattern = fam.program_config(cfg, 128)
    forced = dataclasses.replace(T.mamba_config_of(model), update_impl="kernel",
                                 scan_impl="kernel")
    kernels = T.PatternLM(base, moe.jamba_pattern(4, 2, 4, forced))
    assert _engine(kernels, params).attention_impls()["decode"] == (
        "xla ssm=kernel scan=kernel")
    assert T.ssm_impls(T.Mamba2Config(update_impl="kernel")) == "ssm=kernel"
    assert T.mamba_config_of(model).inner == 128
    assert T.mamba_config_of(llama.LlamaLM(llama.config_tiny())) is None


def test_the_kernels_run_inside_the_programs_where_asked(tiny):
    """The model with both kernels forced (interpret mode here) serves what
    the XLA paths serve: the chunk programs scan through ``selective_scan``
    and the decode advances the arena through ``selective_update``."""
    cfg, model, params = tiny
    base, _ = fam.program_config(cfg, 128)
    forced = dataclasses.replace(T.mamba_config_of(model), update_impl="kernel",
                                 scan_impl="kernel")
    kernels = T.PatternLM(base, moe.jamba_pattern(4, 2, 4, forced))
    prompt = np.random.default_rng(3).integers(0, 256, size=37).tolist()
    want = _engine(model, params).run([Request(prompt=prompt, max_new_tokens=5)])[0].tokens
    got = _engine(kernels, params).run([Request(prompt=prompt, max_new_tokens=5)])[0].tokens
    assert got == want


def test_the_pattern_is_one_object_a_kind_and_every_layer_has_its_feed_forward():
    mamba = T.Mamba1Config()
    pat = moe.jamba_pattern(28, 7, 14, mamba)
    assert [i for i, k in enumerate(pat) if k.attention is None] == [7, 21]
    assert len({id(k) for k in pat}) == 2 and all(k.solo is None and k.mlp is None for k in pat)
    assert moe.jamba_pattern(28, 7, 14, T.Mamba1Config()) is pat
    assert pat[0].attention.keywords["mamba"] == mamba
