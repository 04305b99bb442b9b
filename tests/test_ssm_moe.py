"""The Mamba-2 + attention + latent-expert family (Nemotron-H layout) at a tiny
float32 size on the CPU: the mixer fed whole (the chunked form), in uneven
chunks and token by token against the reference's scan over tokens; a padded
final chunk leaving the last REAL token's state; layers that are one sub-layer;
the four chips' shares adding up to the uncut layer; the state update kernel
(interpret mode) against ``jax.numpy``; the model on every cache path against
the plain reference; ``ServeEngine``'s pages and GB-scale-by-design state arena
— a reused slot, a mid-prefill slot under other slots' decodes, a zeroed state
caught, the arena aliased in the lowered decode program — and every pages-only
path refused by name."""
import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_ssm_moe as fam
from benchmarks.harness import lowp
from benchmarks.harness import manifest as M
from benchmarks.harness import reference_ssm_moe as ref
from k8s_distributed_deeplearning_tpu.models import generate, llama, moe
from k8s_distributed_deeplearning_tpu.models import transformer as T
from k8s_distributed_deeplearning_tpu.ops import pallas_ssm
from k8s_distributed_deeplearning_tpu.serve import engine as E
from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
from k8s_distributed_deeplearning_tpu.serve.request import Request
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer

CELL = "nemotron-3-super-ep4-d11.chat-backlog-wide"
SEED = 3_300_000_019
TOL = 5e-5          # float32 end to end; what is left is the order of the sums


@pytest.fixture(scope="module")
def tiny():
    """(cfg dict, model, params) of the cell's rehearsal size (the published
    eleven letters M E M E M E M * E M E at tiny widths, 8 experts held of
    32, top-9: the one-pass choice), weights from the seed as the benchmark makes them."""
    cell = M.Cell(M.load_manifest(), CELL)
    cell.apply_rehearsal()
    model, params = fam.build_model_and_params(cell.config, 128, SEED)
    return cell.config, model, params


def _ref_logits(cfg, toks, **kw):
    lg, _, _ = ref.forward_logits(cfg, SEED, [np.asarray(toks)], [np.arange(len(toks))], **kw)
    return lg[0]


MAMBA = T.Mamba2Config(num_heads=4, head_dim=8, n_groups=2, state_size=16, chunk_size=8)


def _mixer(seq=29):
    cfg = T.TransformerConfig(dim=32, n_heads=2, dtype=jnp.float32, max_seq_len=64,
                              norm_eps=1e-5, position="none")
    layer = T.Mamba2(cfg, MAMBA)
    x = jax.random.normal(jax.random.key(0), (2, seq, 32))
    params = nn.meta.unbox(layer.init(jax.random.key(1), x)["params"])
    return layer, params, x


def _reference_mixer(params, x):
    """The reference's ``mamba`` (a ``lax.scan`` over tokens) on the module's
    own parameters, row by row."""
    w = {"attn/in_proj/kernel": params["in_proj"]["kernel"], "attn/conv": params["conv"],
         "attn/conv_bias": params["conv_bias"], "attn/dt_bias": params["dt_bias"],
         "attn/A_log": params["A_log"], "attn/D": params["D"],
         "attn/norm/scale": params["norm"]["scale"],
         "attn/out_proj/kernel": params["out_proj"]["kernel"]}
    mm = functools.partial(lowp.einsum, precision="f32")
    return jnp.stack([ref.mamba(row, w, mm, sizes=(4, 8, 2, 16, 4), eps=1e-5) for row in x])


@pytest.mark.parametrize("seq", [8, 16, 29, 3], ids=["one-chunk", "two-chunks", "ragged", "short"])
def test_mamba2_chunked_form_equals_the_references_scan_over_tokens(seq):
    """Lengths that are and are not multiples of ``chunk_size`` 8."""
    layer, params, x = _mixer(seq)
    got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_reference_mixer(params, x)),
                               atol=1e-5)


@pytest.mark.parametrize("splits", [(29,), (1, 7, 2, 19), (8, 21), (1,) * 29, (13,) + (1,) * 16],
                         ids=["whole", "uneven", "two", "token-by-token", "chunk-then-steps"])
def test_mamba2_whole_equals_chunked_equals_token_by_token(splits):
    layer, params, x = _mixer()
    want = layer.apply({"params": params}, x)
    got, cache, at = [], None, 0
    for n in splits:
        variables = {"params": params} if cache is None else {"params": params, "cache": cache}
        y, new = layer.apply(variables, x[:, at:at + n], decode=True, mutable=["cache"])
        cache, at = new["cache"], at + n
        got.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want),
                               atol=1e-5)
    assert cache["conv_state"].shape == (2, 3, MAMBA.conv_dim)
    assert cache["ssm_state"].shape == (2, 4 * 16, 8) and cache["ssm_state"].dtype == jnp.float32


@pytest.mark.parametrize("real", [1, 2, 5, 16])
def test_a_padded_final_chunk_leaves_the_last_real_tokens_state(real):
    """A chunk right-padded to its bucket of 16: with ``lengths`` both leaves
    are what the ``real`` tokens alone leave; without, the pad's."""
    layer, params, x = _mixer()
    _, before = layer.apply({"params": params}, x[:, :7], decode=True, mutable=["cache"])
    padded = x[:, 7:23].at[:, real:].set(9.0)
    run = lambda chunk, **kw: layer.apply(
        {"params": params, "cache": before["cache"]}, chunk, decode=True, mutable=["cache"], **kw)
    want_y, want = run(x[:, 7:7 + real])
    got_y, got = run(padded, lengths=jnp.full((2,), real, jnp.int32))
    for leaf in ("conv_state", "ssm_state"):
        np.testing.assert_allclose(np.asarray(got["cache"][leaf]),
                                   np.asarray(want["cache"][leaf]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y[:, :real]), np.asarray(want_y), atol=1e-5)
    if real < 16:
        _, pads = run(padded)
        assert not np.allclose(np.asarray(pads["cache"]["ssm_state"]),
                               np.asarray(want["cache"]["ssm_state"]))


def test_the_state_layout_round_trips_and_packs_two_narrow_heads_to_a_row():
    assert pallas_ssm.state_shape(128, 64, 128, 8) == (8192, 128)      # the published widths
    assert pallas_ssm.state_shape(4, 8, 16, 2) == (64, 8)              # nothing to pack
    s = jax.random.normal(jax.random.key(0), (3, 32, 64, 128))
    packed = pallas_ssm.pack_state(s, 2)
    assert packed.shape == (3, 16 * 128, 128)
    np.testing.assert_array_equal(np.asarray(pallas_ssm.unpack_state(packed, 32, 64, 2)),
                                  np.asarray(s))
    # row (h // 2) * N + n, lane (h % 2) * P + p holds S_h[p, n]
    assert float(packed[1, 5 * 128 + 7, 64 + 3]) == float(s[1, 11, 3, 7])


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 1], [0] * 6, [1] * 6, [0, 0, 0, 0, 0, 1]],
                         ids=["mixed", "none", "all", "last"])
def test_ssm_update_kernel_equals_jax_numpy_and_touches_live_rows_only(live):
    """The kernel in interpret mode at two 64-lane heads a row: the live rows
    advanced as the equations say, the others bit for bit what they were."""
    b, h, p, g, n = 6, 32, 64, 2, 128
    ks = jax.random.split(jax.random.key(0), 6)
    s4 = jax.random.normal(ks[0], (b, h, p, n))
    x = jax.random.normal(ks[1], (b, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, h)))
    a = -jnp.exp(jax.random.normal(ks[3], (h,)))
    bm, cm = jax.random.normal(ks[4], (b, g, n)), jax.random.normal(ks[5], (b, g, n))
    live = jnp.asarray(live, bool)
    state = pallas_ssm.pack_state(s4, g)
    got, y = pallas_ssm.ssm_update(state, x, dt, a, bm, cm, live, interpret=True)
    want, y_want = pallas_ssm.ssm_update_reference(state, x, dt, a, bm, cm, live)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got[~live]), np.asarray(state[~live]))
    assert not np.asarray(y[~live]).any()
    # and the equations themselves, in their own order
    rep = h // g
    eq = (s4 * jnp.exp(dt * a)[:, :, None, None]
          + (dt[:, :, None] * x)[..., None] * jnp.repeat(bm, rep, 1)[:, :, None, :])
    np.testing.assert_allclose(np.asarray(pallas_ssm.unpack_state(got, h, p, g)[live]),
                               np.asarray(eq[live]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y[live]),
        np.asarray(jnp.einsum("bhpn,bhn->bhp", eq, jnp.repeat(cm, rep, 1))[live]), atol=1e-4)
    with pytest.raises(ValueError, match="float32 arena"):
        pallas_ssm.ssm_update(state.astype(jnp.bfloat16), x, dt, a, bm, cm, live, interpret=True)


def test_the_mixers_one_token_path_takes_the_kernel_where_asked():
    """``update_impl="kernel"`` (interpret mode here) and ``"xla"`` give the
    same step and leave the row without a cursor alone."""
    cfg = T.TransformerConfig(dim=32, n_heads=2, dtype=jnp.float32, norm_eps=1e-5)
    mk = lambda impl: T.Mamba2(cfg, dataclasses.replace(MAMBA, update_impl=impl))
    x = jax.random.normal(jax.random.key(0), (3, 1, 32))
    params = nn.meta.unbox(mk("xla").init(jax.random.key(1), x)["params"])
    arena = {"conv_state": jax.random.normal(jax.random.key(2), (3, 3, MAMBA.conv_dim)),
             "ssm_state": jax.random.normal(jax.random.key(3), (3, 64, 8))}
    run = lambda impl: mk(impl).apply(
        {"params": params, "cache": arena}, x, decode=True, mutable=["cache"],
        cache_positions=jnp.asarray([4, 0, 9], jnp.int32),
        block_tables=jnp.zeros((3, 2), jnp.int32))
    (y_k, c_k), (y_x, c_x) = run("kernel"), run("xla")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_k["cache"]["ssm_state"]),
                               np.asarray(c_x["cache"]["ssm_state"]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c_k["cache"]["ssm_state"][1]),
                                  np.asarray(arena["ssm_state"][1]))
    assert T.ssm_update_impl(MAMBA) == "xla"                    # "auto" off the TPU
    assert T.ssm_update_impl(dataclasses.replace(MAMBA, update_impl="kernel")) == "kernel"
    with pytest.raises(ValueError, match="requires an engine-provided state arena"):
        mk("xla").apply({"params": params}, x, decode=True, mutable=["cache"],
                        block_tables=jnp.zeros((3, 2), jnp.int32))
    with pytest.raises(NotImplementedError, match="causal over the whole row"):
        mk("xla").apply({"params": params}, x, segment_ids=jnp.ones((3, 1), jnp.int32))


@pytest.mark.parametrize("solo", ["mixer", "mlp"])
def test_a_layer_that_is_one_sub_layer_has_one_norm_and_one_residual(solo):
    cfg = T.TransformerConfig(dim=16, n_heads=2, mlp_dim=24, activation="relu2",
                              position="none", dtype=jnp.float32, norm_eps=1e-5)
    block = T.Block(cfg, kind=T.LayerKind(solo=solo))
    x = jax.random.normal(jax.random.key(0), (2, 5, 16))
    params = nn.meta.unbox(block.init(jax.random.key(1), x)["params"])
    half = {"mixer": {"attn_norm", "attn"}, "mlp": {"mlp_norm", "mlp"}}[solo]
    assert set(params) == half
    got = block.apply({"params": params}, x)
    norm = "attn_norm" if solo == "mixer" else "mlp_norm"
    h = T.make_norm(cfg, norm).apply({"params": params[norm]}, x)
    if solo == "mixer":
        want = x + T.Attention(cfg).apply({"params": params["attn"]}, h)
    else:
        assert set(params["mlp"]) == {"up_proj", "down_proj"}
        assert "bias" not in params["mlp"]["up_proj"] and "bias" not in params["mlp"]["down_proj"]
        want = x + jnp.square(jax.nn.relu(h @ params["mlp"]["up_proj"]["kernel"])) @ \
            params["mlp"]["down_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError, match="solo must be"):
        T.LayerKind(solo="both")


def test_the_gelu_and_swiglu_feed_forwards_are_what_they_were():
    x = jax.random.normal(jax.random.key(0), (1, 3, 8))
    for act, leaves in (("gelu", {"up_proj": {"kernel", "bias"}, "down_proj": {"kernel", "bias"}}),
                        ("swiglu", {"gate_proj": {"kernel"}, "up_proj": {"kernel"},
                                    "down_proj": {"kernel"}})):
        cfg = T.TransformerConfig(dim=8, n_heads=2, mlp_dim=12, activation=act, dtype=jnp.float32)
        params = nn.meta.unbox(T.MLP(cfg).init(jax.random.key(1), x)["params"])
        assert {k: set(v) for k, v in params.items()} == leaves


def _share_layer(cfg, offset, w_all, held):
    """One ``E`` layer of the program holding experts [offset, offset + held)
    of the uncut reference layer's weights *w_all*."""
    cfg = {**cfg, "n_routed_experts": held, "expert_offset": offset}
    base, pattern = fam.program_config(cfg, 32)
    factory = next(k.mlp for k in pattern if k.mlp is not None)
    layer = factory(base)
    x = jnp.zeros((1, 4, cfg["hidden_size"]))
    params = nn.meta.unbox(layer.init(jax.random.key(0), x, decode=True)["params"])
    params = {
        "router": w_all["mlp/router"], "router_bias": w_all["mlp/router_bias"],
        "fc1_latent": {"kernel": w_all["mlp/fc1_latent/kernel"]},
        "fc2_latent": {"kernel": w_all["mlp/fc2_latent/kernel"]},
        "w_up": w_all["mlp/w_up"][offset:offset + held],
        "w_down": w_all["mlp/w_down"][offset:offset + held],
        "shared": {"up_proj": {"kernel": w_all["mlp/shared/up_proj/kernel"]},
                   "down_proj": {"kernel": w_all["mlp/shared/down_proj/kernel"]}}}
    return layer, params


@pytest.mark.parametrize("rows", [6, 40], ids=["dense-dispatch", "grouped-dispatch"])
def test_the_four_shares_add_up_to_the_uncut_layer(tiny, rows, monkeypatch):
    """Offsets 0 / 8 / 16 / 24 of a 32-expert layer, top-9: the four chips'
    routed parts plus the shared expert ONCE equal the uncut reference layer
    (what every chip computes alike — the router, the latent projections, the
    shared expert — is not multiplied by four)."""
    cfg, _, _ = tiny
    uncut = {**cfg, "n_routed_experts": 32, "num_hidden_layers": 2}   # layer 1 is an E
    w = ref.make_layer(uncut, SEED, 1)
    x = jax.random.normal(jax.random.key(3), (1, rows, cfg["hidden_size"]))
    if rows == 40:
        monkeypatch.setattr(moe, "GROUPED_MIN_ROWS_PER_EXPERT", 8)
    mm = functools.partial(lowp.einsum, precision="f32")
    want_routed, _, chosen = ref.routed_experts(x[0], w, mm, k=9, scaling=5.0, offset=0)
    shared = ref.shared_expert(x[0], w, mm)
    total, landed = 0.0, 0
    for offset in (0, 8, 16, 24):
        layer, params = _share_layer(cfg, offset, w, held=8)
        y, stats = layer.apply({"params": params}, x, decode=True, mutable=["moe_stats"])
        assert moe.serving_dispatch(rows, layer.moe) == ("grouped" if rows == 40 else "dense")
        counts = np.asarray(jax.tree.leaves(stats["moe_stats"])[0])
        np.testing.assert_array_equal(
            counts, np.bincount(np.asarray(chosen).ravel(), minlength=32)[offset:offset + 8])
        landed += int(counts.sum())
        total = total + (y[0] - shared)
    assert landed == rows * 9                                   # every pick landed on ONE chip
    np.testing.assert_allclose(np.asarray(total), np.asarray(want_routed), atol=TOL)
    # and one share alone is the reference's share (what the benchmark compares)
    layer, params = _share_layer(cfg, 8, w, held=8)
    part, _, _ = ref.routed_experts(
        x[0], {**w, "mlp/w_up": w["mlp/w_up"][8:16], "mlp/w_down": w["mlp/w_down"][8:16]},
        mm, k=9, scaling=5.0, offset=8)
    got = layer.apply({"params": params}, x, decode=True, mutable=["moe_stats"])[0][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(part + shared), atol=TOL)


def test_latent_experts_are_a_serving_layout(tiny):
    cfg, model, _ = tiny
    layer = next(k.mlp for k in model.pattern if k.mlp is not None)(
        model.cfg, moe=dataclasses.replace(moe.moe_config_of(model), experts_held=None))
    with pytest.raises(NotImplementedError, match="latent experts"):
        layer.init(jax.random.key(0), jnp.zeros((1, 4, 64)))
    with pytest.raises(ValueError, match="expert_act"):
        moe.MoEConfig(expert_act="gelu")


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
    assert model.cfg.position == "none" and model.cfg.activation == "relu2"
    assert "head" in params and not model.cfg.tie_embeddings     # untied
    assert ref.pattern(cfg) == "MEMEMEM*EME"


def _arena_for(model, cfg, pages, page_tokens, slots):
    lanes = model.cfg.resolved_kv_heads * model.cfg.resolved_head_dim
    h, p, g, n, k = ref.mamba_sizes(cfg)
    pool = lambda: jnp.zeros((pages, page_tokens, lanes), jnp.float32)
    out = {}
    for i, c in enumerate(ref.pattern(cfg)):
        if c == "M":
            out[f"block_{i}"] = {"attn": {
                "conv_state": jnp.zeros((slots, k - 1, h * p + 2 * g * n), jnp.float32),
                "ssm_state": jnp.zeros((slots,) + pallas_ssm.state_shape(h, p, n, g),
                                       jnp.float32)}}
        elif c == "*":
            out[f"block_{i}"] = {"attn": {"cached_key": pool(), "cached_value": pool()}}
    return {"transformer": out}


@pytest.mark.parametrize("impl", ["xla", "paged_flash"])
def test_paged_chunks_then_slot_decode_equal_reference_logits(tiny, impl):
    """Prefill in chunks of 16 through the block table, the last one padded,
    then slot decode beside an idle row (whose state must stay zero) — through
    the XLA gather and through the paged kernel (interpret mode, 2 query heads
    a KV head) — against the logits of the reference's ONE full forward."""
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
        lg, arena, counts = generate.prefill_chunk(
            model, params, arena, jnp.asarray(chunk)[None], positions=pos,
            block_tables=jnp.asarray(table[1:2]), lengths=jnp.asarray([n], jnp.int32))
        got.append(np.asarray(lg[0, :n]))
        assert counts.shape == (5, cfg["n_routed_experts"])
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
    """Nine requests through three slots — prompts of one token, of exactly a
    chunk, of several chunks and a padded tail — with a cancel mid-prefill:
    every request that ran to its end was served, token for token, what the
    reference's ONE full forward pass puts first, and equals one-shot
    ``generate()``. The three programs compile once; nothing is left behind."""
    cfg, model, params = tiny
    tracer = Tracer(ring_size=8192)
    eng = _engine(model, params, tracer=tracer)
    rng = np.random.default_rng(2)
    sizes = [(40, 6), (17, 5), (9, 8), (33, 4), (50, 7), (21, 3), (16, 5), (32, 5), (1, 4)]
    reqs = [Request(prompt=rng.integers(0, 256, size=n).tolist(), max_new_tokens=k,
                    request_id=f"r{i}") for i, (n, k) in enumerate(sizes)]
    for r in reqs:
        eng.submit(r)
    done = eng.step()
    gone = eng.cancel("r0")                                     # mid-prefill (3 chunks)
    assert gone.finish_reason == "aborted" and not gone.tokens
    sizes_before = None
    while eng.busy():
        done.extend(eng.step())
        if sizes_before is None and eng.stats.decode_tokens > 4:
            sizes_before = (eng.decode_cache_size(), eng.chunk_cache_size(),
                            eng.prefill_cache_size())
    assert (eng.decode_cache_size(), eng.chunk_cache_size(),
            eng.prefill_cache_size()) == sizes_before
    outs = {o.request_id: o for o in done}
    served = reqs[1:]
    assert all(outs[r.request_id].finish_reason == "length" for r in served)
    for r in served:
        want = generate.generate(model, params, jnp.asarray([r.prompt], jnp.int32),
                                 max_new_tokens=r.max_new_tokens)
        assert outs[r.request_id].tokens == np.asarray(want[0]).tolist()
    sample = [{"prompt": np.asarray(r.prompt), "tokens": np.asarray(outs[r.request_id].tokens)}
              for r in served]
    sc = ref.score_served(cfg, SEED, sample)
    assert sc["gaps"]["all_max"] < 1e-4 and sc["not_reference_best"] == 0
    # the arena: one row a slot beside the pages, found by name
    attn = eng._cache["transformer"]["block_0"]["attn"]
    assert attn["ssm_state"].shape == (3, 8 * 16, 8) and attn["ssm_state"].dtype == jnp.float32
    assert attn["conv_state"].shape == (3, 3, 64 + 2 * 2 * 16)
    assert eng._state_names == ["conv_state", "ssm_state"]
    assert eng._slot_state_nbytes == 5 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert eng._cache["transformer"]["block_7"]["attn"]["cached_key"].shape == (65, 8, 32)
    assert "block_1" not in eng._cache["transformer"]            # an E layer holds nothing
    spans = tracer.recent_spans()
    dec = [s for s in spans if s["name"] == "decode"]
    assert dec and all(1 <= s["state_rows"] <= 3 for s in dec)
    assert all(s["state_bytes_moved"] == 2 * s["state_rows"] * eng._slot_state_nbytes
               for s in dec)
    assert all(s["state_rows"] >= s["rows"] for s in dec)
    calls = [s for s in spans if s["name"] in ("prefill", "prefill_counts")]
    assert {s["state_from"] for s in calls} == {"zero", "carried"}
    summ = eng.stats.summary()
    assert summ["state_slots"] == 0 and summ["kv_pages_used"] == 0
    assert 1 <= summ["state_update_rows"] <= 3
    assert eng.shutdown() == []


def test_a_reused_slot_starts_from_zeros_whatever_its_last_occupant_left(tiny):
    _, model, params = tiny
    eng = _engine(model, params, num_slots=2)
    prompt = np.random.default_rng(5).integers(0, 256, size=21).tolist()
    first = eng.run([Request(prompt=prompt, max_new_tokens=5, request_id="a")])[0].tokens
    eng._cache = jax.tree_util.tree_map_with_path(
        lambda p, leaf: jnp.full_like(leaf, 1e3) if p[-1].key in E._STATE_LEAVES else leaf,
        eng._cache)
    again = eng.run([Request(prompt=prompt, max_new_tokens=5, request_id="b")])[0].tokens
    assert again == first


def _until_decoding(eng, rid, tokens, outs):
    slot = lambda: next((i for i, fl in enumerate(eng._slots) if fl is not None
                         and fl.req.request_id == rid and len(fl.tokens) >= tokens), None)
    while slot() is None:
        outs += eng.step()
    return slot()


def test_a_slot_mid_prefill_keeps_its_state_under_other_slots_decodes(tiny):
    """One long prompt admitted while another request decodes: its chunks
    interleave with decode steps that ride every slot. Between two of its
    chunks the slot's rows are — to the last bit — what the chunk left (the
    decode's update masks the row out; nothing copies it); mid-decode they ARE
    what the same tokens leave in ``generate()``'s own row cache."""
    _, model, params = tiny
    eng = _engine(model, params, num_slots=2)
    rng = np.random.default_rng(6)
    short = Request(prompt=rng.integers(0, 256, size=5).tolist(), max_new_tokens=14,
                    request_id="short")
    long_ = Request(prompt=rng.integers(0, 256, size=90).tolist(), max_new_tokens=9,
                    request_id="long")
    eng.submit(short)
    outs = eng.step() + eng.step()                          # `short` is decoding
    eng.submit(long_)
    outs += eng.step()                                      # `long` admitted: its first chunk
    slot = next(iter(eng._pending))
    assert eng._pending[slot].pos == 16 and int(eng._kv_lens[slot]) == 0
    rows = lambda: [np.asarray(eng._cache["transformer"][f"block_{b}"]["attn"][leaf][slot])
                    for b in (0, 2, 4, 6, 9) for leaf in ("conv_state", "ssm_state")]
    _, want = generate.prefill(model, params, jnp.asarray([long_.prompt[:16]], jnp.int32))
    for got, b in zip(rows()[1::2], (0, 2, 4, 6, 9)):
        np.testing.assert_allclose(
            got, np.asarray(want["transformer"][f"block_{b}"]["attn"]["ssm_state"][0]), atol=1e-6)
    held = rows()
    # decode steps alone (no chunk of `long` in them): the other slot advances
    for _ in range(3):
        nxt, _, eng._cache = eng._decode_step()
        jax.block_until_ready(nxt)
    for before, after in zip(held, rows()):
        np.testing.assert_array_equal(before, after)
    i = _until_decoding(eng, "long", 4, outs)
    consumed = long_.prompt + eng._slots[i].tokens[:-1]
    _, full = generate.prefill(model, params, jnp.asarray([consumed], jnp.int32))
    for b in (0, 2, 4, 6, 9):
        for leaf in ("conv_state", "ssm_state"):
            np.testing.assert_allclose(
                np.asarray(eng._cache["transformer"][f"block_{b}"]["attn"][leaf][i]),
                np.asarray(full["transformer"][f"block_{b}"]["attn"][leaf][0]), atol=2e-6)
    assert {o.request_id for o in outs + eng.run()} >= {"long"}


def test_a_slots_state_zeroed_between_two_chunks_is_caught(tiny):
    """The fault of this mechanism, planted in the PROGRAM: after a request's
    first chunk its slot's ``ssm_state`` rows are set to zero. Its logits leave
    the reference's by far more than ten times the float32 tolerance (at this width the
    argmax need not move: the logits are compared)."""
    cfg, model, params = tiny
    eng = _engine(model, params, num_slots=2)
    prompt = np.random.default_rng(7).integers(0, 256, size=40).tolist()
    want = _ref_logits(cfg, prompt)[-1]
    for plant in (False, True):
        eng.submit(Request(prompt=prompt, max_new_tokens=2, request_id=f"p{plant}"))
        eng.step()                                           # the first chunk of three
        slot = next(iter(eng._pending))
        if plant:
            eng._cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: leaf.at[slot].set(0.0) if p[-1].key == "ssm_state" else leaf,
                eng._cache)
        # the last prompt position's logits, as the final chunk computes them
        pend = eng._pending[slot]
        chunk = np.asarray(prompt[16:32], np.int32)[None]
        cache, _ = E._chunk_core(model, params, eng._cache, jnp.asarray(chunk),
                                 jnp.asarray(pend.table[None, :]), jnp.int32(16), jnp.int32(slot))
        tail = np.zeros((1, 16), np.int32)
        tail[0, :8] = prompt[32:]
        lg, _, _ = generate.prefill_chunk(
            model, params, E._slot_state(cache, jnp.int32(slot), jnp.int32(32)),
            jnp.asarray(tail), positions=(32 + jnp.arange(16, dtype=jnp.int32))[None],
            block_tables=jnp.asarray(pend.table[None, :]), lengths=jnp.asarray([8], jnp.int32))
        gap = np.abs(np.asarray(lg[0, 7]) - want).max()
        assert (gap > 10 * TOL) if plant else (gap < TOL)
        eng.cancel(f"p{plant}")
    # and the reference standing in for such a program (score_served's fault)
    faulty = _ref_logits(cfg, prompt, zero_state_at=16)
    assert np.abs(faulty[:16] - _ref_logits(cfg, prompt)[:16]).max() == 0.0
    assert np.abs(faulty[-1] - want).max() > 10 * TOL


def test_the_decode_program_aliases_the_arena_and_selects_over_no_state_of_its_size(tiny):
    """Lowered at the tiny size with a 6-slot arena, the one-token update as
    the kernel (interpret mode here; compiled for the chip in
    ``test_tpu_compile.py``): every cache leaf is donated and aliased to an
    output (no second arena), and no ``select`` yields an ``ssm_state``-shaped
    array — the small ``conv_state`` is the engine's select, the large leaf is
    masked inside the update."""
    cfg, model, params = tiny
    base, pattern = fam.program_config(cfg, 128)
    mamba = dataclasses.replace(T.mamba_config_of(model), update_impl="kernel")
    model = T.PatternLM(base, moe.hybrid_pattern(ref.pattern(cfg), moe.moe_config_of(model), mamba))
    eng = _engine(model, params, num_slots=6)
    assert eng.attention_impls()["decode"].endswith("ssm=kernel")
    lowered = E._decode_program.lower(model, params, eng._cache, *eng._registers())
    text = lowered.as_text()
    n_leaves = len(jax.tree.leaves(eng._cache))
    assert text.count("tf.aliasing_output") >= n_leaves + 1     # the cache and the keys
    ssm, conv = "tensor<6x128x8xf32>", "tensor<6x3x128xf32>"
    selects = [l for l in text.splitlines() if "stablehlo.select" in l]
    assert any(conv in l for l in selects)                       # the engine's, over the tails
    assert not any(l.rstrip().endswith(ssm) for l in selects)
    assert "input_output_alias" in lowered.compile().as_text()


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
    with pytest.raises(ValueError,
                       match=r"per-slot state \(\['conv_state', 'ssm_state'\] leaves\).*"
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
    with pytest.raises(ValueError, match=call + r".*per-slot state \(\['conv_state', 'ssm_state'\]"):
        eng.export_request_kv("x") if call == "export_request_kv" else eng.import_request_kv(blob)
    eng.shutdown()


def test_a_draft_with_state_is_refused(tiny):
    _, model, params = tiny
    target = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
    tparams = target.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="draft model with per-slot state"):
        ServeEngine(target, tparams, num_slots=2, draft_model=model, draft_params=params,
                    spec_k=2)


def test_attention_impls_names_each_programs_expert_dispatch_and_state_update(tiny):
    _, model, params = tiny
    eng = _engine(model, params, num_slots=4, prefill_chunk_tokens=64, min_bucket=32)
    impls = eng.attention_impls()
    assert set(impls) == {"decode", "chunk_64", "final_chunk_32", "final_chunk_64"}
    assert all(v.startswith("xla experts=") and v.endswith(" ssm=xla") for v in impls.values())
    assert impls["decode"] == "xla experts=dense ssm=xla"
    assert T.mamba_config_of(model).num_heads == 8
    assert T.mamba_config_of(llama.LlamaLM(llama.config_tiny())) is None


def test_the_pattern_is_one_object_a_letter_and_models_built_twice_are_equal(tiny):
    cfg, model, _ = tiny
    kinds = model.pattern
    assert [k.solo for k in kinds] == ["mixer", "mlp"] * 3 + ["mixer", "mixer", "mlp", "mixer", "mlp"]
    assert kinds[0] is kinds[2] is kinds[9] and kinds[1] is kinds[3] is kinds[10]
    assert kinds[7].attention is None and kinds[7].mlp is None and kinds[7] != kinds[0]
    again = T.PatternLM(*fam.program_config(cfg, 128))
    assert again == model and hash(again) == hash(model)
    dense = moe.hybrid_pattern("M-*", None, MAMBA)
    assert [k.solo for k in dense] == ["mixer", "mlp", "mixer"] and dense[1].mlp is None
    with pytest.raises(ValueError, match="the pattern names"):
        moe.hybrid_pattern("MX", moe.MoEConfig(), MAMBA)
    with pytest.raises(ValueError, match="the pattern names"):
        moe.hybrid_pattern("ME", None, MAMBA)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        fam.program_config({**cfg, "n_group": 2}, 128)


def test_the_five_leaves_of_the_published_initialisation_are_drawn_so(tiny):
    """``A_log``, ``dt_bias``, ``D`` and the convolution's taps and bias in the
    program's parameter tree: the published ranges, not N(0, 0.02) — and the
    reference makes the same values again from the seed alone."""
    cfg, _, params = tiny
    mixer = params["transformer"]["block_0"]["attn"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    step = np.log1p(np.exp(np.asarray(mixer["dt_bias"], np.float64)))
    assert step.min() >= 1e-4 * 0.999 and step.max() <= 0.1 * 1.001
    np.testing.assert_array_equal(np.asarray(mixer["D"]), 1.0)
    for leaf in ("conv", "conv_bias"):
        v = np.asarray(mixer[leaf])
        assert np.abs(v).max() <= 0.5 and v.std() > 0.2
    again = ref.make_layer(cfg, SEED, 0)
    for name in ("A_log", "dt_bias", "D", "conv", "conv_bias"):
        np.testing.assert_array_equal(np.asarray(again["attn/" + name]), np.asarray(mixer[name]))
    np.testing.assert_array_equal(np.asarray(again["attn/in_proj/kernel"]),
                                  np.asarray(mixer["in_proj"]["kernel"]))
    assert [ref.leaf_kind(n) for n in ("a/A_log", "a/conv_bias", "n/scale", "m/router_bias")] == \
        ["A_log", "tap", "scale", "weight"]


@pytest.mark.parametrize("score_fn, bias", [("sigmoid", True), ("softmax", False)])
def test_the_one_pass_choice_is_the_loops_choice_tie_order_and_gates(score_fn, bias):
    """22 of 512 (and 9 of 32): the same experts in the same order — planted
    ties go to the lower index in both — and the same gates."""
    for t, e, k in ((37, 512, 22), (11, 32, 9)):
        cfg = moe.MoEConfig(num_experts=e, top_k=k, score_fn=score_fn, select_bias=bias,
                            routed_scale=5.0)
        logits = jax.random.normal(jax.random.key(e), (t, e))
        logits = logits.at[:, 3].set(4.0)                                            # chosen,
        logits = logits.at[:, 7].set(logits[:, 3]).at[:, e - 1].set(logits[:, 3])   # and tied
        b = 0.1 * jax.random.normal(jax.random.key(1), (e,)) if bias else None
        if b is not None:
            b = b.at[7].set(b[3]).at[e - 1].set(b[3])
        scores, idx_list, _, gate_stack = moe._topk_assignments(logits, k, cfg, b)
        scores1, idx, gates = moe._topk_one_pass(logits, k, cfg, b)
        np.testing.assert_array_equal(np.asarray(jnp.stack(idx_list, axis=1)), np.asarray(idx))
        np.testing.assert_allclose(np.asarray(gate_stack.T), np.asarray(gates), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(scores), np.asarray(scores1))
        tied = np.asarray(idx)
        rows = [r for r in tied if 3 in r and 7 in r]
        assert rows and all(list(r).index(3) < list(r).index(7) for r in rows)


def test_many_choices_take_the_one_pass_dispatches_and_few_the_loops(tiny, monkeypatch):
    """The serving dispatches at top-9 of 32 (one pass) against the SAME layer
    with the threshold raised (the k-fold loops): dense and grouped agree to
    float32 rounding, with the same counts."""
    cfg, _, _ = tiny
    w = ref.make_layer({**cfg, "num_hidden_layers": 2}, SEED, 1)
    layer, params = _share_layer(cfg, 8, {**w, "mlp/w_up": jnp.tile(w["mlp/w_up"], (4, 1, 1)),
                                          "mlp/w_down": jnp.tile(w["mlp/w_down"], (4, 1, 1))}, held=8)
    x = jax.random.normal(jax.random.key(4), (1, 40, cfg["hidden_size"]))
    got = {}
    for above in (8, 64):
        for grouped_from in (32, 8):
            monkeypatch.setattr(moe, "ONE_PASS_TOPK_ABOVE", above)
            monkeypatch.setattr(moe, "GROUPED_MIN_ROWS_PER_EXPERT", grouped_from)
            y, stats = layer.apply({"params": params}, x, decode=True, mutable=["moe_stats"])
            got[above, grouped_from] = (np.asarray(y), np.asarray(jax.tree.leaves(stats)[0]))
    want_y, want_counts = got[64, 32]                        # the loops, dense
    for key, (y, counts) in got.items():
        np.testing.assert_allclose(y, want_y, atol=TOL, err_msg=str(key))
        np.testing.assert_array_equal(counts, want_counts)
    assert want_counts.sum() > 0
