"""MoE routing invariants + expert-parallel training."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from k8s_distributed_deeplearning_tpu.models import llama, moe
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
from k8s_distributed_deeplearning_tpu.parallel import sharding


def test_routing_respects_capacity_and_gates():
    t, e, k, cap = 32, 4, 2, 6
    logits = jax.random.normal(jax.random.key(0), (t, e))
    dispatch, combine, aux = moe.top_k_routing(logits, k, cap)
    assert dispatch.shape == (t, e, cap)
    # No slot double-booked: each (e, c) pair holds at most one token.
    per_slot = np.asarray(dispatch).sum(axis=0)
    assert per_slot.max() <= 1
    # Each token's combine weights sum to <= 1 (== 1 when nothing dropped).
    sums = np.asarray(combine).sum(axis=(1, 2))
    assert (sums <= 1.0 + 1e-5).all()
    # A token is dispatched to at most k experts.
    per_token = (np.asarray(dispatch).sum(axis=2) > 0).sum(axis=1)
    assert (per_token <= k).all()
    assert float(aux["load_balance_loss"]) >= 1.0 - 1e-5  # >= 1 by Cauchy-Schwarz


def test_routing_tiny_capacity_drops_tokens():
    t, e = 16, 2
    logits = jnp.zeros((t, e)).at[:, 0].set(1.0)  # all tokens want expert 0
    dispatch, combine, aux = moe.top_k_routing(logits, 1, 4)
    assert np.asarray(dispatch)[:, 0].sum() == 4  # capacity caps it
    assert float(aux["fraction_dropped"]) > 0.5


def _tiny_moe():
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    return moe.MoELM(cfg, mcfg), cfg, mcfg


def test_moe_forward_and_loss():
    model, cfg, mcfg = _tiny_moe()
    tokens = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), tokens)["params"]
    loss, aux = moe.loss_fn(model, mcfg, params, {"tokens": tokens})
    assert jnp.isfinite(loss)
    assert float(aux["aux_loss"]) > 0.0
    grads = jax.grad(lambda p: moe.loss_fn(model, mcfg, p,
                                           {"tokens": tokens})[0])(params)
    assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("spec", [{"data": 8}, {"data": 2, "expert": 4},
                                  {"expert": 4, "tensor": 2}])
def test_moe_trains_on_expert_mesh(spec):
    model, cfg, mcfg = _tiny_moe()
    mesh = mesh_lib.make_mesh(spec)

    def loss(params, batch, rng):
        return moe.loss_fn(model, mcfg, params, batch, rng)

    tr = sharding.ShardedTrainer(loss, optax.adam(2e-3), mesh)
    state = tr.init(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.key(0))
    step = tr.make_step(donate=False)
    tokens = jax.random.randint(jax.random.key(7), (8, 17), 0, cfg.vocab_size)
    batch = tr.shard_batch({"tokens": tokens})
    losses = []
    for i in range(3):
        state, l, aux = step(state, batch, jax.random.key(i))
        losses.append(float(l))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


def test_expert_weights_sharded_on_expert_mesh():
    model, cfg, mcfg = _tiny_moe()
    mesh = mesh_lib.make_mesh({"data": 2, "expert": 4})
    tr = sharding.ShardedTrainer(
        lambda p, b, r: moe.loss_fn(model, mcfg, p, b, r),
        optax.adam(1e-3), mesh)
    state = tr.init(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.key(0))
    import flax
    flat = flax.traverse_util.flatten_dict(
        sharding.unbox(state.params), sep="/")
    w = next(v for k, v in flat.items() if k.endswith("mlp/w_gate"))
    assert not w.sharding.is_fully_replicated
    assert "expert" in (w.sharding.spec[0] or ())


def test_moe_scan_layers_and_remat():
    """MoE must ride the shared transformer core: scan_layers/remat work and
    sown router metrics survive the scan (stacked along the layer axis)."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=3, scan_layers=True,
                            remat=True)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    model = moe.MoELM(cfg, mcfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), tokens)["params"]
    loss, aux = moe.loss_fn(model, mcfg, params, {"tokens": tokens})
    assert jnp.isfinite(loss)
    assert float(aux["aux_loss"]) > 0.0
    grads = jax.grad(lambda p: moe.loss_fn(model, mcfg, p,
                                           {"tokens": tokens})[0])(params)
    assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads))


def test_moe_scan_matches_loop_with_same_weights():
    """Same weights, scan vs loop layer stacking: identical loss AND identical
    aux loss (sum over layers — scan stacks the sown metrics into one leaf)."""
    import dataclasses
    import flax.linen as nn
    cfg_loop = llama.config_tiny(dtype=jnp.float32, n_layers=3,
                                 scan_layers=False)
    cfg_scan = dataclasses.replace(cfg_loop, scan_layers=True)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    m_loop, m_scan = moe.MoELM(cfg_loop, mcfg), moe.MoELM(cfg_scan, mcfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 16), 0,
                                cfg_loop.vocab_size)

    p = nn.meta.unbox(m_loop.init(jax.random.key(1), tokens)["params"])
    tr = p["transformer"]
    blocks = [tr[f"block_{i}"] for i in range(cfg_loop.n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    p_scan = {"transformer": {"blocks": stacked, "tok_embed": tr["tok_embed"],
                              "final_norm": tr["final_norm"]},
              "head": p["head"]}

    l_loop, a_loop = moe.loss_fn(m_loop, mcfg, p, {"tokens": tokens})
    l_scan, a_scan = moe.loss_fn(m_scan, mcfg, p_scan, {"tokens": tokens})
    np.testing.assert_allclose(float(l_scan), float(l_loop), rtol=1e-5)
    np.testing.assert_allclose(float(a_scan["aux_loss"]),
                               float(a_loop["aux_loss"]), rtol=1e-5)


def test_expert_choice_routing_invariants():
    """Expert-choice (round 3): every expert exactly full, no slot
    double-booked, combine weights bounded, uncovered fraction reported."""
    t, e, cap = 32, 4, 6
    logits = jax.random.normal(jax.random.key(0), (t, e))
    dispatch, combine, aux = moe.expert_choice_routing(logits, cap)
    d = np.asarray(dispatch)
    assert d.shape == (t, e, cap)
    # 100% utilization by construction: each (e, c) slot holds EXACTLY one
    # token — the policy's defining property (topk leaves slots empty).
    assert (d.sum(axis=0) == 1).all()
    # Combine weight is the token->expert softmax affinity: <= 1 per slot.
    c = np.asarray(combine)
    assert ((c >= 0) & (c <= 1 + 1e-6)).all()
    assert (c > 0).sum() == e * cap
    assert 0.0 <= float(aux["fraction_dropped"]) < 1.0
    assert "load_balance_loss" not in aux  # balanced by construction


def test_expert_choice_skewed_tokens_keeps_experts_full():
    """The utilization claim: even when every token prefers expert 0,
    expert choice fills ALL experts to capacity (topk would drop everything
    beyond expert 0's capacity slots). The dual trade shows too: with
    identical affinities both experts pick the SAME top tokens, so half the
    tokens here go uncovered (reported, not silently lost — they ride the
    residual)."""
    t, e = 16, 2
    logits = jnp.zeros((t, e)).at[:, 0].set(5.0)
    dispatch, _, aux = moe.expert_choice_routing(logits, 8)
    d = np.asarray(dispatch)
    assert d[:, 0].sum() == 8 and d[:, 1].sum() == 8   # both experts full
    assert float(aux["fraction_dropped"]) == 0.5
    # Distinct affinities -> distinct picks -> full coverage.
    logits2 = jnp.asarray(np.random.default_rng(0).normal(size=(t, e)) * 5)
    _, _, aux2 = moe.expert_choice_routing(logits2, 8)
    assert float(aux2["fraction_dropped"]) <= 0.25


def test_moe_expert_choice_trains():
    """End-to-end: expert-choice MoE trains (loss decreases, grads finite)
    and runs on the expert mesh."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0,
                         routing="expert_choice")
    model = moe.MoELM(cfg, mcfg)
    mesh = mesh_lib.make_mesh({"data": 2, "expert": 4})
    tokens = jax.random.randint(jax.random.key(0), (8, 17), 0,
                                cfg.vocab_size)

    def loss(params, batch, rng):
        return moe.loss_fn(model, mcfg, params, batch)

    tr = sharding.ShardedTrainer(loss, optax.adam(1e-2), mesh)
    state = tr.init(lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32))[
        "params"], jax.random.key(1))
    step = tr.make_step(donate=False)
    batch = tr.shard_batch({"tokens": tokens})
    losses = []
    for i in range(4):
        state, l, aux = step(state, batch, jax.random.key(i))
        losses.append(float(l))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses


@pytest.mark.parametrize("routing", ["topk", "expert_choice"])
def test_index_dispatch_matches_einsum(routing):
    """The index dispatch must be numerically equivalent to the dense
    one-hot einsum formulation — same params, same tokens, same output and
    grads — for both routing policies, including under capacity drops
    (capacity_factor=1.0 forces overflow)."""
    import dataclasses
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mk = lambda dispatch: moe.MoELM(cfg, moe.MoEConfig(
        num_experts=4, top_k=2, capacity_factor=1.0, routing=routing,
        dispatch=dispatch))
    m_sort, m_ein = mk("index"), mk("einsum")
    tokens = jax.random.randint(jax.random.key(3), (4, 16), 0,
                                cfg.vocab_size)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore")  # expert_choice causal warning, expected
        params = m_sort.init(jax.random.key(1), tokens)["params"]
        mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=1.0,
                             routing=routing)
        l_s, a_s = moe.loss_fn(m_sort, mcfg, params, {"tokens": tokens})
        l_e, a_e = moe.loss_fn(m_ein, mcfg, params, {"tokens": tokens})
        np.testing.assert_allclose(float(l_s), float(l_e), rtol=2e-5)
        np.testing.assert_allclose(float(a_s["aux_loss"]),
                                   float(a_e["aux_loss"]), rtol=2e-5)
        g_s = jax.grad(lambda p: moe.loss_fn(m_sort, mcfg, p,
                                             {"tokens": tokens})[0])(params)
        g_e = jax.grad(lambda p: moe.loss_fn(m_ein, mcfg, p,
                                             {"tokens": tokens})[0])(params)
    for (ks_, a), (_, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(g_s)[0],
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_flatten_with_path(g_e)[0],
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6,
                                   err_msg=str(ks_))


def test_index_routing_keep_set_matches_einsum():
    """Property: the index path's keep/drop decisions equal the einsum
    path's dispatch mask on adversarial logits (everyone wants expert 0),
    and no buffer slot is double-booked."""
    t, e, k, cap = 64, 4, 2, 8
    logits = jnp.zeros((t, e)).at[:, 0].set(3.0)
    logits = logits + 0.01 * jax.random.normal(jax.random.key(5), (t, e))
    dispatch, _, _ = moe.top_k_routing(logits, k, cap)
    dest, gate, keep, _ = moe.top_k_dispatch_indices(logits, k, cap)
    # Rebuild a [T, E] "token kept in expert" mask from both forms.
    ein_mask = np.asarray(dispatch).any(axis=2)
    idx_mask = np.zeros((t, e), bool)
    dest_np, keep_np = np.asarray(dest), np.asarray(keep)
    kept_slots = []
    for c in range(k):
        for tok in range(t):
            if keep_np[c, tok]:
                idx_mask[tok, dest_np[c, tok] // cap] = True
                kept_slots.append(dest_np[c, tok])
    np.testing.assert_array_equal(idx_mask, ein_mask)
    assert len(kept_slots) == len(set(kept_slots))  # slots unique


def test_expert_choice_causal_lm_warns():
    """ADVICE r3 (medium): expert-choice routing in a causal LM leaks
    future tokens through routing — MoELM must warn loudly."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=2, top_k=1, capacity_factor=2.0,
                         routing="expert_choice")
    model = moe.MoELM(cfg, mcfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.warns(UserWarning, match="non-causal"):
        model.init(jax.random.key(0), tokens)


def test_moe_flops_accounting():
    """MoE MFU accounting: active-compute based — expert choice counts
    capacity_factor x top_k expert-slots per token, topk counts top_k; both
    exceed the dense model's FLOPs (router + extra experts)."""
    from k8s_distributed_deeplearning_tpu.models import transformer
    cfg = llama.config_tiny(n_layers=2)
    dense = transformer.flops_per_token(cfg)
    topk = moe.flops_per_token(cfg, moe.MoEConfig(num_experts=4, top_k=2))
    ec = moe.flops_per_token(cfg, moe.MoEConfig(
        num_experts=4, top_k=2, capacity_factor=1.5,
        routing="expert_choice"))
    assert dense < topk < ec


def test_moe_flops_exact_slots_uses_layer_capacity_formula():
    """tokens_per_batch switches flops_per_token to the EXACT dispatched
    slot count E*clamped_capacity(T)/T — the same formula MoEMLP sizes its
    buffers with (ADVICE r3). When the clamp binds (tiny T), the exact
    figure must fall below nominal; when it doesn't, E*C/T >= top_k (the
    buffers compute every slot, filled or not)."""
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=1.25)
    cfg = llama.config_tiny(n_layers=2)
    nominal = moe.flops_per_token(cfg, mcfg)
    # Unclamped: C = int(1.25*2*T/4), active = 4*C/T = 2.5 > top_k == 2.
    big = moe.flops_per_token(cfg, mcfg, tokens_per_batch=4096)
    assert big > nominal
    cap = moe.clamped_capacity(4096, mcfg)
    assert cap == int(1.25 * 2 * 4096 / 4)
    # Clamped: T=2 forces capacity to floor at 1 -> active = 4*1/2 = 2.
    assert moe.clamped_capacity(2, mcfg) == 1
    small = moe.flops_per_token(cfg, mcfg, tokens_per_batch=2)
    assert small < big


def test_expert_choice_capacity_exceeding_tokens_clamps():
    """capacity_factor*top_k > num_experts makes raw capacity exceed the
    token count; the layer must clamp instead of crashing lax.top_k."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=2, top_k=2, capacity_factor=1.25,
                         routing="expert_choice")
    model = moe.MoELM(cfg, mcfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 8), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), tokens)["params"]
    loss, _ = moe.loss_fn(model, mcfg, params, {"tokens": tokens})
    assert jnp.isfinite(loss)


# --- MoE x decode / packed (late round 4: MoELM gains the full LM surface) --

@pytest.mark.parametrize("routing,dispatch", [
    ("topk", "index"), ("expert_choice", "index"), ("topk", "ragged")])
def test_moe_incremental_decode_matches_one_shot_prefill(routing, dispatch):
    """KV-cache decode on an MoE LM: feeding the prompt token-by-token must
    reproduce the one-shot prefill logits. The MoE layers use a DROPLESS
    per-token path at decode (capacity buffers are sized per call, so the
    capacity paths would route a 1-token step differently than a prefill —
    the dropless paths are width-independent by construction): capacity=T
    index buffers by default, the grouped-GEMM ragged path when
    dispatch="ragged" (no [E, T, d] buffers — prefill MLP work stays at
    top_k slots/token). Expert-choice models decode through the same
    forced per-token top-k gates (EC's whole-batch selection has no
    causal decode semantics), so the parity holds for both routings.
    The ragged case uses a WIDE prompt so its prefill actually crosses
    the >=128-token width threshold (ragged grouped-GEMM prefill, index
    decode steps) — the exact hybrid the serving path runs."""
    seq = 80 if dispatch == "ragged" else 10
    prefill = 70 if dispatch == "ragged" else 4
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=128)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0,
                         routing=routing, dispatch=dispatch,
                         ragged_block_m=8)
    model = moe.MoELM(cfg, mcfg)
    toks = jax.random.randint(jax.random.key(0), (2, seq), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), toks)["params"]

    full, _ = model.apply({"params": params}, toks, decode=True,
                          mutable=["cache"])
    logits, vars_ = model.apply({"params": params}, toks[:, :prefill],
                                decode=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, :prefill]),
                               atol=2e-5, rtol=2e-5)
    cache = vars_["cache"]
    for i in range(prefill, toks.shape[1]):
        logits, vars_ = model.apply({"params": params, "cache": cache},
                                    toks[:, i:i + 1], decode=True,
                                    mutable=["cache"])
        cache = vars_["cache"]
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, i]),
                                   atol=3e-5, rtol=3e-5)


def test_moe_generate_greedy():
    """generate() drives an MoE LM end-to-end (windowed KV cache, jitted
    scan): deterministic, in-vocab, and reproducible."""
    from k8s_distributed_deeplearning_tpu.models import generate
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=64)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    model = moe.MoELM(cfg, mcfg)
    toks = jax.random.randint(jax.random.key(0), (2, 6), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), toks)["params"]
    out = generate.generate(model, params, toks, max_new_tokens=8)
    out2 = generate.generate(model, params, toks, max_new_tokens=8)
    assert out.shape == (2, 8)
    a = np.asarray(out)
    assert (a >= 0).all() and (a < cfg.vocab_size).all()
    np.testing.assert_array_equal(a, np.asarray(out2))


def test_moe_packed_matches_separate_rows_when_dropless():
    """Packed MoE training (segment-masked attention + per-document RoPE):
    with a no-drop config (top_k == num_experts, capacity == T, so routing
    is exactly per-token), the packed row's per-token logits equal the
    same documents run as separate rows — attention isolation survives the
    MoE layers."""
    cfg = llama.config_tiny(dtype=jnp.float32, max_seq_len=32)
    mcfg = moe.MoEConfig(num_experts=2, top_k=2, capacity_factor=1.0)
    model = moe.MoELM(cfg, mcfg)
    rng = np.random.default_rng(7)
    d1 = rng.integers(0, cfg.vocab_size, size=8, dtype=np.int32)
    d2 = rng.integers(0, cfg.vocab_size, size=6, dtype=np.int32)
    packed = jnp.asarray(np.concatenate([d1, d2]))[None, :]
    seg = jnp.asarray([1] * 8 + [2] * 6)[None, :]
    params = model.init(jax.random.key(1), packed)["params"]

    from k8s_distributed_deeplearning_tpu.models.transformer import (
        packed_positions)
    lp = model.apply({"params": params}, packed, segment_ids=seg,
                     positions=packed_positions(seg))
    l1 = model.apply({"params": params}, jnp.asarray(d1)[None, :])
    l2 = model.apply({"params": params}, jnp.asarray(d2)[None, :])
    np.testing.assert_allclose(np.asarray(lp[0, :8]), np.asarray(l1[0]),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lp[0, 8:]), np.asarray(l2[0]),
                               atol=3e-5, rtol=3e-5)

    # The packed loss_fn contract: finite loss, aux losses present, and
    # the boundary pair (position 7 -> 8 crosses documents) is excluded.
    loss, aux = moe.loss_fn(model, mcfg, params,
                            {"tokens": packed, "segment_ids": seg})
    assert np.isfinite(float(loss)) and np.isfinite(float(aux["aux_loss"]))


def test_ragged_dispatch_matches_dropless_index():
    """The grouped-GEMM ragged path (dropless by construction) must equal
    the index path when the index path's capacity is large enough that it
    too drops nothing (capacity clamps to T) — same params, same output,
    same grads, same router aux."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mk = lambda dispatch, cf: moe.MoELM(cfg, moe.MoEConfig(
        num_experts=4, top_k=2, capacity_factor=cf, dispatch=dispatch,
        ragged_block_m=8))
    m_rag, m_idx = mk("ragged", 1.25), mk("index", 100.0)
    tokens = jax.random.randint(jax.random.key(3), (4, 16), 0,
                                cfg.vocab_size)
    params = m_rag.init(jax.random.key(1), tokens)["params"]
    mcfg_r = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                           ragged_block_m=8)
    mcfg_i = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=100.0)
    l_r, a_r = moe.loss_fn(m_rag, mcfg_r, params, {"tokens": tokens})
    l_i, a_i = moe.loss_fn(m_idx, mcfg_i, params, {"tokens": tokens})
    np.testing.assert_allclose(float(l_r), float(l_i), rtol=2e-5)
    np.testing.assert_allclose(float(a_r["aux_loss"]),
                               float(a_i["aux_loss"]), rtol=2e-5)
    g_r = jax.grad(lambda p: moe.loss_fn(m_rag, mcfg_r, p,
                                         {"tokens": tokens})[0])(params)
    g_i = jax.grad(lambda p: moe.loss_fn(m_idx, mcfg_i, p,
                                         {"tokens": tokens})[0])(params)
    for (ks_, a), (_, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(g_r)[0],
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_flatten_with_path(g_i)[0],
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=str(ks_))


def test_ragged_dispatch_is_dropless_under_pressure():
    """Adversarial routing (every token prefers expert 0): the capacity
    paths drop; ragged must report fraction_dropped == 0 and still produce
    finite outputs/grads."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=8)
    model = moe.MoELM(cfg, mcfg)
    tokens = jnp.zeros((2, 16), jnp.int32)  # identical tokens -> one expert
    params = model.init(jax.random.key(0), tokens)["params"]
    _, state = model.apply({"params": params}, tokens,
                           mutable=["intermediates"])
    flat = jax.tree_util.tree_flatten_with_path(state["intermediates"])[0]
    dropped = [float(jnp.ravel(v)[0]) for path, v in flat
               if "fraction_dropped" in str(path)]
    assert dropped and all(d == 0.0 for d in dropped)
    loss, _ = moe.loss_fn(model, mcfg, params, {"tokens": tokens})
    g = jax.grad(lambda p: moe.loss_fn(model, mcfg, p,
                                       {"tokens": tokens})[0])(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(g))


def test_ragged_rejects_expert_choice():
    with pytest.raises(ValueError, match="expert choice"):
        moe.MoEConfig(routing="expert_choice", dispatch="ragged")


def test_ragged_trains_end_to_end(mesh8):
    """Smoke: the ragged dispatch through the sharded trainer on the
    8-device data mesh — loss decreases, state stays finite."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=8)
    model = moe.MoELM(cfg, mcfg)
    tr = sharding.ShardedTrainer(
        lambda p, b, r: moe.loss_fn(model, mcfg, p, b, r),
        optax.adam(1e-2), mesh8)
    state = tr.init(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.key(0))
    step = tr.make_step()
    toks = jax.random.randint(jax.random.key(1), (8, 17), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    batch = tr.shard_batch({"tokens": toks})
    losses = []
    for i in range(8):
        state, loss, _ = step(state, batch, jax.random.key(i))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_moe_chunked_ce_matches_unchunked():
    """MoE × chunked CE (round 5 — the former NotImplemented combo):
    hidden-states head chunking must reproduce the unchunked loss AND
    grads exactly at f32, with the aux losses still collected."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0)
    model = moe.MoELM(cfg, mcfg)
    toks = jax.random.randint(jax.random.key(3), (4, 33), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), toks[:, :8])["params"]
    batch = {"tokens": toks}

    l_ref, aux_ref = moe.loss_fn(model, mcfg, params, batch)
    l_ch, aux_ch = moe.loss_fn(model, mcfg, params, batch, chunked=True,
                               chunk_size=8)
    np.testing.assert_allclose(float(l_ch), float(l_ref), rtol=1e-6)
    np.testing.assert_allclose(float(aux_ch["aux_loss"]),
                               float(aux_ref["aux_loss"]), rtol=1e-6)
    g_ref = jax.grad(lambda p: moe.loss_fn(model, mcfg, p, batch)[0])(params)
    g_ch = jax.grad(lambda p: moe.loss_fn(model, mcfg, p, batch,
                                          chunked=True, chunk_size=8)[0])(
        params)
    for (ks_, a), (_, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(g_ref)[0],
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_flatten_with_path(g_ch)[0],
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=1e-6, err_msg=str(ks_))


@pytest.mark.parametrize("spec", [{"data": 8}, {"data": 2, "sequence": 4}])
def test_ragged_shard_mesh_matches_unsharded(spec):
    """shard_map'd ragged dispatch (shard_mesh set) must equal the
    unwrapped path exactly — dropless routing is per-token, so
    shard-local dispatch changes buffer positions, never outputs — and
    must actually SHARD the grouped-GEMM operands (without the wrap a
    Pallas call has no GSPMD rule and every device computes the global
    batch; verified here by the compiled per-device tensor shapes).
    Covers the sequence axis too: the flattened token dim is sharded
    (data, fsdp, sequence), so CP meshes partition the expert compute."""
    mesh8 = mesh_lib.make_mesh(spec)
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=8)
    m_plain = moe.MoELM(cfg, mcfg)
    m_shard = moe.MoELM(cfg, mcfg, shard_mesh=mesh8)
    toks = jax.random.randint(jax.random.key(3), (8, 17), 0, cfg.vocab_size)
    params = m_plain.init(jax.random.key(1), toks)["params"]

    l_p, a_p = moe.loss_fn(m_plain, mcfg, params, {"tokens": toks})
    with mesh8:
        l_s, a_s = jax.jit(lambda p, b: moe.loss_fn(m_shard, mcfg, p, b))(
            params, {"tokens": toks})
    np.testing.assert_allclose(float(l_s), float(l_p), rtol=2e-5)
    np.testing.assert_allclose(float(a_s["aux_loss"]),
                               float(a_p["aux_loss"]), rtol=2e-5)
    g_p = jax.grad(lambda p: moe.loss_fn(m_plain, mcfg, p,
                                         {"tokens": toks})[0])(params)
    with mesh8:
        g_s = jax.jit(jax.grad(lambda p: moe.loss_fn(
            m_shard, mcfg, p, {"tokens": toks})[0]))(params)
    for (ks_, a), (_, b) in zip(
            sorted(jax.tree_util.tree_flatten_with_path(g_p)[0],
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_flatten_with_path(g_s)[0],
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=str(ks_))


def test_ragged_shard_mesh_shards_the_compute(mesh8):
    """The sharding FACT: under dp8 with shard_mesh, the compiled step's
    grouped-GEMM row dimension is the per-device token count, not the
    global batch (the replication hole this wrap closes)."""
    import re

    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False,
                            dim=128, mlp_dim=256)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=64)
    model = moe.MoELM(cfg, mcfg, shard_mesh=mesh8)
    tr = sharding.ShardedTrainer(
        lambda p, b, r: moe.loss_fn(model, mcfg, p, b, r),
        optax.adam(1e-3), mesh8)
    state = tr.init(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (64, 65), 0, cfg.vocab_size)
    batch = tr.shard_batch({"tokens": toks})
    txt = tr.make_step(donate=False).lower(
        state, batch, jax.random.key(0)).compile().as_text()
    # Global T*k = 64*64*2 = 8192 -> global m_pad >= 8192; per-device
    # T*k = 1024 -> local m_pad = 1024 + 4*64 = 1280. The compiled
    # module must contain the LOCAL padded buffer and never the global.
    rows = {int(m.group(1)) for m in re.finditer(r"f32\[(\d+),128\]", txt)}
    assert 1280 in rows, sorted(rows, reverse=True)[:5]
    assert not any(r >= 8192 for r in rows), sorted(rows, reverse=True)[:5]


def test_ragged_indivisible_fallback_raises_under_training(mesh8):
    """A token count that doesn't divide the mesh batch factor can't use
    the shard_map wrap — the Pallas grouped GEMM has no GSPMD rule, so
    the fallback silently replicates the FULL expert compute on every
    device. A mis-sized training batch must fail loudly, not train at
    bfac x the cost."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=8)
    model = moe.MoELM(cfg, mcfg, shard_mesh=mesh8)
    toks = jax.random.randint(jax.random.key(0), (3, 6), 0, cfg.vocab_size)
    # init through a plain model (identical param structure) so the
    # indivisible apply is the FIRST thing the sharded model traces
    params = moe.MoELM(cfg, mcfg).init(jax.random.key(1), toks)["params"]
    # flattened t = 3*6 = 18, not a multiple of the 8-way batch factor
    with pytest.raises(ValueError, match="does not divide"):
        moe.loss_fn(model, mcfg, params, {"tokens": toks})


def test_ragged_indivisible_fallback_warns_once_at_decode(mesh8):
    """Serving widths are arbitrary, so decode keeps the unsharded
    fallback — but says so exactly once (RuntimeWarning), because the
    replication cost is invisible otherwise."""
    import warnings

    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=1, scan_layers=False,
                            max_seq_len=256)
    mcfg = moe.MoEConfig(num_experts=4, top_k=2, dispatch="ragged",
                         ragged_block_m=8)
    model = moe.MoELM(cfg, mcfg, shard_mesh=mesh8)
    # wide prompt: t = 129 >= 128 crosses into the ragged prefill path
    # and 129 % 8 != 0 triggers the fallback
    toks = jax.random.randint(jax.random.key(0), (1, 129), 0, cfg.vocab_size)
    params = moe.MoELM(cfg, mcfg).init(jax.random.key(1),
                                       toks[:, :8])["params"]
    moe._RAGGED_FALLBACK_WARNED.clear()
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            model.apply({"params": params}, toks, decode=True,
                        mutable=["cache"])
            model.apply({"params": params}, toks, decode=True,
                        mutable=["cache"])
        hits = [w for w in rec if issubclass(w.category, RuntimeWarning)
                and "does not divide" in str(w.message)]
        assert len(hits) == 1, [str(w.message) for w in rec]
    finally:
        moe._RAGGED_FALLBACK_WARNED.clear()


def test_grouped_serving_calls_count_their_row_blocks():
    """The engine's ``moe_row_blocks`` / ``moe_live_blocks`` (on the records
    of the calls whose experts take the grouped kernel, and summed in
    ``serve_moe_live_block_share``) against a hand count from each call's
    per-layer counts and against the layouts the program builds from them.
    One chip's share of the experts (4 of 8, top-2): a 32-token chunk is
    grouped (4 row blocks' worth of rows); the decode's 2 rows and an
    8-token final chunk are dense and carry neither."""
    import collections
    import dataclasses

    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    from k8s_distributed_deeplearning_tpu.serve.engine import ServeEngine
    from k8s_distributed_deeplearning_tpu.serve.request import Request
    from k8s_distributed_deeplearning_tpu.telemetry import bridge
    from k8s_distributed_deeplearning_tpu.telemetry.registry import MetricsRegistry
    from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
    cfg, latent, mcfg = moe.config_tiny_latent_moe()
    mcfg = dataclasses.replace(mcfg, experts_held=4, expert_offset=2)
    model = moe.LatentMoELM(cfg, latent, mcfg)
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    tracer = Tracer(ring_size=4096)
    eng = ServeEngine(model, params, num_slots=2, min_bucket=8,
                      prefill_chunk_tokens=32, tracer=tracer)
    reg = MetricsRegistry()
    bridge.serving_collector(reg, eng.stats)
    assert eng.stats.summary()["moe_live_block_share"] is None
    calls, record = [], eng._record_counts
    eng._record_counts = lambda fields, counts, rows: (
        calls.append((np.array(counts), rows)), record(fields, counts, rows))
    rng = np.random.default_rng(5)
    eng.run([Request(prompt=rng.integers(0, 256, size=n).tolist(),
                     max_new_tokens=3, request_id=f"r{n}") for n in (70, 60, 9)])

    want_row_blocks = want_live = 0
    for counts, rows in calls:
        per_layer = counts.reshape(-1, 4)            # 2 expert layers of 3
        assert per_layer.shape[0] == 2 and (per_layer.sum(axis=1) <= rows * 2).all()
        if rows != 32:
            continue
        # 64 picks a layer: 8 blocks of 8 rows + one a held expert; a held
        # expert's live blocks are its rows over 8, rounded up
        want_row_blocks += 2 * (64 // 8 + 4)
        live = int(sum(-(-c // 8) for c in per_layer.ravel()))
        want_live += live
        layouts = [pallas_gmm.grouped_layout(jnp.asarray(c), 64, block_m=8)
                   for c in per_layer]
        assert sum(lay.m_pad // 8 for lay in layouts) == 2 * 12
        assert sum(int(lay.block_live.sum()) for lay in layouts) == live
    # 70 tokens: 2 chunks + a final one of 8; 60: a chunk + a final one of
    # 32; 9: a final one of 16; and the decode steps
    rows_of = collections.Counter(rows for _, rows in calls)
    assert (rows_of[32], rows_of[16], rows_of[8]) == (4, 1, 1) and rows_of[2] > 0

    spans = tracer.recent_spans()
    grouped = [s for s in spans if "moe_row_blocks" in s]
    assert [s["name"] for s in grouped] == ["prefill_counts"] * 4
    assert all(s.get("chunk") == 32 or s.get("bucket") == 32 for s in grouped)
    assert not any("moe_live_blocks" in s for s in spans
                   if s["name"] == "decode" or s.get("bucket") in (8, 16))
    assert (sum(s["moe_row_blocks"] for s in grouped),
            sum(s["moe_live_blocks"] for s in grouped)) == (want_row_blocks,
                                                            want_live)
    s = eng.stats.summary()
    assert (s["moe_row_blocks"], s["moe_live_blocks"]) == (want_row_blocks,
                                                           want_live)
    assert s["moe_live_block_share"] == round(want_live / want_row_blocks, 4)
    assert f"serve_moe_live_block_share {s['moe_live_block_share']}" in reg.render()
