"""Transformer core: shapes, causality, RoPE, GQA, scan/loop equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.models import llama, moe
from k8s_distributed_deeplearning_tpu.models.transformer import (
    RMSNorm, TransformerConfig, apply_rope, rope_frequencies)
from k8s_distributed_deeplearning_tpu.ops import attention as attn_ops


def test_rmsnorm_normalizes():
    m = RMSNorm(dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (4, 16)) * 10.0
    params = m.init(jax.random.key(1), x)
    y = m.apply(params, x)
    rms = np.sqrt(np.mean(np.asarray(y) ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_rope_preserves_norm_and_relative_position():
    cos, sin = rope_frequencies(8, 32, 10000.0)
    x = jax.random.normal(jax.random.key(0), (1, 32, 2, 8))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-5)
    # Relative property: <rope(q,i), rope(k,j)> depends only on i-j.
    q = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.key(2), (1, 1, 1, 8))
    def dot_at(i, j):
        qi = apply_rope(q, cos, sin, positions=jnp.array([[i]]))
        kj = apply_rope(k, cos, sin, positions=jnp.array([[j]]))
        return float(jnp.vdot(qi, kj))
    assert dot_at(3, 1) == pytest.approx(dot_at(7, 5), rel=1e-4)


def test_attention_causal_masks_future():
    b, s, h, d = 2, 8, 2, 4
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, h, d))
    v = jax.random.normal(jax.random.key(2), (b, s, h, d))
    out_full = attn_ops.dot_product_attention(q, k, v, causal=True)
    # Truncating the future must not change earlier outputs.
    out_trunc = attn_ops.dot_product_attention(
        q[:, :4], k[:, :4], v[:, :4], causal=True)
    np.testing.assert_allclose(np.asarray(out_full[:, :4]),
                               np.asarray(out_trunc), atol=1e-5)


def test_attention_gqa_matches_repeated_mha():
    b, s, d = 2, 8, 4
    q = jax.random.normal(jax.random.key(0), (b, s, 4, d))
    k = jax.random.normal(jax.random.key(1), (b, s, 2, d))
    v = jax.random.normal(jax.random.key(2), (b, s, 2, d))
    gqa = attn_ops.dot_product_attention(q, k, v)
    mha = attn_ops.dot_product_attention(
        q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
    np.testing.assert_allclose(np.asarray(gqa), np.asarray(mha), atol=1e-6)


def test_llama_forward_and_loss():
    cfg = llama.config_tiny(dtype=jnp.float32)
    model = llama.LlamaLM(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.key(1), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    loss, aux = llama.loss_fn(model, params, {"tokens": tokens})
    assert jnp.isfinite(loss)
    assert 0.0 <= float(aux["accuracy"]) <= 1.0
    # Untrained loss should be near ln(vocab).
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.5


def test_scan_and_loop_layers_agree():
    kwargs = dict(dtype=jnp.float32, n_layers=2)
    tokens = jax.random.randint(jax.random.key(0), (1, 8), 0, 256)
    m_scan = llama.LlamaLM(llama.config_tiny(scan_layers=True, **kwargs))
    m_loop = llama.LlamaLM(llama.config_tiny(scan_layers=False, **kwargs))
    import flax.linen as nn
    p_scan = nn.meta.unbox(m_scan.init(jax.random.key(1), tokens)["params"])
    p_loop = nn.meta.unbox(m_loop.init(jax.random.key(1), tokens)["params"])
    # Same parameter count either way.
    n = sum(x.size for x in jax.tree.leaves(p_scan))
    m = sum(x.size for x in jax.tree.leaves(p_loop))
    assert n == m
    # Copy scan-stacked weights into the loop layout; outputs must agree.
    import flax
    flat_scan = flax.traverse_util.flatten_dict(p_scan, sep="/")
    flat_loop = flax.traverse_util.flatten_dict(p_loop, sep="/")
    for key, val in flat_loop.items():
        if "/block_" in key:
            prefix, rest = key.split("/block_", 1)
            idx, rest = rest.split("/", 1)
            stacked = flat_scan[f"{prefix}/blocks/{rest}"]
            flat_loop[key] = stacked[int(idx)]
        else:
            flat_loop[key] = flat_scan[key]
    p_loop2 = flax.traverse_util.unflatten_dict(flat_loop, sep="/")
    out_scan = m_scan.apply({"params": p_scan}, tokens)
    out_loop = m_loop.apply({"params": p_loop2}, tokens)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_loop),
                               atol=1e-5)


def test_remat_matches_no_remat():
    tokens = jax.random.randint(jax.random.key(0), (1, 8), 0, 256)
    m1 = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32, remat=False))
    m2 = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32, remat=True))
    p = m1.init(jax.random.key(1), tokens)["params"]
    g1 = jax.grad(lambda p: llama.loss_fn(m1, p, {"tokens": tokens})[0])(p)
    g2 = jax.grad(lambda p: llama.loss_fn(m2, p, {"tokens": tokens})[0])(p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5), g1, g2)


def test_packed_sequences_equal_separate_documents():
    """Packed training semantics: a [doc A | doc B] row with segment_ids must
    produce the same per-position logits as running each document alone, for
    both attention impls — the sequence-packing correctness property."""
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, n_heads=4,
                            n_kv_heads=4, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    a = jax.random.randint(jax.random.key(0), (1, 16), 0, cfg.vocab_size)
    b = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab_size)
    packed = jnp.concatenate([a, b], axis=1)                  # [1, 32]
    seg = jnp.concatenate([jnp.zeros((1, 16), jnp.int32),
                           jnp.ones((1, 16), jnp.int32)], axis=1)
    params = model.init(jax.random.key(2), packed)["params"]

    # RoPE positions restart per document, like separate forward passes.
    pos = jnp.concatenate([jnp.arange(16), jnp.arange(16)])[None]
    out_packed = model.apply({"params": params}, packed, segment_ids=seg,
                             positions=pos)
    out_a = model.apply({"params": params}, a)
    out_b = model.apply({"params": params}, b)
    np.testing.assert_allclose(np.asarray(out_packed[:, :16]),
                               np.asarray(out_a), atol=2e-5)
    np.testing.assert_allclose(np.asarray(out_packed[:, 16:]),
                               np.asarray(out_b), atol=2e-5)


def test_packed_loss_masks_document_boundary():
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2)
    model = llama.LlamaLM(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 33), 0, cfg.vocab_size)
    seg = jnp.concatenate([jnp.zeros((2, 17), jnp.int32),
                           jnp.ones((2, 16), jnp.int32)], axis=1)
    params = model.init(jax.random.key(1), tokens)["params"]
    loss, aux = llama.loss_fn(model, params,
                              {"tokens": tokens, "segment_ids": seg})
    assert np.isfinite(float(loss))


def test_packed_loss_equals_separate_document_loss():
    """llama.loss_fn on a packed batch (with positions derived internally
    from segment_ids) must equal the token-weighted CE of training each
    document separately — the end-to-end packing-parity property."""
    import optax
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        packed_positions)
    cfg = llama.config_tiny(dtype=jnp.float32, n_layers=2, n_heads=4,
                            n_kv_heads=4, max_seq_len=64)
    model = llama.LlamaLM(cfg)
    a = jax.random.randint(jax.random.key(0), (1, 16), 0, cfg.vocab_size)
    b = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab_size)
    packed = jnp.concatenate([a, b], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 16), jnp.int32),
                           jnp.ones((1, 16), jnp.int32)], axis=1)
    params = model.init(jax.random.key(2), packed)["params"]

    # positions restart at each document (the invariant loss_fn relies on)
    np.testing.assert_array_equal(
        np.asarray(packed_positions(seg)[0]),
        np.concatenate([np.arange(16), np.arange(16)]))

    loss_packed, _ = llama.loss_fn(model, params,
                                   {"tokens": packed, "segment_ids": seg})

    def doc_ce(toks):
        logits = model.apply({"params": params}, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]).sum(), toks.shape[1] - 1

    ca, na = doc_ce(a)
    cb, nb = doc_ce(b)
    expected = (float(ca) + float(cb)) / (na + nb)
    np.testing.assert_allclose(float(loss_packed), expected, rtol=1e-5)


def test_remat_policy_variants():
    """Remat policies only change what the BACKWARD saves — compare loss
    AND grads against the no-remat reference for every policy."""
    import dataclasses
    import pytest
    from k8s_distributed_deeplearning_tpu.models import llama

    base = llama.config_tiny(dtype=jnp.float32, remat=True)
    ref_model = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
    toks = jax.random.randint(jax.random.key(0), (2, 17), 0, 256)
    params = ref_model.init(jax.random.key(1), toks[:, :8])["params"]
    batch = {"tokens": toks}
    (ref_loss, _), ref_grads = jax.value_and_grad(
        lambda p: llama.loss_fn(ref_model, p, batch), has_aux=True)(params)
    for policy in ("dots", "nothing"):
        m = llama.LlamaLM(dataclasses.replace(base, remat_policy=policy))
        (loss, _), grads = jax.value_and_grad(
            lambda p: llama.loss_fn(m, p, batch), has_aux=True)(params)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-6), grads, ref_grads)
    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(base, remat_policy="bogus")


@pytest.mark.parametrize("attention", ["dense", "latent"])
def test_slot_decode_without_block_tables_is_refused(attention):
    """Per-row cursors exist only over the paged pool: ``cache_positions``
    without ``block_tables`` is refused by both attention classes (at trace,
    before any cache is made), and so is ``slot_decode_step`` without its
    tables."""
    from k8s_distributed_deeplearning_tpu.models import generate
    if attention == "dense":
        model = llama.LlamaLM(llama.config_tiny(dtype=jnp.float32))
    else:
        model = moe.LatentMoELM(*moe.config_tiny_latent_moe())
    toks = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="requires block_tables"):
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), toks, decode=True,
            cache_positions=jnp.zeros((2,), jnp.int32)))
    with pytest.raises(TypeError, match="block_tables"):
        generate.slot_decode_step(model, None, None, toks[:, 0],
                                  jnp.zeros((2,), jnp.int32))
