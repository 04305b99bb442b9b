"""Pallas flash attention (interpret mode on CPU) vs reference attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from k8s_distributed_deeplearning_tpu.ops import attention as attn_ops
from k8s_distributed_deeplearning_tpu.ops import pallas_flash


def _qkv(b=2, sq=64, sk=64, h=2, hkv=None, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, sq, h, d)),
            jax.random.normal(ks[1], (b, sk, hkv or h, d)),
            jax.random.normal(ks[2], (b, sk, hkv or h, d)))


# The shape classes the kernels adapt to: a head of 16 lanes a grid cell (the
# folded view), and PAIRS of 64-lane heads (h == hkv) — whole sequence in one
# block, and streamed in blocks of 128 (_BLOCK_Q/_BLOCK_K narrowed).
SHAPE_CLASSES = [
    pytest.param(dict(), None, id="folded"),
    pytest.param(dict(sq=128, sk=128, h=4, d=64), None, id="pairs-resident"),
    pytest.param(dict(sq=256, sk=256, h=2, d=64), 128, id="pairs-streamed"),
]


def _narrow_blocks(monkeypatch, block):
    if block:
        monkeypatch.setattr(pallas_flash, "_BLOCK_Q", block)
        monkeypatch.setattr(pallas_flash, "_BLOCK_K", block)


@pytest.mark.parametrize("shape,block", SHAPE_CLASSES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal, shape, block, monkeypatch):
    _narrow_blocks(monkeypatch, block)
    q, k, v = _qkv(**shape)
    ref = attn_ops.dot_product_attention(q, k, v, causal=causal)
    out = pallas_flash.flash_attention(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gqa():
    q, k, v = _qkv(h=4, hkv=2)
    ref = attn_ops.dot_product_attention(q, k, v, causal=True)
    out = pallas_flash.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("h,hkv", [(4, 2), (4, 1), (12, 4)])
def test_flash_gqa_grads_match_reference(h, hkv):
    """Native-GQA backward: dK/dV accumulate the query-head-group sum
    in-kernel (group heads stream through the dkv grid) — grads must match
    the XLA reference, which realizes the same sum through jnp.repeat's VJP.
    Covers GQA (4/2), MQA (4/1), and the flagship ratio (12/4)."""
    q, k, v = _qkv(sq=32, sk=32, h=h, hkv=hkv)

    def loss_ref(q, k, v):
        o = attn_ops.dot_product_attention(q, k, v, causal=True)
        return (o * o).sum()

    def loss_flash(q, k, v):
        o = pallas_flash.flash_attention(q, k, v, causal=True, interpret=True)
        return (o * o).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        assert a.shape == b.shape, f"d{name} shape"
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_streaming_superblocks(causal, monkeypatch):
    """GQA with MULTIPLE Q superblocks per head: the dkv streaming dim
    interleaves (head, superblock) steps — head-local causal coordinates
    and cross-head accumulation must both hold, fwd and bwd."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_flash as pf
    monkeypatch.setattr(pf, "_SUPERBLOCK", 64)
    B, S, H, HKV, D = 2, 256, 4, 2, 16      # 4 superblocks x group 2
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, S, HKV, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, S, HKV, D), jnp.float32) * 0.5
    out = pf.flash_attention(q, k, v, causal=causal)
    ref = attn_ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q, k, v: (pf.flash_attention(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (attn_ops.dot_product_attention(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_gqa_segments_grads(monkeypatch):
    """GQA x packed segments through the streaming kernels: the segment
    BlockSpecs on the dkv grid index by (batch, head-local superblock)."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_flash as pf
    monkeypatch.setattr(pf, "_SUPERBLOCK", 64)
    B, S, H, HKV, D = 1, 128, 4, 2, 16
    ks = jax.random.split(jax.random.key(22), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, S, HKV, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, S, HKV, D), jnp.float32) * 0.5
    seg = jnp.concatenate([jnp.zeros((B, 70), jnp.int32),
                           jnp.ones((B, 58), jnp.int32)], axis=1)
    g = jax.grad(lambda q, k, v: pf.flash_attention(
        q, k, v, causal=True, q_segment_ids=seg,
        kv_segment_ids=seg).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: attn_ops.multi_head_attention(
        q, k, v, causal=True, segment_ids=seg,
        impl="xla").sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_cross_attention_lengths():
    q, k, v = _qkv(sq=32, sk=128)
    ref = attn_ops.dot_product_attention(q, k, v)
    out = pallas_flash.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("shape,block", SHAPE_CLASSES)
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal, shape, block, monkeypatch):
    _narrow_blocks(monkeypatch, block)
    q, k, v = _qkv(**(shape or dict(sq=32, sk=32)))

    def loss_ref(q, k, v):
        o = attn_ops.dot_product_attention(q, k, v, causal=causal)
        return (o * o).sum()  # nontrivial cotangent

    def loss_flash(q, k, v):
        o = pallas_flash.flash_attention(q, k, v, causal=causal,
                                         interpret=True)
        return (o * o).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   err_msg=f"d{name} mismatch")


def _eqns(jaxpr):
    """Every equation of a jaxpr, inner jaxprs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _pallas_calls(jaxpr) -> list:
    return [e.params["name"] for e in _eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def _big_transposes(jaxpr, size) -> list:
    return [e for e in _eqns(jaxpr) if e.primitive.name == "transpose"
            and e.invars[0].aval.size >= size]


def _grad_jaxpr(q, k, v, **kw):
    return jax.make_jaxpr(jax.grad(lambda q, k, v: (pallas_flash.flash_attention(
        q, k, v, interpret=True, **kw) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr


def test_pairs_of_heads_take_rows_of_heads_without_a_transpose():
    """Head size 64 with as many KV heads as query heads: a pair of heads
    is one 128-lane column block of [B, S, H*D], so no operand, output or
    gradient is transposed on its way to or from the kernels. The folded
    view (GQA, other head sizes) does transpose."""
    q, k, v = _qkv(sq=128, sk=128, h=4, d=64)
    assert not _big_transposes(_grad_jaxpr(q, k, v), q.size)
    q, k, v = _qkv(sq=128, sk=128, h=4, hkv=2, d=64)
    assert _big_transposes(_grad_jaxpr(q, k, v), k.size)


def test_resident_sequence_lowers_one_backward_kernel(monkeypatch):
    """One query block and one key block hold the sequence: dq, dk and dv
    come from ONE recomputation of the probabilities. A streamed sequence
    keeps the dq and the dk/dv kernel (each accumulates over its own grid)."""
    q, k, v = _qkv(sq=256, sk=256, h=2, d=64)
    assert _pallas_calls(_grad_jaxpr(q, k, v)) == [
        "flash_attn_fwd", "flash_attn_bwd"]
    assert _pallas_calls(_grad_jaxpr(q, k, v, causal=True)) == [
        "flash_attn_fwd", "flash_attn_bwd"]
    _narrow_blocks(monkeypatch, 128)
    assert _pallas_calls(_grad_jaxpr(q, k, v)) == [
        "flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"]


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _qkv()
    ref = attn_ops.dot_product_attention(q, k, v, causal=True)
    out = pallas_flash.flash_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2)


def test_flash_under_jit_and_dispatch():
    q, k, v = _qkv(sq=32, sk=32)
    out = jax.jit(lambda q, k, v: attn_ops.multi_head_attention(
        q, k, v, causal=True, impl="flash"))(q, k, v)
    ref = attn_ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sq,sk", [(32, 128), (16, 64)])
def test_flash_causal_decode_alignment(sq, sk):
    """Causal with Sq != Sk must align the diagonal at col == row + (Sk-Sq),
    matching the reference mask (attention.py decode semantics)."""
    q, k, v = _qkv(sq=sq, sk=sk)
    ref = attn_ops.dot_product_attention(q, k, v, causal=True)
    out = pallas_flash.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal_decode_grads_match():
    q, k, v = _qkv(sq=16, sk=64)

    def loss_ref(q, k, v):
        return attn_ops.dot_product_attention(q, k, v, causal=True).sum()

    def loss_flash(q, k, v):
        return pallas_flash.flash_attention(q, k, v, causal=True,
                                            interpret=True).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-5)


def test_flash_rejects_indivisible_gqa():
    q, k, v = _qkv(h=4, hkv=3)
    with pytest.raises(ValueError, match="not divisible"):
        pallas_flash.flash_attention(q, k, v, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_match_reference(causal):
    """Packed-sequence masking: flash with segment ids == reference with the
    equivalent boolean mask (forward)."""
    q, k, v = _qkv(sq=64, sk=64)
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], 2, axis=0).repeat(16, axis=1))
    ref = attn_ops.dot_product_attention(
        q, k, v, causal=causal, mask=attn_ops.segment_mask(seg, seg))
    out = pallas_flash.flash_attention(q, k, v, causal=causal,
                                       q_segment_ids=seg, kv_segment_ids=seg,
                                       interpret=True)
    # Rows whose segment has no visible keys are NaN in the reference
    # (softmax over all -inf) but 0 in flash; none exist here by design.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_segment_ids_grads_match():
    q, k, v = _qkv(sq=32, sk=32)
    seg = jnp.asarray(np.repeat([[0, 1]], 2, axis=0).repeat(16, axis=1))
    mask = attn_ops.segment_mask(seg, seg)

    def loss_ref(q, k, v):
        return attn_ops.dot_product_attention(q, k, v, causal=True,
                                              mask=mask).sum()

    def loss_flash(q, k, v):
        return pallas_flash.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg, kv_segment_ids=seg,
            interpret=True).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-5)


def test_flash_segment_ids_isolate_documents():
    """A token's output must not change when OTHER segments' contents change
    — the packing-isolation property."""
    q, k, v = _qkv(sq=32, sk=32, seed=0)
    seg = jnp.asarray(np.repeat([[0, 1]], 2, axis=0).repeat(16, axis=1))
    base = pallas_flash.flash_attention(q, k, v, causal=True,
                                        q_segment_ids=seg,
                                        kv_segment_ids=seg, interpret=True)
    # Perturb only segment-1 keys/values; segment-0 outputs must be identical.
    k2 = k.at[:, 16:].set(jax.random.normal(jax.random.key(9), k[:, 16:].shape))
    v2 = v.at[:, 16:].set(jax.random.normal(jax.random.key(10), v[:, 16:].shape))
    out2 = pallas_flash.flash_attention(q, k2, v2, causal=True,
                                        q_segment_ids=seg,
                                        kv_segment_ids=seg, interpret=True)
    np.testing.assert_array_equal(np.asarray(base[:, :16]),
                                  np.asarray(out2[:, :16]))
    assert not np.allclose(np.asarray(base[:, 16:]), np.asarray(out2[:, 16:]))


def test_flash_segment_ids_validation():
    q, k, v = _qkv()
    seg = jnp.zeros(q.shape[:2], jnp.int32)
    with pytest.raises(ValueError, match="together"):
        pallas_flash.flash_attention(q, k, v, q_segment_ids=seg,
                                     interpret=True)
    with pytest.raises(ValueError, match=r"\[B, Sq\]"):
        pallas_flash.flash_attention(q, k, v, q_segment_ids=seg[:, :8],
                                     kv_segment_ids=seg, interpret=True)


def test_default_impl_rule():
    """The impl="auto" rule as measured on a TPU v5e (PERF.md §6, PR 30):
    flash on a TPU when both lengths are whole multiples of the kernel's
    512-token block and the head size is one it tiles; XLA otherwise, and
    always on a CPU."""
    from k8s_distributed_deeplearning_tpu.ops.attention import default_impl
    assert default_impl(512, 512, 64, platform="tpu") == "flash"    # BERT's
    assert default_impl(512, None, 128, platform="tpu") == "flash"
    assert default_impl(1024, None, 64, platform="tpu") == "flash"
    assert default_impl(2048, None, 128, platform="tpu") == "flash"
    assert default_impl(384, None, 64, platform="tpu") == "xla"     # blocks of 128
    assert default_impl(256, None, 64, platform="tpu") == "xla"     # a tie: stays
    assert default_impl(640, None, 64, platform="tpu") == "xla"
    assert default_impl(1100, None, 64, platform="tpu") == "xla"    # unaligned
    assert default_impl(197, None, 64, platform="tpu") == "xla"     # ViT
    assert default_impl(512, None, 80, platform="tpu") == "xla"     # odd head size
    assert default_impl(512, None, 16, platform="tpu") == "xla"
    assert default_impl(4096, None, 128, platform="cpu") == "xla"   # interpret mode
    assert default_impl(4096, None, 128) == "xla"                   # CI runs on CPU
    # Cross-attention: BOTH lengths must tile well (ADVICE r2 item 4).
    assert default_impl(2048, 2048, 128, platform="tpu") == "flash"
    assert default_impl(2048, 512, 128, platform="tpu") == "flash"
    assert default_impl(2048, 1100, 128, platform="tpu") == "xla"
    assert default_impl(2048, 256, 128, platform="tpu") == "xla"
    assert default_impl(256, 4096, 128, platform="tpu") == "xla"


def test_auto_impl_dispatches_and_matches():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from k8s_distributed_deeplearning_tpu.ops.attention import (
        multi_head_attention)
    q = jax.random.normal(jax.random.key(0), (1, 64, 2, 16))
    out_auto = multi_head_attention(q, q, q, causal=True, impl="auto")
    out_xla = multi_head_attention(q, q, q, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out_auto), np.asarray(out_xla),
                               atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_streaming_superblock_path_matches_reference(causal, monkeypatch):
    """Force the multi-superblock (streaming) code path at CI-sized shapes
    by shrinking the superblock: scratch-carried online softmax across
    superblocks must match the reference exactly (the path real TPUs take
    at S > 4096)."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_flash as pf
    monkeypatch.setattr(pf, "_SUPERBLOCK", 64)
    B, S, H, D = 2, 256, 2, 16          # 4 superblocks of 64
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) * 0.5
               for kk in ks)
    out = pf.flash_attention(q, k, v, causal=causal)
    ref = attn_ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q, k, v: pf.flash_attention(
        q, k, v, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: attn_ops.dot_product_attention(
        q, k, v, causal=causal).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_streaming_superblock_segments(monkeypatch):
    from k8s_distributed_deeplearning_tpu.ops import pallas_flash as pf
    monkeypatch.setattr(pf, "_SUPERBLOCK", 64)
    B, S, H, D = 1, 128, 2, 16
    ks = jax.random.split(jax.random.key(8), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) * 0.5
               for kk in ks)
    seg = jnp.concatenate([jnp.zeros((B, 70), jnp.int32),
                           jnp.ones((B, 58), jnp.int32)], axis=1)
    out = pf.flash_attention(q, k, v, causal=True,
                             q_segment_ids=seg, kv_segment_ids=seg)
    ref = attn_ops.multi_head_attention(q, k, v, causal=True,
                                         segment_ids=seg, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # Backward through the streaming dq/dkv kernels with segment specs.
    g = jax.grad(lambda q, k, v: pf.flash_attention(
        q, k, v, causal=True, q_segment_ids=seg,
        kv_segment_ids=seg).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: attn_ops.multi_head_attention(
        q, k, v, causal=True, segment_ids=seg,
        impl="xla").sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_diag_split_matches_general_masking():
    """The diagonal-split causal specialization must be numerically
    identical to the general per-block masking it replaces. Forcing
    all-equal segment ids selects the general path (segments disable the
    specialization) while leaving the effective mask purely causal — an
    A/B of the two code paths on the same shapes, fwd and all grads."""
    b, s, h, d = 2, 1024, 4, 64
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    seg = jnp.ones((b, s), jnp.int32)   # same mask, general code path

    def loss_split(q, k, v):
        return (pallas_flash.flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_general(q, k, v):
        return (pallas_flash.flash_attention(
            q, k, v, causal=True, q_segment_ids=seg,
            kv_segment_ids=seg) ** 2).sum()

    out_s = pallas_flash.flash_attention(q, k, v, causal=True)
    out_g = pallas_flash.flash_attention(q, k, v, causal=True,
                                         q_segment_ids=seg,
                                         kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_g),
                               atol=1e-6, rtol=1e-6)
    g_s = jax.grad(loss_split, argnums=(0, 1, 2))(q, k, v)
    g_g = jax.grad(loss_general, argnums=(0, 1, 2))(q, k, v)
    for a_, b_ in zip(g_s, g_g):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_streaming_diag_split_square_blocks(causal, monkeypatch):
    """The STREAMING diagonal-split specialization (square fine blocks,
    aligned diagonals, multi-superblock): outputs and all grads must match
    the reference — covers the cond-guarded triangle block landing in the
    right superblock."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_flash as pf
    monkeypatch.setattr(pf, "_SUPERBLOCK", 128)
    monkeypatch.setattr(pf, "_BLOCK_Q", 64)
    monkeypatch.setattr(pf, "_BLOCK_K", 64)
    B, S, H, D = 1, 512, 2, 16          # 4 superblocks x 2 fine blocks
    ks = jax.random.split(jax.random.key(12), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) * 0.5
               for kk in ks)
    out = pf.flash_attention(q, k, v, causal=causal)
    ref = attn_ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q, k, v: (pf.flash_attention(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (attn_ops.dot_product_attention(
        q, k, v, causal=causal) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
