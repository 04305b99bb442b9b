"""Pallas TPU sliding-window attention of a prefill chunk: ``window_attn``.

A sliding-window layer (``models/transformer.py``: ``Attention(window=W)``)
lets query ``i`` attend keys ``i - W < j <= i``. Over a chunk of ``L`` tokens
at positions ``start + [0, L)`` the keys are the ring's ``W`` positions before
``start`` (in position order) followed by the chunk's own: ``ext`` ``[B, W +
L, KV, hd]``, extended index ``e`` at position ``start - W + e``. Query ``t``
of the chunk then attends ``t < e <= t + W``, and only ``e >= W - start`` (a
position at or after 0).

The grid walks (row, KV head, block of ``W`` queries). Block ``n``'s band lies
in two key blocks of width ``W``: block ``n`` (``e = nW + s``: valid where ``s >
a`` for query ``a`` of the block, and at a position >= 0) and block ``n + 1``
(``e = nW + W + s``: valid where ``s <= a``). Each of the KV head's ``G``
query heads takes one ``[W, hd] x [hd, W]`` product against each half, a
softmax over the two in VMEM, and two ``[W, W] x [W, hd]`` value products: no
score tensor goes to HBM (the XLA form writes ``[.., W, 2W]`` float32 scores
and reads them back for each reduction). Queries, outputs and each key block
are read once a visit; a key block is visited by two query blocks.

:func:`window_attention` wants ``L`` a whole number of windows (the query
block is the window); ``models.transformer.window_attention_impl`` sends it
such chunks on a TPU and every other call to the ``jax.numpy`` form,
:func:`models.transformer.window_chunk_attention`'s, which is its reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

_NEG = -1e30


def _kernel(start_ref, q_ref, ka_ref, kb_ref, va_ref, vb_ref, o_ref, *, groups: int,
            head_dim: int, scale: float):
    w = q_ref.shape[1]
    b, n = pl.program_id(0), pl.program_id(2)
    a = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)           # query in the block
    s = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)           # key in its half
    ok_a = (s > a) & (n * w + s >= w - start_ref[b])
    ok_b = s <= a
    ka, kb, va, vb = ka_ref[0], kb_ref[0], va_ref[0], vb_ref[0]
    dims = (((1,), (1,)), ((), ()))
    for g in range(groups):
        q = q_ref[0, :, g * head_dim:(g + 1) * head_dim]
        sa = jax.lax.dot_general(q, ka, dims, preferred_element_type=jnp.float32) * scale
        sb = jax.lax.dot_general(q, kb, dims, preferred_element_type=jnp.float32) * scale
        sa = jnp.where(ok_a, sa, _NEG)
        sb = jnp.where(ok_b, sb, _NEG)
        m = jnp.maximum(jnp.max(sa, axis=1, keepdims=True), jnp.max(sb, axis=1, keepdims=True))
        pa, pb = jnp.exp(sa - m), jnp.exp(sb - m)
        denom = jnp.sum(pa, axis=1, keepdims=True) + jnp.sum(pb, axis=1, keepdims=True)
        o = (jnp.dot(pa.astype(va.dtype), va, preferred_element_type=jnp.float32)
             + jnp.dot(pb.astype(vb.dtype), vb, preferred_element_type=jnp.float32))
        o_ref[0, :, g * head_dim:(g + 1) * head_dim] = (o / denom).astype(o_ref.dtype)


def window_attention(q, ext_k, ext_v, start, window: int, *,
                     interpret: bool | None = None):
    """q ``[B, L, H, hd]``; ``ext_k`` / ``ext_v`` ``[B, W + L, KV, hd]`` (the
    ring's positions in order, then the chunk's keys); ``start`` ``[B]`` int32
    (the chunk's first position); ``L`` a whole number of windows ``W``. ->
    ``[B, L, H, hd]`` in q's type."""
    if interpret is None:
        interpret = not on_tpu()
    b, length, h, hd = q.shape
    kv, w = ext_k.shape[2], window
    if length % w or ext_k.shape[1] != w + length or h % kv:
        raise ValueError(f"window_attn wants a chunk of whole windows of {w} and "
                         f"{w} + L keys, got q {q.shape}, keys {ext_k.shape}")
    g = h // kv
    q2 = q.reshape(b, length, h * hd)
    k2 = ext_k.astype(q.dtype).reshape(b, w + length, kv * hd)
    v2 = ext_v.astype(q.dtype).reshape(b, w + length, kv * hd)
    q_spec = pl.BlockSpec((1, w, g * hd), lambda i, j, n, start: (i, n, j))
    here = pl.BlockSpec((1, w, hd), lambda i, j, n, start: (i, n, j))
    after = pl.BlockSpec((1, w, hd), lambda i, j, n, start: (i, n + 1, j))
    blocks = b * kv * (length // w)
    itemsize = jnp.dtype(q.dtype).itemsize
    out = pl.pallas_call(
        lambda *refs: _kernel(*refs, groups=g, head_dim=hd, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv, length // w),
            in_specs=[q_spec, here, after, here, after],
            out_specs=q_spec),
        out_shape=jax.ShapeDtypeStruct(q2.shape, q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=blocks * g * 8 * w * w * hd, transcendentals=blocks * g * 2 * w * w,
            bytes_accessed=itemsize * (2 * b * length * h * hd
                                       + 4 * blocks * w * hd)),
        interpret=interpret,
        # the name the device trace carries for the kernel's events
        name="window_attn",
    )(start.astype(jnp.int32), q2, k2, k2, v2, v2)
    return out.reshape(b, length, h, hd)
