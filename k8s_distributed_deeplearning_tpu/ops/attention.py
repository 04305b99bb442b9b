"""Multi-head attention ops: reference XLA path + Pallas flash-attention path.

The reference repo has no attention at all (SURVEY.md §2c — its only model is
an MNIST ConvNet, ``horovod/tensorflow_mnist.py:38-73``); attention enters this
framework through the BASELINE.json scale-out configs (BERT, ViT, Llama) and
the long-context mandate. Two implementations share one signature:

- ``impl="xla"``: einsum softmax attention — XLA fuses it well for short
  sequences and it runs everywhere (CPU CI).
- ``impl="flash"``: the Pallas TPU kernel in :mod:`ops.pallas_flash` — tiled
  online-softmax so the S×S score matrix never materializes in HBM. Falls
  back to interpret mode off-TPU so tests exercise the same code path.

Layout is ``[batch, seq, heads, head_dim]`` (TPU-native: last dim 128-aligned
head_dim rides the MXU lanes; batch*seq tiles the sublanes). GQA is supported
by passing fewer KV heads than Q heads (num_q_heads % num_kv_heads == 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from k8s_distributed_deeplearning_tpu import backend


def default_impl(seq_len: int, kv_seq_len: int | None = None,
                 platform: str | None = None) -> str:
    """Data-driven attention-impl selection (the ``impl="auto"`` rule).

    TPU picks the Pallas flash kernel whenever BOTH sequence lengths tile
    well (>= 1024, 128-aligned). The threshold is inherited from an
    earlier installation and has NOT been measured on this one (ROADMAP,
    speed item 2: no cell trains at S >= 1024 yet). A cross-attention
    caller with an awkward KV length would get degenerate fine blocks
    (``_pick_block`` can fall to 1), so any badly-tiled side falls back
    to xla. Off-TPU (CPU CI) flash runs in the Pallas interpreter, orders
    of magnitude slower than XLA: always xla.
    """
    tpu = backend.on_tpu() if platform is None else platform == "tpu"
    kv = seq_len if kv_seq_len is None else kv_seq_len
    well_tiled = all(s >= 1024 and s % 128 == 0 for s in (seq_len, kv))
    return "flash" if tpu and well_tiled else "xla"


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Expand KV heads to match Q heads for grouped-query attention."""
    num_kv = k.shape[2]
    if num_kv == num_q_heads:
        return k
    if num_q_heads % num_kv:
        raise ValueError(f"{num_q_heads} q heads not divisible by {num_kv} kv heads")
    return jnp.repeat(k, num_q_heads // num_kv, axis=2)


def segment_mask(q_segment_ids: jax.Array,
                 kv_segment_ids: jax.Array) -> jax.Array:
    """[B, Sq] x [B, Sk] segment ids -> [B, 1, Sq, Sk] bool mask (attend only
    within equal ids) — the packed-sequence/padding mask, shared by the XLA
    path here and the Pallas flash kernels."""
    return (q_segment_ids[:, None, :, None]
            == kv_segment_ids[:, None, None, :])


def dot_product_attention(
    q: jax.Array,  # [B, Sq, Hq, D]
    k: jax.Array,  # [B, Sk, Hkv, D]
    v: jax.Array,  # [B, Sk, Hkv, D]
    *,
    causal: bool = False,
    mask: jax.Array | None = None,  # [B, 1|Hq, Sq, Sk] additive or bool
    softmax_scale: float | None = None,
) -> jax.Array:
    """Reference einsum attention. Scores accumulate in f32 regardless of the
    input dtype (bf16 QKV on the MXU, f32 softmax on the VPU)."""
    *_, sq, hq, d = q.shape
    sk = k.shape[1]
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    k = _repeat_kv(k, hq)
    v = _repeat_kv(v, hq)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        # Offset aligns the causal diagonal when Sq != Sk (decode steps).
        scores = jnp.where(row + (sk - sq) >= col, scores, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -jnp.inf)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "softmax_scale", "impl"))
def multi_head_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = False,
    mask: jax.Array | None = None,
    segment_ids: jax.Array | None = None,   # [B, S] (self-attention)
    softmax_scale: float | None = None,
    impl: str = "xla",
) -> jax.Array:
    """Dispatch between the XLA reference and the Pallas flash kernel.

    ``segment_ids`` is the packed-sequence mask (attend within equal ids);
    the flash path consumes it natively, the XLA path expands it to a
    boolean mask. General ``mask`` arrays force the XLA path.
    ``impl="auto"`` resolves per the measured crossover (:func:`default_impl`).
    """
    if impl == "auto":
        impl = default_impl(q.shape[1], k.shape[1])
    if impl == "flash" and mask is None:
        from k8s_distributed_deeplearning_tpu.ops import pallas_flash
        return pallas_flash.flash_attention(
            q, k, v, causal=causal, softmax_scale=softmax_scale,
            q_segment_ids=segment_ids, kv_segment_ids=segment_ids)
    if segment_ids is not None:
        seg = segment_mask(segment_ids, segment_ids)
        mask = seg if mask is None else (
            mask & seg if mask.dtype == jnp.bool_
            else mask + jnp.where(seg, 0.0, -jnp.inf))
    return dot_product_attention(q, k, v, causal=causal, mask=mask,
                                 softmax_scale=softmax_scale)


def make_mesh_attention_fn(mesh, *, impl: str = "auto"):
    """Attention for GSPMD meshes: :func:`multi_head_attention` wrapped in
    ``jax.shard_map`` over the mesh's batch axes (``data`` × ``fsdp``) and
    head axis (``tensor``).

    Why this exists (round 5, found by the 64-device 8B memory analysis):
    a Pallas call has no SPMD partitioning rule, so under a sharded mesh
    GSPMD REPLICATES the flash kernel — every chip all-gathers the full
    batch and runs all of attention; and even the XLA einsum path lost
    the fsdp factor of its batch sharding through the head-fold reshapes
    (scores replicated fsdp-fold-×). Sharding per-device slices
    explicitly via shard_map fixes both, and makes TP attention
    head-parallel (the Megatron split) by construction.

    Returns a drop-in ``attention_fn`` for the transformer modules
    (same keyword contract as :func:`multi_head_attention`). Shapes that
    don't divide the mesh factors fall back to the unwrapped op — always
    correct, never silently wrong. Not for the decode/cache path (decode
    attention runs under its own TP layout) or CP meshes (ring/Ulysses
    own the sequence axis — ``parallel/context_parallel.py``).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("data", "fsdp") if sizes.get(a, 1) > 1)
    head_axis = "tensor" if sizes.get("tensor", 1) > 1 else None
    if not batch_axes and head_axis is None:
        return functools.partial(multi_head_attention, impl=impl)
    bfac = 1
    for a in batch_axes:
        bfac *= sizes[a]
    hfac = sizes.get("tensor", 1)
    from jax.sharding import PartitionSpec as P

    def fn(q, k, v, *, causal=False, mask=None, segment_ids=None,
           softmax_scale=None):
        b, _, hq, _ = q.shape
        hkv = k.shape[2]
        use_b = batch_axes if b % bfac == 0 else ()
        use_h = (head_axis if head_axis and hq % hfac == 0
                 and hkv % hfac == 0 else None)
        # Broadcast mask dims (size 1) are shardable: the spec builder
        # below replicates them (spec None), so only a non-broadcast dim
        # that doesn't divide its mesh factor forces the fallback.
        mask_ok = mask is None or (
            mask.ndim == 4
            and (mask.shape[0] == 1 or not use_b
                 or mask.shape[0] % bfac == 0)
            and (mask.shape[1] == 1 or use_h is None
                 or mask.shape[1] % hfac == 0))
        if (not use_b and use_h is None) or not mask_ok:
            return multi_head_attention(
                q, k, v, causal=causal, mask=mask, segment_ids=segment_ids,
                softmax_scale=softmax_scale, impl=impl)

        bspec = use_b if use_b else None
        qkv_spec = P(bspec, None, use_h, None)
        operands, specs = [q, k, v], [qkv_spec, qkv_spec, qkv_spec]
        has_mask, has_seg = mask is not None, segment_ids is not None
        if has_mask:
            operands.append(mask)
            specs.append(P(bspec if mask.shape[0] > 1 else None,
                           use_h if mask.shape[1] > 1 else None, None, None))
        if has_seg:
            operands.append(segment_ids)
            specs.append(P(bspec, None))

        def inner(*ops):
            qi, ki, vi = ops[:3]
            rest = list(ops[3:])
            mi = rest.pop(0) if has_mask else None
            si = rest.pop(0) if has_seg else None
            return multi_head_attention(
                qi, ki, vi, causal=causal, mask=mi, segment_ids=si,
                softmax_scale=softmax_scale, impl=impl)

        return jax.shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                             out_specs=qkv_spec, check_vma=False)(*operands)

    return fn
