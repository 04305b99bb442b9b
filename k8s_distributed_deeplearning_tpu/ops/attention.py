"""Multi-head attention ops: reference XLA path + Pallas flash-attention path.

The reference repo has no attention at all (SURVEY.md §2c — its only model is
an MNIST ConvNet, ``horovod/tensorflow_mnist.py:38-73``); attention enters this
framework through the BASELINE.json scale-out configs (BERT, ViT, Llama) and
the long-context mandate. Two implementations share one signature:

- ``impl="xla"``: einsum softmax attention — XLA fuses it well for short
  sequences and it runs everywhere (CPU CI). Under ``scan_layers`` its S×S
  probabilities are stacked over the layers for the backward pass.
- ``impl="flash"``: the Pallas TPU kernel in :mod:`ops.pallas_flash` — tiled
  online-softmax so the S×S score matrix never materializes in HBM. Falls
  back to interpret mode off-TPU so tests exercise the same code path.
- ``impl="auto"`` (the configs' default) picks between them from the shapes
  and the platform, by a rule measured on the chip (:func:`default_impl`:
  the kernel from S = 512 up). In a GSPMD program the kernel is split over
  the mesh that its builder announces (:func:`program_mesh`).

Layout is ``[batch, seq, heads, head_dim]`` (TPU-native: last dim 128-aligned
head_dim rides the MXU lanes; batch*seq tiles the sublanes). GQA is supported
by passing fewer KV heads than Q heads (num_q_heads % num_kv_heads == 0).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp

from k8s_distributed_deeplearning_tpu import backend


# The flash kernels' block of query rows and of keys (ops/pallas_flash.py):
# the lengths "auto" sends to them are whole multiples of it.
_FLASH_BLOCK = 512


def default_impl(seq_len: int, kv_seq_len: int | None, head_dim: int,
                 platform: str | None = None) -> str:
    """Data-driven attention-impl selection (the ``impl="auto"`` rule).

    On a TPU the Pallas flash kernel, when BOTH sequence lengths are whole
    multiples of 512 — its block — and the head size is one it tiles (64 or
    128); the einsum path otherwise. As measured on a TPU v5e on 3 Oct 2026
    (PERF.md §6, PR 30; forward + backward of a weighted sum under jit,
    bf16, alone, ms — einsum : flash):

    - ``[16, S, 12q/12kv, 64]`` non-causal (BERT's step): S = 128
      0.46 : 0.45, S = 256 0.47 : 0.46 (ties — the einsum path stays),
      **S = 512 1.74 : 0.71**, S = 1,024 8.01 : 3.91;
    - S = 512 at the other head shapes: ``12q/4kv x 64`` 1.73 : 0.78,
      ``[8, 512, 16q/16kv, 128]`` 1.23 : 0.72; causal
      ``[2, 2048, 32q/8kv, 128]`` 15.0 : 3.4;
    - a length the block does not divide gets finer blocks and loses: S = 384
      (blocks of 128) 0.66 : 2.49; at blocks of 256 / 128, S = 512 reads
      1.86 / 3.41 and S = 1,024 6.49 / 12.9.

    Off-TPU (CPU CI) flash runs in the Pallas interpreter, orders of
    magnitude slower than XLA: always xla.
    """
    tpu = backend.on_tpu() if platform is None else platform == "tpu"
    kv = seq_len if kv_seq_len is None else kv_seq_len
    whole_blocks = seq_len % _FLASH_BLOCK == 0 and kv % _FLASH_BLOCK == 0
    return "flash" if tpu and whole_blocks and head_dim in (64, 128) else "xla"


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


def _attend(q, k, v, *, causal, mask, segment_ids, softmax_scale, impl):
    """One device's attention by a RESOLVED *impl* ("flash" or "xla"); a
    general ``mask`` array forces the einsum path."""
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


# The mesh of the GSPMD program being traced (None: none was announced).
_PROGRAM_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "attention_program_mesh", default=None)


@contextlib.contextmanager
def program_mesh(mesh):
    """Trace-time context, entered by whoever builds a GSPMD program over
    *mesh* (``ShardedTrainer.make_step``): attention traced inside knows the
    mesh its operands are sharded over. A Pallas call has no partition rule;
    left to XLA the flash kernel would be REPLICATED — every chip
    all-gathering the whole batch and computing all of it — so
    :func:`multi_head_attention` runs it under :func:`on_mesh` instead. The
    einsum path needs no such help and takes no notice."""
    token = _PROGRAM_MESH.set(mesh)
    try:
        yield
    finally:
        _PROGRAM_MESH.reset(token)


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
    Inside :func:`program_mesh` (and outside any ``shard_map``) the flash
    kernel is split over that mesh by :func:`on_mesh`.
    """
    mesh = _PROGRAM_MESH.get()
    if mesh is not None and jax.sharding.get_abstract_mesh().manual_axes:
        mesh = None                       # inside a shard_map already
    return _dispatch(q, k, v, mask, segment_ids, causal=causal,
                     softmax_scale=softmax_scale, impl=impl, mesh=mesh)


def _dispatch(q, k, v, mask, segment_ids, *, causal, softmax_scale, impl,
              mesh):
    if impl == "auto":
        impl = default_impl(q.shape[1], k.shape[1], q.shape[3])
    kw = dict(causal=causal, mask=mask, segment_ids=segment_ids,
              softmax_scale=softmax_scale, impl=impl)
    if impl == "flash" and mask is None and mesh is not None:
        return on_mesh(mesh, q, k, v, **kw)
    return _attend(q, k, v, **kw)


# One traced sub-program per (shapes, statics); the mesh is a static too.
# It keeps the public function's name: that is its name in lowered programs.
_dispatch.__name__ = _dispatch.__qualname__ = "multi_head_attention"
_dispatch = jax.jit(_dispatch, static_argnames=(
    "causal", "softmax_scale", "impl", "mesh"))


def on_mesh(mesh, q, k, v, *, causal, mask, segment_ids, softmax_scale, impl):
    """Attention by a resolved *impl* as ``jax.shard_map`` over *mesh*'s
    batch axes (``data`` x ``fsdp``) and head axis (``tensor``): each device
    attends its own rows and heads. Shapes that do not divide the mesh
    factors take the einsum path unwrapped, which GSPMD partitions itself
    (the kernel would be replicated): always correct, never silently wrong.
    """
    from jax.sharding import PartitionSpec as P
    sizes = mesh.shape
    batch_axes = tuple(a for a in ("data", "fsdp") if sizes.get(a, 1) > 1)
    bfac = 1
    for a in batch_axes:
        bfac *= sizes[a]
    hfac = sizes.get("tensor", 1)
    b, _, hq, _ = q.shape
    hkv = k.shape[2]
    use_b = batch_axes if b % bfac == 0 else ()
    use_h = "tensor" if hfac > 1 and hq % hfac == 0 and hkv % hfac == 0 else None
    # Broadcast mask dims (size 1) are shardable: the spec builder
    # below replicates them (spec None), so only a non-broadcast dim
    # that doesn't divide its mesh factor forces the fallback.
    mask_ok = mask is None or (
        mask.ndim == 4
        and (mask.shape[0] == 1 or not use_b or mask.shape[0] % bfac == 0)
        and (mask.shape[1] == 1 or use_h is None
             or mask.shape[1] % hfac == 0))
    if (not use_b and use_h is None) or not mask_ok:
        # Nothing to split (a trivial mesh): the op as it is. Something to
        # split that does not divide: the path GSPMD can partition.
        return _attend(q, k, v, causal=causal, mask=mask,
                       segment_ids=segment_ids, softmax_scale=softmax_scale,
                       impl="xla" if batch_axes or hfac > 1 else impl)

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
        return _attend(qi, ki, vi, causal=causal, mask=mi, segment_ids=si,
                       softmax_scale=softmax_scale, impl=impl)

    return jax.shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv_spec, check_vma=False)(*operands)


def make_mesh_attention_fn(mesh, *, impl: str = "auto"):
    """Attention for GSPMD meshes as a drop-in ``attention_fn`` for the
    transformer modules (same keyword contract as
    :func:`multi_head_attention`): :func:`on_mesh` over an explicit *mesh*,
    for BOTH paths.

    Why this exists (round 5, found by the 64-device 8B memory analysis):
    even the XLA einsum path lost the fsdp factor of its batch sharding
    through the head-fold reshapes (scores replicated fsdp-fold-x).
    Sharding per-device slices explicitly via shard_map fixes that, and
    makes TP attention head-parallel (the Megatron split) by construction.
    (The flash kernel's own need — it would be replicated — is met without
    a caller's help wherever ``ShardedTrainer`` builds the step: see
    :func:`multi_head_attention`.) Not for the decode/cache path (decode
    attention runs under its own TP layout) or CP meshes (ring/Ulysses own
    the sequence axis — ``parallel/context_parallel.py``).
    """
    def fn(q, k, v, *, causal=False, mask=None, segment_ids=None,
           softmax_scale=None):
        resolved = (default_impl(q.shape[1], k.shape[1], q.shape[3])
                    if impl == "auto" else impl)
        return on_mesh(mesh, q, k, v, causal=causal, mask=mask,
                       segment_ids=segment_ids, softmax_scale=softmax_scale,
                       impl=resolved)
    return fn
