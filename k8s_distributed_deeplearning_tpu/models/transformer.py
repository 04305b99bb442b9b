"""Decoder/encoder transformer core — shared by BERT, ViT, Llama, MoE.

The reference has no transformer (its one model is the MNIST ConvNet,
``horovod/tensorflow_mnist.py:38-73``); this module exists for the
BASELINE.json scale-out configs and the long-context mandate. Design is
TPU-first throughout:

- every weight is created through :func:`flax.linen.with_logical_partitioning`
  with **logical axis names** (``"embed"``, ``"mlp"``, ``"heads"`` …); the
  mapping logical-axis → mesh-axis lives in one rule table
  (:mod:`parallel.sharding`), so the same module runs pure-DP, FSDP,
  Megatron-style TP, or any mix by swapping rules — no model edits;
- activations carry :func:`flax.linen.with_logical_constraint` annotations at
  layer boundaries so XLA's SPMD partitioner keeps them sharded instead of
  round-tripping through replicated form;
- compute dtype is bfloat16 by default (MXU-native), params stay f32;
- the layer stack is a :func:`flax.linen.scan` (one compiled block body,
  weights stacked on a leading ``"layers"`` axis) — compile time stays flat in
  depth and the stacked layout is exactly what pipeline parallelism consumes;
- optional :func:`flax.linen.remat` trades FLOPs for HBM (checkpointing every
  block boundary), the standard long-context memory lever.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from k8s_distributed_deeplearning_tpu.ops import attention as attention_ops
from k8s_distributed_deeplearning_tpu.ops import collectives
from k8s_distributed_deeplearning_tpu.ops import pallas_latent_attn
from k8s_distributed_deeplearning_tpu.ops import pallas_paged_attn
from k8s_distributed_deeplearning_tpu.ops import pallas_selective_scan
from k8s_distributed_deeplearning_tpu.ops import pallas_ssm
from k8s_distributed_deeplearning_tpu.ops import pallas_window_attn
from k8s_distributed_deeplearning_tpu.backend import on_tpu

Dtype = Any
default_init = nn.initializers.xavier_uniform
embed_init = nn.initializers.normal(stddev=0.02)

# Rematerialization policies (config knob `remat_policy`): "dots" keeps
# matmul outputs through remat (skips recomputing the MXU work — measured
# fastest at S=2048 on chip, round 3); "dots_attn" additionally saves
# the flash-attention output (tagged `checkpoint_name` in Attention) — the
# Pallas call is not a dot, so "dots" alone recomputes the whole attention
# forward in the backward pass; saving it costs [B,S,D_model] bf16 per
# layer and removes that recompute, but the extra residual traffic measured
# slightly SLOWER than recomputing (105.9k vs 108.8k tok/s at S=2048) — it
# exists for configs where attention recompute dominates (long S);
# "nothing" recomputes everything (minimal memory). Shared by the
# scan/remat stack here and the pipeline engine's per-layer checkpointing
# (parallel/pipeline_lm.py).
#
# "dots" also saves outputs tagged "gmm_out" — the MoE grouped-GEMM
# (ops/pallas_gmm, a Pallas call, so not a dot the policy's matcher can
# see) is exactly the MXU work the policy exists to keep. Measured within
# noise on the ragged 8-expert bench config (the kernel's custom VJP
# already stashes its operands, so the backward never re-runs a GEMM
# either way); the tag keeps the policy's meaning consistent — "matmul
# outputs are saved" — for remat styles that would otherwise replay the
# whole MLP. Dense models sow no such name: their residual set is
# unchanged.
_SAVE_GMM = jax.checkpoint_policies.save_only_these_names("gmm_out")
REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable, _SAVE_GMM),
    "dots_attn": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            _SAVE_GMM),
        jax.checkpoint_policies.save_only_these_names("attn_out")),
    "nothing": jax.checkpoint_policies.nothing_saveable,
}


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture knobs shared by all transformer families."""

    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int | None = None       # < n_heads => GQA (Llama-3 style)
    head_dim: int | None = None         # default dim // n_heads
    mlp_dim: int | None = None          # default 4*dim (gelu) / per-family
    max_seq_len: int = 2048
    causal: bool = True
    activation: str = "swiglu"          # "swiglu" | "gelu" | "relu2"
    norm: str = "rmsnorm"               # "rmsnorm" | "layernorm"
    position: str = "rope"              # "rope" | "learned" | "none"
    rope_theta: float = 500000.0        # Llama-3 default
    tie_embeddings: bool = False
    norm_eps: float = 1e-6              # every norm's epsilon (RMSNorm and
                                        # LayerNorm; the per-head q/k norms)
    qk_norm: bool = False               # Attention: an RMSNorm with a learned
                                        # gain over each query head's and each
                                        # key head's lanes, before RoPE
    dtype: Dtype = jnp.bfloat16         # compute dtype; params stay f32
    attention_impl: str = "auto"        # "auto" | "xla" | "flash" (pallas)
                                        # | "paged_flash"; auto = measured
                                        # per-platform/seq-len rule
                                        # (ops.attention.default_impl) for
                                        # training/prefill, and for the
                                        # block-table branch the fused
                                        # paged kernel on TPU at the query
                                        # widths it serves
                                        # (paged_attention_impl below).
                                        # "paged_flash" forces that kernel
                                        # (interpret-mode off-TPU) — for a
                                        # latent pool, the latent kernels
                                        # (latent_attention_impl)
    remat: bool = False                 # checkpoint each block
    remat_policy: str = "dots"          # "dots" (keep matmul outputs —
                                        # measured slightly faster) |
                                        # "nothing" (minimal memory)
    scan_layers: bool = True            # stack layers via nn.scan
    dropout_rate: float = 0.0
    tp_axis: str | None = None          # serving tensor parallelism
                                        # (serve/engine.py): when set, this
                                        # module is the PER-SHARD model
                                        # inside a shard_map over that mesh
                                        # axis — n_heads/n_kv_heads/mlp_dim
                                        # are the LOCAL (per-shard) counts,
                                        # and the row-parallel projections
                                        # (attn o_proj, mlp down_proj) psum
                                        # their partial outputs over the
                                        # axis: Megatron's two reductions
                                        # per block. Training TP does NOT
                                        # use this — it shards the same
                                        # logical axes via GSPMD rule
                                        # tables (parallel/sharding.py) and
                                        # lets XLA place the collectives.

    kv_quant: str | None = None         # "int8" quantizes the PAGED KV pool
                                        # (graftquant): pool arenas store
                                        # int8 rows plus per-token-per-head
                                        # absmax scales in sibling
                                        # cached_{key,value}_scale leaves
                                        # [num_pages, page_tokens, kv];
                                        # quantize-on-write at the paged
                                        # scatter, dequant-on-read in both
                                        # the XLA gather path and the Pallas
                                        # kernel. None = fp pool; the dense
                                        # (non-paged) cache paths are always
                                        # fp — quantization is a pool-
                                        # residency lever, not a compute one.

    def __post_init__(self):
        if self.remat_policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat_policy must be one of {sorted(REMAT_POLICIES)}, "
                f"got {self.remat_policy!r}")
        if self.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {self.kv_quant!r}")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def resolved_kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def resolved_mlp_dim(self) -> int:
        return self.mlp_dim or 4 * self.dim


def param_dense(features, axes, name=None, dtype=jnp.bfloat16, use_bias=False):
    """DenseGeneral whose kernel carries logical partitioning metadata."""
    return nn.DenseGeneral(
        features=features,
        axis=-1,
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(default_init(), axes),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, axes[1:]),
        name=name,
    )


class RMSNorm(nn.Module):
    """Root-mean-square layer norm (no mean subtraction, no bias) — the
    Llama-family norm; variance accumulates in f32."""

    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16
    axis: str | None = "embed"          # logical axis of the normed lanes

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale", nn.with_logical_partitioning(nn.initializers.ones, (self.axis,)),
            (x.shape[-1],), jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(self.dtype)


def make_norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "rmsnorm":
        return RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(
        epsilon=cfg.norm_eps, dtype=cfg.dtype, param_dtype=jnp.float32,
        name=name,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)))


def packed_positions(segment_ids: jax.Array) -> jax.Array:
    """Per-document positions for packed rows: [B, S] segment ids (contiguous
    runs — the packing invariant) -> positions restarting at 0 at each
    document start, so RoPE treats every packed document like an unpacked
    one."""
    b, s = segment_ids.shape
    idx = jnp.arange(s)[None, :]
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    doc_start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return idx - doc_start


def lm_batch_views(batch) -> tuple:
    """Shared next-token-LM batch preamble: shift tokens (position i
    predicts i+1), slice packed segment ids, derive per-document positions,
    and build the loss mask (optional caller "mask" ∧ cross-document
    boundary-pair exclusion). ONE definition so the llama and MoE losses
    cannot drift. Returns (inputs, targets, seg_in, positions, mask);
    seg_in/positions are None for unpacked batches."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    seg = batch.get("segment_ids")
    seg_in = None if seg is None else seg[:, :-1]
    positions = None if seg_in is None else packed_positions(seg_in)
    mask = batch.get("mask")
    mask = (jnp.ones_like(targets, jnp.float32) if mask is None
            else mask[:, 1:])
    if seg is not None:
        mask = mask * (seg[:, :-1] == seg[:, 1:]).astype(jnp.float32)
    return inputs, targets, seg_in, positions, mask


def rope_frequencies(head_dim: int, max_seq_len: int,
                     theta: float) -> tuple[jax.Array, jax.Array]:
    """Precompute RoPE cos/sin tables, shape [max_seq_len, head_dim/2], f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array | None = None) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]) by position-dependent angles.

    x: [B, S, H, D]; cos/sin: [max_seq, D/2]; positions: [B, S] or None
    (None => 0..S-1). Rotation happens in f32 and casts back.
    """
    b, s, _, _ = x.shape
    if positions is None:
        cos_p, sin_p = cos[:s][None], sin[:s][None]          # [1, S, D/2]
    else:
        cos_p, sin_p = cos[positions], sin[positions]        # [B, S, D/2]
    cos_p = cos_p[:, :, None, :]                             # [B|1, S, 1, D/2]
    sin_p = sin_p[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., ::2], xf[..., 1::2]
    r1 = x1 * cos_p - x2 * sin_p
    r2 = x2 * cos_p + x1 * sin_p
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def paged_attention_impl(cfg: TransformerConfig, sq: int) -> str:
    """Which implementation a block-table (paged) attention call with
    ``sq`` query tokens per row resolves to: ``"paged_flash"`` (the Pallas
    kernel) or ``"xla"`` (gather the row's pages, attend with the einsum
    path). ``"auto"`` decides from the platform, ``sq`` and the query heads
    a KV head serves (:func:`ops.pallas_paged_attn.default_impl`: on a TPU
    the kernel up to 128 tokens a row and for every wider call that is a
    whole number of query blocks); ``"paged_flash"`` forces the kernel;
    ``"xla"``/``"flash"`` take the gather. The model branches on this and
    ``ServeEngine.attention_impls`` reports it per program, so what a server
    says it runs is what it runs."""
    if cfg.attention_impl == "auto":
        return pallas_paged_attn.default_impl(
            sq, group=cfg.n_heads // cfg.resolved_kv_heads)
    return "paged_flash" if cfg.attention_impl == "paged_flash" else "xla"


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    """Multi-head latent attention (DeepSeek-V2 §2.1) layered on a
    TransformerConfig, which gives ``dim``, ``n_heads`` and ``rope_theta``:
    keys and values are up-projections of ONE ``kv_lora_rank``-wide latent a
    token, beside one ``qk_rope_head_dim``-wide rope key shared by all heads —
    and that pair is all the cache holds. ``rope_factor`` > 1 turns on
    ``deepseek_yarn`` scaling (:func:`yarn_inv_freq`)."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_norm: bool = True                # RMSNorm over each query head, pre-RoPE
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def cache_lanes(self) -> int:
        """Lanes of one cached token: latent + rope key, padded to whole
        128-lane tiles. The pad is free on the chip — an array 576 lanes wide
        occupies 640 under the (8, 128) tiling — and Mosaic copies whole
        tiles only (PR 27's compile of the 576-lane pool was refused)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1·mscale·ln(factor) + 1`` (1 at factor <= 1): YaRN's attention
    temperature."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> jax.Array:
    """``deepseek_yarn`` inverse frequencies ``[dim/2]``: pair ``j`` keeps
    ``theta^(-2j/dim)`` where it turns more than ``beta_fast`` times over the
    original context, is divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, with a linear ramp between the two pair indices."""
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * j / dim)
    if factor <= 1.0:
        return inv
    turns = lambda beta: (dim * math.log(original_max / (2 * math.pi * beta))
                          / (2 * math.log(theta)))
    lo = max(math.floor(turns(beta_fast)), 0)
    hi = min(math.ceil(turns(beta_slow)), dim - 1)
    ramp = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv * ((1.0 - ramp) + ramp / factor)


def _window_query_block(sq: int) -> int:
    """Queries a block of a sliding-window chunk: the largest divisor of
    *sq* up to 128, so that no block's scores reach past ``128 + window``
    keys."""
    return max(d for d in range(1, min(sq, 128) + 1) if sq % d == 0)


def window_attention_impl(cfg: TransformerConfig, sq: int, window: int) -> str:
    """What a sliding-window layer's call of ``sq`` tokens a row attends
    through: ``"kernel"`` (:mod:`ops.pallas_window_attn`, ``window_attn``) or
    ``"xla"`` (:func:`window_chunk_attention`'s ``jax.numpy`` blocks; decode
    always: one token a row reads its ring, :func:`window_decode_attention`).
    The kernel takes a chunk of whole windows: ``"auto"`` sends it those on a
    TPU at lane-aligned widths (a window and a head of whole 128-lane tiles),
    ``"paged_flash"`` forces it (interpret mode off the TPU), anything else
    takes XLA. ``ServeEngine.attention_impls`` reports it per program."""
    whole = sq > 1 and sq % window == 0
    if cfg.attention_impl == "auto":
        aligned = window % 128 == 0 and cfg.resolved_head_dim % 128 == 0
        return "kernel" if whole and aligned and on_tpu() else "xla"
    return "kernel" if whole and cfg.attention_impl == "paged_flash" else "xla"


def window_chunk_attention(q, k, v, ring_k, ring_v, start, window: int,
                           lengths=None, impl: str = "xla"):
    """Sliding-window attention of a chunk over a ring.

    q ``[B, L, H, hd]``, k and v ``[B, L, KV, hd]`` (rotated where the layer
    takes positions), the chunk's tokens at positions ``start + [0, L)``
    (``start`` ``[B]``); ``ring_k`` / ``ring_v`` ``[B, W, KV·hd]`` hold the
    row's last ``W = window`` positions before ``start``, position ``p`` in
    entry ``p % W`` (None: the chunk starts the sequence). Query ``i`` attends
    keys ``i - W < j <= i`` at positions ``>= 0``. The chunk's keys follow the
    ring's, in position order, and each block of ``Q`` queries
    (:func:`_window_query_block`) reads the ``Q + W`` keys that hold its band:
    no ``[L, W + L]`` score tensor is made. Returns ``(out [B, L, H, hd],
    ring_k, ring_v)``, the rings as they stand after the chunk's last real
    token (``lengths`` ``[B]``: real tokens of a right-padded chunk; None =
    all ``L``). ``impl="kernel"``: the same attention through
    :func:`ops.pallas_window_attn.window_attention` (a chunk of whole
    windows)."""
    b, sq, h, hd = q.shape
    kv, w = k.shape[2], window
    start = start.astype(jnp.int32)
    if ring_k is None:
        ring_k = ring_v = jnp.zeros((b, w, kv * hd), k.dtype)
    # the ring in position order: entry i holds position start - W + i
    order = (start[:, None] + jnp.arange(w, dtype=jnp.int32)) % w      # [B, W]
    take = lambda ring: jnp.take_along_axis(ring, order[:, :, None], axis=1)
    ext_k = jnp.concatenate([take(ring_k).reshape(b, w, kv, hd),
                             k.astype(ring_k.dtype)], axis=1)        # [B, W+L, ..]
    ext_v = jnp.concatenate([take(ring_v).reshape(b, w, kv, hd),
                             v.astype(ring_v.dtype)], axis=1)
    if impl == "kernel":
        out = pallas_window_attn.window_attention(q, ext_k, ext_v, start, w)
    else:
        out = _window_blocks(q, ext_k, ext_v, start, w)
    # the ring after token n - 1: positions start + n - W .. start + n - 1,
    # extended indices n .. n + W - 1, position p back in entry p % W
    n = (jnp.full((b,), sq, jnp.int32) if lengths is None
         else lengths.astype(jnp.int32))
    back = n[:, None] + (jnp.arange(w, dtype=jnp.int32)[None, :]
                         - start[:, None] - n[:, None]) % w          # [B, W]
    put = lambda ext: jnp.take_along_axis(
        ext.reshape(b, w + sq, kv * hd), back[:, :, None], axis=1)
    return out, put(ext_k), put(ext_v)


def _window_blocks(q, ext_k, ext_v, start, w: int):
    """The band in ``jax.numpy``: each block of ``Q`` queries
    (:func:`_window_query_block`) against the ``Q + W`` extended keys that
    hold it."""
    b, sq, h, hd = q.shape
    kv = ext_k.shape[2]
    qb = _window_query_block(sq)
    nb, g = sq // qb, h // kv
    keys = (jnp.arange(nb)[:, None] * qb
            + jnp.arange(qb + w)[None, :])                           # [nb, Q+W]
    kb, vb = ext_k[:, keys], ext_v[:, keys]                          # [B,nb,Q+W,KV,hd]
    qg = q.reshape(b, nb, qb, kv, g, hd)
    scores = jnp.einsum("bnqkgd,bnskd->bnkgqs", qg, kb.astype(q.dtype),
                        preferred_element_type=jnp.float32) * hd ** -0.5
    # query a of block n is chunk offset t = n·Q + a, key s is extended index
    # e = n·Q + s at position start - W + e: a < s <= a + W, and e >= W - start
    a = jnp.arange(qb)[:, None]
    s = jnp.arange(qb + w)[None, :]
    band = (s > a) & (s <= a + w)                                    # [Q, Q+W]
    real = keys[None] >= (w - start)[:, None, None]                  # [B, nb, Q+W]
    ok = band[None, None] & real[:, :, None, :]                      # [B,nb,Q,Q+W]
    scores = jnp.where(ok[:, :, None, None], scores, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", p, vb.astype(q.dtype))
    return out.reshape(b, sq, h, hd)


def window_decode_attention(q, k, v, ring_k, ring_v, pos):
    """One token a row against the row's ring: q ``[B, 1, H, hd]``, k and v
    ``[B, 1, KV, hd]`` of the token at position ``pos`` ``[B]``, the rings
    ``[B, W, KV·hd]``. The rows with a cursor (``pos > 0``) write their token
    into entry ``pos % W`` in place (a scatter; the others' index falls out of
    range and is dropped, so an idle or mid-prefill slot's ring is left bit
    for bit); each row attends its ring, entry ``j`` holding position ``pos -
    ((pos - j) mod W)``, masked where that is below 0. Returns ``(out [B, 1,
    H, hd], ring_k, ring_v)``."""
    b, _, h, hd = q.shape
    kv, w = k.shape[2], ring_k.shape[1]
    pos = pos.astype(jnp.int32)
    entry = jnp.where(pos > 0, pos % w, w)
    rows = jnp.arange(b)
    ring_k = ring_k.at[rows, entry].set(
        k.reshape(b, kv * hd).astype(ring_k.dtype), mode="drop")
    ring_v = ring_v.at[rows, entry].set(
        v.reshape(b, kv * hd).astype(ring_v.dtype), mode="drop")
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg,
                        ring_k.reshape(b, w, kv, hd).astype(q.dtype),
                        preferred_element_type=jnp.float32) * hd ** -0.5
    j = jnp.arange(w, dtype=jnp.int32)[None, :]
    ok = (pos[:, None] - (pos[:, None] - j) % w) >= 0                 # [B, W]
    scores = jnp.where(ok[:, None, None], scores, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p,
                     ring_v.reshape(b, w, kv, hd).astype(q.dtype))
    return out.reshape(b, 1, h, hd), ring_k, ring_v


_SLOT_NEEDS_TABLES = ("cache_positions (slot decode) requires block_tables: "
                      "per-row cursors exist only over the paged pool")


class Attention(nn.Module):
    """Multi-head / grouped-query attention with optional RoPE.

    Logical sharding: Q/K/V kernels are [embed, heads|kv, head_dim] so a TP
    rule mapping "heads"/"kv" to the tensor axis shards the heads dimension
    (Megatron-style column parallel); the output projection is
    [heads, head_dim, embed] (row parallel — XLA inserts the psum).

    ``decode=True`` switches to KV-cache autoregressive mode: cached K/V
    ([B, max_seq_len, kv, head_dim], static shapes — XLA-friendly
    ``dynamic_update_slice``, never a growing array) live in the mutable
    "cache" collection; each call appends the current chunk and attends the
    chunk's queries against the cache prefix.

    ``block_tables`` ([B, n_blocks] int32) selects PAGED decode mode: the
    cache leaves are one POOL of fixed-size KV pages
    (``[num_pages, page_tokens, kv·hd]``) shared by every row, and each
    row's table maps its virtual sequence onto pool pages
    (vLLM's PagedAttention layout). The caller (serve/engine.py) owns
    allocation/refcounts; this module only scatters the chunk's tokens at
    ``(table[pos // page_tokens], pos % page_tokens)`` and gathers the
    row's pages back for attention. Page 0 is the caller's reserved
    SCRATCH page: table entries default to it and out-of-table writes are
    redirected there, so right-pad garbage never lands where a live row
    attends. The write positions come from explicit ``positions`` (a
    prefill chunk at any start — the token-granular scatter has no
    ``dynamic_update_slice`` clamping hazard, so a right-padded tail chunk
    is safe at any cursor) or from ``cache_positions``.

    ``cache_positions`` ([B] int32) is SLOT decode over the paged pool (the
    continuous-batching serving engine, :mod:`serve.engine`) and requires
    ``block_tables``: each batch row is an independent request slot with
    its OWN cursor — token ``i`` of the chunk writes through the row's
    table at position ``cache_positions[b] + i`` and attends positions
    ``<= cache_positions[b] + i``. A [B, 1] chunk is classic one-token
    decode; a [B, W] chunk is a speculative VERIFY window — W draft
    tokens written at consecutive per-row positions, each attending its
    own causal prefix, so one pass scores every draft (serve/engine.py
    truncates the cursor to the accepted length; stale KV beyond it is
    never attended, which is what makes rollback free). Positions beyond
    a slot's cursor are never read, so a freed slot can be re-filled by a
    new request's prefill without clearing the stale K/V the previous
    occupant left behind. Per-slot lengths are the caller's registers.

    ``window`` makes a SLIDING-WINDOW layer: query ``i`` attends keys ``i -
    window < j <= i`` (``window`` keys, itself included). Its cache is not
    pages but a RING a row, ``window_key`` / ``window_value`` ``[B, window,
    kv·hd]`` (keys after norm and RoPE): position ``p`` lives in entry ``p %
    window`` (:func:`window_chunk_attention`, :func:`window_decode_attention`).
    ``rope`` overrides ``cfg.position`` for this layer (a hybrid whose global
    layers take no positions). The defaults leave the layer as it was.
    """

    cfg: TransformerConfig
    window: int | None = None
    rope: bool | None = None

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        # lengths (real tokens of a right-padded chunk, for a mixer that
        # carries state: ShortConv) means nothing here — pad K/V land beyond
        # the cursor or in the scratch page and are never attended.
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        q = nn.DenseGeneral((cfg.n_heads, hd), axis=-1, use_bias=False,
                            dtype=cfg.dtype, param_dtype=jnp.float32,
                            kernel_init=nn.with_logical_partitioning(
                                default_init(), ("embed", "heads", "head_dim")),
                            name="q_proj")(x)
        k = nn.DenseGeneral((cfg.resolved_kv_heads, hd), axis=-1, use_bias=False,
                            dtype=cfg.dtype, param_dtype=jnp.float32,
                            kernel_init=nn.with_logical_partitioning(
                                default_init(), ("embed", "kv", "head_dim")),
                            name="k_proj")(x)
        v = nn.DenseGeneral((cfg.resolved_kv_heads, hd), axis=-1, use_bias=False,
                            dtype=cfg.dtype, param_dtype=jnp.float32,
                            kernel_init=nn.with_logical_partitioning(
                                default_init(), ("embed", "kv", "head_dim")),
                            name="v_proj")(x)
        if cfg.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axis=None,
                        name="q_norm")(q)
            k = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axis=None,
                        name="k_norm")(k)
        if self.window is not None:
            if (mask is not None or segment_ids is not None
                    or attention_fn is not None or cfg.tp_axis is not None):
                raise NotImplementedError(
                    "a sliding-window layer takes no mask, segment_ids, "
                    "attention_fn or tp_axis")
            return self._project_out(self._sliding(
                q, k, v, positions=positions, decode=decode,
                cache_positions=cache_positions, block_tables=block_tables,
                lengths=lengths))
        cur = None
        if cache_positions is not None and not decode:
            raise ValueError("cache_positions requires decode=True")
        if cache_positions is not None and block_tables is None:
            raise ValueError(_SLOT_NEEDS_TABLES)
        if decode:
            if mask is not None or attention_fn is not None:
                raise NotImplementedError(
                    "decode mode builds its own cache-prefix mask and local "
                    "attention; a caller-provided mask/attention_fn would be "
                    "silently wrong")
            b, sq = x.shape[0], x.shape[1]
            kv = cfg.resolved_kv_heads
            if block_tables is not None:
                # Paged mode: the "cache" collection holds ONE pool of
                # fixed-size pages [num_pages, page_tokens, kv·hd] shared
                # by every row — there is no sensible per-call init (pool
                # sizing is an engine capacity decision), so a missing
                # pool is a caller bug, not something to zero-fill.
                if segment_ids is not None:
                    raise NotImplementedError(
                        "paged decode isolates rows via per-row block "
                        "tables; segment_ids have no meaning here")

                def _pool_missing():
                    raise ValueError(
                        "paged decode (block_tables) requires an engine-"
                        "provided page-pool cache; it cannot be "
                        "initialised from inside the model")

                cached_k = self.variable("cache", "cached_key",
                                         _pool_missing)
                cached_v = self.variable("cache", "cached_value",
                                         _pool_missing)
                if cfg.kv_quant == "int8":
                    # Scale siblings exist ONLY under quant so the
                    # quant-off cache treedef is bit-identical to the
                    # unquantized engine's. Page dim stays at axis -3
                    # (matching the pool leaves), so every page-granular
                    # consumer — gather/scatter shipping, disagg codec,
                    # trie sharing, TP last-dim sharding — composes
                    # without special cases.
                    cached_ks = self.variable("cache", "cached_key_scale",
                                              _pool_missing)
                    cached_vs = self.variable("cache", "cached_value_scale",
                                              _pool_missing)
                if positions is None:
                    if cache_positions is None:
                        raise ValueError(
                            "paged chunk prefill requires explicit "
                            "positions (the chunk's absolute write "
                            "positions); only slot decode can derive "
                            "them from cache_positions")
                    positions = (cache_positions[:, None]
                                 + jnp.arange(sq, dtype=jnp.int32)[None, :])
            else:
                # Cache layout [B, S, kv·hd] — heads FOLDED into the lane
                # dim. The natural [B, S, kv, hd] layout tiles its
                # (kv, hd) minors to (8, 128): at 4 KV heads × head_dim 64
                # the buffer occupies 4× its logical bytes, and the
                # per-step update measured ~82 µs (a full padded-buffer
                # copy at HBM rate — the decode trace's top non-matmul
                # cost). Folded, the same update measures 3.9 µs (in-place
                # sliver write, no padding); the attention-side unfold is
                # a cheap view (round 5).
                cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                         (b, cfg.max_seq_len, kv * hd),
                                         cfg.dtype)
                cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                         (b, cfg.max_seq_len, kv * hd),
                                         cfg.dtype)
                # Per-position document ids, same contract as training:
                # decode queries attend only cache entries with THEIR
                # document id. id 0 marks left-padding (batched serving
                # pads unequal prompts at the FRONT); pad K/V enter the
                # cache but are never attended. The STATIC presence of
                # segment_ids selects the masked variant — plain decode
                # pays nothing — so a caller that prefills with
                # segment_ids must pass them on every decode step too (the
                # padded/packed generate paths do).
                use_seg = segment_ids is not None
                cached_seg = self.variable("cache", "cached_seg", jnp.ones,
                                           (b, cfg.max_seq_len), jnp.int32)
                cache_index = self.variable("cache", "cache_index",
                                            lambda: jnp.zeros((), jnp.int32))
                cur = cache_index.value
                if use_seg:
                    seg_now = segment_ids.astype(jnp.int32)
                    cached_seg.value = jax.lax.dynamic_update_slice(
                        cached_seg.value, seg_now, (0, cur))
                segment_ids = None  # consumed into the cache mask below
                if positions is None:
                    # Absolute positions for RoPE: the cache cursor
                    # onward. (Left-padded callers pass explicit
                    # per-row positions.)
                    positions = (cur + jnp.arange(sq))[None, :]

        q, k = self._rotate(q, k, positions)

        if decode and block_tables is not None:
            # Paged write + gather-attend. Each token of the chunk lands at
            # (page, offset) = (table[pos // page_tokens], pos % page_tokens)
            # via a token-granular scatter — unlike dynamic_update_slice
            # there is no start-clamping hazard, so a right-padded tail
            # chunk is safe at ANY cursor: pad tokens past the table's last
            # block are redirected to the scratch page (0) explicitly
            # rather than relying on XLA out-of-bounds semantics. Reads
            # gather the row's pages back into a [B, n_blocks·page_tokens]
            # virtual sequence and mask col <= pos — allocated-but-unwritten
            # tail positions and scratch garbage are never attended.
            b, sq = x.shape[0], x.shape[1]
            kv = cfg.resolved_kv_heads
            pool_k, pool_v = cached_k.value, cached_v.value
            page_tokens = pool_k.shape[-2]
            n_blocks = block_tables.shape[1]
            wpos = positions.astype(jnp.int32)                    # [B, sq]
            blk = wpos // page_tokens
            pg = jnp.take_along_axis(block_tables,
                                     jnp.minimum(blk, n_blocks - 1), axis=1)
            pg = jnp.where(blk >= n_blocks, 0, pg)                # scratch
            off = wpos % page_tokens
            quant = cfg.kv_quant == "int8"
            if quant:
                # Quantize-on-write: per-token-per-head symmetric absmax.
                # The scatter stays write-local (each token owns its
                # (page, offset) cell and its scale cell), so there is no
                # read-modify-write of neighbouring tokens' scales and the
                # write cost matches the fp path's sliver update.
                k_w = k.reshape(b, sq, kv, hd).astype(jnp.float32)
                v_w = v.reshape(b, sq, kv, hd).astype(jnp.float32)
                k_sc = jnp.max(jnp.abs(k_w), axis=-1) / 127.0     # [B,sq,kv]
                v_sc = jnp.max(jnp.abs(v_w), axis=-1) / 127.0
                k_q = jnp.clip(jnp.round(
                    k_w / jnp.where(k_sc > 0.0, k_sc, 1.0)[..., None]),
                    -127, 127).astype(jnp.int8)
                v_q = jnp.clip(jnp.round(
                    v_w / jnp.where(v_sc > 0.0, v_sc, 1.0)[..., None]),
                    -127, 127).astype(jnp.int8)
                pool_k = pool_k.at[pg, off].set(k_q.reshape(b, sq, kv * hd))
                pool_v = pool_v.at[pg, off].set(v_q.reshape(b, sq, kv * hd))
                pool_ks = cached_ks.value.at[pg, off].set(k_sc)
                pool_vs = cached_vs.value.at[pg, off].set(v_sc)
                cached_ks.value, cached_vs.value = pool_ks, pool_vs
            else:
                pool_k = pool_k.at[pg, off].set(
                    k.reshape(b, sq, kv * hd).astype(pool_k.dtype))
                pool_v = pool_v.at[pg, off].set(
                    v.reshape(b, sq, kv * hd).astype(pool_v.dtype))
            cached_k.value, cached_v.value = pool_k, pool_v
            if paged_attention_impl(cfg, sq) == "paged_flash":
                # Fused gather+attend (ops/pallas_paged_attn.py): the
                # kernel streams the row's pages straight from the pool
                # via the scalar-prefetched block table, so the
                # [B, n_blocks·page_tokens] virtual sequence never
                # materializes in HBM. Off-TPU "paged_flash" runs the
                # same kernel in interpret mode (parity tests); "auto"
                # picks it on TPU for decode, a verify window and every
                # prefill width that is a whole number of query blocks
                # (a 512-token chunk attends in blocks of queries, each
                # up to its own last position), and the XLA gather below
                # for a width off that grid. Under kv_quant the
                # kernel fuses the dequant into its page stream: int8
                # pages are copied by the same prefetched block table
                # (their f32 scales, 1/32 of the bytes, are gathered
                # beside it), so dequantized K/V never hit HBM either.
                out = pallas_paged_attn.paged_decode_attention(
                    q, pool_k, pool_v, block_tables, wpos,
                    k_scale=pool_ks if quant else None,
                    v_scale=pool_vs if quant else None)
            else:
                s_virt = n_blocks * page_tokens
                k_all = pool_k[block_tables].reshape(b, s_virt, kv, hd)
                v_all = pool_v[block_tables].reshape(b, s_virt, kv, hd)
                if quant:
                    # XLA reference dequant: gathered scales broadcast
                    # over head_dim; compute re-enters cfg.dtype so the
                    # attention math matches the fp path's precision.
                    ks_all = pool_ks[block_tables].reshape(b, s_virt, kv)
                    vs_all = pool_vs[block_tables].reshape(b, s_virt, kv)
                    k_all = (k_all.astype(jnp.float32)
                             * ks_all[..., None]).astype(cfg.dtype)
                    v_all = (v_all.astype(jnp.float32)
                             * vs_all[..., None]).astype(cfg.dtype)
                col = jnp.arange(s_virt)
                dmask = (col[None, None, :] <= wpos[:, :, None])[:, None]
                out = attention_ops.multi_head_attention(
                    q, k_all, v_all, causal=False, mask=dmask, impl="xla")
        elif decode:
            # Append this chunk at the cursor (static-shape cache update) and
            # attend the chunk's queries against the cache prefix: query at
            # absolute position cur+i sees columns <= cur+i.
            b, sq = x.shape[0], x.shape[1]
            kv = cfg.resolved_kv_heads
            k_all = jax.lax.dynamic_update_slice(
                cached_k.value,
                k.reshape(b, sq, kv * hd).astype(cached_k.value.dtype),
                (0, cur, 0))
            v_all = jax.lax.dynamic_update_slice(
                cached_v.value,
                v.reshape(b, sq, kv * hd).astype(cached_v.value.dtype),
                (0, cur, 0))
            cached_k.value, cached_v.value = k_all, v_all
            cache_index.value = cur + sq
            k_all = k_all.reshape(b, cfg.max_seq_len, kv, hd)
            v_all = v_all.reshape(b, cfg.max_seq_len, kv, hd)
            col = jnp.arange(cfg.max_seq_len)
            row_pos = cur + jnp.arange(sq)
            base = (col[None, :] <= row_pos[:, None])[None, None]  # [1,1,sq,Smax]
            diag = (col[None, :] == row_pos[:, None])[None, None]
            if use_seg:
                # Same-document columns only (pads are id 0, never any
                # query's id); `col == row` keeps the query's own slot so
                # even an all-pad row has one finite score (no NaN softmax
                # — pad-row outputs are garbage but never attended by real
                # rows).
                same = (cached_seg.value[:, None, None, :]
                        == seg_now[:, None, :, None])              # [B,1,sq,Smax]
                dmask = (base & same) | diag
            else:
                # Safety net for a caller that prefilled WITH segment ids
                # but stepped without them: pad entries (id 0) stay
                # invisible (full per-document isolation still needs the
                # ids passed every step). All-ones cache => no-op mask;
                # measured within decode run-to-run noise.
                ok = cached_seg.value[:, None, None, :] != 0
                dmask = (base & ok) | diag
            out = attention_ops.multi_head_attention(
                q, k_all, v_all, causal=False, mask=dmask, impl="xla")
        else:
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
            k = nn.with_logical_constraint(k, ("batch", "seq", "kv", "head_dim"))
            v = nn.with_logical_constraint(v, ("batch", "seq", "kv", "head_dim"))
            if attention_fn is not None:
                # Packed sequences compose with context-parallel attention:
                # the CP wrappers accept segment_ids (ring rotates the
                # K-side ids with their shard; Ulysses all-gathers them).
                kw = {} if segment_ids is None else {
                    "segment_ids": segment_ids}
                out = attention_fn(q, k, v, causal=cfg.causal, mask=mask,
                                   **kw)
            else:
                out = attention_ops.multi_head_attention(
                    q, k, v, causal=cfg.causal, mask=mask,
                    segment_ids=segment_ids, impl=cfg.attention_impl)
            # Tag for the "dots_attn" remat policy: lets jax.checkpoint save
            # exactly this tensor so the backward pass skips re-running the
            # attention forward (a no-op under other policies).
            out = checkpoint_name(out, "attn_out")
        return self._project_out(out)

    def _rotate(self, q, k, positions):
        """RoPE on q and k where this layer takes positions (``rope``, else
        ``cfg.position``)."""
        cfg = self.cfg
        if not (cfg.position == "rope" if self.rope is None else self.rope):
            return q, k
        cos, sin = rope_frequencies(cfg.resolved_head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        return (apply_rope(q, cos, sin, positions),
                apply_rope(k, cos, sin, positions))

    def _sliding(self, q, k, v, *, positions, decode, cache_positions,
                 block_tables, lengths):
        """A sliding-window layer's attention over its ring: ``[B, sq, H,
        hd]``. Three cache modes, as the full layer's: none (a whole sequence
        from position 0), a row cache with a shared scalar cursor
        (``generate()``; ``cache_index``) and slot serving over the paged
        pool (``block_tables``: the ring is then the engine's state arena,
        one row a slot — a chunk call hands in ONE slot's row, the decode
        call every slot's with ``cache_positions``)."""
        cfg, w = self.cfg, self.window
        b, sq = q.shape[0], q.shape[1]
        lanes = cfg.resolved_kv_heads * cfg.resolved_head_dim
        if not decode:
            if cache_positions is not None:
                raise ValueError("cache_positions requires decode=True")
            if positions is not None:
                raise NotImplementedError(
                    "a sliding-window layer runs a whole sequence from "
                    "position 0: no packed positions")
            start = jnp.zeros((b,), jnp.int32)
            positions = start[:, None] + jnp.arange(sq, dtype=jnp.int32)
            q, k = self._rotate(q, k, positions)
            return window_chunk_attention(
                q, k, v, None, None, start, w,
                impl=window_attention_impl(cfg, sq, w))[0]

        def _arena_missing():
            raise ValueError(
                "paged decode (block_tables) requires an engine-provided "
                "ring arena (window_key, window_value); it cannot be "
                "initialised from inside the model")
        if block_tables is not None:
            ring_k = self.variable("cache", "window_key", _arena_missing)
            ring_v = self.variable("cache", "window_value", _arena_missing)
        else:
            ring_k = self.variable("cache", "window_key", jnp.zeros,
                                   (b, w, lanes), cfg.dtype)
            ring_v = self.variable("cache", "window_value", jnp.zeros,
                                   (b, w, lanes), cfg.dtype)
            index = self.variable("cache", "cache_index",
                                  lambda: jnp.zeros((), jnp.int32))
            cur = index.value
            index.value = cur + sq
            positions = (cur + jnp.arange(sq, dtype=jnp.int32))[None, :]
        if cache_positions is not None:
            if sq != 1:
                raise NotImplementedError(
                    "a sliding-window layer decodes one token a row: a "
                    "verify window would overwrite ring entries a rejected "
                    "draft still needs")
            pos = cache_positions.astype(jnp.int32)
            q, k = self._rotate(q, k, pos[:, None])
            out, ring_k.value, ring_v.value = window_decode_attention(
                q, k, v, ring_k.value, ring_v.value, pos)
            return out
        positions = jnp.broadcast_to(positions.astype(jnp.int32), (b, sq))
        q, k = self._rotate(q, k, positions)
        out, ring_k.value, ring_v.value = window_chunk_attention(
            q, k, v, ring_k.value, ring_v.value, positions[:, 0], w, lengths,
            impl=window_attention_impl(cfg, sq, w))
        return out

    def _project_out(self, out):
        """The output projection ``W_o`` (and, under serving TP, its psum)."""
        cfg = self.cfg
        out = nn.with_logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
        out = nn.DenseGeneral(cfg.dim, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, param_dtype=jnp.float32,
                              kernel_init=nn.with_logical_partitioning(
                                  default_init(), ("heads", "head_dim", "embed")),
                              name="o_proj")(out)
        if cfg.tp_axis is not None:
            # Row-parallel output projection under serving TP: each shard
            # holds n_heads/tp heads, so o_proj emits a partial sum over the
            # hidden dim — one psum completes it (Megatron's g operator).
            out = collectives.tree_psum(out, cfg.tp_axis)
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


def latent_attention_impl(cfg: TransformerConfig) -> str:
    """Which implementation a LATENT block-table call resolves to:
    ``"latent_flash"`` (:mod:`ops.pallas_latent_attn`
    — the absorbed decode kernel up to its ``MAX_QUERY_TOKENS``, the expanded
    chunk kernel beyond) or ``"xla"`` (either form over the table's pages
    gathered whole: the CPU tests' path and the kernels' reference — on a
    TPU ``"auto"`` always resolves to the kernels)."""
    if cfg.attention_impl == "auto":
        return pallas_latent_attn.default_impl()
    return "latent_flash" if cfg.attention_impl == "paged_flash" else "xla"


class LatentAttention(nn.Module):
    """Multi-head latent attention with the three cache modes the serving
    path uses (same keywords as :class:`Attention`):

    - ``decode=True`` alone: a row cache ``cached_latent``
      ``[B, max_seq_len, cache_lanes]`` with the shared scalar cursor —
      ``generate()``'s prefill and steps;
    - ``block_tables`` + explicit ``positions``: a prefill chunk written
      through the table into the page POOL ``[pages, page_tokens, lanes]``;
    - ``block_tables`` + ``cache_positions``: paged slot decode.

    One token's cache row is ``[RMSNorm(c) ; RoPE(k^R) ; 0-pad]`` and nothing
    else. Two forms of the same function: EXPANDED (``k^N = W_UK c``,
    ``v = W_UV c`` per head — wherever a call carries more than
    ``pallas_latent_attn.MAX_QUERY_TOKENS`` queries a row; over the pool the
    chunk kernel runs it by blocks of KV tokens with an online softmax, so
    the expanded K and V of a long prefix never exist whole) and ABSORBED (``q̃ = W_UKᵀ q^N`` scores the latent itself and
    ``W_UV`` is applied to the weighted latent — decode, where the cache is
    read once and is key and value both).
    """

    cfg: TransformerConfig
    latent: LatentAttentionConfig

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None) -> jax.Array:
        cfg, la = self.cfg, self.latent
        if mask is not None or segment_ids is not None or attention_fn is not None:
            raise NotImplementedError(
                "LatentAttention builds its own causal / cache-prefix mask; "
                "mask, segment_ids and attention_fn are not supported")
        if cfg.tp_axis is not None or (cfg.kv_quant is not None
                                       and block_tables is not None):
            raise NotImplementedError(
                "LatentAttention has no tp_axis / kv_quant path: the latent "
                "cache has no head axis to shard and no int8 scales")
        if cache_positions is not None and block_tables is None:
            raise ValueError(_SLOT_NEEDS_TABLES)
        b, sq, _ = x.shape
        h = cfg.n_heads
        r, dn, dr, dv = (la.kv_lora_rank, la.qk_nope_head_dim,
                         la.qk_rope_head_dim, la.v_head_dim)
        lanes = la.cache_lanes
        absorbed = sq <= pallas_latent_attn.MAX_QUERY_TOKENS
        dense = functools.partial(nn.DenseGeneral, axis=-1, use_bias=False,
                                  dtype=cfg.dtype, param_dtype=jnp.float32)
        q = dense((h, dn + dr), kernel_init=nn.with_logical_partitioning(
            default_init(), ("embed", "heads", "head_dim")), name="q_proj")(x)
        if la.qk_norm:
            q = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axis=None,
                        name="q_norm")(q)
        ckr = dense(r + dr, kernel_init=nn.with_logical_partitioning(
            default_init(), ("embed", None)), name="kv_down")(x)
        c = RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype, axis=None,
                    name="kv_norm")(ckr[..., :r])
        w_up = self.param(
            "kv_up", nn.with_logical_partitioning(
                default_init(), (None, "heads", "head_dim")),
            (r, h, dn + dv), jnp.float32).astype(cfg.dtype)
        w_uk, w_uv = w_up[..., :dn], w_up[..., dn:]

        cur = None
        if decode and block_tables is not None:
            def _pool_missing():
                raise ValueError(
                    "paged decode (block_tables) requires an engine-provided "
                    "page-pool cache; it cannot be initialised from inside "
                    "the model")
            cached = self.variable("cache", "cached_latent", _pool_missing)
            if positions is None:
                if cache_positions is None:
                    raise ValueError(
                        "paged chunk prefill requires explicit positions")
                positions = (cache_positions[:, None]
                             + jnp.arange(sq, dtype=jnp.int32)[None, :])
        elif decode:
            cached = self.variable("cache", "cached_latent", jnp.zeros,
                                   (b, cfg.max_seq_len, lanes), cfg.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((), jnp.int32))
            cur = cache_index.value
            if positions is None:
                positions = (cur + jnp.arange(sq))[None, :]

        inv = yarn_inv_freq(dr, cfg.rope_theta, la.rope_factor,
                            la.rope_original_max, la.beta_fast, la.beta_slow)
        ang = jnp.outer(jnp.arange(cfg.max_seq_len, dtype=jnp.float32), inv)
        amp = (yarn_mscale(la.rope_factor, la.mscale)
               / yarn_mscale(la.rope_factor, la.mscale_all_dim))
        cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
        q_n = q[..., :dn]
        q_r = apply_rope(q[..., dn:], cos, sin, positions)
        k_r = apply_rope(ckr[..., None, r:], cos, sin, positions)[:, :, 0]
        row = jnp.concatenate(
            [c, k_r, jnp.zeros((b, sq, lanes - r - dr), cfg.dtype)], axis=-1)
        scale = la.softmax_scale

        def expanded(lat, allow):
            """lat [B, K, lanes], allow [B, sq, K] -> scores [B, H, sq, K]
            (f32, masked) and v [B, K, H, dv]."""
            k_n = jnp.einsum("bkr,rhd->bkhd", lat[..., :r], w_uk)
            v = jnp.einsum("bkr,rhd->bkhd", lat[..., :r], w_uv)
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_n, k_n,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_r, lat[..., r:r + dr],
                              preferred_element_type=jnp.float32)) * scale
            return jnp.where(allow[:, None], s, pallas_paged_attn.NEG_INF), v

        def attend(lat, allow, absorbed):
            """Attention of the call's queries over cache rows ``lat``, in
            the expanded or the absorbed form."""
            if not absorbed:
                s, v = expanded(lat, allow)
                p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
                return jnp.einsum("bhqk,bkhd->bqhd", p, v)
            qt = jnp.einsum("bqhd,rhd->bqhr", q_n, w_uk)
            s = (jnp.einsum("bqhr,bkr->bhqk", qt, lat[..., :r],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_r, lat[..., r:r + dr],
                              preferred_element_type=jnp.float32)) * scale
            s = jnp.where(allow[:, None], s, pallas_paged_attn.NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            u = jnp.einsum("bhqk,bkr->bqhr", p, lat[..., :r])
            return jnp.einsum("bqhr,rhd->bqhd", u, w_uv)

        if decode and block_tables is not None:
            pool = cached.value
            page_tokens, n_blocks = pool.shape[-2], block_tables.shape[1]
            wpos = positions.astype(jnp.int32)                    # [B, sq]
            blk = wpos // page_tokens
            pg = jnp.take_along_axis(block_tables,
                                     jnp.minimum(blk, n_blocks - 1), axis=1)
            pg = jnp.where(blk >= n_blocks, 0, pg)                # scratch
            pool = pool.at[pg, wpos % page_tokens].set(row.astype(pool.dtype))
            cached.value = pool
            if latent_attention_impl(cfg) != "latent_flash":
                # XLA: every page of the table gathered whole, then the same
                # two forms as the row cache below — the kernels' reference
                # and the CPU tests' path. "auto" never takes it on a TPU
                # (a 17 k-token prefix expanded at once is gigabytes).
                s_virt = n_blocks * page_tokens
                lat = pool[block_tables].reshape(b, s_virt, lanes)
                out = attend(lat, jnp.arange(s_virt)[None, None, :]
                             <= wpos[:, :, None], absorbed)
            elif absorbed:
                qt = jnp.einsum("bqhd,rhd->bqhr", q_n, w_uk)
                q_abs = jnp.concatenate(
                    [qt, q_r, jnp.zeros((b, sq, h, lanes - r - dr), cfg.dtype)],
                    axis=-1)
                u = pallas_latent_attn.latent_decode_attention(
                    q_abs, pool, block_tables, wpos, rank=r,
                    softmax_scale=scale)
                out = jnp.einsum("bqhr,rhd->bqhd", u, w_uv)
            else:
                # A chunk works through the row's pages CHUNK_BLOCK_K tokens
                # at a time (whole blocks of the table): the pages gathered
                # in table order (one 640-lane row a token — 22 MB at 17 k),
                # expanded to keys and values block by block INSIDE the
                # kernel, so the expanded K and V of a long prefix never
                # exist whole.
                ppb = pallas_latent_attn.chunk_pages_per_block(page_tokens)
                tbl = jnp.pad(block_tables, ((0, 0), (0, -n_blocks % ppb)))
                out = pallas_latent_attn.latent_chunk_attention(
                    q_n, q_r, pool[tbl].reshape(b, -1, lanes), w_uk, w_uv,
                    wpos, rank=r, softmax_scale=scale,
                    block_k=ppb * page_tokens)
        elif decode:
            lat = jax.lax.dynamic_update_slice(
                cached.value, row.astype(cached.value.dtype), (0, cur, 0))
            cached.value = lat
            cache_index.value = cur + sq
            col = jnp.arange(cfg.max_seq_len)
            allow = col[None, None, :] <= (cur + jnp.arange(sq))[None, :, None]
            out = attend(lat, jnp.broadcast_to(allow, (b, sq, cfg.max_seq_len)),
                         absorbed)
        else:
            allow = (jnp.arange(sq)[None, :] <= jnp.arange(sq)[:, None]
                     if cfg.causal else jnp.ones((sq, sq), bool))
            out = checkpoint_name(
                attend(row, jnp.broadcast_to(allow, (b, sq, sq)), False),
                "attn_out")
        out = nn.with_logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
        out = nn.DenseGeneral(cfg.dim, axis=(-2, -1), use_bias=False,
                              dtype=cfg.dtype, param_dtype=jnp.float32,
                              kernel_init=nn.with_logical_partitioning(
                                  default_init(), ("heads", "head_dim", "embed")),
                              name="o_proj")(out)
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


class ShortConv(nn.Module):
    """Gated short convolution — the ``conv`` mixer of the LFM2 family, a
    :class:`LayerKind` ``attention`` factory beside :class:`Attention` (same
    keywords; tables and positions mean nothing to it):

        [B | C | u] = W_in x          (dim -> 3 dim, three equal parts, no bias)
        z = B * u
        v[t] = sum_j w[j] * z[t - (width - 1) + j]     (depthwise, causal,
                                       zeros before the sequence, no bias)
        out = W_out (C * v)

    Its whole memory is the last ``width - 1`` columns of ``z``. With
    ``decode=True`` that tail is the cache leaf ``conv_state``
    ``[B, width - 1, dim]``: read before the call's tokens, written after
    them. ``generate()``'s row cache makes it itself (zeros: before the
    sequence). Under ``block_tables`` it is the serving engine's per-slot
    STATE ARENA (serve/engine.py): the decode program hands every slot's row
    (the call's batch is the slots), a chunk program its one slot's — and
    ``lengths`` ([B] int32) says how many of a right-padded final chunk's
    tokens are real, so that the state left is the last REAL token's."""

    cfg: TransformerConfig
    width: int = 3

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        cfg, tail = self.cfg, self.width - 1
        if mask is not None or segment_ids is not None or attention_fn is not None:
            raise NotImplementedError(
                "ShortConv is causal over the whole row: mask, segment_ids "
                "and attention_fn are not supported")
        if cfg.tp_axis is not None:
            raise NotImplementedError("ShortConv has no tp_axis path")
        b, sq, d = x.shape
        bcu = param_dense(3 * d, ("embed", "mlp"), "in_proj", cfg.dtype)(x)
        gate_b, gate_c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
        z = gate_b * u
        w = self.param("conv", nn.with_logical_partitioning(
            default_init(), (None, "mlp")), (self.width, d), jnp.float32)
        state = None
        if decode:
            def _arena_missing():
                raise ValueError(
                    "paged decode (block_tables) requires an engine-provided "
                    "state arena (conv_state); it cannot be initialised from "
                    "inside the model")
            state = (self.variable("cache", "conv_state", _arena_missing)
                     if block_tables is not None else
                     self.variable("cache", "conv_state", jnp.zeros,
                                   (b, tail, d), cfg.dtype))
            before = state.value.astype(z.dtype)
        else:
            before = jnp.zeros((b, tail, d), z.dtype)
        zfull = jnp.concatenate([before, z], axis=1)        # [B, tail + sq, d]
        if state is not None:
            # the tail after n real tokens: rows n .. n + tail - 1 of zfull
            # (all sq of them real unless the caller says otherwise)
            after = zfull[:, sq:] if lengths is None else jax.vmap(
                lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, tail, 0)
            )(zfull, lengths.astype(jnp.int32))
            state.value = after.astype(state.value.dtype)
        zf = zfull.astype(jnp.float32)
        v = sum(w[j] * zf[:, j:j + sq] for j in range(self.width))
        out = param_dense(d, ("mlp", "embed"), "out_proj", cfg.dtype)(
            gate_c * v.astype(cfg.dtype))
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    """A Mamba-2 mixer's sizes (the Nemotron-H family's config keys in
    brackets): ``num_heads`` heads [mamba_num_heads] of ``head_dim`` lanes
    [mamba_head_dim], ``n_groups`` groups of B and C [n_groups] (head h uses
    group ``h // (num_heads / n_groups)``), a state of ``state_size`` columns a
    lane [ssm_state_size], a causal depthwise convolution of ``conv_kernel``
    taps [conv_kernel] with a bias, and the chunk of the full-sequence form
    [chunk_size]. ``time_step_*`` are the published initialisation of
    ``dt_bias``. ``state_dtype``: what the carried state is kept in (float32:
    the published serving recipe's; the convolution's tail is the model's
    type). ``update_impl``: the one-token update — ``"kernel"``
    (:mod:`ops.pallas_ssm`), ``"xla"``, or ``"auto"`` (:func:`ssm_update_impl`)."""

    num_heads: int = 128
    head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    state_dtype: Dtype = jnp.float32
    update_impl: str = "auto"

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size

    @property
    def state_rows_lanes(self) -> tuple[int, int]:
        """One row's ``ssm_state`` (the update kernel's layout)."""
        return pallas_ssm.state_shape(self.num_heads, self.head_dim,
                                      self.state_size, self.n_groups)


def ssm_update_impl(mamba: Mamba2Config | Mamba1Config) -> str:
    """Which implementation a one-token state update resolves to:
    ``"kernel"`` (:func:`ops.pallas_ssm.ssm_update`, for a Mamba-1 mixer
    :func:`ops.pallas_selective_scan.selective_update`: in place over the live
    rows) or ``"xla"``. ``"auto"``: the kernel on a TPU for a float32 state."""
    if mamba.update_impl != "auto":
        return mamba.update_impl
    return ("kernel" if on_tpu() and mamba.state_dtype == jnp.float32
            else "xla")


def mamba_config_of(model) -> Mamba2Config | Mamba1Config | None:
    """The :class:`Mamba2Config` (or :class:`Mamba1Config`) the
    :class:`Mamba2` (:class:`Mamba1`) factories of *model*'s ``pattern`` were
    made with; None for a model with no such layer."""
    for kind in getattr(model, "pattern", None) or ():
        mamba = getattr(kind.attention, "keywords", {}).get("mamba")
        if mamba is not None:
            return mamba
    return None


def ssd_chunked(xs, dt, a, bmat, cmat, h0, chunk: int, dtype):
    """The state-space recurrence over a whole sequence in the chunked
    (state-space-duality) form: within a chunk of ``chunk`` tokens the output
    is a masked matrix product, between chunks a state is carried — so a
    512-token prefill chunk is matrix products and 4 steps, not 512.

    xs ``[B, S, H, P]``, dt ``[B, S, H]`` f32 (after the softplus; 0 where a
    token must not move the state), a ``[H]`` f32 (negative), bmat / cmat
    ``[B, S, G, N]``, h0 ``[B, H, P, N]`` f32. -> (y ``[B, S, H, P]`` f32
    without the ``D x`` term, the state after the last token, f32). Decays
    and sums are float32; the products' operands are *dtype*."""
    b, s, h, p = xs.shape
    g, n = bmat.shape[2:]
    r = h // g
    pad = -s % chunk
    if pad:
        xs, dt, bmat, cmat = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                              for v in (xs, dt, bmat, cmat))
    nc = (s + pad) // chunk
    f32 = jnp.float32
    xs = xs.reshape(b, nc, chunk, g, r, p).astype(dtype)
    dt = dt.reshape(b, nc, chunk, g, r)
    bmat = bmat.reshape(b, nc, chunk, g, n).astype(dtype)
    cmat = cmat.reshape(b, nc, chunk, g, n).astype(dtype)
    cs = jnp.cumsum(dt * a.reshape(g, r), axis=2)           # [B, nc, L, G, R]
    # inside a chunk: y_l = sum_{s <= l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cmat, bmat, preferred_element_type=f32)
    seg = cs[:, :, :, None] - cs[:, :, None, :]             # [B, nc, L, S, G, R]
    tri = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    w = jnp.where(tri[None, None, :, :, None, None], jnp.exp(seg), 0.0)
    w = w * dt[:, :, None] * jnp.moveaxis(cb, 2, 4)[..., None]
    y = jnp.einsum("bclsgr,bcsgrp->bclgrp", w.astype(dtype), xs,
                   preferred_element_type=f32)
    # what a chunk adds to the state by its end, and the state carried in
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt               # [B, nc, L, G, R]
    added = jnp.einsum("bclgrp,bclgn->bcgrpn",
                       (xs.astype(f32) * to_end[..., None]).astype(dtype), bmat,
                       preferred_element_type=f32)
    whole = jnp.exp(cs[:, :, -1])                           # [B, nc, G, R]

    def carry(hc, step):
        dec, add = step
        return dec[..., None, None] * hc + add, hc
    h_end, h_in = jax.lax.scan(
        carry, h0.astype(f32).reshape(b, g, r, p, n),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                         # [B, nc, G, R, P, N]
    y = y + (jnp.einsum("bclgn,bcgrpn->bclgrp", cmat, h_in.astype(dtype),
                        preferred_element_type=f32)
             * jnp.exp(cs)[..., None])
    return (y.reshape(b, nc * chunk, h, p)[:, :s], h_end.reshape(b, h, p, n))


class GroupRMSNorm(nn.Module):
    """RMSNorm over each of ``groups`` equal runs of the lanes, with one
    learned gain over all of them (a Mamba-2 mixer's output norm)."""

    groups: int
    eps: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale", nn.with_logical_partitioning(nn.initializers.ones, ("mlp",)),
            (x.shape[-1],), jnp.float32)
        xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (self.groups, -1))
        xg = xg * jax.lax.rsqrt(
            jnp.mean(jnp.square(xg), axis=-1, keepdims=True) + self.eps)
        return (xg.reshape(x.shape) * scale).astype(self.dtype)


class Mamba2(nn.Module):
    """Mamba-2 mixer — the ``M`` layers of the Nemotron-H family, a
    :class:`LayerKind` ``attention`` factory beside :class:`ShortConv` (same
    keywords; tables and positions mean nothing to it):

        [z | xBC | dt] = W_in x            (no bias)
        xBC = silu(conv(xBC))              (depthwise, causal, WITH bias)
        x [H, P], B [G, N], C [G, N] = split(xBC);   D = softplus(dt + dt_bias)
        h_t = exp(D_t A) h_(t-1) + D_t x_t (x) B_t;   y_t = h_t C_t + D x_t
        out = W_out RMSNorm_g(y * silu(z))     (the gate BEFORE the norm)

    Its memory is two cache leaves: ``conv_state`` ``[B, conv_kernel - 1,
    conv_dim]`` (the pre-convolution ``xBC`` tail, the model's type) and
    ``ssm_state`` ``[B, rows, lanes]`` (every head's ``h``, ``state_dtype``,
    in :mod:`ops.pallas_ssm`'s layout). ``generate()``'s row cache makes both
    itself (zeros). Under ``block_tables`` they are the serving engine's
    per-slot STATE ARENA: a chunk call gets one slot's rows and runs the
    chunked form from the state it is handed (``lengths``: the state left is
    the last REAL token's); the decode call gets every slot's, and advances
    ``ssm_state`` IN PLACE for the rows with a cursor (``cache_positions >
    0``) only — masked inside the update, never by a select over the arena
    (serve/engine.py keeps the small ``conv_state`` of the other rows)."""

    cfg: TransformerConfig
    mamba: Mamba2Config = Mamba2Config()

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        cfg, m = self.cfg, self.mamba
        if mask is not None or segment_ids is not None or attention_fn is not None:
            raise NotImplementedError(
                "Mamba2 is causal over the whole row: mask, segment_ids and "
                "attention_fn are not supported")
        if cfg.tp_axis is not None:
            raise NotImplementedError("Mamba2 has no tp_axis path")
        b, sq, d = x.shape
        hh, p, g, n, tail = (m.num_heads, m.head_dim, m.n_groups, m.state_size,
                             m.conv_kernel - 1)
        f32 = jnp.float32
        zxd = param_dense(2 * m.inner + 2 * g * n + hh, ("embed", "mlp"),
                          "in_proj", cfg.dtype)(x)
        z, xbc, dt = (zxd[..., :m.inner], zxd[..., m.inner:m.inner + m.conv_dim],
                      zxd[..., m.inner + m.conv_dim:])
        w = self.param("conv", nn.with_logical_partitioning(
            default_init(), (None, "mlp")), (m.conv_kernel, m.conv_dim), f32)
        w_bias = self.param("conv_bias", nn.with_logical_partitioning(
            nn.initializers.zeros, ("mlp",)), (m.conv_dim,), f32)

        def dt_bias_init(key, shape, dtype):
            step = jnp.exp(jax.random.uniform(key, shape, f32)
                           * (math.log(m.time_step_max) - math.log(m.time_step_min))
                           + math.log(m.time_step_min))
            step = jnp.maximum(step, m.time_step_floor)
            return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
        dt_bias = self.param("dt_bias", dt_bias_init, (hh,), f32)
        a_log = self.param(
            "A_log", lambda key, shape, dtype: jnp.log(jax.random.uniform(
                key, shape, f32, 1.0, 16.0)).astype(dtype), (hh,), f32)
        skip = self.param("D", nn.initializers.ones, (hh,), f32)

        conv_state = ssm_state = None
        if decode:
            def _arena_missing():
                raise ValueError(
                    "paged decode (block_tables) requires an engine-provided "
                    "state arena (conv_state, ssm_state); it cannot be "
                    "initialised from inside the model")
            paged = block_tables is not None
            conv_state = (
                self.variable("cache", "conv_state", _arena_missing) if paged
                else self.variable("cache", "conv_state", jnp.zeros,
                                   (b, tail, m.conv_dim), cfg.dtype))
            ssm_state = (
                self.variable("cache", "ssm_state", _arena_missing) if paged
                else self.variable("cache", "ssm_state", jnp.zeros,
                                   (b,) + m.state_rows_lanes, m.state_dtype))
            before = conv_state.value.astype(xbc.dtype)
        else:
            before = jnp.zeros((b, tail, m.conv_dim), xbc.dtype)
        full = jnp.concatenate([before, xbc], axis=1)       # [B, tail + sq, C]
        if conv_state is not None:
            after = full[:, sq:] if lengths is None else jax.vmap(
                lambda row, k: jax.lax.dynamic_slice_in_dim(row, k, tail, 0)
            )(full, lengths.astype(jnp.int32))
            conv_state.value = after.astype(conv_state.value.dtype)
        ff = full.astype(f32)
        xbc = nn.silu(sum(w[j] * ff[:, j:j + sq] for j in range(m.conv_kernel))
                      + w_bias).astype(cfg.dtype)
        xs = xbc[..., :m.inner].reshape(b, sq, hh, p)
        bmat = xbc[..., m.inner:m.inner + g * n].reshape(b, sq, g, n)
        cmat = xbc[..., m.inner + g * n:].reshape(b, sq, g, n)
        step = jax.nn.softplus(dt.astype(f32) + dt_bias)    # [B, sq, H]
        a = -jnp.exp(a_log.astype(f32))

        if ssm_state is not None and sq == 1:
            # one token a row: the update itself, over the rows with a cursor
            live = (jnp.ones((b,), bool) if cache_positions is None
                    else cache_positions > 0)
            update = (pallas_ssm.ssm_update
                      if ssm_update_impl(m) == "kernel"
                      else pallas_ssm.ssm_update_reference)
            ssm_state.value, y = update(
                ssm_state.value, xs[:, 0], step[:, 0], a, bmat[:, 0],
                cmat[:, 0], live)
            y = y[:, None]
        else:
            if lengths is not None:
                # a pad moves no state: its step is 0
                step = jnp.where(jnp.arange(sq)[None, :, None]
                                 < lengths[:, None, None], step, 0.0)
            h0 = (jnp.zeros((b, hh, p, n), f32) if ssm_state is None else
                  pallas_ssm.unpack_state(ssm_state.value.astype(f32), hh, p, g))
            y, h_end = ssd_chunked(xs, step, a, bmat, cmat, h0, m.chunk_size,
                                   cfg.dtype)
            if ssm_state is not None:
                ssm_state.value = pallas_ssm.pack_state(h_end, g).astype(
                    ssm_state.value.dtype)
        y = y + skip.astype(f32)[:, None] * xs.astype(f32)
        y = (y.reshape(b, sq, m.inner) * nn.silu(z.astype(f32))).astype(cfg.dtype)
        y = GroupRMSNorm(g, eps=cfg.norm_eps, dtype=cfg.dtype, name="norm")(y)
        out = param_dense(d, ("mlp", "embed"), "out_proj", cfg.dtype)(y)
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


@dataclasses.dataclass(frozen=True)
class Mamba1Config:
    """A Mamba-1 mixer's sizes (the Jamba family's config keys in brackets):
    ``inner`` channels [mamba_expand x hidden_size], a state of
    ``state_size`` columns a channel [mamba_d_state], a time step through a
    rank-``dt_rank`` bottleneck [mamba_dt_rank], a causal depthwise
    convolution of ``conv_kernel`` taps [mamba_d_conv] with a bias.
    ``time_step_*`` are the published initialisation of ``dt_proj``'s bias.
    ``state_dtype``: what the carried state is kept in. ``update_impl`` /
    ``scan_impl``: the one-token update and the scan over a chunk —
    ``"kernel"`` (:mod:`ops.pallas_selective_scan`), ``"xla"``, or ``"auto"``
    (:func:`ssm_update_impl`, :func:`selective_scan_impl`)."""

    inner: int = 5120
    state_size: int = 16
    dt_rank: int = 160
    conv_kernel: int = 4
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    state_dtype: Dtype = jnp.float32
    update_impl: str = "auto"
    scan_impl: str = "auto"

    @property
    def state_rows_lanes(self) -> tuple[int, int]:
        """One row's ``ssm_state``: the state columns on the sublanes, the
        channels on the lanes."""
        return (self.state_size, self.inner)


def selective_scan_impl(mamba: Mamba1Config) -> str:
    """Which implementation a Mamba-1 scan over several tokens resolves to:
    ``"kernel"`` (:func:`ops.pallas_selective_scan.selective_scan`) or
    ``"xla"`` (a ``lax.scan`` over tokens). ``"auto"``: the kernel on a TPU
    for a float32 state."""
    if mamba.scan_impl != "auto":
        return mamba.scan_impl
    return ("kernel" if on_tpu() and mamba.state_dtype == jnp.float32
            else "xla")


def ssm_impls(mamba: Mamba2Config | Mamba1Config) -> str:
    """What a model's state-space mixers run as, for
    ``ServeEngine.attention_impls``: the one-token update (``ssm=``) and,
    for a Mamba-1 mixer, the scan over a chunk (``scan=``)."""
    out = f"ssm={ssm_update_impl(mamba)}"
    if isinstance(mamba, Mamba1Config):
        out += f" scan={selective_scan_impl(mamba)}"
    return out


class LowRankStep(nn.Module):
    """A Mamba-1 mixer's time step from its rank-``dt_rank`` bottleneck:
    ``softplus(W_dt r + b_dt)`` in float32, ``W_dt`` drawn uniform within
    ``dt_rank^-1/2`` and ``b_dt`` the inverse softplus of a step log-uniform
    in [``time_step_min``, ``time_step_max``] (the published
    initialisation)."""

    mamba: Mamba1Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, r: jax.Array) -> jax.Array:
        m, f32 = self.mamba, jnp.float32
        bound = m.dt_rank ** -0.5

        def bias_init(key, shape, dtype):
            step = jnp.exp(jax.random.uniform(key, shape, f32)
                           * (math.log(m.time_step_max) - math.log(m.time_step_min))
                           + math.log(m.time_step_min))
            step = jnp.maximum(step, m.time_step_floor)
            return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
        kernel = self.param("kernel", nn.with_logical_partitioning(
            lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, -bound, bound), (None, "mlp")),
            (m.dt_rank, m.inner), f32)
        bias = self.param("bias", nn.with_logical_partitioning(
            bias_init, ("mlp",)), (m.inner,), f32)
        dt = jnp.einsum("...r,re->...e", r.astype(self.dtype),
                        kernel.astype(self.dtype), preferred_element_type=f32)
        return jax.nn.softplus(dt + bias.astype(f32))


class Mamba1(nn.Module):
    """Mamba-1 mixer — the Mamba layers of the Jamba family, a
    :class:`LayerKind` ``attention`` factory beside :class:`Mamba2` (same
    keywords; tables and positions mean nothing to it):

        [u | z] = W_in x                   (no bias)
        u = silu(conv(u))                  (depthwise, causal, WITH bias)
        [r | B | C] = W_x u;   r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)
        dt = softplus(W_dt r + b_dt) [E];   A = -exp(A_log) [E, N]
        h_t = exp(dt_t A) h_(t-1) + dt_t B_t u_t;   y_t = h_t C_t + D u_t
        out = W_out (y * silu(z))          (no output norm)

    The decay differs for every (channel, state column): the scan over a
    chunk is :func:`ops.pallas_selective_scan.selective_scan`, not
    :func:`ssd_chunked`'s matrix products. Its memory is two cache leaves:
    ``conv_state`` ``[B, conv_kernel - 1, inner]`` (the pre-convolution ``u``
    tail, the model's type) and ``ssm_state`` ``[B, state_size, inner]``
    (``state_dtype``: the channels on the lanes). ``generate()``'s row cache
    makes both itself (zeros). Under ``block_tables`` they are the serving
    engine's per-slot STATE ARENA, as for :class:`Mamba2`: a chunk call gets
    one slot's rows (``lengths``: a pad moves no state), the decode call
    every slot's, and ``ssm_state`` is advanced IN PLACE for the rows with a
    cursor (``cache_positions > 0``) only — masked inside the update."""

    cfg: TransformerConfig
    mamba: Mamba1Config = Mamba1Config()

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        cfg, m = self.cfg, self.mamba
        if mask is not None or segment_ids is not None or attention_fn is not None:
            raise NotImplementedError(
                "Mamba1 is causal over the whole row: mask, segment_ids and "
                "attention_fn are not supported")
        if cfg.tp_axis is not None:
            raise NotImplementedError("Mamba1 has no tp_axis path")
        b, sq, d = x.shape
        e, n, r, tail = m.inner, m.state_size, m.dt_rank, m.conv_kernel - 1
        f32 = jnp.float32
        uz = param_dense(2 * e, ("embed", "mlp"), "in_proj", cfg.dtype)(x)
        u, z = uz[..., :e], uz[..., e:]
        tap = lambda key, shape, dtype: jax.random.uniform(
            key, shape, dtype, -1.0, 1.0) / math.sqrt(m.conv_kernel)
        w = self.param("conv", nn.with_logical_partitioning(tap, (None, "mlp")),
                       (m.conv_kernel, e), f32)
        w_bias = self.param("conv_bias", nn.with_logical_partitioning(
            tap, ("mlp",)), (e,), f32)
        a_log = self.param("A_log", nn.with_logical_partitioning(
            lambda key, shape, dtype: jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=dtype)), shape),
            ("mlp", None)), (e, n), f32)
        skip = self.param("D", nn.with_logical_partitioning(
            nn.initializers.ones, ("mlp",)), (e,), f32)

        conv_state = ssm_state = None
        if decode:
            def _arena_missing():
                raise ValueError(
                    "paged decode (block_tables) requires an engine-provided "
                    "state arena (conv_state, ssm_state); it cannot be "
                    "initialised from inside the model")
            paged = block_tables is not None
            conv_state = (
                self.variable("cache", "conv_state", _arena_missing) if paged
                else self.variable("cache", "conv_state", jnp.zeros,
                                   (b, tail, e), cfg.dtype))
            ssm_state = (
                self.variable("cache", "ssm_state", _arena_missing) if paged
                else self.variable("cache", "ssm_state", jnp.zeros,
                                   (b,) + m.state_rows_lanes, m.state_dtype))
            before = conv_state.value.astype(u.dtype)
        else:
            before = jnp.zeros((b, tail, e), u.dtype)
        full = jnp.concatenate([before, u], axis=1)          # [B, tail + sq, E]
        if conv_state is not None:
            after = full[:, sq:] if lengths is None else jax.vmap(
                lambda row, k: jax.lax.dynamic_slice_in_dim(row, k, tail, 0)
            )(full, lengths.astype(jnp.int32))
            conv_state.value = after.astype(conv_state.value.dtype)
        ff = full.astype(f32)
        u = nn.silu(sum(w[j] * ff[:, j:j + sq] for j in range(m.conv_kernel))
                    + w_bias)                                # [B, sq, E] f32
        rbc = param_dense(r + 2 * n, ("mlp", None), "x_proj", cfg.dtype)(
            u.astype(cfg.dtype))
        norm = lambda name: RMSNorm(eps=cfg.norm_eps, dtype=f32, axis=None, name=name)
        step = LowRankStep(m, cfg.dtype, name="dt_proj")(norm("dt_norm")(rbc[..., :r]))
        bmat = norm("b_norm")(rbc[..., r:r + n])             # [B, sq, N] f32
        cmat = norm("c_norm")(rbc[..., r + n:])
        a = -jnp.exp(a_log.astype(f32)).T                    # [N, E]

        if ssm_state is not None and sq == 1:
            # one token a row: the update itself, over the rows with a cursor
            live = (jnp.ones((b,), bool) if cache_positions is None
                    else cache_positions > 0)
            update = (pallas_selective_scan.selective_update
                      if ssm_update_impl(m) == "kernel"
                      else pallas_selective_scan.selective_update_reference)
            ssm_state.value, y = update(ssm_state.value, u[:, 0], step[:, 0], a,
                                        bmat[:, 0], cmat[:, 0], live)
            y = y[:, None]
        else:
            if lengths is not None:
                # a pad moves no state: its step is 0
                step = jnp.where(jnp.arange(sq)[None, :, None]
                                 < lengths[:, None, None], step, 0.0)
            h0 = (jnp.zeros((b, n, e), f32) if ssm_state is None
                  else ssm_state.value.astype(f32))
            scan = (pallas_selective_scan.selective_scan
                    if selective_scan_impl(m) == "kernel"
                    else pallas_selective_scan.selective_scan_reference)
            y, h_end = scan(u, step, a, bmat, cmat, h0)
            if ssm_state is not None:
                ssm_state.value = h_end.astype(ssm_state.value.dtype)
        y = (y + skip.astype(f32) * u) * nn.silu(z.astype(f32))
        out = param_dense(d, ("mlp", "embed"), "out_proj", cfg.dtype)(
            y.astype(cfg.dtype))
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


class MLP(nn.Module):
    """Feed-forward: SwiGLU (Llama), GELU with biases (BERT/ViT) or squared
    ReLU without (``"relu2"``: ``W_2 relu(W_1 x)^2``, the Nemotron-H family).
    Column-parallel up projections ("mlp" logical axis), row-parallel down
    projection."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        mlp = cfg.resolved_mlp_dim
        if cfg.activation == "swiglu":
            gate = param_dense(mlp, ("embed", "mlp"), "gate_proj", cfg.dtype)(x)
            up = param_dense(mlp, ("embed", "mlp"), "up_proj", cfg.dtype)(x)
            h = nn.silu(gate) * up
        elif cfg.activation == "relu2":
            h = jnp.square(nn.relu(
                param_dense(mlp, ("embed", "mlp"), "up_proj", cfg.dtype)(x)))
        else:
            h = param_dense(mlp, ("embed", "mlp"), "up_proj", cfg.dtype,
                            use_bias=True)(x)
            h = nn.gelu(h)
        h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
        if cfg.tp_axis is not None and cfg.activation not in ("swiglu", "relu2"):
            # The GELU path's down_proj carries a bias; psumming after it
            # would add the (replicated) bias tp times. Serving TP only
            # targets the bias-free families — fail at trace, not with
            # silently-wrong logits.
            raise NotImplementedError(
                "tp_axis requires a bias-free down projection "
                "(activation='swiglu' or 'relu2'); got activation="
                f"{cfg.activation!r}")
        out = param_dense(cfg.dim, ("mlp", "embed"), "down_proj", cfg.dtype,
                          use_bias=cfg.activation not in ("swiglu", "relu2"))(h)
        if cfg.tp_axis is not None:
            # Row-parallel down projection: partial sum over the sharded mlp
            # dim — Megatron's second reduction per block.
            out = collectives.tree_psum(out, cfg.tp_axis)
        return nn.with_logical_constraint(out, ("batch", "seq", "act_embed"))


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer of a stack is made of: two factories
    ``(cfg, name=...) -> module``. ``attention=None`` is :class:`Attention`
    (any other — :class:`LatentAttention`, :class:`ShortConv`,
    :class:`Mamba2`, :class:`Mamba1` — must take its keywords); ``mlp=None`` is the dense
    :class:`MLP`, any other (e.g. the expert-parallel
    :class:`models.moe.MoEMLP`) must accept a ``decode`` keyword — the static
    mode flag rides to it so that it can switch to its dropless serving
    dispatch."""

    attention: Callable | None = None
    mlp: Callable | None = None
    # A layer that is ONE sub-layer (the Nemotron-H family: every layer is a
    # mixer or a feed-forward, never both): "mixer" keeps the first half of
    # the block (attn_norm, attn, its residual), "mlp" the second (mlp_norm,
    # mlp, its residual). None: both.
    solo: str | None = None
    # Post-norm (the EXAONE 4 family): no norm on a sub-layer's input, an
    # RMSNorm on its OUTPUT before the residual add (``attn_post_norm``,
    # ``mlp_post_norm``). False: pre-norm.
    post_norm: bool = False

    def __post_init__(self):
        if self.solo not in (None, "mixer", "mlp"):
            raise ValueError(f"solo must be None, 'mixer' or 'mlp', "
                             f"got {self.solo!r}")
        if self.post_norm and self.solo is not None:
            raise ValueError("a post-norm layer is a mixer and a feed-forward")


class Block(nn.Module):
    """Pre-norm transformer block: x + attn(norm(x)); x + mlp(norm(x)) — or,
    where ``kind.solo`` says so, one of the two alone (one norm, one
    residual), or where ``kind.post_norm`` says so, x + norm(attn(x)); x +
    norm(mlp(x)).

    ``kind`` swaps the attention and the feed-forward module while keeping
    the block's norm/residual/dropout structure — and therefore scan/remat —
    shared.
    """

    cfg: TransformerConfig
    kind: LayerKind = LayerKind()
    # attention_fn rides as a module ATTRIBUTE (static), not a call
    # argument: under nn.remat every call argument is traced, and a
    # python callable cannot be turned into a tracer — passing e.g. the
    # shard_map'd mesh attention or a CP ring through a remat'd scanned
    # stack needs it here (the call kwarg remains for non-remat users).
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 deterministic: bool = True,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        cfg, kind = self.cfg, self.kind
        attention_fn = attention_fn or self.attention_fn
        if kind.solo is not None:
            return self._solo(x, deterministic=deterministic, decode=decode,
                              mask=mask, positions=positions,
                              segment_ids=segment_ids,
                              attention_fn=attention_fn,
                              cache_positions=cache_positions,
                              block_tables=block_tables,
                              **({} if lengths is None else {"lengths": lengths}))
        post = kind.post_norm
        h = x if post else make_norm(cfg, "attn_norm")(x)
        attn = (kind.attention or Attention)(cfg, name="attn")
        # lengths rides only where a caller gives it (a stack with a mixer
        # that carries state): the other mixers' signatures stay theirs.
        h = attn(h, mask=mask, positions=positions,
                 segment_ids=segment_ids, attention_fn=attention_fn,
                 decode=decode, cache_positions=cache_positions,
                 block_tables=block_tables,
                 **({} if lengths is None else {"lengths": lengths}))
        if post:
            h = make_norm(cfg, "attn_post_norm")(h)
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)(h)
        x = x + h
        h = x if post else make_norm(cfg, "mlp_norm")(x)
        if kind.mlp is not None:
            if cfg.tp_axis is not None:
                # Factory MLPs (MoE) don't know about the serving-TP psum
                # contract — running one under tp_axis would return partial
                # sums as if complete.
                raise NotImplementedError(
                    "tp_axis (serving tensor parallelism) supports only the "
                    "dense MLP; got a layer kind with its own mlp")
            h = kind.mlp(cfg, name="mlp")(h, decode=decode)
        else:
            h = MLP(cfg, name="mlp")(h)
        if post:
            h = make_norm(cfg, "mlp_post_norm")(h)
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)(h)
        x = x + h
        return nn.with_logical_constraint(x, ("batch", "seq", "act_embed"))

    def _solo(self, x, *, deterministic, decode, **mixer_kw):
        """The block of a ``kind.solo`` layer: the same names as the half of
        the whole block it keeps."""
        cfg, kind = self.cfg, self.kind
        if kind.solo == "mixer":
            h = (kind.attention or Attention)(cfg, name="attn")(
                make_norm(cfg, "attn_norm")(x), decode=decode, **mixer_kw)
        else:
            if cfg.tp_axis is not None and kind.mlp is not None:
                raise NotImplementedError(
                    "tp_axis (serving tensor parallelism) supports only the "
                    "dense MLP; got a layer kind with its own mlp")
            h = make_norm(cfg, "mlp_norm")(x)
            h = (MLP(cfg, name="mlp")(h) if kind.mlp is None
                 else kind.mlp(cfg, name="mlp")(h, decode=decode))
        if cfg.dropout_rate:
            h = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)(h)
        return nn.with_logical_constraint(x + h, ("batch", "seq", "act_embed"))


class Transformer(nn.Module):
    """Token-in, hidden-states-out transformer stack.

    ``nn.scan`` stacks the block weights on a leading "layers" axis (constant
    compile time in depth; the layout pipeline parallelism slices); ``remat``
    checkpoints each block for long-context memory. Both are config flags so
    tests can exercise either path.

    ``pattern`` says what each layer is made of (one :class:`LayerKind` a
    layer; None = every layer the default attention and dense MLP). A
    uniform pattern keeps the scanned path and its parameter tree; layers
    that differ (a leading dense layer before expert layers) have no one
    block body to scan and need ``scan_layers=False``.
    """

    cfg: TransformerConfig
    pattern: tuple[LayerKind, ...] | None = None

    @nn.compact
    def __call__(self, tokens_or_embeds: jax.Array, *,
                 mask: jax.Array | None = None,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 deterministic: bool = True,
                 attention_fn: Callable | None = None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 lengths: jax.Array | None = None) -> jax.Array:
        cfg = self.cfg
        if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
            x = nn.Embed(cfg.vocab_size, cfg.dim, dtype=cfg.dtype,
                         param_dtype=jnp.float32,
                         embedding_init=nn.with_logical_partitioning(
                             embed_init, ("vocab", "embed")),
                         name="tok_embed")(tokens_or_embeds)
        else:
            x = tokens_or_embeds.astype(cfg.dtype)
        if cfg.position == "learned":
            if decode and positions is None:
                if cache_positions is not None:
                    # Slot decode carries per-row cursors — exactly the
                    # absolute positions the embedding needs.
                    positions = cache_positions[:, None]
                else:
                    # The cache cursor lives inside Attention; learned
                    # positions would need it at embed time. RoPE models
                    # (the causal-LM families) are unaffected.
                    raise NotImplementedError(
                        "decode with position='learned' requires explicit "
                        "positions — pass positions=cache_cursor + arange(S)")
            pos = positions if positions is not None else jnp.arange(x.shape[1])
            x = x + nn.Embed(cfg.max_seq_len, cfg.dim, dtype=cfg.dtype,
                             param_dtype=jnp.float32,
                             embedding_init=nn.with_logical_partitioning(
                                 embed_init, (None, "embed")),
                             name="pos_embed")(pos)
        x = nn.with_logical_constraint(x, ("batch", "seq", "act_embed"))

        pattern = self.pattern or (LayerKind(),) * cfg.n_layers
        if len(pattern) != cfg.n_layers:
            raise ValueError(f"pattern names {len(pattern)} layers, the "
                             f"config has {cfg.n_layers}")
        if cfg.scan_layers and any(k != pattern[0] for k in pattern):
            raise ValueError("layers of different kinds cannot be scanned: "
                             "set scan_layers=False")
        block_cls = Block
        if cfg.remat and not decode:
            # remat trades FLOPs for backward-pass memory; decode has no
            # backward pass, and remat + mutable cache writes don't mix.
            block_cls = nn.remat(
                Block, prevent_cse=False,
                static_argnums=(),
                policy=REMAT_POLICIES[cfg.remat_policy])
        # Pass decode only when set: under nn.remat every call argument is
        # traced, which would turn the static `decode` python bool into a
        # tracer (remat is never combined with decode — guarded above).
        dkw = {"decode": True} if decode else {}
        if cache_positions is not None:
            dkw["cache_positions"] = cache_positions
        if block_tables is not None:
            dkw["block_tables"] = block_tables
        if lengths is not None:
            dkw["lengths"] = lengths
        if cfg.scan_layers:
            x, _ = nn.scan(
                lambda mdl, carry, _: (
                    mdl(carry, mask=mask, positions=positions,
                        segment_ids=segment_ids,
                        deterministic=deterministic, **dkw), None),
                variable_axes={"params": 0, "intermediates": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(block_cls(cfg, kind=pattern[0],
                        attention_fn=attention_fn, name="blocks"),
              x, None)
        else:
            for i in range(cfg.n_layers):
                x = block_cls(cfg, kind=pattern[i],
                              attention_fn=attention_fn,
                              name=f"block_{i}")(
                    x, mask=mask, positions=positions,
                    segment_ids=segment_ids,
                    deterministic=deterministic, **dkw)
        return make_norm(cfg, "final_norm")(x)


def flops_per_token(cfg: TransformerConfig, *, seq_len: int | None = None,
                    include_vocab: bool = True) -> float:
    """Approximate fwd+bwd FLOPs per token for MFU accounting (6N + attention
    convention): QKV/O projections, the MLP matmuls — 3 for SwiGLU, 2 for
    GELU (reusing the SwiGLU count for GELU models overstated BERT/ViT MFU
    ~20%) — the S^2 attention score+PV term at the *actual* sequence length,
    and the embedding/unembedding matmul when the model has a vocab head.
    Causal kernels do ~half the S^2 work; the full-S^2 convention is kept
    (PaLM-style), so causal MFU is conservative."""
    hd = cfg.resolved_head_dim
    s = seq_len or cfg.max_seq_len
    n_mlp_matmuls = 3 if cfg.activation == "swiglu" else 2
    per_layer = (
        2 * cfg.dim * cfg.n_heads * hd                    # q proj
        + 2 * 2 * cfg.dim * cfg.resolved_kv_heads * hd    # k, v proj
        + 2 * cfg.n_heads * hd * cfg.dim                  # o proj
        + n_mlp_matmuls * 2 * cfg.dim * cfg.resolved_mlp_dim
        + 2 * 2 * cfg.n_heads * hd * s                    # scores + PV
    )
    vocab = 2 * cfg.dim * cfg.vocab_size if include_vocab else 0
    return 3.0 * (cfg.n_layers * per_layer + vocab)


class LMHead(nn.Module):
    """Hidden states -> vocab logits; optionally tied to the input embedding."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 embedding: jax.Array | None = None) -> jax.Array:
        cfg = self.cfg
        if cfg.tie_embeddings:
            if embedding is None:
                raise ValueError("tie_embeddings requires the embedding table")
            logits = jnp.einsum("bsd,vd->bsv", x, embedding.astype(cfg.dtype),
                                preferred_element_type=jnp.float32)
        else:
            logits = param_dense(cfg.vocab_size, ("embed", "vocab"),
                                 "lm_head", cfg.dtype)(x)
        # f32 logits for a numerically stable softmax-CE.
        return logits.astype(jnp.float32)


def lm_forward(module: nn.Module, cfg: TransformerConfig,
               pattern: tuple[LayerKind, ...] | None, tokens: jax.Array, *,
               return_hidden: bool = False, **kw) -> jax.Array:
    """The body of a decoder-only LM made of a layer ``pattern``: the stack
    (``transformer``), then the head (``head``; tied to the embedding where
    the config says so) — built in *module*'s own scope, so that every model
    that is this body with a pattern of its own (:class:`PatternLM`,
    :class:`models.moe.LatentMoELM`) has the same parameter tree."""
    x = Transformer(cfg, pattern=pattern, name="transformer")(tokens, **kw)
    if return_hidden:
        return x
    embedding = None
    if cfg.tie_embeddings:
        embedding = module.variables["params"]["transformer"]["tok_embed"][
            "embedding"]
        if hasattr(embedding, "unbox"):     # raw access skips flax's unboxing
            embedding = embedding.unbox()
    return LMHead(cfg, name="head")(x, embedding)


class PatternLM(nn.Module):
    """Decoder-only LM whose layers are what ``pattern`` says, one
    :class:`LayerKind` a layer: any mix of mixers (:class:`Attention`,
    :class:`LatentAttention`, :class:`ShortConv`, :class:`Mamba2`,
    :class:`Mamba1`) and feed-forwards (the dense :class:`MLP`, :class:`models.moe.MoEMLP`), a layer
    being both or — ``LayerKind.solo`` — one alone. Layers that differ are
    unrolled (``cfg.scan_layers=False``). The serving engine calls it like
    :class:`models.llama.LlamaLM`; ``lengths`` reaches the mixers that carry
    state (:class:`ShortConv`, :class:`Mamba2`, :class:`Mamba1`)."""

    cfg: TransformerConfig
    pattern: tuple[LayerKind, ...] | None = None

    @nn.compact
    def __call__(self, tokens, *, positions=None, deterministic: bool = True,
                 decode: bool = False, cache_positions=None,
                 block_tables=None, lengths=None,
                 return_hidden: bool = False):
        return lm_forward(
            self, self.cfg, self.pattern, tokens, return_hidden=return_hidden,
            positions=positions, deterministic=deterministic, decode=decode,
            cache_positions=cache_positions, block_tables=block_tables,
            lengths=lengths)
