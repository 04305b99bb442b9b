"""Llama-3-family causal language model — the framework's flagship config.

Reference parity note: the reference trains only an MNIST ConvNet
(``horovod/tensorflow_mnist.py:38-73``); the Llama config comes from the
BASELINE.json scale-out list ("Llama-3 8B FSDP-style param shard (all-gather +
reduce-scatter over ICI on v5p-64)"). Architecture is the public Llama-3
recipe: RMSNorm pre-norm, RoPE (theta 500k), GQA, SwiGLU MLP, untied output
head — expressed entirely through :class:`models.transformer.TransformerConfig`.

Shardability is inherited from the transformer core's logical axes: the same
module is pure-DP, FSDP (shard "embed"/"mlp"/"vocab" over the fsdp mesh axis
=> XLA emits the all-gather/reduce-scatter pattern), or Megatron TP (shard
"heads"/"mlp" over tensor) purely via rule tables in :mod:`parallel.sharding`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from k8s_distributed_deeplearning_tpu.models.transformer import (
    TransformerConfig, lm_batch_views, lm_forward)

import flax.linen as nn


class LlamaLM(nn.Module):
    """Decoder-only causal LM: tokens -> logits over vocab."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, *,
                 positions: jax.Array | None = None,
                 segment_ids: jax.Array | None = None,
                 deterministic: bool = True,
                 attention_fn=None,
                 decode: bool = False,
                 cache_positions: jax.Array | None = None,
                 block_tables: jax.Array | None = None,
                 return_hidden: bool = False) -> jax.Array:
        # return_hidden: final hidden states for a chunked LM-head loss
        # (ops/chunked_ce.py). Only valid at apply time: init must take the
        # default path so LMHead params get created.
        return lm_forward(
            self, self.cfg, None, tokens, return_hidden=return_hidden,
            positions=positions, segment_ids=segment_ids,
            deterministic=deterministic, attention_fn=attention_fn,
            decode=decode, cache_positions=cache_positions,
            block_tables=block_tables)


def config_llama3_8b(**overrides) -> TransformerConfig:
    """Llama-3 8B (public architecture numbers)."""
    base = dict(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                n_kv_heads=8, mlp_dim=14336, max_seq_len=8192,
                rope_theta=500000.0, activation="swiglu", norm="rmsnorm",
                position="rope", causal=True, remat=True)
    base.update(overrides)
    return TransformerConfig(**base)


def config_tiny(**overrides) -> TransformerConfig:
    """Tiny config with the same topology (GQA, SwiGLU, RoPE) for tests/CI."""
    base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                mlp_dim=128, max_seq_len=128, activation="swiglu",
                norm="rmsnorm", position="rope", causal=True)
    base.update(overrides)
    return TransformerConfig(**base)


def unembedding(cfg: TransformerConfig, params) -> tuple[jax.Array, str]:
    """The LM-head weight and its layout for the chunked-CE kernel: the
    ``lm_head`` kernel ``[D, V]`` ("dv") when untied, the input embedding
    table ``[V, D]`` ("vd") when tied. Handles boxed (``nn.Partitioned``)
    and plain leaves — ShardedTrainer losses see boxed params."""
    if cfg.tie_embeddings:
        w = params["transformer"]["tok_embed"]["embedding"]
        layout = "vd"
    else:
        w = params["head"]["lm_head"]["kernel"]
        layout = "dv"
    if hasattr(w, "unbox"):
        w = w.unbox()
    return w, layout


def loss_fn(model: LlamaLM, params, batch, rng=None, *,
            attention_fn=None, chunked: bool = False,
            chunk_size: int = 1024) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy. ``batch``: {"tokens": [B,S] int32, optional
    "mask": [B,S] 1.0 = count this position, optional "segment_ids": [B,S]
    int32 packed-document ids (attention stays within a document, and
    cross-document boundary positions don't count toward the loss)}.
    Shifts internally: position i predicts token i+1.

    ``chunked=True`` routes through :func:`ops.chunked_ce
    .chunked_softmax_cross_entropy`: the model returns final hidden states
    (``return_hidden``) and the LM-head matmul + CE run per sequence chunk
    under remat, so the full ``[B, S, V]`` logits tensor is never
    materialized — the memory lever that lets the 8B config's 128k vocab fit.
    Numerics match the unchunked path exactly at f32; at bf16 the chunked
    path is at least as accurate (its head matmul accumulates in f32 via
    ``preferred_element_type`` where ``LMHead`` emits bf16 then upcasts).
    """
    # Shared shift/positions/mask contract (transformer.lm_batch_views):
    # RoPE positions restart per packed document — without this, packed
    # training silently diverges from training the documents unpacked —
    # and cross-document boundary pairs stay out of the loss.
    inputs, targets, seg_in, positions, mask = lm_batch_views(batch)
    rngs = {"dropout": rng} if rng is not None else None
    apply_kw = dict(
        segment_ids=seg_in, positions=positions,
        deterministic=rng is None, rngs=rngs, attention_fn=attention_fn)

    if chunked:
        from k8s_distributed_deeplearning_tpu.ops.chunked_ce import (
            chunked_softmax_cross_entropy)
        hidden = model.apply({"params": params}, inputs,
                             return_hidden=True, **apply_kw)
        w, layout = unembedding(model.cfg, params)
        loss, acc = chunked_softmax_cross_entropy(
            hidden, w, targets, mask, chunk_size=chunk_size, w_layout=layout)
        return loss, {"accuracy": acc, "perplexity": jnp.exp(loss)}

    logits = model.apply({"params": params}, inputs, **apply_kw)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    loss = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    acc = (((logits.argmax(-1) == targets) * mask).sum()
           / jnp.maximum(mask.sum(), 1.0))
    return loss, {"accuracy": acc, "perplexity": jnp.exp(loss)}


def flops_per_token(cfg: TransformerConfig, *,
                    seq_len: int | None = None) -> float:
    """Approximate fwd+bwd FLOPs per token (6N + attention) for MFU — the
    shared per-architecture accounting in :func:`models.transformer
    .flops_per_token` (SwiGLU => 3 MLP matmuls here)."""
    from k8s_distributed_deeplearning_tpu.models import transformer
    return transformer.flops_per_token(cfg, seq_len=seq_len)
