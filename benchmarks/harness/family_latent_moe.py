"""Glue between a ``latent-moe`` configuration file (multi-head latent
attention, a leading dense layer, sparse-expert layers with a shared expert)
and the program: the model is ``models/moe.py:LatentMoELM`` — this chip's
share of the experts (``num_experts`` held of ``router_outputs``) — and the
engine is ``serve.engine.ServeEngine`` with the cell's options. Names the
plain reference that goes with it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_latent_moe, weights

reference = reference_latent_moe


def program_config(cfg: dict, max_seq_len: int):
    """-> (TransformerConfig, LatentAttentionConfig, MoEConfig)."""
    from k8s_distributed_deeplearning_tpu.models.moe import MoEConfig
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        LatentAttentionConfig, TransformerConfig)
    rs = cfg["rope_scaling"]
    base = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        head_dim=cfg["q_head_dim"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(cfg["rope_theta"]),
        activation="swiglu", norm="rmsnorm", position="rope", causal=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.dtype(cfg.get("torch_dtype", "bfloat16")), scan_layers=False)
    latent = LatentAttentionConfig(
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        qk_norm=bool(cfg["use_qk_norm"]), rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    moe = MoEConfig(
        num_experts=cfg["router_outputs"], top_k=cfg["num_experts_per_tok"],
        dispatch="ragged", ragged_block_m=128, score_fn="sigmoid",
        select_bias=bool(cfg["moe_router_enable_expert_bias"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared_experts=cfg["num_shared_experts"],
        expert_mlp_dim=cfg["moe_intermediate_size"],
        experts_held=cfg["num_experts"], expert_offset=0)
    return base, latent, moe


def build_model_and_params(cfg: dict, max_seq_len: int, seed: int):
    """The model and its weights, made on the device from the seed in one
    jitted call, in the type they are served in."""
    from k8s_distributed_deeplearning_tpu.models.moe import LatentMoELM
    import flax.linen as nn

    model = LatentMoELM(*program_config(cfg, max_seq_len),
                        first_dense=cfg["first_k_dense_replace"])
    abstract = jax.eval_shape(
        lambda: nn.meta.unbox(model.init(jax.random.key(0),
                                         jnp.zeros((1, 8), jnp.int32))["params"]))
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    params = jax.jit(lambda s: weights.fill_like(s, abstract, dtype))(
        weights.seed_operand(seed))
    return model, params
