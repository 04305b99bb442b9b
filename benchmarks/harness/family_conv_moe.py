"""Glue between a ``conv-moe`` configuration file (LFM2-MoE layout: gated
short convolutions beside grouped-query attention with per-head q/k norms,
leading dense layers, then sparse-expert layers, a tied head) and the
program: the model is ``models/transformer.py:PatternLM`` over
``models/moe.py:conv_moe_pattern`` — the first ``num_hidden_layers`` of the
published ``layer_types`` — and the engine is ``serve.engine.ServeEngine``
with the cell's options (a state arena beside its page pool). Names the plain
reference that goes with it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_conv_moe, weights

reference = reference_conv_moe


def program_config(cfg: dict, max_seq_len: int):
    """-> (TransformerConfig, layer pattern)."""
    from k8s_distributed_deeplearning_tpu.models.moe import MoEConfig, conv_moe_pattern
    from k8s_distributed_deeplearning_tpu.models.transformer import TransformerConfig
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program's router renormalises the chosen gates: "
                         "norm_topk_prob must be true")
    base = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]), activation="swiglu", norm="rmsnorm",
        norm_eps=float(cfg["norm_eps"]), qk_norm=True, position="rope", causal=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.dtype(cfg.get("torch_dtype", "bfloat16")), scan_layers=False)
    moe = MoEConfig(
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        dispatch="ragged", ragged_block_m=128, score_fn="sigmoid",
        select_bias=bool(cfg["use_expert_bias"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        expert_mlp_dim=cfg["moe_intermediate_size"])
    pattern = conv_moe_pattern(
        tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]), moe,
        num_dense=cfg["num_dense_layers"], conv_width=cfg["conv_L_cache"])
    return base, pattern


def build_model_and_params(cfg: dict, max_seq_len: int, seed: int):
    """The model and its weights, made on the device from the seed in one
    jitted call, in the type they are served in."""
    from k8s_distributed_deeplearning_tpu.models.transformer import PatternLM
    import flax.linen as nn

    model = PatternLM(*program_config(cfg, max_seq_len))
    abstract = jax.eval_shape(
        lambda: nn.meta.unbox(model.init(jax.random.key(0),
                                         jnp.zeros((1, 8), jnp.int32))["params"]))
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    params = jax.jit(lambda s: weights.fill_like(s, abstract, dtype))(
        weights.seed_operand(seed))
    return model, params
