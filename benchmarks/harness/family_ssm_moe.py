"""Glue between an ``ssm-moe`` configuration file (Nemotron-H layout: Mamba-2
mixers, grouped-query attention with no positional embedding, latent
sparse-expert layers of non-gated squared-ReLU experts with one shared
expert; every layer ONE sub-layer, an untied head) and the program: the model
is ``models/transformer.py:PatternLM`` over ``models/moe.py:hybrid_pattern``
— the first ``num_hidden_layers`` letters of the published
``hybrid_override_pattern``, this chip's share of the experts
(``n_routed_experts`` held of ``router_outputs``, from ``expert_offset``) —
and the engine is ``serve.engine.ServeEngine`` with the cell's options (a
state arena beside its page pool). Names the plain reference that goes with
it, and makes the weights by that reference's rule."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_ssm_moe, weights

reference = reference_ssm_moe


def program_config(cfg: dict, max_seq_len: int):
    """-> (TransformerConfig, layer pattern)."""
    from k8s_distributed_deeplearning_tpu.models.moe import MoEConfig, hybrid_pattern
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        Mamba2Config, TransformerConfig)
    if not cfg["norm_topk_prob"] or cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the program's router takes one plain top-k over all the "
                         "experts and renormalises the chosen gates: norm_topk_prob "
                         "must be true, n_group and topk_group 1")
    if cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu":
        raise ValueError("this family's experts are relu2 and its mixers silu")
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    base = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        activation="relu2", norm="rmsnorm", norm_eps=float(cfg["norm_eps"]),
        position="none", causal=True, tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=dtype, scan_layers=False)
    moe = MoEConfig(
        num_experts=cfg["router_outputs"], top_k=cfg["num_experts_per_tok"],
        dispatch="ragged", ragged_block_m=128, score_fn="sigmoid", select_bias=True,
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared_experts=cfg["n_shared_experts"],
        expert_mlp_dim=cfg["moe_intermediate_size"], expert_act="relu2",
        latent_dim=cfg["moe_latent_size"],
        shared_mlp_dim=cfg["moe_shared_expert_intermediate_size"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=int(cfg.get("expert_offset", 0)))
    mamba = Mamba2Config(
        num_heads=cfg["mamba_num_heads"], head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"], state_size=cfg["ssm_state_size"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=float(cfg["time_step_min"]),
        time_step_max=float(cfg["time_step_max"]),
        time_step_floor=float(cfg["time_step_floor"]),
        state_dtype=jnp.dtype(cfg.get("ssm_state_dtype", "float32")))
    return base, hybrid_pattern(reference_ssm_moe.pattern(cfg), moe, mamba)


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

    def fill(s):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: reference_ssm_moe.leaf(
                cfg, s, weights.path_name(path), a.shape, dtype), abstract)
    return model, jax.jit(fill)(weights.seed_operand(seed))
