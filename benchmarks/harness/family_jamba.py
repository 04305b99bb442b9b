"""Glue between a ``jamba`` configuration file (Mamba-1 mixers and a few
multi-query attention layers with no positional embedding, a dense SwiGLU
feed-forward in every layer, a head tied to the embedding) and the program:
the model is ``models/transformer.py:PatternLM`` over
``models/moe.py:jamba_pattern`` (layer i is attention iff ``i %
attn_layer_period == attn_layer_offset``), and the engine is
``serve.engine.ServeEngine`` with the cell's options (a state arena beside
its page pool). Names the plain reference that goes with it, and makes the
weights by that reference's rule."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_jamba, weights

reference = reference_jamba


def program_config(cfg: dict, max_seq_len: int):
    """-> (TransformerConfig, layer pattern)."""
    from k8s_distributed_deeplearning_tpu.models.moe import jamba_pattern
    from k8s_distributed_deeplearning_tpu.models.transformer import (
        Mamba1Config, TransformerConfig)
    if cfg["num_experts"] != 1 or cfg["hidden_act"] != "silu":
        raise ValueError("this family's feed-forwards are ONE dense SwiGLU a layer: "
                         "num_experts must be 1 and hidden_act silu")
    if not cfg["tie_word_embeddings"] or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("the program's Mamba-1 mixer has a convolution bias and no "
                         "projection bias, and the head is tied")
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    e, n, r, k = reference_jamba.mamba_sizes(cfg)
    base = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        activation="swiglu", norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        position="none", causal=True, tie_embeddings=True, dtype=dtype, scan_layers=False)
    mamba = Mamba1Config(
        inner=e, state_size=n, dt_rank=r, conv_kernel=k,
        time_step_min=float(cfg["time_step_min"]), time_step_max=float(cfg["time_step_max"]),
        time_step_floor=float(cfg["time_step_floor"]),
        state_dtype=jnp.dtype(cfg.get("ssm_state_dtype", "float32")))
    return base, jamba_pattern(cfg["num_hidden_layers"], cfg["attn_layer_offset"],
                               cfg["attn_layer_period"], mamba)


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
            lambda path, a: reference_jamba.leaf(
                cfg, s, weights.path_name(path), a.shape, dtype), abstract)
    return model, jax.jit(fill)(weights.seed_operand(seed))
