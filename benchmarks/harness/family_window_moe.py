"""Glue between a ``window-moe`` configuration file (sliding-window attention
layers beside global ones with no positions, post-norm blocks, a leading
dense layer, then sparse-expert layers with a shared expert) and the program:
the model is ``models/transformer.py:PatternLM`` over
``models/moe.py:exaone_pattern`` — this chip's share of the experts
(``num_experts`` held of ``router_outputs``) — and the engine is
``serve.engine.ServeEngine`` with the cell's options (the window layers'
rings in its state arena, the global layers' K/V on its page pool). Names the
plain reference that goes with it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_window_moe, weights

reference = reference_window_moe


def program_config(cfg: dict, max_seq_len: int):
    """-> (TransformerConfig, layer pattern)."""
    from k8s_distributed_deeplearning_tpu.models.moe import MoEConfig, exaone_pattern
    from k8s_distributed_deeplearning_tpu.models.transformer import TransformerConfig
    if not cfg["norm_topk_prob"] or cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1:
        raise ValueError("the program's router takes sigmoid scores, one group, and "
                         "renormalises the chosen gates")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("this family's feed-forwards are SwiGLU and its head is untied")
    n = cfg["num_hidden_layers"]
    base = TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=n,
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], mlp_dim=cfg["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        activation="swiglu", norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        qk_norm=True, position="rope", causal=True, tie_embeddings=False,
        dtype=jnp.dtype(cfg.get("torch_dtype", "bfloat16")), scan_layers=False)
    moe = MoEConfig(
        num_experts=cfg["router_outputs"], top_k=cfg["num_experts_per_tok"],
        dispatch="ragged", ragged_block_m=128, score_fn="sigmoid", select_bias=True,
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared_experts=cfg["num_shared_experts"],
        expert_mlp_dim=cfg["moe_intermediate_size"],
        experts_held=cfg["num_experts"], expert_offset=0)
    return base, exaone_pattern(tuple(cfg["layer_types"][:n]),
                                tuple(cfg["mlp_layer_types"][:n]), moe,
                                int(cfg["sliding_window"]))


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
