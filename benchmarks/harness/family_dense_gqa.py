"""Glue between a ``dense-gqa`` configuration file (Mistral-family decoder)
and the program: the model is ``models/llama.py:LlamaLM`` — the engine's
dense GQA block, whose equations this configuration matches — and the
engine is ``serve.engine.ServeEngine`` with the cell's options. Names the
plain reference that goes with it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import reference_mistral, weights

reference = reference_mistral


def program_config(cfg: dict, max_seq_len: int):
    from k8s_distributed_deeplearning_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        mlp_dim=cfg["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(cfg["rope_theta"]), activation="swiglu",
        norm="rmsnorm", position="rope", causal=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.dtype(cfg.get("torch_dtype", "bfloat16")),
        scan_layers=False)


def build_model_and_params(cfg: dict, max_seq_len: int, seed: int):
    """The model and its weights, made on the device from the seed in one
    jitted call, in the type they are served in."""
    from k8s_distributed_deeplearning_tpu.models import llama
    import flax.linen as nn

    model = llama.LlamaLM(program_config(cfg, max_seq_len))
    abstract = jax.eval_shape(
        lambda: nn.meta.unbox(model.init(jax.random.key(0),
                                         jnp.zeros((1, 8), jnp.int32))["params"]))
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    params = jax.jit(lambda s: weights.fill_like(s, abstract, dtype))(
        weights.seed_operand(seed))
    return model, params
