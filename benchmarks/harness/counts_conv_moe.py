"""Operations and bytes of the ``conv-moe`` family from shapes: the layers
that are run (the first ``num_hidden_layers`` of ``layer_types``), every
expert held, the whole vocabulary, the head tied to the embedding.
Conventions as ``counts.py``: a multiply-add is 2 operations; attention at
the real context and in the attention layers only; element-wise work (norms,
gates, the softmax) is not counted except the convolution's own taps.
"""
from __future__ import annotations


def mixers(cfg: dict) -> tuple[int, int]:
    """(convolution layers, attention layers) among the layers run."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return kinds.count("conv"), kinds.count("full_attention")


def _sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_flops(cfg: dict) -> int:
    """One token through one routed expert: three products."""
    return 2 * expert_params(cfg)


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """The weights of one routed expert."""
    return expert_params(cfg) * itemsize


def conv_mixer_params(cfg: dict) -> int:
    """W_in (d x 3d), the taps (L x d), W_out (d x d)."""
    d = cfg["hidden_size"]
    return 3 * d * d + cfg["conv_L_cache"] * d + d * d


def attention_mixer_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o and the two per-head norm gains."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd + 2 * hd


def param_count(cfg: dict) -> int:
    """Every parameter of the model as run (the tied embedding once)."""
    d = cfg["hidden_size"]
    n_conv, n_attn = mixers(cfg)
    dense = cfg["num_dense_layers"] * 3 * d * cfg["intermediate_size"]
    router = d * cfg["num_experts"] + (cfg["num_experts"] if cfg["use_expert_bias"] else 0)
    sparse = _sparse_layers(cfg) * (cfg["num_experts"] * expert_params(cfg) + router)
    norms = 2 * d * cfg["num_hidden_layers"] + d
    return (n_conv * conv_mixer_params(cfg) + n_attn * attention_mixer_params(cfg)
            + dense + sparse + norms + cfg["vocab_size"] * d)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One cached token: K and V of every KV head in the attention layers."""
    return mixers(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def slot_state_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One slot's state: the last L - 1 columns of z in every convolution
    layer."""
    return mixers(cfg)[0] * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize


def token_flops(cfg: dict) -> int:
    """One token through every layer's weight products outside the routed
    experts, and the convolutions' taps: the mixers' projections, the dense
    leading feed-forwards, the routers."""
    d = cfg["hidden_size"]
    n_conv, n_attn = mixers(cfg)
    conv = 2 * (3 * d * d + d * d) + 2 * cfg["conv_L_cache"] * d
    attn = 2 * (attention_mixer_params(cfg) - 2 * cfg["head_dim"])
    return (n_conv * conv + n_attn * attn
            + cfg["num_dense_layers"] * 3 * 2 * d * cfg["intermediate_size"]
            + _sparse_layers(cfg) * 2 * d * cfg["num_experts"])


def decode_step_flops(cfg: dict, rows: int, context_tokens: float,
                      moe_assignments: float) -> float:
    """One decode step: *rows* live rows attending *context_tokens* positions
    in all (in the attention layers), *moe_assignments* rows through an
    expert summed over the expert layers, logits for every row."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attn = mixers(cfg)[1] * 2 * 2 * h * hd * context_tokens
    return (rows * token_flops(cfg) + attn + moe_assignments * expert_flops(cfg)
            + rows * 2 * cfg["hidden_size"] * cfg["vocab_size"])


def prefill_flops(cfg: dict, tokens: int, start: int, moe_assignments: float,
                  *, head: bool) -> float:
    """One prefill chunk of *tokens* real tokens at positions ``start + [0,
    tokens)`` (causal); *head*: the chunk that samples computes one row of
    logits."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attended = tokens * start + tokens * (tokens + 1) / 2
    return (tokens * token_flops(cfg) + mixers(cfg)[1] * 2 * 2 * h * hd * attended
            + moe_assignments * expert_flops(cfg)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def paged_attention_call(cfg: dict, rows: int, context_tokens: float,
                         itemsize: int = 2) -> dict:
    """Decode attention in ONE attention layer: the K and V of every
    attended position read once, the queries in and the outputs out."""
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {"flops": 2 * 2 * h * hd * context_tokens,
            "bytes": 2 * kv * hd * itemsize * context_tokens + 2 * rows * h * hd * itemsize}


def conv_call(cfg: dict, tokens: float, rows: int, itemsize: int = 2) -> dict:
    """The convolution itself in ONE layer over *tokens* tokens in *rows*
    rows: L taps a lane; z in, v out, each row's state read and written."""
    d, width = cfg["hidden_size"], cfg["conv_L_cache"]
    return {"flops": 2 * width * d * tokens,
            "bytes": (2 * tokens + 2 * rows * (width - 1)) * d * itemsize}


def decode_stream_bytes(cfg: dict, rows: int, context_tokens: float,
                        experts_touched: float, itemsize: int = 2) -> float:
    """The least a decode step has to read: the weights of every expert a row
    landed on (*experts_touched*, summed over the expert layers), every other
    weight once (the mixers, the dense layers, the routers, the norms, the
    embedding as the head), the K/V of the attended positions, and the live
    rows' state read and written. A lower bound: activations, the logits and
    whatever an implementation reads twice are left out."""
    outside = param_count(cfg) - (_sparse_layers(cfg) * cfg["num_experts"]
                                  * expert_params(cfg))
    return (experts_touched * expert_bytes(cfg, itemsize) + outside * itemsize
            + kv_bytes_per_token(cfg, itemsize) * context_tokens
            + 2 * rows * slot_state_bytes(cfg, itemsize))
