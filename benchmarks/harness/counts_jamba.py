"""Operations and bytes of the ``jamba`` family from shapes: Mamba-1 layers
and multi-query attention layers by ``attn_layer_period`` /
``attn_layer_offset``, a dense SwiGLU in every layer, the head tied to the
embedding. Conventions as ``counts.py``: a multiply-add is 2 operations;
element-wise work (norms, gates, the convolution's activation) is not counted
except the selective scan's own and the convolution's taps.
"""
from __future__ import annotations

from benchmarks.harness import reference_jamba


def layers(cfg: dict) -> tuple[int, int]:
    """(Mamba-1, attention) layers among the layers run."""
    n_a = sum(reference_jamba.is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - n_a, n_a


def mamba_params(cfg: dict) -> int:
    """The Mamba-1 mixer alone: W_in, the taps and their bias, W_x, the
    three inner norms, W_dt and its bias, A_log, D, W_out."""
    d = cfg["hidden_size"]
    e, n, r, k = reference_jamba.mamba_sizes(cfg)
    return (d * 2 * e + (k + 1) * e + e * (r + 2 * n) + (r + 2 * n)
            + r * e + e + e * n + e + e * d)


def attention_params(cfg: dict) -> int:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def param_count(cfg: dict) -> int:
    """Every parameter of the model as run: each layer its mixer, its
    feed-forward and two norms; the tied embedding once; the final norm."""
    n_m, n_a = layers(cfg)
    d = cfg["hidden_size"]
    per = mlp_params(cfg) + 2 * d
    return (n_m * (mamba_params(cfg) + per) + n_a * (attention_params(cfg) + per)
            + cfg["vocab_size"] * d + d)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One cached token: K and V of every KV head in the attention layers."""
    return layers(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def state_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """One slot's state: a Mamba layer's float32 state [N, E] and its
    convolution tail [K - 1, E] in the served type."""
    e, n, _, k = reference_jamba.mamba_sizes(cfg)
    return layers(cfg)[0] * (4 * n * e + (k - 1) * e * itemsize)


def selective_update_call(cfg: dict, rows: int) -> dict:
    """One layer's one-token update over *rows* live rows: the float32 state
    read and written (the bytes bind), 5 operations an element."""
    e, n, _, _ = reference_jamba.mamba_sizes(cfg)
    return {"bytes": rows * 2 * 4 * n * e, "flops": rows * 5 * n * e}


def selective_scan_call(cfg: dict, tokens: int, rows: int = 1) -> dict:
    """One layer's scan over *tokens* tokens of each of *rows* rows, as the
    kernel walks them (a final chunk's whole bucket): u and dt read and y
    written once (float32, [tokens, E] each), B and C read ([tokens, N]),
    the state read and written once and A read; 7 operations a (token,
    channel, state column) — the decay's product, the two of the state, the
    input's, the output's multiply-add."""
    e, n, _, _ = reference_jamba.mamba_sizes(cfg)
    return {"bytes": 4 * rows * (3 * tokens * e + 2 * tokens * n + 2 * n * e) + 4 * n * e,
            "flops": 7 * rows * tokens * n * e}
