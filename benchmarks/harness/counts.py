"""Operations and bytes from shapes — the benchmark's own arithmetic.

Conventions: a multiply-add is 2 operations; the backward pass of a matrix
product costs twice its forward; recomputation is not counted; the attention
score and value products are counted at the full (non-causal) S x S for an
encoder and at the real context length for a decoder token.
"""
from __future__ import annotations


def _dense_layer_matmul_flops(d: int, heads: int, kv_heads: int, hd: int,
                              ffn: int, mlp_matmuls: int) -> int:
    """Forward operations of one block's weight matmuls, per token."""
    return (2 * d * heads * hd            # q
            + 2 * 2 * d * kv_heads * hd   # k, v
            + 2 * heads * hd * d          # o
            + mlp_matmuls * 2 * d * ffn)


def bert_forward_flops_per_token(cfg: dict, seq_len: int) -> int:
    d, L, h = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_attention_heads"]
    hd, f, v = d // h, cfg["intermediate_size"], cfg["vocab_size"]
    layer = _dense_layer_matmul_flops(d, h, h, hd, f, 2) + 2 * 2 * h * hd * seq_len
    head = 2 * d * d + 2 * d * v          # mlm transform + tied decode
    return L * layer + head


def bert_train_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward + backward (3 x forward), every position decoded to the
    vocabulary as the program does."""
    return 3 * bert_forward_flops_per_token(cfg, seq_len)


def gqa_forward_flops_per_token(cfg: dict, context: float, *, lm_head: bool) -> float:
    """One token of a dense GQA decoder at *context* attended positions
    (SwiGLU: three MLP matmuls). *lm_head*: whether this token's logits are
    computed (every decode token; the last token of a prompt)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layer = (_dense_layer_matmul_flops(d, h, kv, hd, cfg["intermediate_size"], 3)
             + 2 * 2 * h * hd * context)
    return L * layer + (2 * d * cfg["vocab_size"] if lm_head else 0)


def gqa_param_count(cfg: dict) -> int:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * cfg["intermediate_size"] + 2 * d
    return L * layer + 2 * d * cfg["vocab_size"] + d


def paged_attention_call(cfg_or_layer: dict, contexts: list[int], sq: int = 1,
                         itemsize: int = 2) -> dict:
    """One call of paged attention in ONE layer: *contexts* are the attended
    lengths of the live rows (a free slot contributes nothing), *sq* query
    tokens per row. Bytes: the K and V of every attended position (kv heads x
    head size each), read once, plus the queries in and the outputs out —
    what the live contexts need, whatever implements the read. Operations:
    scores and the value product for every query head."""
    h, kv, hd = (cfg_or_layer["num_attention_heads"],
                 cfg_or_layer["num_key_value_heads"], cfg_or_layer["head_dim"])
    ctx = float(sum(contexts))
    rows = len([c for c in contexts if c > 0])
    flops = 2 * 2 * h * hd * sq * ctx
    bytes_ = 2 * kv * hd * itemsize * ctx + 2 * rows * sq * h * hd * itemsize
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak binds."""
    t_f = flops / peaks["bf16_flops"]
    t_b = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
