"""Parameters, operations and bytes of the ``window-moe`` family from shapes —
THIS CHIP'S work: the layers run (the first ``num_hidden_layers`` of
``layer_types`` / ``mlp_layer_types``), the experts held here (those a pick
landed on), the vocabulary slice, attention, the routers and the shared
experts whole. Conventions as ``counts.py``: a multiply-add is 2 operations;
attention at the real context, banded in the sliding layers; element-wise
work (norms, gates, the softmax) is not counted.
"""
from __future__ import annotations


def mixers(cfg: dict, layers: int | None = None) -> tuple[int, int]:
    """(sliding layers, global layers) among the first *layers* (default:
    those run)."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"] if layers is None else layers]
    return kinds.count("sliding_attention"), kinds.count("full_attention")


def feed_forwards(cfg: dict, layers: int | None = None) -> tuple[int, int]:
    """(dense layers, expert layers) among the first *layers*."""
    kinds = cfg["mlp_layer_types"][:cfg["num_hidden_layers"] if layers is None else layers]
    return kinds.count("dense"), kinds.count("sparse")


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_o and the two per-head norm gains."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd + 2 * hd


def expert_params(cfg: dict) -> int:
    """One routed expert, or the shared one: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_flops(cfg: dict) -> int:
    return 2 * expert_params(cfg)


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    return expert_params(cfg) * itemsize


def router_params(cfg: dict) -> int:
    """The router's product over all its outputs and the selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["router_outputs"]


def dense_layer_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return attention_params(cfg) + 3 * d * cfg["intermediate_size"] + 2 * d


def expert_layer_params(cfg: dict, experts: int | None = None) -> int:
    """One expert layer holding *experts* routed experts (default: those
    held here), the shared ones beside them."""
    e = cfg["num_experts"] if experts is None else experts
    return (attention_params(cfg) + router_params(cfg)
            + (e + cfg["num_shared_experts"]) * expert_params(cfg) + 2 * cfg["hidden_size"])


def param_count(cfg: dict) -> int:
    """Every parameter this chip holds: the layers run, the vocabulary slice
    of the embedding and of the untied head, the final norm."""
    dense, sparse = feed_forwards(cfg)
    d = cfg["hidden_size"]
    return (dense * dense_layer_params(cfg) + sparse * expert_layer_params(cfg)
            + 2 * cfg["vocab_size"] * d + d)


def published_params(cfg: dict) -> tuple[int, int]:
    """(all, active a token) of the whole published model: every layer of
    ``source_values``' depth, all ``num_experts_published`` experts, the whole
    vocabulary; a token is active in the top-k and the shared experts."""
    n = cfg["source_values"]["num_hidden_layers"]
    dense, sparse = feed_forwards(cfg, n)
    d, v = cfg["hidden_size"], cfg["source_values"]["vocab_size"]
    outer = 2 * v * d + d
    every = (dense * dense_layer_params(cfg)
             + sparse * expert_layer_params(cfg, cfg["num_experts_published"]) + outer)
    active = (dense * dense_layer_params(cfg)
              + sparse * expert_layer_params(cfg, cfg["num_experts_per_tok"]) + outer)
    return every, active


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One token on the page pool: K and V of every KV head in the GLOBAL
    layers (the sliding layers keep rings)."""
    return mixers(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ring_bytes_per_slot(cfg: dict, itemsize: int = 2) -> int:
    """One slot's rings: ``sliding_window`` positions of K and V in every
    sliding layer."""
    return (mixers(cfg)[0] * 2 * cfg["sliding_window"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def token_flops(cfg: dict) -> int:
    """One token through every layer's weight products outside the routed
    experts: the attention projections, the dense layers' feed-forward, the
    routers and the shared experts."""
    d = cfg["hidden_size"]
    dense, sparse = feed_forwards(cfg)
    n = cfg["num_hidden_layers"]
    return (n * 2 * (attention_params(cfg) - 2 * cfg["head_dim"])
            + dense * 3 * 2 * d * cfg["intermediate_size"]
            + sparse * (2 * d * cfg["router_outputs"]
                        + cfg["num_shared_experts"] * expert_flops(cfg)))


def _attention_flops(cfg: dict, pairs: float) -> float:
    """Scores and the value product of *pairs* (query, key) pairs, every
    query head, one layer."""
    return 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def decode_step_flops(cfg: dict, rows: int, context_tokens: float, window_tokens: float,
                      moe_assignments: float) -> float:
    """One decode step: *rows* live rows attending *context_tokens* positions
    in all in the global layers and *window_tokens* ring entries in all in
    each sliding layer, *moe_assignments* rows through a held expert summed
    over the expert layers, logits for every row over the slice."""
    n_slide, n_global = mixers(cfg)
    return (rows * token_flops(cfg)
            + n_global * _attention_flops(cfg, context_tokens)
            + n_slide * _attention_flops(cfg, window_tokens)
            + moe_assignments * expert_flops(cfg)
            + rows * 2 * cfg["hidden_size"] * cfg["vocab_size"])


def prefill_flops(cfg: dict, tokens: int, start: int, window_pairs: float,
                  moe_assignments: float, *, head: bool) -> float:
    """One prefill chunk of *tokens* real tokens at positions ``start + [0,
    tokens)``: causal in the global layers, *window_pairs* (query, key) pairs
    inside the band in each sliding layer; *head*: the chunk that samples
    computes one row of logits."""
    n_slide, n_global = mixers(cfg)
    attended = tokens * start + tokens * (tokens + 1) / 2
    return (tokens * token_flops(cfg)
            + n_global * _attention_flops(cfg, attended)
            + n_slide * _attention_flops(cfg, window_pairs)
            + moe_assignments * expert_flops(cfg)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def decode_stream_bytes(cfg: dict, context_tokens: float, window_tokens: float,
                        experts_touched: float, itemsize: int = 2) -> float:
    """The least a decode step has to read: the weights of every held expert
    a row landed on (*experts_touched*, summed over the expert layers), every
    other weight once (attention, the dense layer, the routers, the shared
    experts, the norms, the head — not the embedding table, of which a step
    reads its rows alone), the global layers' K/V of the attended positions
    and the ring entries the live rows attend (*window_tokens*, in each
    sliding layer). A lower bound: activations, the logits and whatever an
    implementation reads twice are left out."""
    d = cfg["hidden_size"]
    outside = (param_count(cfg) - cfg["vocab_size"] * d
               - feed_forwards(cfg)[1] * cfg["num_experts"] * expert_params(cfg))
    kv_lanes = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return (experts_touched * expert_bytes(cfg, itemsize) + outside * itemsize
            + kv_bytes_per_token(cfg, itemsize) * context_tokens
            + mixers(cfg)[0] * kv_lanes * window_tokens)


def window_attention_call(cfg: dict, tokens: float, pairs: float, itemsize: int = 2) -> dict:
    """Sliding-window attention of prefill chunks in ONE window layer:
    *pairs* (query, key) pairs inside the band (``window_pairs``), every query
    head; the queries in and the outputs out for *tokens* real tokens, and the
    keys and values they read — their own and the ring's ``sliding_window``
    positions before each chunk — once. What the kernel computes beyond the
    band (whole ``[W, W]`` tiles, masked) is not counted."""
    h, kv, hd, w = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], cfg["sliding_window"])
    return {"flops": _attention_flops(cfg, pairs),
            "bytes": itemsize * (2 * tokens * h * hd + 2 * (tokens + w) * kv * hd)}
