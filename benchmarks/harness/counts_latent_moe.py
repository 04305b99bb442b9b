"""Operations and bytes of the ``latent-moe`` family from shapes — THIS
CHIP'S work: the experts held here (those a pick landed on), the vocabulary
slice, attention and the shared expert whole. Conventions as ``counts.py``:
a multiply-add is 2 operations; what an implementation recomputes (the
re-expansion of a prefix's keys and values for every later chunk) is not
counted; attention at the real context.
"""
from __future__ import annotations


def _dims(cfg: dict) -> tuple:
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def expert_flops(cfg: dict) -> int:
    """One token through one routed (or one shared) expert: three products."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg: dict, itemsize: int = 2) -> int:
    """The weights of one routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def layer_token_flops(cfg: dict) -> int:
    """One token through ONE layer's weight products outside the routed
    experts and the feed-forward: W_Q, W_DKV, W_O, and the latent's
    up-projections — per head ``W_UK`` and ``W_UV`` applied to this token's
    latent (expanded, prefill) or to its query and its weighted latent
    (absorbed, decode): ``2 H r (dn + dv)`` either way."""
    d, h, r, dn, dr, dv = _dims(cfg)
    return (2 * d * h * (dn + dr) + 2 * d * (r + dr) + 2 * h * dv * d
            + 2 * h * r * (dn + dv))


def feed_forward_flops(cfg: dict, tokens: float, moe_assignments: float) -> float:
    """All layers' feed-forward for *tokens* tokens: the dense leading
    layers, and per expert layer the router, the shared expert and the
    routed experts' part — *moe_assignments* picks that landed on held
    experts, summed over the expert layers."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    per_token = (dense * 3 * 2 * d * cfg["intermediate_size"]
                 + sparse * (2 * d * cfg["router_outputs"]
                             + cfg["num_shared_experts"] * expert_flops(cfg)))
    return tokens * per_token + moe_assignments * expert_flops(cfg)


def decode_step_flops(cfg: dict, rows: int, context_tokens: float,
                      moe_assignments: float) -> float:
    """One decode step: *rows* live rows attending *context_tokens*
    positions in all (absorbed form: scores over the 576-wide row, values
    over its 512-wide latent), logits for every row over the slice."""
    d, h, r, dn, dr, dv = _dims(cfg)
    L = cfg["num_hidden_layers"]
    attn = 2 * h * ((r + dr) + r) * context_tokens
    return (L * (rows * layer_token_flops(cfg) + attn)
            + feed_forward_flops(cfg, rows, moe_assignments)
            + rows * 2 * d * cfg["vocab_size"])


def prefill_flops(cfg: dict, tokens: int, start: int, moe_assignments: float,
                  *, head: bool) -> float:
    """One prefill chunk of *tokens* real tokens at positions ``start +
    [0, tokens)`` (expanded form: 192-wide keys, 128-wide values, causal);
    *head*: the chunk that samples computes one row of logits."""
    d, h, r, dn, dr, dv = _dims(cfg)
    L = cfg["num_hidden_layers"]
    attended = tokens * start + tokens * (tokens + 1) / 2
    attn = 2 * h * ((dn + dr) + dv) * attended
    return (L * (tokens * layer_token_flops(cfg) + attn)
            + feed_forward_flops(cfg, tokens, moe_assignments)
            + (2 * d * cfg["vocab_size"] if head else 0))


def latent_attention_call(cfg: dict, rows: int, context_tokens: float,
                          itemsize: int = 2) -> dict:
    """Absorbed latent decode attention in ONE layer: every attended
    position's row (latent + rope key: 576 lanes; the layout's pad lanes are
    not needed) read ONCE — it is key and value both — plus the queries in
    (576 a head) and the weighted latents out (512 a head)."""
    d, h, r, dn, dr, dv = _dims(cfg)
    return {"flops": 2 * h * ((r + dr) + r) * context_tokens,
            "bytes": ((r + dr) * itemsize * context_tokens
                      + rows * h * ((r + dr) + r) * itemsize)}


def latent_chunk_attention_call(cfg: dict, tokens: float, attended: float,
                                positions: float, itemsize: int = 2) -> dict:
    """Expanded latent attention of prefill chunks in ONE layer: *attended*
    (query, key) pairs under the causal mask — ``tokens * start + tokens
    (tokens + 1) / 2`` a chunk, as :func:`prefill_flops` has them — through
    192-wide keys and 128-wide values in every head; *positions* cache rows
    read (``start + tokens`` a chunk, 576 lanes each), *tokens* queries in
    and outputs back. The up-projection of the prefix's keys and values,
    which the expanded form repeats for every later chunk, is recomputation
    by this module's convention and is not counted."""
    d, h, r, dn, dr, dv = _dims(cfg)
    return {"flops": 2 * h * ((dn + dr) + dv) * attended,
            "bytes": ((r + dr) * itemsize * positions
                      + tokens * h * ((dn + dr) + dv) * itemsize)}


def expert_products(cfg: dict, experts_touched: float, assignments: float,
                    itemsize: int = 2) -> dict:
    """The routed experts' three products over calls in which
    *experts_touched* (expert, call) pairs had at least one row and
    *assignments* rows were computed: each touched expert's weights read
    once a call, each row in and out."""
    d = cfg["hidden_size"]
    return {"flops": assignments * expert_flops(cfg),
            "bytes": (experts_touched * expert_bytes(cfg, itemsize)
                      + assignments * 2 * d * itemsize)}
