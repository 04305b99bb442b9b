"""Operations and bytes of the ``ssm-moe`` family from shapes: the layers that
are run (the first ``num_hidden_layers`` letters of ``hybrid_override_pattern``),
the experts HELD (``n_routed_experts`` of ``router_outputs``), the vocabulary
rows held, the head untied. Conventions as ``counts.py``: a multiply-add is 2
operations; attention at the real context and in the attention layers only;
element-wise work (norms, gates, the softmax, the convolution's activation)
is not counted except the state-space recurrence's own and the convolution's
taps.
"""
from __future__ import annotations


def layers(cfg: dict) -> tuple[int, int, int]:
    """(Mamba-2, attention, expert) layers among the layers run."""
    run = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return run.count("M"), run.count("*"), run.count("E")


def _mamba(cfg: dict) -> tuple[int, int, int, int, int]:
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["conv_kernel"])


def mamba_params(cfg: dict) -> int:
    """W_in, the taps and their bias, dt_bias / A_log / D, the gated norm's
    gain, W_out, the layer's norm."""
    d = cfg["hidden_size"]
    h, p, g, n, k = _mamba(cfg)
    inner, conv = h * p, h * p + 2 * g * n
    return d * (inner + conv + h) + (k + 1) * conv + 3 * h + inner + inner * d + d


def attention_params(cfg: dict) -> int:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * h * hd + 2 * d * kv * hd + d


def expert_params(cfg: dict) -> int:
    """One routed expert: TWO matrices of moe_latent_size x moe_intermediate_size."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_outside_params(cfg: dict, router_outputs: int | None = None) -> int:
    """An expert layer without its routed experts: the router over ALL its
    outputs and its selection bias, both latent projections, the shared
    expert, the layer's norm."""
    d, e = cfg["hidden_size"], router_outputs or cfg["router_outputs"]
    return (d * e + e + 2 * d * cfg["moe_latent_size"]
            + 2 * d * cfg["moe_shared_expert_intermediate_size"] + d)


def param_count(cfg: dict) -> int:
    """Every parameter of the model as run."""
    n_m, n_a, n_e = layers(cfg)
    return (n_m * mamba_params(cfg) + n_a * attention_params(cfg)
            + n_e * (expert_layer_outside_params(cfg)
                     + cfg["n_routed_experts"] * expert_params(cfg))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def published_counts(cfg: dict) -> dict:
    """The uncut model's totals from the same per-layer counts: the whole
    ``hybrid_override_pattern``, every expert, the whole vocabulary — and
    what one token touches (top-k experts a layer, one row of the embedding)."""
    sv = cfg["source_values"]
    run = cfg["hybrid_override_pattern"]
    n_m, n_a, n_e = run.count("M"), run.count("*"), run.count("E")
    assert len(run) == sv["num_hidden_layers"]
    outside = (n_m * mamba_params(cfg) + n_a * attention_params(cfg)
               + n_e * expert_layer_outside_params(cfg, sv["n_routed_experts"])
               + 2 * sv["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])
    return {"total": outside + n_e * sv["n_routed_experts"] * expert_params(cfg),
            "active": (outside - sv["vocab_size"] * cfg["hidden_size"]
                       + n_e * cfg["num_experts_per_tok"] * expert_params(cfg))}


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    """One cached token: K and V of every KV head in the attention layers."""
    return layers(cfg)[1] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def ssm_state_bytes(cfg: dict) -> int:
    """One Mamba-2 layer's state of one sequence: H x P x N float32."""
    h, p, _, n, _ = _mamba(cfg)
    return h * p * n * 4


def conv_state_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One Mamba-2 layer's convolution tail: the last K - 1 columns of xBC."""
    h, p, g, n, k = _mamba(cfg)
    return (k - 1) * (h * p + 2 * g * n) * itemsize


def slot_state_bytes(cfg: dict, itemsize: int = 2) -> int:
    """One slot's state: every Mamba-2 layer's state and tail."""
    return layers(cfg)[0] * (ssm_state_bytes(cfg) + conv_state_bytes(cfg, itemsize))


def ssm_update_call(cfg: dict, rows: int) -> dict:
    """The one-token state update in ONE layer over *rows* live rows: per
    state element a decay multiply, an outer-product multiply-add and the
    output's multiply-add (5 operations); the state read and written once."""
    h, p, _, n, _ = _mamba(cfg)
    return {"flops": 5 * h * p * n * rows, "bytes": 2 * ssm_state_bytes(cfg) * rows}


def ssm_scan_call(cfg: dict, tokens: int) -> dict:
    """The recurrence over *tokens* tokens of ONE sequence in ONE layer, in
    the chunked form at ``chunk_size`` L: per chunk and head the L x L score
    block (N deep), its product with x (P wide), the chunk's state (L deep)
    and the carried state's output (N deep) — 2 L (L N + L P + 2 P N) a chunk
    a head; x, B, C, dt in and y out, the state read and written once."""
    h, p, g, n, _ = _mamba(cfg)
    chunk = cfg["chunk_size"]
    chunks = -(-tokens // chunk)
    flops = chunks * h * 2 * chunk * (chunk * n + chunk * p + 2 * p * n)
    bytes_ = tokens * (2 * h * p + 2 * g * n + h) * 2 + 2 * ssm_state_bytes(cfg)
    return {"flops": flops, "bytes": bytes_}


def expert_products(cfg: dict, experts_touched: float, assignments: float,
                    itemsize: int = 2) -> dict:
    """The routed experts' TWO products: each touched expert's weights read
    once, each assigned row through both."""
    return {"flops": assignments * 2 * expert_params(cfg),
            "bytes": experts_touched * expert_params(cfg) * itemsize}


def token_flops(cfg: dict) -> int:
    """One token through every layer's weight products outside the routed
    experts and outside attention's scores: the mixers' projections and the
    recurrence's own update (a token's 5 H P N), the convolutions' taps, the
    routers, the latent projections, the shared experts."""
    d = cfg["hidden_size"]
    h, p, g, n, k = _mamba(cfg)
    inner, conv = h * p, h * p + 2 * g * n
    n_m, n_a, n_e = layers(cfg)
    mamba = 2 * d * (inner + conv + h) + 2 * k * conv + 5 * h * p * n + 2 * inner * d
    attn = 2 * (attention_params(cfg) - d)
    expert = 2 * (d * cfg["router_outputs"] + 2 * d * cfg["moe_latent_size"]
                  + 2 * d * cfg["moe_shared_expert_intermediate_size"])
    return n_m * mamba + n_a * attn + n_e * expert


def decode_step_flops(cfg: dict, rows: int, context_tokens: float,
                      moe_assignments: float) -> float:
    """One decode step: *rows* live rows attending *context_tokens* positions
    in all (in the attention layers), *moe_assignments* rows through a held
    expert summed over the expert layers, logits for every row."""
    hq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attn = layers(cfg)[1] * 2 * 2 * hq * hd * context_tokens
    return (rows * token_flops(cfg) + attn + moe_assignments * 2 * expert_params(cfg)
            + rows * 2 * cfg["hidden_size"] * cfg["vocab_size"])


def prefill_flops(cfg: dict, tokens: int, start: int, moe_assignments: float,
                  *, head: bool) -> float:
    """One prefill chunk of *tokens* real tokens at positions ``start + [0,
    tokens)`` (causal); *head*: the chunk that samples computes one row of
    logits. The recurrence is counted in its per-token form (5 H P N a token,
    in ``token_flops``): the chunked form's extra products are the
    implementation's, not the model's."""
    hq, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attended = tokens * start + tokens * (tokens + 1) / 2
    return (tokens * token_flops(cfg) + layers(cfg)[1] * 2 * 2 * hq * hd * attended
            + moe_assignments * 2 * expert_params(cfg)
            + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0))


def decode_stream_bytes(cfg: dict, rows: int, context_tokens: float,
                        experts_touched: float, itemsize: int = 2) -> float:
    """The least a decode step has to read and write: the weights of every
    held expert a row landed on (*experts_touched*, summed over the expert
    layers), every other weight once but the embedding (a step gathers its
    rows' embeddings, it does not stream the table), the K/V of the attended
    positions, and the live rows' state read AND written. A lower bound:
    activations, the logits and whatever an implementation reads twice are
    left out."""
    outside = (param_count(cfg) - layers(cfg)[2] * cfg["n_routed_experts"] * expert_params(cfg)
               - cfg["vocab_size"] * cfg["hidden_size"])
    return (experts_touched * expert_params(cfg) * itemsize + outside * itemsize
            + kv_bytes_per_token(cfg, itemsize) * context_tokens
            + 2 * rows * slot_state_bytes(cfg, itemsize))
