"""Mixture-of-Experts layers + expert parallelism.

Absent from the reference (SURVEY.md §2c lists EP as a gap to fill); built
TPU-first with two dispatch mechanisms, both fully static-shaped:

- ``dispatch="index"`` (default): index-based dispatch — position-in-
  expert from k cumsum passes over [T, E] (the same capacity accounting
  the einsum path uses), then k direct scatters build the [E, C, d]
  expert buffers and a pure gather combines. O(T·k·d) memory traffic and
  no sort, replacing the round-3 dense one-hot einsums whose
  dispatch/combine cost T·E·C·d MAC each — at 8 experts that dense path
  burned ~half the layer's FLOPs moving zeros (BENCHMARKS.md r3 MoE
  table: 22-26% MFU vs 47% dense; the index path measures 33-36%).
- ``dispatch="einsum"``: the Switch-style dense one-hot formulation,
  retained as the readable reference both for parity tests and for meshes
  where a contraction lowers better than scatter.
- ``dispatch="ragged"``: DROPLESS grouped-GEMM dispatch (round 5) — tokens
  scatter into one flat buffer sorted by expert (block-aligned ragged
  layout, no per-expert capacity padding) and the expert MLP runs as three
  Pallas grouped matmuls (:mod:`ops.pallas_gmm`) whose per-expert MXU work
  is proportional to REAL tokens. Removes both the ≥20% zero-padding the
  capacity buffers multiply at cf=1.25 and the capacity-overflow drops.
  Batch-parallel via ``shard_mesh`` (the whole dispatch shard_maps over
  the mesh's data/fsdp axes — a Pallas call has no GSPMD rule, so
  unwrapped it would run replicated on every device); the EXPERT axis
  remains the index path's domain (use ``"index"`` with EP — the EP
  dryrun does).

Expert parallelism falls out of the logical-axis system: expert weights carry
the "expert" logical axis -> the rule table maps it to the "expert" mesh axis
-> dispatching tokens (sharded over "data") into expert buffers (sharded over
"expert") makes XLA emit the collective a hand-written MoE framework would
place as NCCL alltoall calls.

Router details: top-k gating with renormalized probabilities, position-in-
expert by cumulative sum (earlier tokens win capacity), overflow tokens pass
through the residual unchanged (standard drop policy), Switch load-balance
aux loss + router z-loss exposed via ``sow("intermediates", ...)``. Both
dispatch mechanisms implement IDENTICAL routing semantics (same keep set:
drops only start once an expert is full, after which both drop everything
later in choice-major order) — asserted by parity tests.

Serving (``decode=True``) is dropless and per-token whatever the config says,
by ONE rule on the rows a call has (:func:`serving_dispatch`): the grouped
kernel from ``GROUPED_MIN_ROWS_PER_EXPERT`` rows an expert or
``GROUPED_MIN_ROW_BLOCKS`` row blocks' worth of rows, every held expert on every
row below. A layer may be ONE CHIP'S SHARE of an expert-parallel
deployment (``experts_held`` / ``expert_offset``): it routes over all the
experts and computes the part its own experts give. The router's conventions
(softmax, or sigmoid scores with a selection-only bias and scaled gates) and
a shared expert are config; :class:`LatentMoELM` is the family with latent
attention and a leading dense layer.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from k8s_distributed_deeplearning_tpu.models.transformer import (
    MLP, Attention, LatentAttention, LatentAttentionConfig, LayerKind, LMHead, Mamba1,
    Mamba1Config, Mamba2, Mamba2Config, ShortConv, Transformer,
    TransformerConfig, default_init, lm_batch_views, lm_forward, param_dense)

Dtype = Any

# One-time latch for the ragged indivisible-batch fallback warning (decode
# path only; training raises). A list so tests can clear it.
_RAGGED_FALLBACK_WARNED: list[bool] = []


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """MoE knobs layered on top of a TransformerConfig.

    ``routing`` picks the assignment policy:

    - ``"topk"``: tokens pick their top-k experts; per-expert capacity
      overflow DROPS tokens to the residual (Switch/GShard policy; needs
      the load-balance aux loss to keep experts even).
    - ``"expert_choice"``: experts pick their top-C tokens (Zhou et al.) —
      every expert runs exactly full (no capacity overflow, no
      load-balance loss needed); the dual trade is that a token may be
      picked by no expert (it passes through the residual) or by several.
    """

    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3
    routing: str = "topk"            # "topk" | "expert_choice"
    dispatch: str = "index"          # "index" | "einsum" | "ragged"
    ragged_block_m: int = 512        # grouped-GEMM row block (see pallas_gmm)
    # The router's conventions. "softmax": probabilities over all experts,
    # the chosen k renormalised. "sigmoid": independent scores; with
    # ``select_bias`` a learned per-expert bias is added FOR THE CHOICE ONLY
    # (aux-loss-free balancing) and the gates are the chosen experts' own
    # scores, renormalised over the chosen k, times ``routed_scale``.
    score_fn: str = "softmax"        # "softmax" | "sigmoid"
    select_bias: bool = False
    routed_scale: float = 1.0
    shared_experts: int = 0          # always-on experts beside the routed
    expert_mlp_dim: int | None = None   # routed/shared width (None: cfg's)
    # The experts' own form. "swiglu": three matrices, silu(W_g x) * W_u x.
    # "relu2": TWO matrices, W_d relu(W_u x)^2, no gate (the Nemotron-H
    # family); the shared expert takes the same activation.
    expert_act: str = "swiglu"       # "swiglu" | "relu2"
    # Latent experts: the routed experts see ``latent_dim`` lanes — one
    # projection down before them (``fc1_latent``) and one up after their
    # gated sum (``fc2_latent``); the router and the shared expert see the
    # whole hidden state. None: the experts see the hidden state itself.
    latent_dim: int | None = None
    # The shared expert's width where it is not ``expert_mlp_dim x
    # shared_experts`` (one shared expert of a width of its own).
    shared_mlp_dim: int | None = None
    # One chip's share of an expert-parallel layer: the router keeps all
    # ``num_experts`` outputs and the top-k over them; this module holds
    # experts [expert_offset, expert_offset + experts_held) and computes
    # their part of the result (plus the shared expert, which every share
    # computes alike). None = all of them.
    experts_held: int | None = None
    expert_offset: int = 0

    def __post_init__(self):
        if self.routing not in ("topk", "expert_choice"):
            raise ValueError(f"routing must be 'topk' or 'expert_choice', "
                             f"got {self.routing!r}")
        if self.dispatch not in ("index", "einsum", "ragged"):
            raise ValueError(f"dispatch must be 'index', 'einsum' or "
                             f"'ragged', got {self.dispatch!r}")
        if self.score_fn not in ("softmax", "sigmoid"):
            raise ValueError(f"score_fn must be 'softmax' or 'sigmoid', "
                             f"got {self.score_fn!r}")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"expert_act must be 'swiglu' or 'relu2', "
                             f"got {self.expert_act!r}")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.held}) are not among the {self.num_experts} routed")
        if self.dispatch == "ragged" and self.routing == "expert_choice":
            raise ValueError(
                "dispatch='ragged' targets top-k routing: expert choice "
                "already runs every expert exactly full (its [E, C, d] "
                "buffers carry no capacity padding), so the grouped GEMM "
                "has nothing to reclaim — use dispatch='index'.")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)


# Rows a held expert can expect (rows x top_k / num_experts) from which the
# serving path takes the grouped kernel: below it every held expert runs on
# every row (no scatter; the weights' read binds either way), above it the
# all-rows form's E x T products cost more than the sort. 32 is where the
# old `t >= 128` rule sat for the config it was measured on (8 experts,
# top-2: grouped 4.2k vs 3.8k tok/s below it, round 5). Measured on a v5e
# since: at ~2 rows an expert (32 held experts of 128, top-8, 32 rows: a
# decode step) dense = grouped; at ~64 (a 1,024-token chunk of the same)
# grouped (PR 27); at 16 rows an expert (32 experts, top-4, 128 rows: a decode
# step at 2048 x 1792) dense 29.6 ms a step against grouped 31.5 (PR 31: the
# kernel's row block is 128, so 16 rows are padded to the same products the
# dense form does, plus the sort and the gather). The constant stays.
GROUPED_MIN_ROWS_PER_EXPERT = 32
# What the rows an expert alone cannot see: the all-rows form computes every
# ROW of the call in every held expert, the grouped form one row block of the
# kernel (``ragged_block_m``) an expert at the least. With many experts and a
# wide call the two part ways below 32 rows an expert: 512 rows, top-22 of 512
# (22 rows an expert, 128 held: PR 33) is 128 x 512 row-products a layer in the
# all-rows form — 3.6 TFLOP a chunk, 23.4 ms a chunk program at 95 % of the
# MXU's peak — against 128 blocks of 128 rows; the grouped form served 2,243
# tok/s against 2,193 there. At ONE block's worth of rows (128 rows, block 128:
# PR 31's decode step above) the all-rows form wins. So, below the constant
# above, the grouped kernel from this many row blocks' worth of rows.
GROUPED_MIN_ROW_BLOCKS = 4


def serving_dispatch(rows: int, moe: "MoEConfig") -> str:
    """The serving (``decode=True``) dispatch of a call with *rows* tokens,
    from what the call can see: ``"grouped"`` (dropless grouped matmul,
    :mod:`ops.pallas_gmm` — a ``dispatch="ragged"`` config at enough rows an
    expert, or at enough rows against the kernel's row block) or ``"dense"``
    (every held expert on every row, gated)."""
    if moe.dispatch != "ragged":
        return "dense"
    per_expert = rows * moe.top_k / moe.num_experts
    return ("grouped" if per_expert >= GROUPED_MIN_ROWS_PER_EXPERT
            or rows >= GROUPED_MIN_ROW_BLOCKS * moe.ragged_block_m else "dense")


def grouped_row_blocks(counts: np.ndarray, rows: int,
                       moe: "MoEConfig") -> tuple[int, int]:
    """On the host, the row blocks of a grouped call of *rows* tokens whose
    expert layers counted *counts* (``[layers x held]`` picks a held expert,
    flat or not): every block its layouts hold, ``m_pad / block`` a layer
    (:func:`ops.pallas_gmm.padded_rows`), and the live ones among them,
    ``ceil(count / block)`` a held expert — the steps whose row and weight
    blocks the grouped products read; the others are parked."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    bm = _row_block(rows, moe.top_k, moe)
    per_layer = np.asarray(counts).reshape(-1, moe.held)
    laid_out = pallas_gmm.padded_rows(rows * moe.top_k, moe.held, bm) // bm
    return (per_layer.shape[0] * laid_out,
            int((-(-per_layer // bm)).sum()))


def clamped_capacity(tokens: int, moe: "MoEConfig") -> int:
    """Per-expert buffer capacity: capacity_factor·k·T/E, int-floored,
    clamped to [1, T]. THE single formula — MoEMLP sizes its buffers with
    it and :func:`flops_per_token` derives exact active slots from it
    (capacity_factor*top_k > num_experts would otherwise push raw capacity
    past T: expert choice's top_k over the token axis would be ill-formed,
    and topk slots beyond T can never fill)."""
    return min(tokens, max(1, int(moe.capacity_factor * moe.top_k
                                  * tokens / moe.num_experts)))


def _topk_assignments(logits: jax.Array, k: int,
                      moe: "MoEConfig | None" = None,
                      bias: jax.Array | None = None):
    """Greedy top-k expert choices shared by every dispatch mechanism.

    Returns (scores [T, E] f32, idx list of k [T] int32 expert picks,
    assign list of k one-hot [T, E], gate_stack [k, T] renormalized).
    *moe* gives the router's conventions (None: softmax, no bias, scale 1);
    *bias* [E] moves the CHOICE and never the gate."""
    t, e = logits.shape
    sigmoid = moe is not None and moe.score_fn == "sigmoid"
    scores = (jax.nn.sigmoid if sigmoid else
              functools.partial(jax.nn.softmax, axis=-1))(
                  logits.astype(jnp.float32))

    remaining = scores if bias is None else scores + bias.astype(jnp.float32)
    idx_list = []   # k [T] argmax picks
    assign = []     # k one-hot [T, E] masks
    gates = []      # k [T] gate values
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        one_hot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        idx_list.append(idx.astype(jnp.int32))
        assign.append(one_hot)
        gates.append(jnp.sum(scores * one_hot, axis=-1))
        remaining = jnp.where(one_hot > 0, -jnp.inf, remaining)

    # Renormalize the k gates per token.
    gate_stack = jnp.stack(gates, axis=0)                     # [k, T]
    gate_stack = gate_stack / jnp.maximum(
        jnp.sum(gate_stack, axis=0, keepdims=True), 1e-9)
    if moe is not None and moe.routed_scale != 1.0:
        gate_stack = gate_stack * moe.routed_scale
    return scores, idx_list, assign, gate_stack


# Above this many choices the serving dispatches take the router's choice in
# ONE pass (:func:`_topk_one_pass`) and keep it as ``[T, k]`` arrays: the
# k-fold loop of :func:`_topk_assignments` and the k-fold loops behind it
# (one-hots, cumsums, scatters, gathers) are ~15 small device operations a
# choice and layer — written for k <= 8, and 22 choices of 512 experts make
# them thousands of operations a program. The k <= 8 branch is untouched.
ONE_PASS_TOPK_ABOVE = 8


def _topk_one_pass(logits: jax.Array, k: int, moe: "MoEConfig",
                   bias: jax.Array | None = None):
    """The choices of :func:`_topk_assignments` — the same experts in the same
    order (``lax.top_k`` breaks a tie for the lower index, as the loop's
    argmax does) and the same renormalised, scaled gates — from one sort:
    (scores [T, E] f32, idx [T, k] int32, gates [T, k] f32)."""
    sigmoid = moe.score_fn == "sigmoid"
    scores = (jax.nn.sigmoid if sigmoid else
              functools.partial(jax.nn.softmax, axis=-1))(
                  logits.astype(jnp.float32))
    select = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(select, k)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    gates = picked / jnp.maximum(jnp.sum(picked, axis=1, keepdims=True), 1e-9)
    if moe.routed_scale != 1.0:
        gates = gates * moe.routed_scale
    return scores, idx.astype(jnp.int32), gates


def _row_block(t: int, k: int, moe: "MoEConfig") -> int:
    """The grouped layout's row block for a call of *t* tokens: the
    configured one, clipped to the call's width — at decode steps (t = B) the
    configured 512 block would pad 16 real rows to 4.6k (one mostly-dead block
    per expert) and measure 2.2x SLOWER than the capacity path; a t*k-sized
    block keeps m_pad ~ (E+1)*t*k."""
    return min(moe.ragged_block_m, max(8, 1 << (t * k - 1).bit_length()))


def _experts_hidden(gmm, xs, w_gate, w_up):
    """The grouped experts' hidden activation for training: gated
    (``silu(W_g x) * W_u x``) or, with no gate matrix, squared ReLU — each
    product a differentiable ``gmm`` of its own, the activation XLA's."""
    if w_gate is None:
        return jnp.square(nn.relu(gmm(xs, w_up)))
    return nn.silu(gmm(xs, w_gate)) * gmm(xs, w_up)


def _serving_hidden(moe: "MoEConfig", xs, w_gate, w_up, layout):
    """The same activation for a serving program: ONE grouped product whose
    epilogue applies it (``pallas_gmm.gmm_act``), so no ``[m_pad, F]``
    product goes to HBM and back before the activation. Forward only."""
    from k8s_distributed_deeplearning_tpu.ops import pallas_gmm
    weights = (w_up,) if w_gate is None else (w_gate, w_up)
    return pallas_gmm.gmm_act(xs, weights, layout, moe.expert_act)


def _z_loss(logits: jax.Array) -> jax.Array:
    """Router z-loss (one definition for every routing/dispatch path)."""
    return jnp.mean(jnp.square(jax.nn.logsumexp(
        logits.astype(jnp.float32), axis=-1)))


def _router_aux(logits: jax.Array, probs: jax.Array,
                assign0: jax.Array) -> dict:
    """Switch load-balance loss + router z-loss (shared by both paths)."""
    e = logits.shape[1]
    return {
        "load_balance_loss": e * jnp.sum(jnp.mean(assign0, axis=0)
                                         * jnp.mean(probs, axis=0)),
        "router_z_loss": _z_loss(logits),
    }


def _ragged_aux(f: jax.Array, p: jax.Array, z: jax.Array) -> dict:
    """Final aux dict from (possibly batch-pmean'd) routing statistics:
    f = mean first-choice assignment [E], p = mean router probs [E],
    z = mean router z-loss. Dropless ⇒ fraction_dropped is exactly 0."""
    e = f.shape[0]
    return {"load_balance_loss": e * jnp.sum(f * p),
            "router_z_loss": z,
            "fraction_dropped": jnp.zeros((), jnp.float32)}


def _expert_choice_picks(logits: jax.Array, capacity: int):
    """Expert-choice selection shared by both dispatch paths: each expert
    takes its top-``capacity`` tokens by softmax affinity. Returns
    (gates [E, C] f32, idx [E, C] int32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jax.lax.top_k(probs.T, capacity)


def top_k_dispatch_indices(logits: jax.Array, k: int, capacity: int,
                           moe: "MoEConfig | None" = None,
                           bias: jax.Array | None = None):
    """Index-based top-k routing: the same keep set as :func:`top_k_routing`
    (identical cumsum capacity accounting — choice 0 takes priority, then
    token order) expressed as direct scatter/gather indices instead of
    [T, E, C] one-hots. Costs k cumsum passes over [T, E] — no sort, no
    slot one-hot, no dense dispatch/combine contraction.

    Returns (dest [k, T] int32 flat E*C buffer destination per choice
    (== E*C sentinel when dropped), gate [k, T] f32 renormalized gates,
    keep [k, T] bool, aux dict). All shapes static.
    """
    t, e = logits.shape
    probs, idx_list, assign, gate_stack = _topk_assignments(logits, k, moe,
                                                            bias)

    used = jnp.zeros((e,), jnp.float32)       # kept slots from earlier choices
    dests, keeps = [], []
    for c in range(k):
        one_hot = assign[c]                                   # [T, E]
        pos = jnp.cumsum(one_hot, axis=0) - one_hot + used    # [T, E]
        keep_m = one_hot * (pos < capacity)
        used = used + jnp.sum(keep_m, axis=0)
        pos_t = jnp.sum(pos * one_hot, axis=-1).astype(jnp.int32)  # [T]
        kept = jnp.sum(keep_m, axis=-1) > 0                        # [T]
        dests.append(jnp.where(kept, idx_list[c] * capacity + pos_t,
                               e * capacity))
        keeps.append(kept)
    dest, keep = jnp.stack(dests), jnp.stack(keeps)

    aux = dict(_router_aux(logits, probs, assign[0]),
               fraction_dropped=1.0 - jnp.mean(keep.astype(jnp.float32)))
    return dest, gate_stack, keep, aux


def top_k_routing(logits: jax.Array, k: int, capacity: int):
    """Static-shape top-k routing (dense one-hot formulation).

    logits: [T, E] router scores. Returns (dispatch [T, E, C] bool,
    combine [T, E, C] f32, aux_metrics dict). Token t's c-th capacity slot in
    expert e is set when t routed there and fewer than C earlier tokens did.
    """
    t, e = logits.shape
    probs, _, assign, gate_stack = _topk_assignments(logits, k)

    dispatch = jnp.zeros((t, e, capacity), jnp.bool_)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # Choice 0 for all tokens takes capacity priority over choice 1, then
    # token order breaks ties (cumsum over T).
    used = jnp.zeros((e,), jnp.float32)                       # slots taken so far
    for c in range(k):
        one_hot = assign[c]                                   # [T, E]
        pos = jnp.cumsum(one_hot, axis=0) - one_hot + used    # [T, E] slot index
        keep = one_hot * (pos < capacity)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32)              # [T, E, C]
        sel = keep[..., None] * slot
        dispatch = dispatch | (sel > 0)
        combine = combine + gate_stack[c][:, None, None] * sel
        used = used + jnp.sum(keep, axis=0)

    # Switch load-balance loss: E * Σ_e fraction_tokens_e · mean_prob_e.
    f = jnp.mean(assign[0], axis=0)
    p = jnp.mean(probs, axis=0)
    aux = {
        "load_balance_loss": e * jnp.sum(f * p),
        "router_z_loss": jnp.mean(
            jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1))),
        "fraction_dropped": 1.0 - jnp.sum(combine > 0) / (t * k),
    }
    return dispatch, combine, aux


def expert_choice_routing(logits: jax.Array, capacity: int):
    """Expert-choice routing (static shapes, no drops from overflow).

    logits: [T, E] router scores. Each expert takes its top-``capacity``
    tokens by affinity — ``lax.top_k`` over the token axis — so utilization
    is 100% by construction and no load-balance loss is needed. Returns the
    same (dispatch [T, E, C] bool, combine [T, E, C] f32, aux) contract as
    :func:`top_k_routing`; ``fraction_dropped`` reports tokens NO expert
    picked (they ride the residual unchanged — the scheme's dual trade).
    """
    t, e = logits.shape
    gates, idx = _expert_choice_picks(logits, capacity)           # [E, C]
    sel = jax.nn.one_hot(idx, t, dtype=jnp.float32)               # [E, C, T]
    dispatch = sel.transpose(2, 0, 1) > 0                         # [T, E, C]
    combine = sel.transpose(2, 0, 1) * gates[None]                # [T, E, C]
    covered = jnp.clip(jnp.sum(dispatch, axis=(1, 2)), 0, 1)      # [T]
    aux = {
        "router_z_loss": _z_loss(logits),
        "fraction_dropped": 1.0 - jnp.mean(covered),
    }
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Expert-parallel SwiGLU MLP with top-k or expert-choice routing.

    Expert weights are [E, ...] with the "expert" logical axis; the
    dispatch/combine (index/scatter by default, dense one-hot einsums
    with ``dispatch="einsum"``) bridges token-sharding to expert-sharding
    (XLA inserts the collective when the mesh has an expert axis).
    """

    cfg: TransformerConfig
    moe: MoEConfig
    # Mesh for shard_mapping the ragged dispatch over batch axes (see
    # _ragged_dispatch). A static module attribute, like Block.attention_fn.
    shard_mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, decode: bool = False) -> jax.Array:
        cfg, moe = self.cfg, self.moe
        b, s, d = x.shape
        mlp = moe.expert_mlp_dim or cfg.resolved_mlp_dim
        e, held = moe.num_experts, moe.held
        tokens = x.reshape(b * s, d)
        t = b * s
        capacity = clamped_capacity(t, moe)

        router_w = self.param(
            "router", nn.with_logical_partitioning(default_init(),
                                                   ("embed", "expert")),
            (d, e), jnp.float32)
        # float32 in earnest: on the TPU an f32 product runs in bf16 passes
        # unless asked otherwise, and a near-tie among the top-k would flip.
        logits = jnp.dot(tokens.astype(jnp.float32),
                         router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        bias = None
        if moe.select_bias:
            bias = self.param(
                "router_bias", nn.with_logical_partitioning(
                    nn.initializers.zeros, ("expert",)), (e,), jnp.float32)

        def expert_param(name, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(default_init(), axes),
                shape, jnp.float32).astype(cfg.dtype)

        gated = moe.expert_act == "swiglu"
        # what the routed experts see: the hidden state, or its latent
        lat = moe.latent_dim or d
        if moe.latent_dim is not None:
            if not (decode or held != e):
                raise NotImplementedError(
                    "latent experts (latent_dim) are a serving layout: the "
                    "capacity dispatches are not written for them")
            tokens_e = param_dense(lat, ("embed", None), "fc1_latent",
                                   cfg.dtype)(x).reshape(t, lat)
        else:
            tokens_e = tokens
        w_gate = (expert_param("w_gate", (held, lat, mlp),
                               ("expert", "embed", "mlp")) if gated else None)
        w_up = expert_param("w_up", (held, lat, mlp), ("expert", "embed", "mlp"))
        w_down = expert_param("w_down", (held, mlp, lat), ("expert", "mlp", "embed"))
        shared = 0.0
        if moe.shared_experts:
            shared = MLP(dataclasses.replace(
                cfg, mlp_dim=moe.shared_mlp_dim or mlp * moe.shared_experts,
                **({} if gated else {"activation": moe.expert_act})),
                name="shared")(x)

        def experts_apply(xe):
            """[E, C, d] expert buffers -> [E, C, d] outputs."""
            xe = nn.with_logical_constraint(xe, ("expert", None, "embed"))
            if gated:
                h = jnp.einsum("ecd,edm->ecm", xe, w_gate)
                h = nn.silu(h) * jnp.einsum("ecd,edm->ecm", xe, w_up)
            else:
                h = jnp.square(nn.relu(jnp.einsum("ecd,edm->ecm", xe, w_up)))
            h = nn.with_logical_constraint(h, ("expert", None, "mlp"))
            ye = jnp.einsum("ecm,emd->ecd", h, w_down)
            return nn.with_logical_constraint(ye, ("expert", None, "embed"))

        if decode or held != e:
            # Serving: DROPLESS per-token top-k, so that a token's output is
            # a function of that token alone and incremental decode matches
            # one-shot prefill whatever the call's width (parity-tested).
            # routing="topk" is FORCED: expert choice's whole-batch token
            # selection has no causal decode semantics (see the MoELM
            # warning). Which mechanism computes it is serving_dispatch's
            # stated rule on the rows a call has. One chip's share of the
            # experts (experts_held) is a serving layout: its plain forward
            # takes this path too, and sows no auxiliary loss.
            if serving_dispatch(t, moe) == "grouped":
                y, _ = self._ragged_dispatch(tokens_e, logits, w_gate, w_up,
                                             w_down, decode=True, bias=bias)
            else:
                y = self._dense_serving(tokens_e, logits, bias, experts_apply)
            y = y.reshape(b, s, lat)
            if moe.latent_dim is not None:
                y = param_dense(d, (None, "embed"), "fc2_latent", cfg.dtype)(y)
            return y + shared
        if moe.dispatch == "ragged":
            y, aux = self._ragged_dispatch(tokens, logits,
                                           w_gate, w_up, w_down, bias=bias)
        elif moe.dispatch == "index":
            y, aux = self._index_dispatch(tokens, logits, capacity,
                                          experts_apply, bias=bias)
        else:
            if bias is not None or moe.score_fn != "softmax":
                raise NotImplementedError(
                    "dispatch='einsum' is the softmax reference path")
            y, aux = self._einsum_dispatch(tokens, logits, capacity,
                                           experts_apply)
        for name, val in aux.items():
            self.sow("intermediates", name, val)
        return y.reshape(b, s, d) + shared

    def _held_picks(self, idx_list, assign):
        """The picks that landed on held experts: per choice the local
        expert index (clipped), whether it is held, and the one-hot over
        the held experts alone; and their count per held expert, sown as
        ``moe_stats/assignments`` for the engine's counters."""
        moe = self.moe
        lo, held = moe.expert_offset, moe.held
        local = [jnp.clip(i - lo, 0, held - 1) for i in idx_list]
        mine = [(i >= lo) & (i < lo + held) for i in idx_list]
        assign_held = [a[:, lo:lo + held] for a in assign]
        counts = functools.reduce(
            lambda a, b: a + b, (jnp.sum(a, axis=0) for a in assign_held))
        self.sow("moe_stats", "assignments", counts.astype(jnp.int32),
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return local, mine, assign_held, counts

    def _held_picks_one_pass(self, idx):
        """:meth:`_held_picks` for choices kept as one array ``idx`` [..., k]
        (:func:`_topk_one_pass`): (one-hot over the HELD experts
        [..., k, held] — all zero where the pick is held elsewhere —, whether
        it is held, the local index clipped, the count per held expert, sown
        as ``moe_stats/assignments``)."""
        moe = self.moe
        lo, held = moe.expert_offset, moe.held
        mine = (idx >= lo) & (idx < lo + held)
        local = jnp.clip(idx - lo, 0, held - 1)
        one_hot = jax.nn.one_hot(jnp.where(mine, local, held), held,
                                 dtype=jnp.float32)
        counts = jnp.sum(one_hot.reshape(-1, held), axis=0)
        self.sow("moe_stats", "assignments", counts.astype(jnp.int32),
                 reduce_fn=lambda _, new: new, init_fn=lambda: None)
        return one_hot, mine, local, counts

    def _dense_serving(self, tokens, logits, bias, experts_apply):
        """Every held expert on every row, gated: ``y_t = Σ_e w[t, e]
        E_e(x_t)`` with ``w`` the gate where token t chose held expert e and
        0 elsewhere. No sort, no scatter — at a few rows an expert the
        products are bound by reading each expert's weights, which this
        reads once."""
        cfg, moe = self.cfg, self.moe
        if moe.top_k > ONE_PASS_TOPK_ABOVE:
            _, idx, gates = _topk_one_pass(logits, moe.top_k, moe, bias)
            one_hot, _, _, _ = self._held_picks_one_pass(idx)
            w = jnp.einsum("tk,tke->te", gates, one_hot)
        else:
            _, idx_list, assign, gate_stack = _topk_assignments(
                logits, moe.top_k, moe, bias)
            _, _, assign_held, _ = self._held_picks(idx_list, assign)
            w = sum(a * g[:, None] for a, g in zip(assign_held, gate_stack))
        tok_c = tokens.astype(cfg.dtype)
        ye = experts_apply(jnp.broadcast_to(
            tok_c[None], (moe.held,) + tok_c.shape))            # [E, T, d]
        return jnp.einsum("etd,te->td", ye.astype(jnp.float32), w
                          ).astype(cfg.dtype)   # gates and their sum in f32

    def _einsum_dispatch(self, tokens, logits, capacity, experts_apply):
        """Dense one-hot dispatch/combine (Switch-style reference path)."""
        cfg, moe = self.cfg, self.moe
        if moe.routing == "expert_choice":
            dispatch, combine, aux = expert_choice_routing(logits, capacity)
        else:
            dispatch, combine, aux = top_k_routing(logits, moe.top_k,
                                                   capacity)
        # Dispatch: [T,d] tokens -> [E,C,d] expert buffers (the all-to-all).
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(cfg.dtype),
                        tokens.astype(cfg.dtype))
        ye = experts_apply(xe)
        # Combine back to token order, weighted by the gates.
        y = jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), ye)
        return y, aux

    def _index_dispatch(self, tokens, logits, capacity, experts_apply,
                        bias=None):
        """Index-based scatter/gather dispatch — O(T·k·d) data movement
        instead of the dense path's T·E·C·d dispatch/combine MACs,
        identical routing semantics (parity-tested)."""
        cfg, moe = self.cfg, self.moe
        t, d = tokens.shape
        e = moe.num_experts
        tok_c = tokens.astype(cfg.dtype)

        if moe.routing == "expert_choice":
            gates, idx = _expert_choice_picks(logits, capacity)   # [E, C]
            sel = idx.reshape(-1)
            xe = jnp.take(tok_c, sel, axis=0).reshape(e, capacity, d)
            ye = experts_apply(xe)
            y = jnp.zeros((t, d), cfg.dtype).at[sel].add(
                gates.reshape(-1)[:, None].astype(cfg.dtype)
                * ye.reshape(e * capacity, d))
            covered = jnp.zeros((t,), jnp.float32).at[sel].max(1.0)
            aux = {
                "router_z_loss": _z_loss(logits),
                "fraction_dropped": 1.0 - jnp.mean(covered),
            }
            return y, aux

        dest, gate, keep, aux = top_k_dispatch_indices(
            logits, moe.top_k, capacity, moe, bias)
        # Scatter tokens into [E*C, d] buffers, one scatter per choice (the
        # operand is `tokens` in place — no gather needed); dropped slots
        # carry the out-of-range sentinel and fall away via mode="drop".
        # Slots are unique by construction (one assignment per (e, pos)).
        xe = jnp.zeros((e * capacity, d), cfg.dtype)
        for c in range(moe.top_k):
            xe = xe.at[dest[c]].add(tok_c, mode="drop")
        ye = experts_apply(xe.reshape(e, capacity, d)).reshape(
            e * capacity, d)
        # Combine is a pure gather: dest[c] is already token-indexed.
        y = jnp.zeros((t, d), cfg.dtype)
        for c in range(moe.top_k):
            w = (keep[c] * gate[c])[:, None].astype(cfg.dtype)
            y = y + jnp.take(ye, jnp.minimum(dest[c], e * capacity - 1),
                             axis=0) * w
        return y, aux

    def _ragged_dispatch(self, tokens, logits, w_gate, w_up, w_down,
                         decode=False, bias=None):
        """Dropless grouped-GEMM dispatch (``ops.pallas_gmm``): tokens
        scatter into one flat [M_pad, d] buffer sorted by expert
        (block-aligned ragged layout — the SAME cumsum position accounting
        as the capacity paths, just with per-expert ragged offsets instead
        of a fixed-capacity clamp) and the expert SwiGLU runs as three
        grouped matmuls whose MXU work tracks real token counts. No
        capacity ⇒ no overflow drops and no zero-padding compute.

        With ``shard_mesh`` set, the whole dispatch shard_maps over the
        mesh's batch axes (data × fsdp): a Pallas call has no GSPMD
        partitioning rule, so without the wrap every device all-gathers
        the batch and runs ALL the expert compute (verified in the
        compiled HLO — same hole the mesh attention fn closes). Dropless
        routing is strictly per-token, so shard-local dispatch is EXACT:
        only the position-in-buffer differs, never any token's output.
        Router aux losses pmean over the batch axes (equal shards ⇒ the
        global batch mean). Expert weights stay replicated inside the
        wrap — the expert axis remains the index path's domain."""
        mesh = self.shard_mesh
        if mesh is not None and w_gate is None:
            raise NotImplementedError(
                "shard_mesh wraps the gated (swiglu) experts' three operands")
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            # "sequence" belongs in the row partition too: the flattened
            # [b*s, d] token dim is sharded (data, fsdp) on b (major) and
            # sequence on s (minor) — exactly this axis product — and
            # per-token dispatch makes sequence-local dispatch as exact
            # as batch-local. Without it a CP mesh would all-gather the
            # sequence shards into the grouped GEMM (review catch).
            batch_axes = tuple(a for a in ("data", "fsdp", "sequence")
                               if sizes.get(a, 1) > 1)
            bfac = 1
            for a in batch_axes:
                bfac *= sizes[a]
            if batch_axes and tokens.shape[0] % bfac == 0:
                from jax.sharding import PartitionSpec as P
                bspec, rep = P(batch_axes), P()

                def inner(tk, lg, wg, wu, wd):
                    y, (f, p, z) = self._ragged_core(tk, lg, wg, wu, wd,
                                                     bias, decode)
                    # pmean the ROUTING STATISTICS, not per-shard losses:
                    # the load-balance loss is E·Σ_e f̄_e·p̄_e of GLOBAL
                    # means — averaging per-shard Σ f·p would differ
                    # (mean of products ≠ product of means) and break
                    # exact parity with the unsharded path.
                    stats = jax.lax.pmean((f, p, z), batch_axes)
                    return y, stats

                y, (f, p, z) = jax.shard_map(
                    inner, mesh=mesh,
                    in_specs=(bspec, bspec, rep, rep, rep),
                    out_specs=(bspec, rep), check_vma=False)(
                    tokens, logits, w_gate, w_up, w_down)
                return y, _ragged_aux(f, p, z)
            if batch_axes:
                # The fallback below runs UNSHARDED: a Pallas call has no
                # GSPMD rule, so every device all-gathers the batch and
                # runs the FULL expert compute — bfac× silent replication.
                # A mis-sized training batch must fail loudly; decode
                # (arbitrary serving widths) warns once and proceeds.
                msg = (f"MoE ragged dispatch: token count {tokens.shape[0]}"
                       f" does not divide the mesh batch factor {bfac} "
                       f"({'×'.join(batch_axes)}) — expert compute will run"
                       " unsharded (replicated on every device). Size the "
                       "batch×sequence product to a multiple of the mesh "
                       "batch axes.")
                if not decode:
                    raise ValueError(msg)
                if not _RAGGED_FALLBACK_WARNED:
                    _RAGGED_FALLBACK_WARNED.append(True)
                    warnings.warn(msg, RuntimeWarning, stacklevel=2)
        y, (f, p, z) = self._ragged_core(tokens, logits, w_gate, w_up,
                                         w_down, bias, decode)
        return y, _ragged_aux(f, p, z)

    def _ragged_core(self, tokens, logits, w_gate, w_up, w_down, bias=None,
                     decode=False):
        from k8s_distributed_deeplearning_tpu.ops import pallas_gmm

        cfg, moe = self.cfg, self.moe
        t, d = tokens.shape
        k = moe.top_k
        tok_c = tokens.astype(cfg.dtype)
        if decode and k > ONE_PASS_TOPK_ABOVE:
            return self._ragged_serving_one_pass(tok_c, logits, w_gate, w_up,
                                                 w_down, bias)

        probs, idx_list, assign, gate_stack = _topk_assignments(
            logits, k, moe, bias)
        # Rows exist for picks that landed on HELD experts (all of them
        # unless this module is one chip's share); the others' destination is
        # the out-of-range sentinel, dropped by the scatter and gated to 0.
        local, mine, assign_held, counts = self._held_picks(idx_list, assign)
        layout = pallas_gmm.grouped_layout(
            counts.astype(jnp.int32), t * k, block_m=_row_block(t, k, moe))

        used = jnp.zeros((moe.held,), jnp.float32)
        dests = []
        for c in range(k):
            one_hot = assign_held[c]                              # [T, held]
            pos = jnp.cumsum(one_hot, axis=0) - one_hot + used
            used = used + jnp.sum(one_hot, axis=0)
            pos_t = jnp.sum(pos * one_hot, axis=-1).astype(jnp.int32)
            dests.append(jnp.where(mine[c],
                                   layout.row_offset[local[c]] + pos_t,
                                   layout.m_pad))

        # Destinations are unique across tokens AND choices (one row per
        # (expert, position); a share's picks on experts held elsewhere all
        # carry the sentinel and are dropped). Padding rows stay zero (the
        # gmm contract relies on this).
        if decode:
            # Serving: the buffer is ONE gather — each row's source token,
            # scattered as int32 (k·T numbers, not k passes over the
            # [M_pad, d] buffer: on the chip those k scatter-adds were 10 ms
            # of a 1,024-token chunk, PR 27), then the rows themselves.
            src = jnp.full((layout.m_pad,), -1, jnp.int32)
            for c in range(k):
                src = src.at[dests[c]].set(jnp.arange(t, dtype=jnp.int32),
                                           mode="drop")
            xs = jnp.where((src >= 0)[:, None],
                           jnp.take(tok_c, jnp.maximum(src, 0), axis=0), 0)
        else:
            # Training: add ≡ set on unique rows — and add's VJP is just a
            # gather, where set's pays an extra zeroing scatter on the base.
            xs = jnp.zeros((layout.m_pad, d), cfg.dtype)
            for c in range(k):
                xs = xs.at[dests[c]].add(tok_c, mode="drop",
                                         unique_indices=True)
        if decode:
            # Serving: forward-only products, W_down included, at the wide
            # column block (pallas_gmm.wide_block).
            ys = pallas_gmm.gmm_forward(
                _serving_hidden(moe, xs, w_gate, w_up, layout), w_down, layout)
        else:
            # checkpoint_name: a Pallas call is not a dot XLA's remat policy
            # can match, so without the tag remat policies that save matmul
            # outputs would recompute all three grouped GEMMs in the backward
            # (see REMAT_POLICIES in models/transformer.py).
            from jax.ad_checkpoint import checkpoint_name
            gmm = lambda x, w: checkpoint_name(
                pallas_gmm.gmm(x, w, layout), "gmm_out")
            ys = gmm(_experts_hidden(gmm, xs, w_gate, w_up), w_down)
        # Serving sums the k gated rows in f32, as the dense serving form
        # does (the two serving dispatches agree); training sums in the
        # model's type.
        acc = jnp.float32 if decode else cfg.dtype
        y = jnp.zeros((t, d), acc)
        for c in range(k):
            y = y + (jnp.take(ys, jnp.minimum(dests[c], layout.m_pad - 1),
                              axis=0)
                     * (gate_stack[c] * mine[c])[:, None].astype(acc))
        y = y.astype(cfg.dtype)
        # Raw routing statistics, not losses: the caller (sharded or not)
        # forms the load-balance loss from (pmean'd) means via
        # _ragged_aux, keeping sharded and unsharded numerics identical.
        f = jnp.mean(assign[0], axis=0)
        p = jnp.mean(probs, axis=0)
        return y, (f, p, _z_loss(logits))


    def _ragged_serving_one_pass(self, tok_c, logits, w_gate, w_up, w_down,
                                 bias):
        """:meth:`_ragged_core`'s serving form for many choices a token
        (``top_k > ONE_PASS_TOPK_ABOVE``): the same rows in the same layout —
        a held expert's rows in choice-major, then token order — from ONE
        cumsum over the ``k T`` picks, one scatter of their sources, one
        gather and one gated sum, where the loop form does each k times."""
        from k8s_distributed_deeplearning_tpu.ops import pallas_gmm

        cfg, moe = self.cfg, self.moe
        t, k = tok_c.shape[0], moe.top_k
        probs, idx, gates = _topk_one_pass(logits, k, moe, bias)
        # choice-major: pick (c, t) at c * T + t
        one_hot, mine, local, counts = self._held_picks_one_pass(idx.T)
        one_hot, mine, local = (one_hot.reshape(k * t, -1), mine.reshape(-1),
                                local.reshape(-1))
        layout = pallas_gmm.grouped_layout(counts.astype(jnp.int32), t * k,
                                           block_m=_row_block(t, k, moe))
        pos = jnp.sum((jnp.cumsum(one_hot, axis=0) - one_hot) * one_hot,
                      axis=-1).astype(jnp.int32)
        dest = jnp.where(mine, layout.row_offset[local] + pos, layout.m_pad)
        src = jnp.full((layout.m_pad,), -1, jnp.int32).at[dest].set(
            jnp.tile(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
        xs = jnp.where((src >= 0)[:, None],
                       jnp.take(tok_c, jnp.maximum(src, 0), axis=0), 0)
        ys = pallas_gmm.gmm_forward(
            _serving_hidden(moe, xs, w_gate, w_up, layout), w_down, layout)
        gate = (gates.T.reshape(-1) * mine)[:, None]               # f32
        rows = jnp.take(ys, jnp.minimum(dest, layout.m_pad - 1), axis=0)
        y = jnp.sum((rows * gate).reshape(k, t, -1), axis=0).astype(cfg.dtype)
        first = jax.nn.one_hot(idx[:, 0], moe.num_experts, dtype=jnp.float32)
        return y, (jnp.mean(first, axis=0), jnp.mean(probs, axis=0),
                   _z_loss(logits))


class MoELM(nn.Module):
    """Decoder-only MoE language model (every layer MoE, GShard-dense layout).

    Rides the shared :class:`~models.transformer.Transformer` core with
    a uniform ``pattern`` swapping the dense MLP for :class:`MoEMLP`, so
    scan_layers / remat / dropout / packed ``segment_ids`` /
    ``decode`` (KV-cache generation via :func:`models.generate.generate`)
    all work for MoE exactly as for dense models. Decode routes the MoE
    layers through the DROPLESS per-token path (see ``MoEMLP.__call__``):
    the capacity paths size buffers from the call's token count, which
    would make decode-step routing differ from prefill; the dropless path
    is width-independent, so incremental decode matches one-shot prefill
    exactly (parity-tested).

    .. warning:: ``routing="expert_choice"`` is NON-CAUSAL in this decoder:
       each expert selects its top-C tokens over the whole flattened [B*S]
       batch, so position i's routing depends on future tokens (and other
       batch rows). Training/eval leak future information through the
       routing decision, and autoregressive decode (which cannot see the
       future) routes differently from training (decode falls back to
       per-token top-k gates). Prefer ``routing="topk"`` (strictly
       per-token, causal-safe) for LMs; expert choice fits non-causal
       models (BERT/ViT-style) — Zhou et al. use it for encoders. A
       warning is emitted at construction when combined with this causal
       LM.
    """

    cfg: TransformerConfig
    moe: MoEConfig
    shard_mesh: Any = None   # forwarded to MoEMLP (ragged batch shard_map)

    @nn.compact
    def __call__(self, tokens, *, positions=None, segment_ids=None,
                 attention_fn=None, deterministic: bool = True,
                 decode: bool = False, return_hidden: bool = False):
        if self.moe.routing == "expert_choice":
            warnings.warn(
                "expert_choice routing inside a causal LM is non-causal: "
                "experts pick their top-C tokens across the whole batch, "
                "so routing for position i sees future tokens and decode "
                "routes differently from training. Use routing='topk' for "
                "causal LMs (see MoELM docstring).",
                UserWarning, stacklevel=2)
        factory = functools.partial(MoEMLP, moe=self.moe,
                                    shard_mesh=self.shard_mesh)
        x = Transformer(self.cfg,
                        pattern=(LayerKind(mlp=factory),) * self.cfg.n_layers,
                        name="transformer")(
            tokens, positions=positions, segment_ids=segment_ids,
            deterministic=deterministic,
            attention_fn=attention_fn, decode=decode)
        if return_hidden:
            # Final hidden states for the chunked LM-head loss (same
            # contract as LlamaLM.return_hidden): apply-time only — init
            # takes the default path so LMHead params get created.
            return x
        return LMHead(self.cfg, name="head")(x)


class LatentMoELM(nn.Module):
    """Decoder-only LM of the latent-attention + sparse-expert family
    (DeepSeek-V2/V3 layout): every layer attends through
    :class:`~models.transformer.LatentAttention`; the first ``first_dense``
    layers have the dense SwiGLU MLP (``cfg.mlp_dim`` wide), the rest
    :class:`MoEMLP` (``moe.expert_mlp_dim`` wide, with its shared expert).
    Layers differ, so ``cfg.scan_layers`` must be False. The serving engine
    calls it like :class:`models.llama.LlamaLM`; a ``moe`` with
    ``experts_held`` makes it one chip's share of an expert-parallel
    deployment (serving only)."""

    cfg: TransformerConfig
    latent: LatentAttentionConfig
    moe: MoEConfig
    first_dense: int = 1

    @nn.compact
    def __call__(self, tokens, *, positions=None, deterministic: bool = True,
                 decode: bool = False, cache_positions=None,
                 block_tables=None, return_hidden: bool = False):
        attn = functools.partial(LatentAttention, latent=self.latent)
        dense = LayerKind(attention=attn)
        sparse = LayerKind(attention=attn,
                           mlp=functools.partial(MoEMLP, moe=self.moe))
        n = self.cfg.n_layers
        pattern = ((dense,) * min(self.first_dense, n)
                   + (sparse,) * max(n - self.first_dense, 0))
        return lm_forward(
            self, self.cfg, pattern, tokens, return_hidden=return_hidden,
            positions=positions, deterministic=deterministic, decode=decode,
            cache_positions=cache_positions, block_tables=block_tables)


def config_tiny_latent_moe(**overrides):
    """(cfg, latent, moe) of a tiny float32 latent-MoE with the family's
    topology — one dense layer, expert layers with a shared expert, sigmoid
    router with a selection bias, YaRN — for tests and ``chip_smoke``."""
    base = dict(vocab_size=256, dim=64, n_layers=3, n_heads=4, mlp_dim=128,
                max_seq_len=128, rope_theta=10000.0, activation="swiglu",
                norm="rmsnorm", position="rope", causal=True,
                scan_layers=False, dtype=jnp.float32)
    base.update(overrides)
    latent = LatentAttentionConfig(
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_factor=4.0, rope_original_max=32,
        mscale=1.0, mscale_all_dim=1.0)
    moe = MoEConfig(num_experts=8, top_k=2, dispatch="ragged",
                    ragged_block_m=8, score_fn="sigmoid", select_bias=True,
                    routed_scale=2.5, shared_experts=1, expert_mlp_dim=32)
    return TransformerConfig(**base), latent, moe


@functools.lru_cache(maxsize=None)
def conv_moe_pattern(layer_types: tuple[str, ...], moe: MoEConfig,
                     num_dense: int, conv_width: int = 3
                     ) -> tuple[LayerKind, ...]:
    """The layer pattern of the gated-short-convolution + sparse-expert
    family (LFM2-MoE layout), for :class:`~models.transformer.PatternLM`:
    layer ``i``'s mixer is :class:`~models.transformer.ShortConv` where
    ``layer_types[i]`` is ``"conv"`` and :class:`~models.transformer.Attention`
    where it is ``"full_attention"``; its feed-forward is the dense SwiGLU MLP
    in the first ``num_dense`` layers and :class:`MoEMLP` after them. One
    :class:`LayerKind` object a kind (and one tuple per argument set, cached),
    so that equal layers — and models built twice from the same arguments —
    compare equal: a jitted program compiled for one serves the other."""
    unknown = set(layer_types) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"layer_types names {sorted(unknown)}; known: "
                         "'conv', 'full_attention'")
    conv = functools.partial(ShortConv, width=conv_width)
    experts = functools.partial(MoEMLP, moe=moe)
    kinds = {(t, sparse): LayerKind(attention=conv if t == "conv" else None,
                                    mlp=experts if sparse else None)
             for t in ("conv", "full_attention") for sparse in (False, True)}
    return tuple(kinds[t, i >= num_dense] for i, t in enumerate(layer_types))


@functools.lru_cache(maxsize=None)
def hybrid_pattern(layers: str, moe: MoEConfig | None,
                   mamba: Mamba2Config) -> tuple[LayerKind, ...]:
    """The layer pattern of the Mamba-2 + attention + sparse-expert family
    (Nemotron-H layout) from its ``hybrid_override_pattern`` string, for
    :class:`~models.transformer.PatternLM`. Every layer is ONE sub-layer
    (``LayerKind.solo``): ``M`` a :class:`~models.transformer.Mamba2` mixer,
    ``*`` attention, ``E`` a :class:`MoEMLP`, ``-`` the dense MLP. One
    :class:`LayerKind` object a letter and one tuple per argument set
    (cached), as :func:`conv_moe_pattern`."""
    kinds = {"M": LayerKind(attention=functools.partial(Mamba2, mamba=mamba),
                            solo="mixer"),
             "*": LayerKind(solo="mixer"),
             "-": LayerKind(solo="mlp")}
    if moe is not None:
        kinds["E"] = LayerKind(mlp=functools.partial(MoEMLP, moe=moe),
                               solo="mlp")
    unknown = set(layers) - set(kinds)
    if unknown:
        raise ValueError(f"the pattern names {sorted(unknown)}; known: "
                         f"{sorted(kinds)}")
    return tuple(kinds[c] for c in layers)


@functools.lru_cache(maxsize=None)
def jamba_pattern(n_layers: int, attn_offset: int, attn_period: int,
                  mamba: Mamba1Config) -> tuple[LayerKind, ...]:
    """The layer pattern of the Mamba-1 + attention family (Jamba layout),
    for :class:`~models.transformer.PatternLM`: layer ``i``'s mixer is
    :class:`~models.transformer.Attention` where ``i % attn_period ==
    attn_offset`` (the config's ``attn_layer_period`` /
    ``attn_layer_offset``) and a :class:`~models.transformer.Mamba1` mixer
    elsewhere; every layer is a mixer AND the dense feed-forward. One
    :class:`LayerKind` object a kind and one tuple per argument set
    (cached), as :func:`conv_moe_pattern`."""
    ssm = LayerKind(attention=functools.partial(Mamba1, mamba=mamba))
    attn = LayerKind()
    return tuple(attn if i % attn_period == attn_offset else ssm
                 for i in range(n_layers))


@functools.lru_cache(maxsize=None)
def exaone_pattern(layer_types: tuple[str, ...], mlp_layer_types: tuple[str, ...],
                   moe: MoEConfig, window: int) -> tuple[LayerKind, ...]:
    """The layer pattern of the window-and-global attention + sparse-expert
    family (EXAONE 4 / K-EXAONE layout), for
    :class:`~models.transformer.PatternLM`: layer ``i``'s mixer is a
    sliding-window :class:`~models.transformer.Attention` (``window`` keys,
    RoPE) where ``layer_types[i]`` is ``"sliding_attention"`` and a global one
    with NO positions where it is ``"full_attention"``; its feed-forward is
    the dense SwiGLU MLP where ``mlp_layer_types[i]`` is ``"dense"`` and
    :class:`MoEMLP` where it is ``"sparse"``; every block is post-norm
    (``LayerKind.post_norm``). The layers run are as many as the lists name.
    One :class:`LayerKind` object a kind and one tuple per argument set
    (cached), as :func:`conv_moe_pattern`."""
    if len(layer_types) != len(mlp_layer_types):
        raise ValueError("layer_types and mlp_layer_types name different "
                         "numbers of layers")
    unknown = (set(layer_types) - {"sliding_attention", "full_attention"}
               | set(mlp_layer_types) - {"dense", "sparse"})
    if unknown:
        raise ValueError(f"the pattern names {sorted(unknown)}; known: "
                         "'sliding_attention', 'full_attention', 'dense', "
                         "'sparse'")
    mixers = {"sliding_attention": functools.partial(Attention, window=window),
              "full_attention": functools.partial(Attention, rope=False)}
    mlps = {"dense": None, "sparse": functools.partial(MoEMLP, moe=moe)}
    kinds = {(t, f): LayerKind(attention=mixers[t], mlp=mlps[f], post_norm=True)
             for t in mixers for f in mlps}
    return tuple(kinds[t, f] for t, f in zip(layer_types, mlp_layer_types))


def moe_config_of(model) -> MoEConfig | None:
    """The expert layers' config of *model*: its ``moe`` field
    (:class:`MoELM`, :class:`LatentMoELM`) or what the :class:`MoEMLP`
    factories of its ``pattern`` were made with — None for a model with no
    expert layer. What :meth:`serve.engine.ServeEngine.attention_impls` asks
    :func:`serving_dispatch` with."""
    moe = getattr(model, "moe", None)
    if moe is None:
        for kind in getattr(model, "pattern", None) or ():
            moe = getattr(kind.mlp, "keywords", {}).get("moe", moe)
    return moe


def flops_per_token(cfg: TransformerConfig, moe: MoEConfig, *,
                    seq_len: int | None = None,
                    tokens_per_batch: int | None = None) -> float:
    """Approximate fwd+bwd FLOPs per token for MFU: the dense transformer
    accounting (:func:`models.transformer.flops_per_token`) with the MLP
    term scaled by the NOMINAL active expert-slots per token — top_k for
    token-choice routing, capacity_factor·top_k for expert choice — plus
    the router matmul. Pass ``tokens_per_batch`` (= B*S of the training
    step) to instead use the exact dispatched-slot count E*C/T with the
    same int-floor + min(T, ·) capacity clamp MoEMLP applies; without it
    the nominal figure slightly overstates compute when the clamp binds
    (small T) and, for topk, ignores capacity-overflow drops."""
    from k8s_distributed_deeplearning_tpu.models import transformer
    dense = transformer.flops_per_token(cfg, seq_len=seq_len)
    mlp_term = 3.0 * 3 * 2 * cfg.dim * cfg.resolved_mlp_dim   # swiglu, x3 fwd+bwd
    if moe.dispatch == "ragged":
        # Dropless grouped GEMM: exactly top_k expert slots per token —
        # no capacity padding to count, no drops to ignore (the ≤1-block
        # per-expert round-up slack is skipped or multiplies zeros).
        tokens_per_batch = None
    if tokens_per_batch is not None:
        t = tokens_per_batch
        capacity = clamped_capacity(t, moe)   # the exact MoEMLP formula
        active = moe.num_experts * capacity / t   # dispatched slots/token
    else:
        active = (moe.capacity_factor * moe.top_k
                  if moe.routing == "expert_choice" else moe.top_k)
    router = 3.0 * 2 * cfg.dim * moe.num_experts
    return dense + cfg.n_layers * (mlp_term * (active - 1) + router)


def loss_fn(model: MoELM, moe: MoEConfig, params, batch, rng=None, *,
            attention_fn=None, chunked: bool = False,
            chunk_size: int = 1024):
    """Next-token CE + load-balance and router-z auxiliary losses.

    ``batch``: {"tokens": [B,S] int32, optional "mask": [B,S] 1.0 = count
    this position, optional "segment_ids": [B,S] packed-document ids} —
    the same packed contract as ``llama.loss_fn`` — one shared preamble,
    :func:`models.transformer.lm_batch_views` (segment-masked attention,
    per-document RoPE restarts, cross-document boundary pairs out of the
    loss). Note the routing itself is per-token but capacity contention is
    batch-global, so packing changes WHICH tokens drop under pressure —
    the same property any batch composition has for MoE.

    ``chunked=True`` is the same long-vocab memory lever as
    ``llama.loss_fn``: hidden states come back via ``return_hidden``
    (aux-loss sows still collected) and the LM-head matmul + CE run per
    sequence chunk, so ``[B, S, V]`` logits never materialize."""
    inputs, targets, seg_in, positions, mask = lm_batch_views(batch)
    rngs = {"dropout": rng} if rng is not None else None
    apply_kw = dict(segment_ids=seg_in, positions=positions,
                    deterministic=rng is None, rngs=rngs,
                    attention_fn=attention_fn, mutable=["intermediates"])

    if chunked:
        from k8s_distributed_deeplearning_tpu.models.llama import unembedding
        from k8s_distributed_deeplearning_tpu.ops.chunked_ce import (
            chunked_softmax_cross_entropy)
        hidden, state = model.apply({"params": params}, inputs,
                                    return_hidden=True, **apply_kw)
        w, layout = unembedding(model.cfg, params)
        ce, acc = chunked_softmax_cross_entropy(
            hidden, w, targets, mask, chunk_size=chunk_size,
            w_layout=layout)
    else:
        logits, state = model.apply({"params": params}, inputs, **apply_kw)
        ce_tok = optax.softmax_cross_entropy_with_integer_labels(logits,
                                                                 targets)
        denom = jnp.maximum(mask.sum(), 1.0)   # chunked CE normalizes itself
        ce = (ce_tok * mask).sum() / denom
        acc = ((logits.argmax(-1) == targets) * mask).sum() / denom

    flat = jax.tree_util.tree_flatten_with_path(state["intermediates"])[0]
    lb = [v for path, v in flat if "load_balance_loss" in str(path)]
    zs = [v for path, v in flat if "router_z_loss" in str(path)]
    # SUM over layers: under nn.scan the per-layer sows stack into one
    # [n_layers] leaf, under the python loop they are n_layers scalar leaves —
    # jnp.sum makes both aggregate identically.
    aux_loss = (moe.aux_loss_weight * sum(jnp.sum(l) for l in lb)
                + moe.router_z_weight * sum(jnp.sum(z) for z in zs))
    loss = ce + aux_loss
    return loss, {"ce": ce, "aux_loss": aux_loss, "accuracy": acc}
