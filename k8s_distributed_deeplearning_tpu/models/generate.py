"""Autoregressive generation with a static-shape KV cache.

The reference trains and (in the Keras variant) saves/evaluates models
(``tensorflow_mnist_gpu.py:184-191``) but has no inference path at all; a
complete LM framework needs one. TPU-first design:

- the KV cache is a fixed ``[B, max_seq_len, kv·head_dim]`` buffer per
  layer — heads FOLDED into the lane dim so TPU tiling doesn't pad the
  (kv, head_dim) minors 4× and the per-step update stays an in-place
  sliver write (round 5; see the decode-branch comment in
  :mod:`models.transformer`) — held in the mutable "cache" collection and
  updated with ``dynamic_update_slice``: no growing arrays, so the decode
  step compiles once and reruns for every token;
- the whole generate loop is ONE jitted program: prefill over the prompt,
  then ``lax.scan`` over decode steps (token-at-a-time), greedy or
  temperature sampling inside the scan body;
- early termination on EOS is a mask carried through the scan (lanes keep
  running — SPMD-friendly — but finished sequences emit ``pad_id``).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


def moe_assignments(variables: PyTree) -> jax.Array | None:
    """What the call's expert layers sowed under ``moe_stats``: the count of
    picks that landed on each HELD expert, ``[expert layers, held]`` int32 —
    None for a model with no expert layer."""
    leaves = jax.tree.leaves(variables.get("moe_stats", {}))
    return jnp.stack(leaves) if leaves else None


def prefill(model, params: PyTree, prompt: jax.Array, *,
            positions: jax.Array | None = None,
            segment_ids: jax.Array | None = None
            ) -> tuple[jax.Array, PyTree]:
    """Run ``prompt`` ([B, S] int32) through decode mode, creating and
    filling a fresh KV cache sized by the model's ``max_seq_len``.

    Returns ``(logits, cache)``: logits are [B, S, V] (the next token
    samples from column len-1 of its row), cache is the mutable "cache"
    collection ready for :func:`decode_step` / :func:`slot_decode_step`.
    This is the prompt-ingest half of the old monolithic ``_generate``;
    the serving engine (serve/engine.py) calls it per admission with a
    [1, P] prompt and splices the result into its slot arena.
    """
    kw: dict = {}
    if positions is not None:
        kw["positions"] = positions
    if segment_ids is not None:
        kw["segment_ids"] = segment_ids
    logits, vars_ = model.apply({"params": params}, prompt, decode=True,
                                mutable=["cache"], **kw)
    return logits, vars_["cache"]


def prefill_chunk(model, params: PyTree, cache: PyTree, chunk: jax.Array, *,
                  start: jax.Array | int | None = None,
                  positions: jax.Array | None = None,
                  segment_ids: jax.Array | None = None,
                  block_tables: jax.Array | None = None,
                  lengths: jax.Array | None = None
                  ) -> tuple[jax.Array, PyTree, jax.Array | None]:
    """Resume prefill on an EXISTING cache: run ``chunk`` ([B, C] int32)
    through the shared-cursor decode path starting at cache position
    ``start`` (default: wherever the cache's cursor already is). Returns
    ``(logits [B, C, V], cache, counts)`` with the cursor advanced by C;
    ``counts`` is what the model's expert layers sowed under ``moe_stats``
    (:func:`moe_assignments`; None for a model without any).

    This is what makes chunked prefill possible without touching the model:
    the shared-cursor decode branch (models/transformer.py) already appends
    a [B, C] window at the scalar cursor with causal masking against the
    full written prefix, so feeding a prompt in C-token slices produces the
    same KV (and the same logits per position) as one monolithic prefill —
    KV projections are per-token and the attended region per position is
    identical. ``start`` rewrites the cache's ``cache_index`` leaves before
    the step, which lets the serving engine (a) resume after splicing a
    cached prefix whose cursor is mid-prompt and (b) re-run an overlapping
    final chunk idempotently (rewinding rewrites identical KV in place).

    With ``block_tables`` the cache is a paged pool (no ``cache_index``
    leaves — ``start`` is then a no-op) and the caller MUST pass explicit
    ``positions``: the paged scatter derives each token's (page, offset)
    from its absolute position, not from any cursor.

    ``lengths`` ([B] int32; only for a model with a mixer that carries state
    from token to token, :class:`models.transformer.ShortConv` or
    :class:`models.transformer.Mamba2`): how many of
    a RIGHT-PADDED chunk's tokens are real. Attention needs no such thing (pad
    K/V lie beyond the cursor, never attended); a state must be left as the
    last real token left it, not as the pad did. The state itself is a leaf of
    ``cache`` (``conv_state``, ``ssm_state``; one row per row of the call), read before the
    chunk and written after it: a caller resumes a prompt by handing back the
    cache the previous chunk returned.
    """
    if start is not None:
        def set_cursor(path, x):
            if getattr(path[-1], "key", None) == "cache_index":
                return jnp.full(x.shape, start, x.dtype)
            return x
        cache = jax.tree_util.tree_map_with_path(set_cursor, cache)
    kw: dict = {}
    if positions is not None:
        kw["positions"] = positions
    if segment_ids is not None:
        kw["segment_ids"] = segment_ids
    if block_tables is not None:
        kw["block_tables"] = block_tables
    if lengths is not None:
        kw["lengths"] = lengths
    logits, vars_ = model.apply({"params": params, "cache": cache}, chunk,
                                decode=True, mutable=["cache", "moe_stats"],
                                **kw)
    return logits, vars_["cache"], moe_assignments(vars_)


def decode_step(model, params: PyTree, cache: PyTree, token: jax.Array, *,
                positions: jax.Array | None = None,
                segment_ids: jax.Array | None = None
                ) -> tuple[jax.Array, PyTree]:
    """One shared-cursor decode step: ``token`` [B] int32 enters at the
    cache's scalar cursor for every row. Returns ``(logits, cache)`` with
    logits [B, V] for the next position. All rows advance in lockstep —
    the contract of the one-shot ``generate()`` scan body."""
    kw: dict = {}
    if positions is not None:
        kw["positions"] = positions
    if segment_ids is not None:
        kw["segment_ids"] = segment_ids
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                token[:, None], decode=True,
                                mutable=["cache"], **kw)
    return logits[:, -1, :], vars_["cache"]


def slot_decode_step(model, params: PyTree, cache: PyTree,
                     tokens: jax.Array, slot_positions: jax.Array,
                     block_tables: jax.Array
                     ) -> tuple[jax.Array, PyTree, jax.Array | None]:
    """One SLOT decode step over the paged pool: row i's ``tokens[i]`` is
    written at that row's own cursor ``slot_positions[i]`` ([B] int32) —
    page ``block_tables[i, pos // bt]`` ([B, n_blocks] int32), offset
    ``pos % bt`` — and attends its table-gathered prefix
    ``0..slot_positions[i]`` only. Rows live independent lifetimes — the
    continuous-batching engine's per-iteration program. Returns
    ``(logits, cache, counts)`` with logits [B, V] and
    :func:`moe_assignments` (None for a model with no expert layer). The
    caller owns cursor arithmetic (pass position = tokens-written-so-far
    for each row) and must keep ``slot_positions`` within ``max_seq_len``;
    stale KV beyond a row's cursor is never attended, so freed slots are
    reusable without clearing. A model with state beside its pages
    (``conv_state`` leaves, ``[B, ...]``: row i is slot i's) advances every
    row's in place, a free slot's too — its next request starts from zeros
    (the engine's chunk programs see to that); a ``Mamba2``'s ``ssm_state``
    is advanced for the rows with a cursor (``slot_positions > 0``) alone."""
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                tokens[:, None], decode=True,
                                cache_positions=slot_positions,
                                block_tables=block_tables,
                                mutable=["cache", "moe_stats"])
    return logits[:, -1, :], vars_["cache"], moe_assignments(vars_)


def slot_verify_step(model, params: PyTree, cache: PyTree,
                     tokens: jax.Array, slot_positions: jax.Array,
                     block_tables: jax.Array) -> tuple[jax.Array, PyTree]:
    """One speculative VERIFY window: row i's ``tokens[i]`` ([B, W] int32)
    is written through the row's table at consecutive per-row positions
    ``slot_positions[i] + [0, W)`` and each window token attends its own
    causal prefix (writes land before the gather, so window tokens see
    each other).
    Returns ``(logits [B, W, V], cache)``: position ``i`` of the window
    scores the continuation AFTER ``tokens[:, :i+1]``, which is exactly
    what the draft-and-verify accept rule compares against. The caller
    owns the accepted-length cursor arithmetic; rejected window tokens
    stay in the cache beyond the truncated cursor and are never attended
    (rollback = cursor truncation, no KV copies)."""
    logits, vars_ = model.apply({"params": params, "cache": cache},
                                tokens, decode=True,
                                cache_positions=slot_positions,
                                block_tables=block_tables,
                                mutable=["cache"])
    return logits, vars_["cache"]


def filter_logits(logits: jax.Array, top_k: int | None = None,
                  top_p: float | None = None) -> jax.Array:
    """Top-k / nucleus (top-p) filtering on a [..., V] logits slice: tokens
    outside the k most likely, and outside the smallest set whose
    probability mass reaches *top_p*, get -inf. The highest-probability
    token always survives. Composable (k first, then p — the usual order).
    """
    if (top_k is None or top_k <= 0) and (top_p is None or top_p >= 1.0):
        return logits
    if top_p is None or top_p >= 1.0:
        # top_k only: lax.top_k retrieves k values without sorting the full
        # (possibly 128k-wide) vocab in the per-token decode loop.
        kvals, _ = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))
        return jnp.where(logits < kvals[..., -1, None], -jnp.inf, logits)
    # Both filters: one descending sort serves top-k and the nucleus scan.
    sorted_desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    if top_k is not None and top_k > 0:
        kth = sorted_desc[..., min(top_k, logits.shape[-1]) - 1, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        sorted_desc = jnp.where(
            jnp.arange(sorted_desc.shape[-1]) < top_k, sorted_desc, -jnp.inf)
    if top_p is not None and top_p < 1.0:
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        # Keep a sorted token while the mass BEFORE it is < top_p, so the
        # first token is always kept and the kept set is the smallest one
        # reaching the target mass. max(·, 1) keeps the argmax even for
        # top_p <= 0 from direct callers.
        exclusive = jnp.cumsum(probs, axis=-1) - probs
        n_keep = jnp.maximum(
            jnp.sum(exclusive < top_p, axis=-1, keepdims=True), 1)
        thresh = jnp.take_along_axis(sorted_desc, n_keep - 1, axis=-1)
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def generate(model, params: PyTree, prompt: jax.Array, *,
             max_new_tokens: int, rng: jax.Array | None = None,
             temperature: float = 0.0, top_k: int | None = None,
             top_p: float | None = None, eos_id: int | None = None,
             pad_id: int = 0,
             prompt_mask: jax.Array | None = None) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` ([B, S] int32).

    ``temperature=0`` is greedy argmax; otherwise categorical sampling with
    logits/temperature, optionally restricted by ``top_k`` and/or nucleus
    ``top_p`` filtering (``filter_logits``; requires *rng*). Returns
    [B, max_new_tokens] int32. Prompt + new tokens must fit the model's
    ``max_seq_len``. Only the greedy/sampling CHOICE is compile-time; the
    temperature value itself is a traced operand, so sweeping temperatures
    reuses one compiled program.

    ``prompt_mask`` ([B, S], 0/False = padding) enables batching prompts of
    UNEQUAL lengths: pad each prompt at the FRONT (left-padding, so every
    row's last real token sits at column S-1 where the first sampled token
    reads its logits), and pass the validity mask. Pad positions are
    excluded from attention and RoPE positions count real tokens only, so
    each row decodes exactly as it would unpadded (parity-tested).
    """
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling requires rng")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if temperature <= 0.0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require temperature > 0 (greedy decoding ignores "
            "them — silently dropping the request would mislead)")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    cfg = getattr(model, "cfg", None)
    max_seq = getattr(cfg, "max_seq_len", None)
    if max_seq is not None and prompt.shape[1] + max_new_tokens > max_seq:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds the model's max_seq_len ({max_seq}) — the KV cache "
            "would overflow")
    # Window the KV cache to what this call can actually fill: the cache
    # buffer (and every decode step's attention) is sized max_seq_len, but a
    # 64-token generation on an 8k-context model only ever touches the first
    # prompt+new positions. Shrinking cfg.max_seq_len to a 128-aligned bound
    # makes each decode step attend O(needed), not O(max context). Safe for
    # RoPE/none positions (tables are position-indexed, params untouched);
    # "learned" keeps the full window (its pos-embed param is sized by it).
    import dataclasses
    if (max_seq is not None and getattr(cfg, "position", None) != "learned"
            and dataclasses.is_dataclass(cfg) and hasattr(model, "clone")):
        # (The dataclass/clone guards keep generate()'s duck-typed contract:
        # a wrapper model with a plain-object config just skips the window.)
        need = prompt.shape[1] + max_new_tokens
        window = min(max_seq, max(128, -(-need // 128) * 128))
        if window < max_seq:
            # Module.clone keeps every other field (e.g. MoE configs).
            model = model.clone(cfg=dataclasses.replace(
                cfg, max_seq_len=window))
    if prompt_mask is not None:
        if prompt_mask.shape != prompt.shape:
            raise ValueError(f"prompt_mask {prompt_mask.shape} must match "
                             f"prompt {prompt.shape}")
        if not isinstance(prompt_mask, jax.core.Tracer):
            # Value check only on concrete masks — under an outer jit/vmap
            # the caller owns the left-padding contract (a tracer here
            # would otherwise force a device sync or a trace error).
            import numpy as np
            pm = np.asarray(prompt_mask).astype(bool)
            if not (pm[:, -1].all() and
                    (np.diff(pm.astype(np.int8), axis=1) >= 0).all()):
                raise ValueError(
                    "prompt_mask must be LEFT-padded: zeros before ones, "
                    "last column all-real (each row's final token is where "
                    "decoding starts)")
    rng = jax.random.key(0) if rng is None else rng
    return _generate(model, params, prompt, jnp.float32(temperature), rng,
                     prompt_mask, greedy=temperature <= 0.0,
                     max_new_tokens=max_new_tokens, eos_id=eos_id,
                     pad_id=pad_id, top_k=top_k, top_p=top_p)


@functools.partial(jax.jit, static_argnames=("model", "greedy",
                                             "max_new_tokens", "eos_id",
                                             "pad_id", "top_k", "top_p"))
def _generate(model, params: PyTree, prompt: jax.Array,
              temperature: jax.Array, rng: jax.Array,
              prompt_mask: jax.Array | None = None, *, greedy: bool,
              max_new_tokens: int, eos_id: int | None,
              pad_id: int, top_k: int | None = None,
              top_p: float | None = None) -> jax.Array:
    b, s = prompt.shape
    prefill_kw: dict = {}
    lens = None
    # Learned-position models need explicit positions at EMBED time (the
    # cache cursor lives inside Attention, models/transformer.py decode
    # branch): prefill is 0..s-1, decode step t sits at absolute s+t. RoPE
    # models derive positions from the cursor internally. The left-padded
    # branch below overrides both with per-row real-token positions.
    learned = getattr(getattr(model, "cfg", None), "position",
                      None) == "learned"
    if learned:
        prefill_kw = dict(positions=jnp.arange(s)[None, :])
    if prompt_mask is not None:
        # Left-padded batch: RoPE positions count REAL tokens (pads don't
        # advance a row's position), and the mask rides into the cache as
        # per-position validity (models/transformer.py decode branch).
        ok = (prompt_mask != 0).astype(jnp.int32)
        lens = ok.sum(-1).astype(jnp.int32)                    # [B]
        start = s - lens
        prefill_kw = dict(
            positions=jnp.clip(jnp.arange(s)[None, :] - start[:, None],
                               0, None),
            segment_ids=ok)
    # Prefill: run the prompt through decode mode, filling the cache.
    logits, cache = prefill(model, params, prompt, **prefill_kw)

    def sample(logits_last, step_rng):
        if not greedy:
            logits_t = filter_logits(logits_last / temperature,
                                     top_k=top_k, top_p=top_p)
            return jax.random.categorical(step_rng, logits_t, axis=-1)
        return jnp.argmax(logits_last, axis=-1)

    rng, r0 = jax.random.split(rng)
    first = sample(logits[:, -1, :], r0).astype(jnp.int32)     # [B]
    # The first sampled token is emitted as-is; sequences that emitted EOS
    # are no longer alive and pad from the next step on.
    alive0 = (first != eos_id if eos_id is not None
              else jnp.ones_like(first, jnp.bool_))

    def body(carry, xs):
        cache, token, alive = carry
        step_rng, t = xs
        step_kw = {}
        if lens is not None:
            # Decode token t sits at real position lens + t per row; the
            # step keeps passing segment ids (all real) so the cache's
            # pad-validity mask stays active (static-presence contract,
            # models/transformer.py decode branch).
            step_kw["positions"] = (lens + t)[:, None]
            step_kw["segment_ids"] = jnp.ones((b, 1), jnp.int32)
        elif learned:
            # Unpadded learned-position decode: step t's token occupies
            # absolute slot s + t (prefill filled 0..s-1).
            step_kw["positions"] = jnp.full((b, 1), s + t, jnp.int32)
        logits, cache = decode_step(model, params, cache, token, **step_kw)
        nxt = sample(logits, step_rng).astype(jnp.int32)
        if eos_id is not None:
            nxt = jnp.where(alive, nxt, pad_id)
            alive = alive & (nxt != eos_id)
        return (cache, nxt, alive), nxt

    n_rest = max(max_new_tokens - 1, 0)
    steps = (jax.random.split(rng, n_rest), jnp.arange(n_rest))
    (_, _, _), rest = jax.lax.scan(body, (cache, first, alive0), steps)
    out = jnp.concatenate([first[:, None], rest.T], axis=1)
    return out
