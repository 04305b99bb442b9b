"""Continuous-batching engine: ONE compiled decode step over a PAGED KV
pool, with prefix-reuse page sharing and chunked prefill on the admission
path.

Design contract (the compile-once discipline that makes in-flight admission
free, plus the paged-pool discipline that makes HBM proportional to LIVE
tokens):

- The KV cache is ONE pool of fixed-size pages per cache leaf
  (``[num_pages, page_tokens, kv·head_dim]``, the folded-head decode
  layout — vLLM's PagedAttention block-table design). Each decode slot
  owns a host-side block table (``[max_blocks]`` int32 row) mapping its
  virtual sequence onto pool pages; the model's paged decode branch
  (models/transformer.py) scatters each written token at
  ``(table[pos // page_tokens], pos % page_tokens)`` and gathers the
  table's pages back for attention. HBM is paid per ALLOCATED page, so a
  pool sized for N worst-case slots serves far more short-request slots
  concurrently — the dense ``[num_slots, max_seq_len, ·]`` arena this
  replaced paid worst-case HBM per slot unconditionally.
- Page bookkeeping is host-side (serve/page_pool.py): pages are
  refcounted so the prefix trie and any number of slots can share one
  page; admission allocates the prompt's pages and RESERVES the request's
  worst-case decode growth (``max_new_tokens - 1`` positions), making the
  mid-decode page-boundary allocation infallible — back-pressure exists
  only at admission, where the scheduler's ``fits`` probe defers any
  request whose page need exceeds the pool's availability (evicting
  unpinned trie pages first). Terminal states deref the slot's pages and
  return unused growth headroom.
- The decode step is SHAPE-STATIC: ``slot_decode_step`` writes each slot's
  token at that slot's own cursor through its block table, so slots live
  independent lifetimes inside one program. It compiles exactly once and
  reruns for every serving iteration regardless of admissions, completions
  or page churn — block tables are a traced int32 operand, never a shape.
- Admission prefills DIRECTLY into pool pages (no single-row side cache,
  no splice). The prompt is filled from up to three sources, all
  shape-static:

  1. **Prefix cache** (``prefix_cache_mb``): the longest trie-cached
     prefix of the prompt is MAPPED — each matched trie node's page id is
     written into the slot's block table and ref'd — with ZERO device
     copies (serve/prefix_cache.py owns the trie, LRU eviction, and the
     refcounts that pin matched segments until their pages are mapped).
     Completed prefills insert their prompt blocks back by handing the
     trie a reference to the slot's own pages — a fleet-wide system
     prompt is prefilled once and thereafter shared by table mapping.
  2. **Intermediate chunks** (``prefill_chunk_tokens``): the uncached
     suffix is carved into exact C-token chunks (``_chunk_program``, one
     compile per C) resumed across engine iterations, each writing
     through the slot's table at explicit absolute positions.
  3. **Final chunk** (``_final_chunk_program``, one compile per
     power-of-two bucket): finishes the suffix and samples the first
     token. The chunk resumes at the prefill cursor RIGHT-PADDED — the
     token-granular paged scatter has no ``dynamic_update_slice``
     clamping hazard, so no rewind/overlap is ever needed, and pad
     writes past the table land in the pool's reserved scratch page
     (page 0), never in a shared page.

- Stale-KV safety: virtual column == absolute position, attention masks
  ``col <= cursor`` per row, and decode writes land at the cursor BEFORE
  attention reads — so freed pages are reusable without clearing and
  right-pad garbage is never attended.
- Per-slot sampling params are traced array operands (``temperature <= 0``
  => greedy; ``top_k == 0`` / ``top_p == 1.0`` => off), so heterogeneous
  sampling across slots never recompiles. The same operand decides on the
  device what a step pays: with no temperature above 0 in the register file
  (a freed slot's is reset) the sampler takes the argmax and splits the keys,
  and skips the sort of the vocabulary, the softmax, the cumsum and the draw
  (``_sample_slots``; counted as ``serve_sampler_sort_share``).

Greedy decoding through this engine is token-identical to one-shot
``generate()`` for the same prompt — on the cold path, the prefix-hit path
AND the chunked-prefill path: KV projections are per-token, the attended
region per position is independent of how the prompt was fed or which
pages hold it, and masked columns contribute exactly zero (parity asserted
in tests/test_serve.py, tests/test_prefix_cache.py and
tests/test_paged_kv.py).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import zlib
from collections import deque
from typing import Any, Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from k8s_distributed_deeplearning_tpu import faults as _faults
from k8s_distributed_deeplearning_tpu.models import generate, transformer
from k8s_distributed_deeplearning_tpu.models import moe as moe_lib
from k8s_distributed_deeplearning_tpu.ops import pallas_latent_attn, pallas_paged_attn
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
from k8s_distributed_deeplearning_tpu.parallel import sharding as sharding_lib
from k8s_distributed_deeplearning_tpu.serve import quant as quant_lib
from k8s_distributed_deeplearning_tpu.serve.page_pool import PagePool
from k8s_distributed_deeplearning_tpu.serve.prefix_cache import PrefixCache
from k8s_distributed_deeplearning_tpu.serve.request import (
    EngineDraining, QueueFull, Request, RequestOutput, SamplingParams)
from k8s_distributed_deeplearning_tpu.serve.sched import (
    TenantConfig, TenantScheduler)
from k8s_distributed_deeplearning_tpu.telemetry.trace import Tracer
from k8s_distributed_deeplearning_tpu.utils.metrics import ServingStats

_NULL_TRACER = Tracer(enabled=False)

PyTree = Any


def _sample_slots(logits: jax.Array, temps: jax.Array, top_ks: jax.Array,
                  top_ps: jax.Array, keys: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Per-slot sampling with TRACED params: logits [B, V], temps [B] f32,
    top_ks [B] int32 (0 = off), top_ps [B] f32 (1.0 = off), keys [B, 2]
    uint32 (legacy PRNG keys — a plain array, so the register file stays
    ``.at``-updatable). Returns (new_keys, tokens [B] int32).

    Same k-then-p semantics as :func:`models.generate.filter_logits`, but
    with k and p as array operands (one descending sort serves both); rows
    with ``temperature <= 0`` take the argmax instead.

    Everything only a sampling row needs — the sort, the thresholds, the
    softmax, the cumsum and the draw — runs under ONE ``lax.cond`` on
    ``any(temps > 0)``: a step whose rows are all greedy pays the argmax and
    the key split. The keys are split outside the branch, so the register
    comes back the same whichever side ran. (Never ``vmap`` over this
    function: the ``cond`` would become a ``select`` that runs both sides.)
    """
    v = logits.shape[-1]
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_keys, subs = jnp.moveaxis(jax.vmap(jax.random.split)(keys), 1, 0)

    def sampled_branch():
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sorted_desc = -jnp.sort(-scaled, axis=-1)
        k_eff = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v))
        kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
        filt = jnp.where(scaled < kth, -jnp.inf, scaled)
        sorted_k = jnp.where(jnp.arange(v)[None, :] < k_eff[:, None],
                             sorted_desc, -jnp.inf)
        probs = jax.nn.softmax(sorted_k, axis=-1)
        exclusive = jnp.cumsum(probs, axis=-1) - probs
        n_keep = jnp.maximum(
            jnp.sum(exclusive < top_ps[:, None], axis=-1, keepdims=True), 1)
        thresh = jnp.take_along_axis(sorted_k, n_keep - 1, axis=-1)
        filt = jnp.where(filt < thresh, -jnp.inf, filt)
        sampled = jax.vmap(jax.random.categorical)(subs, filt)
        return jnp.where(temps <= 0.0, greedy_tok, sampled).astype(jnp.int32)

    toks = jax.lax.cond(jnp.any(temps > 0.0), sampled_branch,
                        lambda: greedy_tok)
    return new_keys, toks


def _maybe_dequant_params(params: PyTree) -> PyTree:
    """Weight-quant seam for every compiled program: a quantized param
    set is the ``(qparams, scales)`` tuple from quant.quantize_params —
    a STRUCTURAL property, so the branch resolves at trace time and the
    quant-off programs are byte-identical to HEAD. Dequant runs inside
    the jit: the fp weights are fused temporaries, the resident copy
    stays int8."""
    if quant_lib.is_quantized(params):
        return quant_lib.dequantize_params(*params)
    return params


def _with_counts(tokens: jax.Array, moe: jax.Array | None) -> jax.Array:
    """What a program hands back for the host to fetch: its sampled tokens
    and — for a model with expert layers — their ``generate.moe_assignments``
    behind them in ONE int32 array, so that the counts ride the fetch of the
    tokens (``ServeEngine._record_counts`` takes them). A model without expert
    layers hands back its tokens as they are."""
    if moe is None:
        return tokens
    return jnp.concatenate([tokens.reshape(-1), moe.reshape(-1)])


def _decode_core(model, params: PyTree, cache: PyTree, tokens: jax.Array,
                 kv_lens: jax.Array, tables: jax.Array, temps: jax.Array,
                 top_ks: jax.Array, top_ps: jax.Array, keys: jax.Array):
    params = _maybe_dequant_params(params)
    logits, new, moe = generate.slot_decode_step(
        model, params, cache, tokens, kv_lens, block_tables=tables)
    keys, nxt = _sample_slots(logits, temps, top_ks, top_ps, keys)
    return _with_counts(nxt, moe), keys, _keep_idle_state(cache, new, kv_lens)


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache", "keys"))
def _decode_program(model, params: PyTree, cache: PyTree, tokens: jax.Array,
                    kv_lens: jax.Array, tables: jax.Array, temps: jax.Array,
                    top_ks: jax.Array, top_ps: jax.Array, keys: jax.Array):
    """THE serving iteration: every slot advances one token through its
    block table. Free slots ride along as inert rows (their tables are all
    scratch, so their writes land in page 0 and are never attended).
    Compiles once per (model, num_slots, max_blocks). The pool cache AND
    the key register are donated: the step updates both in place — no
    per-iteration arena copy (tests/test_tp_serve.py asserts the aliasing
    by buffer identity). Behind the tokens: the expert layers' counts, where
    the model has any (:func:`_with_counts`). A state arena
    (:data:`_STATE_LEAVES`) is advanced in place, row i being slot i's; the
    rows of slots at cursor 0 (free, or mid-prefill) keep what they held
    (:func:`_keep_idle_state` for a small leaf; inside the mixer's own update
    for one the size of a pool)."""
    return _decode_core(model, params, cache, tokens, kv_lens, tables,
                        temps, top_ks, top_ps, keys)


def _spec_draft_core(model, params: PyTree, cache: PyTree,
                     tokens: jax.Array, kv_lens: jax.Array,
                     tables: jax.Array, steps: int):
    params = _maybe_dequant_params(params)

    def body(carry, _):
        cache, tok, pos = carry
        logits, cache, _ = generate.slot_decode_step(model, params, cache,
                                                     tok, pos,
                                                     block_tables=tables)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, nxt, pos + 1), tok

    (cache, _, _), fed = jax.lax.scan(body, (cache, tokens, kv_lens),
                                      None, length=steps)
    return fed.T, cache


@functools.partial(jax.jit, static_argnames=("model", "steps"),
                   donate_argnames=("cache",))
def _spec_draft_program(model, params: PyTree, cache: PyTree,
                        tokens: jax.Array, kv_lens: jax.Array,
                        tables: jax.Array, *, steps: int):
    """Draft half of a speculative iteration: ``steps`` greedy
    single-token slot decodes through the DRAFT model's paged cache,
    scanned into ONE dispatch. Returns ``(window [B, steps], cache)``:
    column 0 is the input token (each slot's last emitted one) and
    columns 1.. are the draft proposals — exactly the verify window the
    target pass scores. The final scan iteration writes the last draft's
    KV (its logits are discarded), so a fully-accepted window leaves the
    draft cache gap-free at the advanced cursor. Free slots ride along
    inert exactly as in :func:`_decode_program`."""
    return _spec_draft_core(model, params, cache, tokens, kv_lens, tables,
                            steps)


def _spec_verify_core(model, params: PyTree, cache: PyTree,
                      window: jax.Array, kv_lens: jax.Array,
                      tables: jax.Array, temps: jax.Array,
                      top_ks: jax.Array, top_ps: jax.Array,
                      keys: jax.Array):
    params = _maybe_dequant_params(params)
    logits, cache = generate.slot_verify_step(model, params, cache,
                                              window, kv_lens,
                                              block_tables=tables)

    def body(keys, row_logits):
        new_keys, toks = _sample_slots(row_logits, temps, top_ks, top_ps,
                                       keys)
        return new_keys, (toks, new_keys)

    _, (sel, key_states) = jax.lax.scan(body, keys,
                                        jnp.moveaxis(logits, 1, 0))
    sel = sel.T                                            # [B, W]
    key_states = jnp.moveaxis(key_states, 1, 0)            # [B, W, 2]
    matches = (window[:, 1:] == sel[:, :-1]).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
    return sel, key_states, accepted, cache


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def _spec_verify_program(model, params: PyTree, cache: PyTree,
                         window: jax.Array, kv_lens: jax.Array,
                         tables: jax.Array, temps: jax.Array,
                         top_ks: jax.Array, top_ps: jax.Array,
                         keys: jax.Array):
    """Verify half: ONE multi-token target pass over the [B, W] draft
    window (written at per-row positions ``kv_lens + [0, W)`` — rollback
    is the caller truncating its cursor, no KV copies), then a chained
    selection per window position with the SAME per-slot sampling rule as
    :func:`_decode_program`. The key chain splits once per position in
    order, and ``key_states[:, i]`` is the register value after ``i + 1``
    splits — the host sets each slot's key to the state after its actual
    emitted count, so the PRNG stream is bit-identical to non-speculative
    decoding for every sampling config (greedy rows compare argmax;
    sampled rows compare the target's own chained sample — exact-match
    accept). Returns ``(sel [B, W], key_states [B, W, 2],
    accepted [B], cache)`` where ``accepted`` is the per-row count of
    leading drafts matching the target's selections."""
    return _spec_verify_core(model, params, cache, window, kv_lens, tables,
                             temps, top_ks, top_ps, keys)


def _leaf_name(path) -> str | None:
    """Name of a cache leaf from its tree path (DictKey at the tail for
    both unrolled and layer-scanned layouts)."""
    return getattr(path[-1], "key", None)


# Cache leaves that are not pages but STATE: one row a SLOT, read before a
# call's tokens and written after them — a ShortConv's tail, a Mamba2's tail
# and its state (models/transformer.py). They live in the same tree as the
# page pool and are donated with it. A mixer that carries state names its
# leaves here; the conventions it then meets:
#
# - found BY NAME, never by shape; laid out ``[..., slots, rows, F]`` — the
#   batch axis third from the end is the slots' (a single-row cache has 1
#   there), whatever ``rows`` and ``F`` mean to the mixer;
# - a chunk program hands the mixer ONE slot's row (zeros at ``start == 0``)
#   and writes the row it left back in place (:func:`_slot_state`,
#   :func:`_put_slot_state`): one row is copied, never the arena;
# - the decode program hands it EVERY slot's row with ``cache_positions``;
#   the rows at cursor 0 (free, or mid-prefill) must come back as they were.
#   For a small leaf the engine sees to that by a select over the arena
#   (:func:`_keep_idle_state`). A leaf the size of a KV pool cannot afford
#   the select (a third pass over it, and a second arena alive): its mixer
#   masks the update itself, in place, and names the leaf under
#   ``_MASKED_IN_UPDATE`` so that the engine leaves it alone.
_STATE_LEAVES = ("conv_state", "ssm_state")
_MASKED_IN_UPDATE = ("ssm_state",)


def _is_state(path) -> bool:
    return _leaf_name(path) in _STATE_LEAVES


def _slot_state(cache: PyTree, slot: jax.Array | None,
                start: jax.Array) -> PyTree:
    """*cache* as a one-row chunk call sees it: every state leaf cut to
    *slot*'s row — zeros where the chunk is the request's first (``start ==
    0``: whatever the slot's last occupant left is never read, so a reused
    slot needs no clearing). ``slot`` None (a model of pages only): as is."""
    if slot is None:
        return cache

    def one(path, leaf):
        if not _is_state(path):
            return leaf
        row = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=leaf.ndim - 3)
        return jnp.where(start == 0, jnp.zeros_like(row), row)
    return jax.tree_util.tree_map_with_path(one, cache)


def _put_slot_state(arena: PyTree, cache: PyTree,
                    slot: jax.Array | None) -> PyTree:
    """The cache a chunk call returned, its state leaves' one row written
    back into *arena*'s at *slot* (in place: the arena is donated)."""
    if slot is None:
        return cache

    def one(path, old, new):
        if not _is_state(path):
            return new
        return jax.lax.dynamic_update_slice_in_dim(old, new, slot,
                                                   axis=old.ndim - 3)
    return jax.tree_util.tree_map_with_path(one, arena, cache)


def _keep_idle_state(cache: PyTree, new: PyTree, kv_lens: jax.Array) -> PyTree:
    """The cache a decode step returned, with the state rows of the slots it
    did not decode for (cursor 0: free, or mid-prefill — their rider row's
    K/V went to the scratch page) left as they were: a prompt's state carried
    from chunk to chunk must not be advanced by the rider's pad token. A
    tree of pages only comes back as it is, and so does a leaf whose mixer
    masked the update itself (``_MASKED_IN_UPDATE``: an arena too large to
    select over)."""
    def one(path, old, now):
        if not _is_state(path) or _leaf_name(path) in _MASKED_IN_UPDATE:
            return now
        live = (kv_lens > 0).reshape((-1,) + (1,) * 2)
        return jnp.where(live, now, old)
    return jax.tree_util.tree_map_with_path(one, cache, new)


def _chunk_core(model, params: PyTree, cache: PyTree, chunk: jax.Array,
                table: jax.Array, start: jax.Array,
                slot: jax.Array | None = None):
    params = _maybe_dequant_params(params)
    pos = (start + jnp.arange(chunk.shape[1], dtype=jnp.int32))[None, :]
    _, new, moe = generate.prefill_chunk(
        model, params, _slot_state(cache, slot, start), chunk, positions=pos,
        block_tables=table)
    return _put_slot_state(cache, new, slot), moe


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def _chunk_program(model, params: PyTree, cache: PyTree, chunk: jax.Array,
                   table: jax.Array, start: jax.Array,
                   slot: jax.Array | None = None):
    """One INTERMEDIATE prefill chunk: write ``chunk`` ([1, C], all real
    tokens — never padded) through block table ``table`` ([1, max_blocks])
    at absolute positions ``start + [0, C)``. Logits are discarded, so XLA
    dead-code-eliminates the lm_head matmul for every chunk but the final
    one. One compile per C. Returns ``(cache, moe counts or None)``.
    ``slot`` (a model with per-slot state; None otherwise): the row of the
    state arena the chunk reads (zeros at ``start == 0``) and leaves its
    state in."""
    return _chunk_core(model, params, cache, chunk, table, start, slot)


def _final_chunk_core(model, params: PyTree, cache: PyTree,
                      chunk: jax.Array, table: jax.Array,
                      start: jax.Array, length: jax.Array,
                      temp: jax.Array, top_k: jax.Array,
                      top_p: jax.Array, key: jax.Array,
                      slot: jax.Array | None = None):
    params = _maybe_dequant_params(params)
    pos = (start + jnp.arange(chunk.shape[1], dtype=jnp.int32))[None, :]
    # A state is left as the last REAL token left it (lengths), not the pad.
    logits, new, moe = generate.prefill_chunk(
        model, params, _slot_state(cache, slot, start), chunk, positions=pos,
        block_tables=table,
        lengths=None if slot is None else jnp.reshape(length, (1,)))
    last = jax.lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)[:, 0, :]
    new_key, tok = _sample_slots(last, temp[None], top_k[None], top_p[None],
                                 key[None])
    return (_with_counts(tok[0], moe), new_key[0],
            _put_slot_state(cache, new, slot))


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def _final_chunk_program(model, params: PyTree, cache: PyTree,
                         chunk: jax.Array, table: jax.Array,
                         start: jax.Array, length: jax.Array,
                         temp: jax.Array, top_k: jax.Array,
                         top_p: jax.Array, key: jax.Array,
                         slot: jax.Array | None = None):
    """Finish a prefill: write ``chunk`` ([1, bucket], right-padded past
    ``length`` real tokens) at absolute positions ``start + [0, bucket)``
    through ``table`` and sample the first token from the last real column
    ``length - 1`` (all traced operands — one compile per bucket, not per
    prompt length). Pad positions past the table's last block land in the
    pool's scratch page; pad garbage inside the last prompt page sits
    beyond the cursor and is never attended. ``slot``: as
    :func:`_chunk_program`; the state left is that of token ``length - 1``."""
    return _final_chunk_core(model, params, cache, chunk, table, start,
                             length, temp, top_k, top_p, key, slot)


# ------------------------------------------------- serving TP (graftmesh)


def _validate_tp_cfg(cfg, tp: int, what: str) -> None:
    """Offline TP shardability check — raised at the ctor (and mirrored in
    launch/validate.py against rendered manifests), never at first trace."""
    heads = getattr(cfg, "n_heads", None)
    if heads is None:
        raise ValueError(
            f"tp={tp} requires a TransformerConfig-style model config "
            f"(n_heads/n_kv_heads/mlp_dim); {what} has cfg={cfg!r}")
    kv = cfg.resolved_kv_heads
    mlp = cfg.resolved_mlp_dim
    if heads % tp:
        raise ValueError(
            f"{what}: n_heads ({heads}) is not divisible by tp ({tp}) — "
            "every shard must own whole attention heads")
    if kv % tp:
        raise ValueError(
            f"{what}: num_kv_heads ({kv}) is not divisible by tp ({tp}) — "
            "the paged pool shards along the KV head dim, so every shard "
            f"must hold kv_heads/tp whole heads (try tp in "
            f"{[d for d in (1, 2, 4, 8) if d <= kv and kv % d == 0]})")
    if mlp % tp:
        raise ValueError(
            f"{what}: mlp_dim ({mlp}) is not divisible by tp ({tp}) — "
            "the column-parallel gate/up projections split the hidden dim")
    if cfg.activation not in ("swiglu", "relu2"):
        raise ValueError(
            f"{what}: serving TP needs a bias-free down projection "
            f"(activation='swiglu' or 'relu2'), got activation={cfg.activation!r} — "
            "a replicated down_proj bias would be psummed tp times")


def _local_tp_model(model, tp: int):
    """The PER-SHARD model run inside the serving-TP shard_map: identical
    architecture with n_heads / n_kv_heads / mlp_dim divided by tp and the
    row-parallel psums switched on (``TransformerConfig.tp_axis``).
    head_dim is pinned to the full model's resolved value — the default
    (dim // n_heads) would silently change as n_heads shrinks."""
    cfg = model.cfg
    local = dataclasses.replace(
        cfg,
        n_heads=cfg.n_heads // tp,
        n_kv_heads=cfg.resolved_kv_heads // tp,
        head_dim=cfg.resolved_head_dim,
        mlp_dim=cfg.resolved_mlp_dim // tp,
        tp_axis=sharding_lib.SERVE_TP_AXIS)
    return model.clone(cfg=local)


def _tp_param_specs(model) -> PyTree:
    """PartitionSpec prefix tree for the model's params under serving TP
    (parallel/sharding.py rule table: heads/kv/mlp -> "tp", everything
    else — embeddings, LM head, norms — replicated). eval_shape only: no
    FLOPs, no device memory."""
    dummy = jnp.zeros((1, 8), jnp.int32)
    abstract = jax.eval_shape(
        functools.partial(model.init, jax.random.PRNGKey(0)), dummy)
    return sharding_lib.serve_tp_param_specs(abstract["params"])


class _TpPrograms:
    """The compiled serving programs for ONE model under the serving-TP
    shard_map — the same five program bodies as the module-level tp=0
    programs (shared ``*_core`` functions, so the paths cannot drift),
    wrapped in ``shard_map`` over a 1-D ("tp",) mesh. The mesh and specs
    are per-configuration state, so these cannot be plain module-level
    jits — construct through :func:`_tp_programs_for`, which memoizes on
    (model, mesh, specs) so a fresh engine reuses the jit cache exactly
    like the tp=0 programs do.

    Specs: params follow :func:`_tp_param_specs` (Megatron column/row
    sharding, replicated embeddings/LM head); the paged pool shards every
    leaf's last (folded kv·head_dim) dim; every host register operand —
    tokens, cursors, block tables, sampling params, keys — is replicated.
    Because the LM head is replicated, each shard computes the full
    [B, vocab] logits after the last row-parallel psum and sampling is
    replicated too: token outputs need no gather, and the host bookkeeping
    above this seam is identical to tp=0. ``check_vma=False``: outputs
    declared replicated are replicated by construction (same program, same
    replicated inputs on every shard), which the static checker cannot
    prove through the psum chain.

    The pool cache is donated in every program (and the key register in
    decode), so the sharded arena is updated in place per step."""

    def __init__(self, local_model, mesh, param_specs, cache_specs, *,
                 spec_steps: int = 0):
        rep = P()

        def smap(fn, n_host_operands, out_specs):
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=(param_specs, cache_specs) + (rep,) * n_host_operands,
                out_specs=out_specs, check_vma=False)

        def decode(params, cache, tokens, kv_lens, tables, temps, top_ks,
                   top_ps, keys):
            return smap(functools.partial(_decode_core, local_model), 7,
                        (rep, rep, cache_specs))(
                params, cache, tokens, kv_lens, tables, temps, top_ks,
                top_ps, keys)

        self.decode = jax.jit(decode, donate_argnums=(1, 8))

        def chunk(params, cache, chunk_toks, table, start):
            return smap(functools.partial(_chunk_core, local_model), 3,
                        (cache_specs, rep))(
                params, cache, chunk_toks, table, start)

        self.chunk = jax.jit(chunk, donate_argnums=(1,))

        def final_chunk(params, cache, chunk_toks, table, start, length,
                        temp, top_k, top_p, key):
            return smap(functools.partial(_final_chunk_core, local_model),
                        8, (rep, rep, cache_specs))(
                params, cache, chunk_toks, table, start, length, temp,
                top_k, top_p, key)

        self.final_chunk = jax.jit(final_chunk, donate_argnums=(1,))

        def spec_verify(params, cache, window, kv_lens, tables, temps,
                        top_ks, top_ps, keys):
            return smap(functools.partial(_spec_verify_core, local_model),
                        7, (rep, rep, rep, cache_specs))(
                params, cache, window, kv_lens, tables, temps, top_ks,
                top_ps, keys)

        self.spec_verify = jax.jit(spec_verify, donate_argnums=(1,))

        self.spec_draft = None
        if spec_steps:
            def spec_draft(params, cache, tokens, kv_lens, tables):
                return smap(
                    functools.partial(_spec_draft_core, local_model,
                                      steps=spec_steps),
                    3, (rep, cache_specs))(
                    params, cache, tokens, kv_lens, tables)

            self.spec_draft = jax.jit(spec_draft, donate_argnums=(1,))


_TP_PROGRAM_CACHE: dict = {}


def _tp_programs_for(local_model, mesh, param_specs, cache_specs, *,
                     spec_steps: int = 0) -> _TpPrograms:
    """Memoized :class:`_TpPrograms`: engines with the same local model,
    mesh, and pool layout share one set of jitted wrappers. Without this,
    every ServeEngine ctor would mint fresh ``jax.jit`` objects and pay
    full recompiles — the tp=0 path never does (its programs are
    module-level jits). param_specs is derived from the model, so it
    needs no key of its own."""
    spec_leaves, spec_treedef = jax.tree.flatten(
        cache_specs, is_leaf=lambda s: isinstance(s, P))
    key = (local_model, mesh, spec_steps, spec_treedef, tuple(spec_leaves))
    progs = _TP_PROGRAM_CACHE.get(key)
    if progs is None:
        progs = _TP_PROGRAM_CACHE[key] = _TpPrograms(
            local_model, mesh, param_specs, cache_specs,
            spec_steps=spec_steps)
    return progs


def _page_bucket(n: int) -> int:
    """Power-of-two bucket for a KV transfer's page count: gather/scatter
    programs compile once per bucket (logarithmic in pool size), with the
    pad lanes pointed at page 0 — the scratch page, where reads are
    harmless and writes are the pool's designated garbage sink."""
    b = 1
    while b < n:
        b *= 2
    return b


# KV page shipping (graftsplit): move pool pages BY VALUE between engines.
# One gather program stages a slot's pages to the host on the exporter;
# one scatter program adopts the staged values into freshly allocated
# pages on the importer. Page indices are a traced operand, so the
# programs compile per (leaf shape, index bucket) — never per transfer.
@jax.jit
def _gather_pages_program(leaf, idx):
    return jnp.take(leaf, idx, axis=-3)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_pages_program(leaf, vals, idx):
    return leaf.at[..., idx, :, :].set(vals)


class _InFlight:
    """Host-side record for the request occupying a slot."""

    __slots__ = ("req", "tokens", "t_submit", "t_admit", "t_first",
                 "cached_prompt_tokens", "prefill_chunks", "grow_left",
                 "spec_proposed", "spec_accepted", "imported")

    def __init__(self, req: Request, first_token: int, t_admit: float):
        self.req = req
        self.tokens = [first_token]
        self.t_submit = req._t_submit if req._t_submit is not None else t_admit
        self.t_admit = t_admit
        self.t_first = t_admit
        self.cached_prompt_tokens = 0
        self.prefill_chunks = 0
        self.grow_left = 0       # reserved-but-unallocated decode pages
        self.spec_proposed = 0   # draft tokens proposed for this request
        self.spec_accepted = 0   # draft tokens accepted AND emitted
        self.imported = False    # adopted via import_request_kv: this slot
        # never popped the local queue, so no scheduler slot is owed back

    def __repr__(self):
        return (f"_InFlight({self.req.request_id}, "
                f"tokens={len(self.tokens)})")


class _PendingPrefill:
    """Host-side record for a slot whose prompt is still being prefilled
    (reserved: not decodable yet, not admittable either). ``pos`` is the
    prefill cursor — prompt tokens [0, pos) are already in the slot's
    pages (mapped prefix + completed chunks); ``nodes`` pins the trie
    segments backing the mapped region until admission completes;
    ``grow`` is the slot's reserved decode-growth page count; ``first`` is
    None until the final chunk has been dispatched, then what it owes the
    host — ``(token, key, dispatch number, the call's fields)``, the first
    two still on the device (:meth:`ServeEngine._activate` reads them)."""

    __slots__ = ("req", "prompt", "n", "pos", "hit_tokens", "nodes",
                 "t_pop", "chunks", "grow", "table", "first")

    def __init__(self, req: Request, prompt: np.ndarray, pos: int,
                 hit_tokens: int, nodes: list, t_pop: float, grow: int,
                 table: np.ndarray):
        self.req = req
        self.prompt = prompt
        self.n = int(prompt.shape[0])
        self.pos = pos
        self.hit_tokens = hit_tokens
        self.nodes = nodes
        self.t_pop = t_pop
        self.chunks = 0        # compiled prefill program runs so far
        self.grow = grow
        self.first: tuple | None = None
        self.table = table     # PRIVATE block-table row until admission:
        # the engine-wide table must keep this slot all-scratch while the
        # prefill is pending, because the decode program writes a rider
        # KV row for EVERY slot at its (stale, pre-admission) cursor — a
        # half-built table there would take that garbage write into the
        # request's freshly prefilled prompt pages.


class ServeEngine:
    """Synchronous continuous-batching engine over a paged KV pool.

    Usage::

        eng = ServeEngine(model, params, num_slots=8, eos_id=2,
                          prefix_cache_mb=64, prefill_chunk_tokens=128)
        eng.submit(Request(prompt=[...], max_new_tokens=64))
        outputs = eng.run()          # drain queue + in-flight to completion

    or drive iteration-by-iteration with :meth:`step` (each call = one
    decode iteration preceded by bounded admission/prefill work) and stream
    tokens via ``Request.on_token``. ``num_slots >= 2`` (a 1-slot batch is
    not batched serving).

    ``kv_pool_pages`` (None = ``num_slots * max_blocks``, the dense-arena
    equivalent) sizes the shared KV page pool. Because HBM is paid per
    allocated page, an explicit smaller pool lets MORE slots run
    concurrently than a dense arena of the same byte budget whenever mean
    request length is below ``max_seq_len`` — admission defers (scheduler
    back-pressure, no crash) when free pages can't cover a request's
    worst-case need.

    ``prefix_cache_mb`` (None/0 = off) bounds the rank-local prefix-reuse
    trie, which shares pages out of the SAME pool (a trie-cached block is
    one refcounted page, mapped — not copied — into slots that hit it);
    ``prefill_chunk_tokens`` (None = off) bounds each iteration's prefill
    work to that many real prompt tokens (must be a positive multiple of
    ``min_bucket``, the prefill bucket granularity).
    ``prefix_block_tokens`` sets the pool's page size (default
    ``min_bucket``) — trie block and pool page are ONE granularity.

    ``draft_model``/``draft_params``/``spec_k`` (all or none) turn on
    speculative decoding (Leviathan et al.): each iteration, the draft
    model proposes ``spec_k`` greedy tokens per slot through its OWN
    paged cache (same page indices/tables as the target's — one pool,
    two KV arrays — so trie-shared prompt pages carry valid draft KV
    too), and ONE multi-token target pass verifies the window with
    exact-match accept. Output is bit-identical to non-speculative
    decoding for every sampling config; rollback of rejected drafts is
    pure cursor truncation on the paged pool — stale KV beyond the
    cursor is never attended and is overwritten in place by the next
    window before it is read. The draft model must share the target's
    vocabulary and cover its ``max_seq_len``.

    ``tp`` (default 0 = single-device) turns on tensor-parallel decode
    ("graftmesh"): the engine builds a 1-D ``("tp",)`` mesh over the
    first ``tp`` visible devices and runs the SAME compiled programs
    under ``shard_map`` — attention/MLP weights Megatron column/row
    sharded with one psum per sublayer, the paged KV pool sharded along
    the KV head dim (each shard holds ``[num_pages, page_tokens,
    kv_heads/tp · head_dim]``), embeddings and LM head replicated so
    sampling is replicated and token outputs need no gather. Block
    tables, cursors, refcounts, the prefix trie and the scheduler stay
    host-side and replicated, so admission, prefix hits, chunked
    prefill, page growth, migration and speculative decoding work
    unchanged on top of sharded storage. Head/mlp divisibility and mesh
    size are validated here (and offline in launch/validate.py), never
    at first trace. ``tp=1`` is the shard_map path on one device
    (tests/test_tp_serve.py holds it to the plain engine's tokens).

    ``tenants`` (optional) configures the SLO-aware multi-tenant
    scheduler (serve/sched): per-tenant EDF queues drained by
    deficit-weighted round-robin under strict priority classes, with
    token-bucket rate limits and max-concurrent-slot quotas enforced at
    admission. None registers the single unlimited default tenant —
    behaviorally the FCFS queue this engine always had. ``max_queue``
    bounds each tenant that does not set its own ``max_queue``.
    """

    def __init__(self, model, params: PyTree, *, num_slots: int = 8,
                 max_queue: int = 256, eos_id: int | None = None,
                 pad_id: int = 0, min_bucket: int = 32,
                 prefill_chunk_tokens: int | None = None,
                 prefix_cache_mb: float | None = None,
                 prefix_block_tokens: int | None = None,
                 kv_pool_pages: int | None = None,
                 tenants: Iterable[TenantConfig] | None = None,
                 stats: ServingStats | None = None,
                 tracer: Tracer | None = None,
                 request_trace_sample: float = 0.0,
                 request_log: "Any | None" = None,
                 replica_id: str | None = None,
                 draft_model=None, draft_params: PyTree | None = None,
                 spec_k: int = 0, flight: "Any | None" = None,
                 tp: int = 0, prefill_only: bool = False,
                 kv_quant: str | None = None,
                 weight_quant: str | None = None):
        if num_slots < 2:
            raise ValueError(f"num_slots must be >= 2, got {num_slots}")
        if jax.config.jax_default_prng_impl != "threefry2x32":
            # the programs' keys are raw uint32[2]; _first_key makes them
            raise ValueError(
                "ServeEngine needs jax_default_prng_impl 'threefry2x32', "
                f"got {jax.config.jax_default_prng_impl!r}")
        for what, mode in (("kv_quant", kv_quant),
                           ("weight_quant", weight_quant)):
            if mode not in (None, "int8"):
                raise ValueError(
                    f"{what} must be None or 'int8', got {mode!r}")
        self.kv_quant = kv_quant
        self.weight_quant = weight_quant
        if kv_quant is not None and getattr(model, "cfg", None) is not None:
            # The paged-pool quant path lives in the model's decode branch
            # (models/transformer.py), keyed on cfg.kv_quant — rebuild the
            # model (and the draft: its sibling arena shares the page
            # geometry) with the mode threaded in. Quant-off engines never
            # touch the cfg, so their programs/cache treedefs stay
            # byte-identical to an unquantized build.
            model = model.clone(cfg=dataclasses.replace(
                model.cfg, kv_quant=kv_quant))
            if draft_model is not None:
                draft_model = draft_model.clone(cfg=dataclasses.replace(
                    draft_model.cfg, kv_quant=kv_quant))
        cfg = getattr(model, "cfg", None)
        max_seq = getattr(cfg, "max_seq_len", None)
        if max_seq is None:
            raise ValueError("model.cfg.max_seq_len is required — it bounds "
                             "each slot's block table")
        if prefill_chunk_tokens is not None and (
                prefill_chunk_tokens < min_bucket
                or prefill_chunk_tokens % min_bucket):
            raise ValueError(
                f"prefill_chunk_tokens ({prefill_chunk_tokens}) must be a "
                f"positive multiple of min_bucket ({min_bucket}) — chunks "
                "are real-token slices aligned to the prefill bucket "
                "granularity")
        if prefix_cache_mb is not None and prefix_cache_mb < 0:
            raise ValueError(
                f"prefix_cache_mb must be >= 0 (0 = off), got "
                f"{prefix_cache_mb}")
        if not 0.0 <= request_trace_sample <= 1.0:
            raise ValueError(
                f"request_trace_sample must be in [0, 1], got "
                f"{request_trace_sample}")
        if (draft_model is None) != (spec_k == 0):
            raise ValueError(
                "speculative decoding needs BOTH a draft model and "
                f"spec_k >= 1 (got draft_model={draft_model!r}, "
                f"spec_k={spec_k})")
        if prefill_only and spec_k:
            raise ValueError(
                "prefill_only is incompatible with speculative decoding "
                "(spec_k > 0): exported KV blobs carry only the target "
                "arena, and a prefill worker never decodes — run the "
                "draft on the decode workers instead")
        if draft_model is not None:
            if draft_params is None:
                raise ValueError("draft_model set but draft_params is None")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            dcfg = getattr(draft_model, "cfg", None)
            dv = getattr(dcfg, "vocab_size", None)
            tv = getattr(cfg, "vocab_size", None)
            if dv != tv:
                raise ValueError(
                    f"draft vocab_size ({dv}) != target vocab_size ({tv}) "
                    "— draft proposals must be target token ids")
            dmax = getattr(dcfg, "max_seq_len", 0)
            if dmax < max_seq:
                raise ValueError(
                    f"draft max_seq_len ({dmax}) < target max_seq_len "
                    f"({max_seq}) — the draft cache shares the target's "
                    "block tables and must cover every position")
        self.tp = int(tp)
        if self.tp < 0:
            raise ValueError(f"tp must be >= 0 (0 = single-device), got {tp}")
        if self.tp:
            _validate_tp_cfg(cfg, self.tp, "target model")
            ndev = len(jax.devices())
            if ndev < self.tp:
                raise ValueError(
                    f"tp={self.tp} needs {self.tp} devices, but only {ndev} "
                    f"{'is' if ndev == 1 else 'are'} visible — lower tp, or "
                    "expose more devices (CPU: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N)")
            if draft_model is not None:
                _validate_tp_cfg(dcfg, self.tp, "draft model")
        self.model = model
        self.params = params
        if self.weight_quant == "int8":
            qp, sc = quant_lib.quantize_params(params)
            if self.tp:
                # _TpPrograms' shard_map in_specs are a params-tree
                # prefix, which the (qparams, scales) tuple cannot ride
                # through — TP stores fp weights AT THE INT8 GRID POINTS
                # (dequantize-at-load): numerics identical to the tp=0
                # dequant-at-use path, storage benefit forfeited.
                self.params = quant_lib.dequantize_params(qp, sc)
            else:
                self.params = (qp, sc)
            self._weight_fp_nbytes = quant_lib.params_nbytes(params)
            self._weight_q_nbytes = quant_lib.quantized_nbytes(qp, sc)
        else:
            self._weight_fp_nbytes = self._weight_q_nbytes = 0
        self.num_slots = num_slots
        self.max_seq_len = int(max_seq)
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.min_bucket = min_bucket
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.stats = stats if stats is not None else ServingStats()
        # Spans: "admission" (queue pop -> pending created, wrapping the
        # prefix lookup + page mapping), "prefill" (one compiled chunk /
        # final chunk: the dispatch) and "decode" (one pool-wide decode
        # iteration from its dispatch to the host sync, the step's
        # admission work inside it) — the whole list: :meth:`step`.
        self.tracer = tracer if tracer is not None else _NULL_TRACER
        # End-to-end lifecycle traces (graftscope): each terminal path
        # funnels through _emit_request_trace, which emits one sampled
        # ``request_trace`` JSONL event per finished request. Sampling is
        # a pure function of request_id (crc32), so "did request X get
        # traced" is reproducible across ranks and restarts — no RNG.
        self.request_trace_sample = float(request_trace_sample)
        self.request_log = (request_log if request_log is not None
                            else self.tracer.logger)
        # Identity in a multi-replica deployment (gateway routing,
        # request_trace replica= field). None for standalone engines.
        self.replica_id = replica_id
        # Black-box flight recorder (telemetry/flight.py): one per-step
        # snapshot into the shared ring, dumped on drain completion or an
        # injected fault. None = off; the hot path gates every snapshot
        # assembly on it.
        self.flight = flight
        self._last_decode_ms: float | None = None
        self._last_prefill_ms: float | None = None
        self._drain_finalized = False
        if flight is not None:
            # Dump the ring when a fault fires anywhere in-process —
            # including actions (exit/sigterm) that never return control
            # to the serving loop. Weakref-registered, so dead engines
            # fall out of the hook list on their own.
            _faults.add_fire_hook(self)
        self._draining = False
        # Disaggregated prefill role ("graftsplit"): admission + chunked
        # prefill run normally, but a slot that completes admission is
        # immediately exported (pages staged by value, slot freed) instead
        # of entering decode — the coordinator drains take_exports() and
        # ships each blob to a decode worker. A prefill_only engine is
        # driven by its coordinator, never by run().
        self.prefill_only = bool(prefill_only)
        self._exports: list[dict] = []
        self.queue = TenantScheduler(tenants, default_max_queue=max_queue)
        # Page geometry: the trie's block size IS the pool's page size
        # (one trie node = one page), and it applies whether or not the
        # prefix cache is enabled.
        bt = (prefix_block_tokens if prefix_block_tokens is not None
              else min_bucket)
        if bt < 1 or bt > self.max_seq_len:
            raise ValueError(
                f"prefix_block_tokens ({bt}) must be in "
                f"[1, max_seq_len={self.max_seq_len}]")
        self.page_tokens = int(bt)
        self.max_blocks = -(-self.max_seq_len // self.page_tokens)
        usable = (int(kv_pool_pages) if kv_pool_pages is not None
                  else num_slots * self.max_blocks)
        if usable < 1:
            raise ValueError(
                f"kv_pool_pages must be >= 1, got {kv_pool_pages}")
        # +1: page 0 is the scratch page (see serve/page_pool.py).
        self.pool = PagePool(usable + 1, self.page_tokens)
        # Per-slot register file (host numpy; fixed dtypes so the decode
        # program's operand signature — and thus its compilation — never
        # changes). kv_lens doubles as the next write position; _tables
        # rows default to all-scratch (page 0).
        self._tokens = np.full(num_slots, pad_id, np.int32)
        self._kv_lens = np.zeros(num_slots, np.int32)
        self._tables = np.zeros((num_slots, self.max_blocks), np.int32)
        self._temps = np.zeros(num_slots, np.float32)
        self._top_ks = np.zeros(num_slots, np.int32)
        self._top_ps = np.ones(num_slots, np.float32)
        self._keys = np.zeros((num_slots, 2), np.uint32)
        self._slots: list[_InFlight | None] = [None] * num_slots
        self._pending: dict[int, _PendingPrefill] = {}
        # Programs dispatched so far. A result the host will wait for keeps
        # the number its program got: the wait is COVERED when a later
        # program was dispatched before it began (the device has work queued
        # behind the awaited one), and everything dispatched before the
        # awaited program is done when the wait returns.
        self._dispatches = 0
        # The newest program number a wait has been made for (_wait): the
        # device's queue is in order, so everything up to it is done by the
        # next dispatch. ``_in_flight`` counts from here.
        self._fenced = 0
        # (dispatch number, fields, device counts) of the intermediate
        # chunks whose counts have not been fetched yet (_take_chunk_counts)
        self._chunk_counts: deque[tuple] = deque()
        # Outputs of requests that finished when a first token was taken
        # outside step() (cancel, export_request_kv): the next step()
        # returns them.
        self._late_outputs: list[RequestOutput] = []
        # Serving tensor parallelism (graftmesh): a 1-D ("tp",) mesh over
        # the first tp devices. The params are placed column/row-sharded
        # once here, the pool cache below is built sharded-at-birth along
        # its folded KV-head dim, and _TpPrograms wraps the same program
        # bodies as tp=0 in shard_map — every host-side structure (block
        # tables, cursors, refcounts, trie, scheduler) stays replicated
        # and mode-blind.
        self._mesh = None
        self._tp_programs: _TpPrograms | None = None
        self._tp_draft_programs: _TpPrograms | None = None
        # Single-row cache SHAPES (eval_shape: no FLOPs) — the leaf
        # structure the pool is derived from, and the byte source for
        # _block_nbytes.
        dummy = jnp.zeros((1, 1), jnp.int32)
        _, self._row_shapes = jax.eval_shape(
            lambda p, t: generate.prefill(self.model,
                                          _maybe_dequant_params(p), t),
            self.params, dummy)
        other = sorted({_leaf_name(path) for path, leaf in
                        self._pool_rows(self._row_shapes)}
                       - {"cached_key", "cached_value"})
        if other:
            # A pool of other leaves than K and V per head (a latent row)
            # has no head axis to shard over tp, no per-head int8 scales,
            # and no draft model of its family to pair pages with.
            for what, on in (("kv_quant='int8'", kv_quant is not None),
                             (f"tp={self.tp}", self.tp > 0),
                             ("a speculative draft (spec_k)", spec_k > 0)):
                if on:
                    raise ValueError(
                        f"a page pool of {other} leaves cannot take {what} "
                        "yet: those paths assume cached_key/cached_value "
                        "pages of kv_heads x head_dim lanes")
        # State beside the pages (:data:`_STATE_LEAVES`): the leaves, and
        # the bytes one slot's rows hold.
        self._state_rows = self._state_leaves(self._row_shapes)
        self._slot_state_nbytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for _, leaf in self._state_rows)
        self._state_names = sorted(
            {_leaf_name(path) for path, _ in self._state_rows})
        if self._state_rows:
            # Every path that knows pages only is refused by name until it
            # carries the state: what follows a mapped prefix's, a rejected
            # draft's or a shipped request's last token would be lost.
            for what, on in (
                    ("prefix_cache_mb > 0 (the trie maps pages, not the "
                     "state after a prefix's last token)",
                     bool(prefix_cache_mb)),
                    ("a speculative draft (spec_k: rollback truncates a "
                     "cursor, a state has none)", spec_k > 0),
                    (f"tp={self.tp}", self.tp > 0),
                    ("kv_quant='int8'", kv_quant is not None),
                    ("prefill_only (export_request_kv ships pages only)",
                     self.prefill_only)):
                if on:
                    raise ValueError(
                        f"a model with per-slot state ({self._state_names} "
                        f"leaves) cannot take {what} yet")
        if self.tp:
            self._mesh = mesh_lib.make_mesh(
                {sharding_lib.SERVE_TP_AXIS: self.tp},
                devices=jax.devices()[:self.tp])
            self.params = jax.device_put(
                self.params, self._named_shardings(_tp_param_specs(model)))
        self._cache = self._init_pool_cache(
            self._row_shapes, head_dim=cfg.resolved_head_dim)
        # Speculative decoding: the draft cache is a SECOND paged KV
        # arena over the SAME page indices — block tables, the trie and
        # the refcounts are shared, only the arrays (sized for the draft
        # model) are separate. Every prefill/decode write lands in both.
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.spec_k = int(spec_k)
        self._draft_cache: PyTree | None = None
        if self.spec_k:
            if self.weight_quant == "int8":
                dqp, dsc = quant_lib.quantize_params(self.draft_params)
                self.draft_params = (
                    quant_lib.dequantize_params(dqp, dsc) if self.tp
                    else (dqp, dsc))
            if self.tp:
                self.draft_params = jax.device_put(
                    self.draft_params,
                    self._named_shardings(_tp_param_specs(draft_model)))
            _, draft_shapes = jax.eval_shape(
                lambda p, t: generate.prefill(self.draft_model,
                                              _maybe_dequant_params(p), t),
                self.draft_params, dummy)
            if self._state_leaves(draft_shapes):
                raise ValueError(
                    "a draft model with per-slot state cannot be rolled "
                    "back by cursor truncation: spec_k needs a pages-only "
                    "draft")
            self._draft_cache = self._init_pool_cache(
                draft_shapes, head_dim=dcfg.resolved_head_dim,
                max_seq_len=dcfg.max_seq_len)
        if self.tp:
            self._tp_programs = _tp_programs_for(
                _local_tp_model(model, self.tp), self._mesh,
                _tp_param_specs(model),
                sharding_lib.serve_tp_cache_specs(self._cache))
            if self.spec_k:
                self._tp_draft_programs = _tp_programs_for(
                    _local_tp_model(draft_model, self.tp), self._mesh,
                    _tp_param_specs(draft_model),
                    sharding_lib.serve_tp_cache_specs(self._draft_cache),
                    spec_steps=self.spec_k + 1)
        self.prefix_cache: PrefixCache | None = None
        if prefix_cache_mb is not None and prefix_cache_mb > 0:
            self.prefix_cache = PrefixCache(
                int(prefix_cache_mb * 2 ** 20), block_tokens=self.page_tokens,
                block_nbytes=self._block_nbytes(self.page_tokens),
                release_page=self._release_trie_page)
        # Per-step accounting for the chunked-prefill work bound (tested:
        # real prefill tokens per iteration never exceed the chunk budget).
        self.last_step_prefill_tokens = 0
        self._step_prefill_budget: int | None = None
        self._record_pool_gauges()
        # Under tp the weights resident on device are fp (dequantized at
        # load — tuple params can't ride the shard_map in_specs), so the
        # weight gauge honestly reports 0 saved there.
        self.stats.record_quant(
            self.kv_quant, self.weight_quant,
            kv_bytes_saved=self._kv_bytes_saved(),
            weight_bytes_saved=(
                0 if self.tp
                else self._weight_fp_nbytes - self._weight_q_nbytes))
        # The ``attn`` field of a ``prefill`` span and a ``prefill_counts``
        # record: what the chunk's program resolved its attention to, the
        # first word of its :meth:`attention_impls` entry. A constant of the
        # program, looked up per chunk call and never computed there.
        self._prefill_attn = {name: entry.split()[0] for name, entry
                              in self.attention_impls().items()}

    def _named_shardings(self, specs: PyTree) -> PyTree:
        """PartitionSpec tree -> NamedSharding tree over the tp mesh
        (prefix-compatible: works against boxed and plain param trees)."""
        return jax.tree.map(lambda s: NamedSharding(self._mesh, s), specs,
                            is_leaf=lambda s: isinstance(s, P))

    def _pool_rows(self, row_shapes: PyTree,
                   max_seq_len: int | None = None) -> list[tuple]:
        """(path, leaf) of the row-cache leaves a pool is made of: those
        laid out ``[..., 1, max_seq_len, F]`` — one F-lane row a position
        (``cached_key``/``cached_value`` of kv·head_dim lanes, a latent
        model's ``cached_latent``). Cursors and segment ids are not rows.
        *max_seq_len*: the row length of the model the shapes are of (a
        draft's may be longer than the engine's)."""
        n = self.max_seq_len if max_seq_len is None else max_seq_len
        return [(path, leaf) for path, leaf in
                jax.tree_util.tree_flatten_with_path(row_shapes)[0]
                if len(leaf.shape) >= 3 and leaf.shape[-3] == 1
                and leaf.shape[-2] == n and not _is_state(path)]

    @staticmethod
    def _state_leaves(row_shapes: PyTree) -> list[tuple]:
        """(path, leaf) of the single-row cache's STATE leaves
        (:data:`_STATE_LEAVES`, ``[..., 1, rows, F]``): found by name, never
        by shape — a state is not a row a position."""
        return [(path, leaf) for path, leaf in
                jax.tree_util.tree_flatten_with_path(row_shapes)[0]
                if _is_state(path)]

    def _init_pool_cache(self, row_shapes: PyTree, *, head_dim: int,
                         max_seq_len: int | None = None) -> PyTree:
        """Zero-filled page pool with the cache-leaf structure a prefill
        produces (``row_shapes``: the target model's single-row
        eval_shape, or the draft model's for its sibling arena), keeping
        ONLY the row leaves (:meth:`_pool_rows` — what the model's paged
        branch declares) and reshaping each leaf's [..., 1, max_seq, F] row
        layout to [..., num_pages, page_tokens, F] — and beside them the
        STATE ARENA: each state leaf (:meth:`_state_leaves`) with a row a
        slot, [..., 1, rows, F] -> [..., num_slots, rows, F]. KV content is
        irrelevant — nothing is attended until a table maps a written
        page. Under tp the pool is built SHARDED-AT-BIRTH along each
        leaf's folded kv·head_dim lane dim (jit + out_shardings): every
        shard materializes only its kv_heads/tp slice of each page, so
        the full pool never exists on one device.

        Under ``kv_quant="int8"`` the arenas are int8 and each gains a
        sibling ``*_scale`` leaf ``[..., num_pages, page_tokens, kv]``
        f32 (``head_dim`` tells the lane split — row_shapes come from
        the DENSE prefill eval_shape, which carries no quant structure).
        Page dim stays at axis -3 on both, so gather/scatter shipping,
        the disagg codec, trie sharing and TP's last-dim sharding (kv is
        validated tp-divisible) all compose unchanged."""
        bt, pages = self.page_tokens, self.pool.num_pages
        quant = self.kv_quant == "int8"
        rows = {id(leaf) for _, leaf in
                self._pool_rows(row_shapes, max_seq_len)}

        def build(tree):
            out = {}
            for name, v in tree.items():
                if isinstance(v, (dict,)) or hasattr(v, "items"):
                    sub = build(v)
                    if sub:
                        out[name] = sub
                elif name in _STATE_LEAVES:
                    out[name] = jnp.zeros(
                        v.shape[:-3] + (self.num_slots,) + v.shape[-2:],
                        v.dtype)
                elif id(v) in rows:
                    # [1, S, F] -> [P, bt, F]; scanned [L, 1, S, F] ->
                    # [L, P, bt, F] (batch dim 1 at -3 dropped).
                    shape = v.shape[:-3] + (pages, bt) + v.shape[-1:]
                    if quant:
                        out[name] = jnp.zeros(shape, jnp.int8)
                        out[name + "_scale"] = jnp.zeros(
                            shape[:-1] + (shape[-1] // head_dim,),
                            jnp.float32)
                    else:
                        out[name] = jnp.zeros(shape, v.dtype)
            return out

        if self._mesh is None:
            return build(row_shapes)
        abstract = jax.eval_shape(lambda: build(row_shapes))
        shardings = self._named_shardings(
            sharding_lib.serve_tp_cache_specs(abstract))
        return jax.jit(lambda: build(row_shapes),
                       out_shardings=shardings)()

    def _kv_bytes_saved(self) -> int:
        """HBM bytes the int8 KV arenas (target + draft) save vs the fp
        pool they replace: each int8 leaf would have cost ``itemsize``
        per lane in fp, minus the f32 scale siblings' overhead."""
        if self.kv_quant != "int8":
            return 0
        fp_item = jnp.dtype(self.model.cfg.dtype).itemsize
        saved = 0
        for tree in (self._cache, self._draft_cache):
            if tree is None:
                continue
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                if _leaf_name(path).endswith("_scale"):
                    saved -= leaf.size * 4
                else:
                    saved += leaf.size * (fp_item - 1)
        return max(0, saved)

    def _block_nbytes(self, block_tokens: int, *,
                      kv_quant: str | None = "unset") -> int:
        """Bytes of KV one pool page holds (seq dim of every row leaf,
        :meth:`_pool_rows`, cut to block_tokens) — the trie's exact per-node
        cost, known without touching device arrays. Under int8 KV a
        position costs 1 byte per lane plus a 4-byte f32 scale per KV
        head instead of ``itemsize`` per lane (``kv_quant`` overrides the
        engine mode — the bench's fp-vs-int8 bytes/page gate asks both)."""
        mode = self.kv_quant if kv_quant == "unset" else kv_quant
        hd = self.model.cfg.resolved_head_dim
        total = 0
        for _, s in self._pool_rows(self._row_shapes):
            lanes = s.shape[-1]
            lead = int(np.prod(s.shape)) // (s.shape[-2] * lanes)
            if mode == "int8":
                total += lead * block_tokens * (lanes + (lanes // hd) * 4)
            else:
                total += lead * lanes * block_tokens * s.dtype.itemsize
        return total

    def _need_pages(self, req: Request) -> int:
        """Worst-case pool pages a request needs: every position it can
        ever write — prompt [0, n) plus decode growth [n, n+max_new-1)
        (the final sampled token is returned, never written). Conservative
        on purpose: no prefix-hit credit, because the admission probe runs
        BEFORE the trie lookup pins anything."""
        total = len(req.prompt) + req.max_new_tokens - 1
        return -(-total // self.page_tokens)

    # ---------------------------------------------------------------- API

    def submit(self, req: Request, *, requeue: bool = False) -> str:
        """Queue a request under its tenant's policy. Raises QueueFull —
        scoped to the offending tenant — when that tenant's bounded queue
        is at capacity, EngineDraining once :meth:`drain` has been called,
        and ValueError for requests that could never run (or that name an
        unregistered tenant).

        ``requeue=True`` is the migration path (gateway resubmission of a
        request another replica already admitted): the request enters at
        the HEAD of its deadline class with its original ``_t_submit``
        (hence ``deadline_abs``) preserved and its token-bucket/DRR cost
        already paid — see :meth:`serve.sched.TenantScheduler.requeue`."""
        if self._draining:
            raise EngineDraining(
                f"engine{f' {self.replica_id!r}' if self.replica_id else ''}"
                f" is draining — admitting nothing new "
                f"(request {req.request_id})")
        n = len(req.prompt)
        if n < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        if n + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_seq_len ({self.max_seq_len}) — the slot's "
                "block table would overflow")
        need = self._need_pages(req)
        if need > self.pool.num_pages - 1:
            raise ValueError(
                f"request needs {need} KV pages but the pool only has "
                f"{self.pool.num_pages - 1} — raise kv_pool_pages or "
                "lower max_new_tokens")
        if not requeue or req._t_submit is None:
            req._t_submit = time.perf_counter()
        req._finished = False        # re-arm the exactly-once on_finish latch
        if requeue:
            self.queue.requeue(req)
        else:
            self.queue.submit(req)
        return req.request_id

    def busy(self) -> bool:
        """True while any work remains: queued requests, prefills in
        progress (a slot whose first token is still on the device is one of
        them), occupied decode slots, or an output the next :meth:`step`
        still has to hand over. THE loop condition for
        callers driving :meth:`step` (in-progress prefills hold no slot
        entry, so checking queue+slots alone would exit early). A
        prefill-only engine also counts staged exports awaiting pickup —
        they hold client requests, so draining before the coordinator
        collects them would lose work."""
        return bool(len(self.queue) or self._pending or self._exports
                    or self._late_outputs
                    or any(s is not None for s in self._slots))

    def occupied_slots(self) -> int:
        """Decode slots currently running a request (excludes pending
        prefills — they hold a reservation, not a decode row)."""
        return sum(s is not None for s in self._slots)

    def load(self) -> int:
        """Queued + mid-prefill + decoding request count — the gateway's
        least-loaded routing key."""
        return (len(self.queue) + len(self._pending)
                + self.occupied_slots())

    def drain(self, *, flush: bool = False) -> list[Request]:
        """Enter cooperative drain mode: stop admitting (further
        :meth:`submit` calls raise :class:`EngineDraining`) while
        :meth:`step` keeps finishing what the engine already holds —
        the SIGTERM → drain → exit-0 shape for k8s rolling updates.

        ``flush=True`` additionally hands the still-QUEUED requests back
        (removed from the queue, untouched otherwise) so a gateway can
        migrate them to a peer instead of waiting for this replica to
        serve them; without a peer list, leave ``flush=False`` and the
        queue drains through the normal admission path. Idempotent."""
        self._draining = True
        return self.queue.drain() if flush else []

    @property
    def draining(self) -> bool:
        """True once :meth:`drain` has been called (no new admissions)."""
        return self._draining

    @property
    def drained(self) -> bool:
        """True when drain mode is on AND no work remains — the
        ``/healthz`` signal a preStop hook (or the gateway) polls before
        letting the pod die."""
        return self._draining and not self.busy()

    def cancel(self, request_id: str, reason: str = "aborted"
               ) -> RequestOutput | None:
        """Cancel ONE request wherever it currently lives — queued
        (removed, no tokens), mid-prefill (pinned trie segments released,
        pages freed) or decoding (partial tokens, slot freed) — and
        complete it with *reason*. The per-request surface behind gateway
        migration (reason "migrated") and hedge loser cancellation.
        Returns the terminal output, or None for an unknown/already-
        finished request id."""
        remove = getattr(self.queue, "remove", None)
        req = remove(request_id) if remove is not None else None
        if req is not None:
            now = time.perf_counter()
            t0 = req._t_submit if req._t_submit is not None else now
            out = RequestOutput(
                request_id=req.request_id, prompt_len=len(req.prompt),
                tokens=[], finish_reason=reason, queue_s=now - t0,
                ttft_s=None, latency_s=now - t0)
            self.stats.record_completion(latency_s=out.latency_s,
                                         n_tokens=0, reason=reason)
            self._emit_request_trace(req, out)
            self._notify_finish(req, reason)
            return out
        for slot in list(self._pending):
            if self._pending[slot].req.request_id == request_id:
                if self._pending[slot].first is None:
                    return self._cancel_pending(slot, reason)
                # Its first token is out: it is cancelled as the decoding
                # request it is (or has just finished — then it is unknown
                # here, as it would have been a step ago).
                self._activate(slot, self._late_outputs)
        for slot, fl in enumerate(self._slots):
            if fl is not None and fl.req.request_id == request_id:
                return self._finish(slot, reason)
        return None

    # ---------------------------------------------- KV page shipping API
    # Disaggregated serving ("graftsplit", serve/disagg.py): a request's
    # KV pages move BY VALUE between engines — host-staged gathers on the
    # exporter, host-staged scatters into freshly allocated pages on the
    # importer — so the two pools never share device buffers and the
    # same blob survives a process boundary (serve/disagg.py owns the
    # wire codec). Works post-admission at ANY decode cursor: the
    # prefill→decode handoff exports right after admission, and the
    # gateway's live-migration path exports mid-decode.

    def take_exports(self) -> list[dict]:
        """Hand over (and clear) the KV export blobs a prefill-only
        engine staged — the coordinator's pickup point after each
        :meth:`step`."""
        out, self._exports = self._exports, []
        return out

    def export_request_kv(self, request_id: str) -> dict:
        """Stage an occupied slot's KV state to the host and release the
        slot WITHOUT finishing the request — it continues on whichever
        engine imports the blob. The blob carries everything a decode
        needs to resume bit-identically: prompt + emitted tokens, the KV
        cursor, the next input token, per-slot sampling registers, the
        chained PRNG key, and the written pages of every cache leaf (by
        value). Raises KeyError for a request not occupying a slot
        (queued/mid-prefill requests have nothing worth shipping — cancel
        and resubmit those), and ValueError on a speculative engine (the
        draft arena is not shipped)."""
        if self.spec_k:
            raise ValueError(
                "export_request_kv on a speculative engine: the draft "
                "arena's KV is not shipped, so the import side could not "
                "verify drafts — disable spec_k or migrate by token "
                "resubmission instead")
        self._refuse_state("export_request_kv")
        for i, pend in self._owing():
            if pend.req.request_id == request_id:
                self._activate(i, self._late_outputs)   # admitted: take it
        slot = next((i for i, fl in enumerate(self._slots)
                     if fl is not None
                     and fl.req.request_id == request_id), None)
        if slot is None:
            raise KeyError(
                f"request {request_id!r} does not occupy a decode slot "
                "(only admitted requests have KV pages to export)")
        fl = self._slots[slot]
        req, sp = fl.req, fl.req.sampling
        bt = self.page_tokens
        kv_len = int(self._kv_lens[slot])
        nb = -(-kv_len // bt)
        pages = [int(self._tables[slot, j]) for j in range(nb)]
        idx = np.zeros(_page_bucket(nb), np.int32)
        idx[:nb] = pages
        idx = jnp.asarray(idx)
        leaves, _ = jax.tree_util.tree_flatten(self._cache)
        # graftlint: disable=host-sync — staging by value IS the point:
        # the blob must survive this engine (and this process).
        staged = [np.ascontiguousarray(
            np.asarray(_gather_pages_program(leaf, idx))[..., :nb, :, :])
            for leaf in leaves]
        blob = {
            "request_id": req.request_id,
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "emitted": [int(t) for t in fl.tokens],
            "kv_len": kv_len,
            "next_token": int(self._tokens[slot]),
            "key": np.array(self._keys[slot], np.uint32),
            "temperature": float(sp.temperature),
            "top_k": int(sp.top_k),
            "top_p": float(sp.top_p),
            "seed": int(req.seed),
            "tenant": req.tenant,
            "deadline_s": req.deadline_s,
            "trace_id": req.trace_id,
            "t_submit": fl.t_submit,
            "t_admit": fl.t_admit,
            "t_first": fl.t_first,
            "cached_prompt_tokens": fl.cached_prompt_tokens,
            "prefill_chunks": fl.prefill_chunks,
            "page_tokens": bt,
            "n_pages": nb,
            "kv_quant": self.kv_quant,
            "pages": staged,
        }
        # Release the slot WITHOUT the terminal path: no on_finish, no
        # completion stats — the request is alive, just elsewhere now.
        self._slots[slot] = None
        self._tokens[slot] = self.pad_id
        self._kv_lens[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._release_slot_pages(slot, fl.grow_left)
        if not fl.imported:
            self.queue.release(req)
        self.stats.record_disagg_export(
            pages=nb, nbytes=sum(v.nbytes for v in staged))
        self._record_pool_gauges()
        return blob

    def _refuse_state(self, what: str) -> None:
        """KV shipping moves pages; a model's per-slot state would stay
        behind. Refused by name until the blob carries it."""
        if self._state_rows:
            raise ValueError(
                f"{what}: a model with per-slot state ({self._state_names} "
                "leaves) cannot ship a request yet — the blob carries pages "
                "only; migrate by token resubmission instead")

    def _owing(self) -> list[tuple[int, _PendingPrefill]]:
        """(slot, pending record) of every slot whose final chunk has been
        dispatched and whose first token is still on the device."""
        return [(slot, pend) for slot, pend in self._pending.items()
                if pend.first is not None]

    def _free_slot(self) -> int | None:
        for slot in range(self.num_slots):
            if self._slots[slot] is None and slot not in self._pending:
                return slot
        return None

    def _import_need(self, blob: dict) -> tuple[int, int]:
        """(shipped pages, remaining growth reservation) an import costs.
        Growth is recomputed from scratch — the exporter may have already
        claimed growth pages it never wrote (they are not shipped), so
        its remaining reservation undercounts what this pool must hold."""
        nb = int(blob["n_pages"])
        total = -(-(len(blob["prompt"]) + int(blob["max_new_tokens"]) - 1)
                  // self.page_tokens)
        return nb, max(0, total - nb)

    def can_import(self, blob: dict) -> bool:
        """True when :meth:`import_request_kv` would succeed right now:
        not draining, page geometry matches, a free slot exists, and the
        pool covers the shipped pages plus remaining decode growth
        (evicting unpinned trie pages if that closes the gap)."""
        if (self._draining or self.spec_k or self._state_rows
                or int(blob["page_tokens"]) != self.page_tokens
                or blob.get("kv_quant") != self.kv_quant):
            return False
        if (len(blob["prompt"]) + int(blob["max_new_tokens"])
                > self.max_seq_len):
            return False
        if self._free_slot() is None:
            return False
        nb, grow = self._import_need(blob)
        while self.pool.available() < nb + grow:
            if (self.prefix_cache is None
                    or not self.prefix_cache.evict_lru_unpinned()):
                return False
        return True

    def import_request_kv(self, blob: dict,
                          request: Request | None = None) -> int:
        """Adopt an exported request: allocate pages under the
        ``imported`` owner tag, scatter the staged KV by value, install
        the slot registers, and resume decoding from the shipped cursor —
        bit-identical to the uninterrupted run (the chained PRNG key and
        next input token travel in the blob). *request* (optional) is the
        live Request object to attach — the in-process path passes it so
        streaming callbacks survive the hop; when None (the wire path) a
        fresh Request is rebuilt from the blob. Emitted tokens are NOT
        re-fired through ``on_token``. Returns the slot index; raises
        EngineDraining/ValueError/RuntimeError when the blob cannot be
        adopted here (gate with :meth:`can_import`)."""
        if self._draining:
            raise EngineDraining(
                f"engine{f' {self.replica_id!r}' if self.replica_id else ''}"
                " is draining — importing nothing new "
                f"(request {blob.get('request_id')})")
        if self.spec_k:
            raise ValueError(
                "import_request_kv on a speculative engine: the blob "
                "carries no draft-arena KV to verify drafts against")
        self._refuse_state("import_request_kv")
        if int(blob["page_tokens"]) != self.page_tokens:
            raise ValueError(
                f"page geometry mismatch: blob pages hold "
                f"{blob['page_tokens']} tokens, this pool's hold "
                f"{self.page_tokens} — disagg roles must share "
                "prefix_block_tokens/min_bucket")
        if blob.get("kv_quant") != self.kv_quant:
            raise ValueError(
                f"kv_quant mismatch: blob pages are "
                f"{blob.get('kv_quant') or 'fp'}, this pool is "
                f"{self.kv_quant or 'fp'} — disagg roles must share "
                "kv_quant (pages ship as raw arena values)")
        emitted = [int(t) for t in blob["emitted"]]
        if not emitted:
            raise ValueError("blob has no emitted tokens — nothing was "
                             "admitted, resubmit the prompt instead")
        req = request
        if req is None:
            req = Request(
                prompt=[int(t) for t in blob["prompt"]],
                max_new_tokens=int(blob["max_new_tokens"]),
                sampling=SamplingParams(
                    temperature=float(blob["temperature"]),
                    top_k=int(blob["top_k"]),
                    top_p=float(blob["top_p"])),
                request_id=str(blob["request_id"]),
                seed=int(blob["seed"]),
                tenant=blob.get("tenant") or "default",
                deadline_s=blob.get("deadline_s"),
                trace_id=blob.get("trace_id") or None)
        n = len(req.prompt)
        if self.eos_id is not None and emitted[-1] == self.eos_id:
            raise ValueError(
                f"request {req.request_id} already emitted EOS — it is "
                "terminal, not importable")
        if len(emitted) >= req.max_new_tokens:
            raise ValueError(
                f"request {req.request_id} already emitted "
                f"{len(emitted)}/{req.max_new_tokens} tokens — terminal, "
                "not importable")
        if n + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds this engine's max_seq_len ({self.max_seq_len})")
        slot = self._free_slot()
        if slot is None:
            raise RuntimeError("no free slot to import into — gate with "
                               "can_import()")
        nb, grow = self._import_need(blob)
        kv_len = int(blob["kv_len"])
        while self.pool.available() < nb + grow:
            if (self.prefix_cache is None
                    or not self.prefix_cache.evict_lru_unpinned()):
                raise RuntimeError(
                    f"pool cannot cover import: need {nb} shipped + "
                    f"{grow} growth pages, {self.pool.available()} "
                    "available — gate with can_import()")
        leaves, treedef = jax.tree_util.tree_flatten(self._cache)
        staged = blob["pages"]
        if len(staged) != len(leaves):
            raise ValueError(
                f"blob has {len(staged)} cache leaves, this engine's "
                f"pool has {len(leaves)} — different model geometry")
        pages = self.pool.alloc(nb, owner="imported")
        self.pool.reserve(grow)
        try:
            nbp = _page_bucket(nb)
            idx = np.zeros(nbp, np.int32)
            idx[:nb] = pages
            idx = jnp.asarray(idx)
            new_leaves = []
            nbytes = 0
            for leaf, vals in zip(leaves, staged):
                vals = np.asarray(vals)
                want = leaf.shape[:-3] + (nb,) + leaf.shape[-2:]
                if vals.shape != want:
                    raise ValueError(
                        f"staged leaf shape {vals.shape} != expected {want} "
                        "— different model geometry")
                nbytes += vals.nbytes
                if nbp != nb:
                    pad = np.zeros(vals.shape[:-3] + (nbp - nb,)
                                   + vals.shape[-2:], vals.dtype)
                    vals = np.concatenate([vals, pad], axis=-3)
                new_leaves.append(_scatter_pages_program(
                    leaf, jnp.asarray(vals, leaf.dtype), idx))
            self._cache = jax.tree_util.tree_unflatten(treedef, new_leaves)
        except Exception:
            # Roll the allocation back before re-raising: a geometry
            # mismatch (or a failed scatter) answers the caller with an
            # error while this engine keeps serving — without this, the
            # freshly alloc'd pages and growth reservation leaked on
            # every rejected import (transport maps ValueError to a 400
            # and carries on).
            for p in pages:
                self.pool.deref(int(p))
            self.pool.unreserve(grow)
            raise
        row = self._tables[slot]
        row[:] = 0
        row[:nb] = pages
        now = time.perf_counter()
        fl = _InFlight(req, emitted[0], now)
        fl.tokens = emitted
        fl.imported = True
        fl.grow_left = grow
        fl.t_submit = float(blob.get("t_submit") or now)
        fl.t_admit = float(blob.get("t_admit") or now)
        fl.t_first = float(blob.get("t_first") or now)
        fl.cached_prompt_tokens = int(blob.get("cached_prompt_tokens", 0))
        fl.prefill_chunks = int(blob.get("prefill_chunks", 0))
        req._t_submit = fl.t_submit
        req._finished = False        # re-arm the exactly-once latch
        self._slots[slot] = fl
        self._tokens[slot] = int(blob["next_token"])
        self._kv_lens[slot] = kv_len
        self._temps[slot] = req.sampling.temperature
        self._top_ks[slot] = req.sampling.top_k
        self._top_ps[slot] = req.sampling.top_p
        self._keys[slot] = np.asarray(blob["key"], np.uint32)
        self.stats.record_disagg_import(pages=nb, nbytes=nbytes)
        self._record_pool_gauges()
        return slot

    def step(self) -> list[RequestOutput]:
        """One serving iteration: advance every occupied slot one token
        and, under that decode, admit queued requests into free slots
        (page-budget permitting) and run at most ``prefill_chunk_tokens``
        real tokens of prefill work (unlimited when chunking is off).
        Returns the requests that finished during this iteration (possibly
        when their first token is taken: it is already EOS, or
        ``max_new_tokens == 1``).

        **The host never waits for a result with an empty device queue
        behind it, where it can help it.** The order of a step: deadline
        sweep → page growth → DISPATCH the decode of the occupied slots →
        take the first tokens that earlier final chunks owe (they lie
        before this decode in the device's queue) → admission and this
        step's prefill chunks (dispatched behind the decode) → only now
        block on the decode's tokens → emit → epilogue. A final chunk's
        first token is not waited for in the step that dispatches it: the
        slot stays a pending prefill (reserved, not decoded for) until the
        next step has its decode in the queue, and joins the decode after
        that. Where no decode will be dispatched behind it — no slot is
        occupied (the first request on an idle engine), the
        ``prefill_only`` role, :meth:`shutdown` — the token is read at
        once. An idle engine's first request therefore takes one call more
        than one decode per token; streams, finish reasons and sampling
        keys are what they were.

        Deadline enforcement happens here, at the decode boundary: an
        occupied or mid-prefill slot whose request's ``deadline_s`` has
        expired is cancelled FIRST (finish_reason "timeout", slot and
        pages freed — so the admission pass below can reuse both this
        very iteration), and an expired request popped from the queue
        completes as "timeout" without ever prefilling. A hung client
        therefore costs at most one decode iteration of slot time past
        its own budget, and never stalls the other slots.

        Every phase is a span of ``self.tracer``, nested under
        ``engine_step``: ``sweep`` (the deadline sweeps), ``grow``
        (decode-growth pages), ``decode`` (from the decode's dispatch to
        its tokens on the host — so the ``admission`` and ``prefill``
        spans of the step lie inside it when a decode runs),
        ``device_wait`` (ONLY the blocking reads of the device's results:
        ``kind`` ``decode`` / ``spec`` / ``first_token``; ``covered`` 1
        when a program was dispatched behind the awaited one before the
        wait began), ``emit`` (per-slot bookkeeping and ``on_token``
        after the fence) and ``epilogue``.

        Inside ``decode``, ``prefill`` and ``device_wait`` each call and
        each read has a span of its own, in the step's order:
        ``decode_call`` (the register copies and the jitted decode call —
        a speculative step's draft and verify calls — to its return;
        ``in_flight``) → per chunk, inside its ``prefill``:
        ``chunk_operands`` (the numpy operands and scalars) → on a final
        chunk ``first_key`` (the request's first sampling key made on the
        host, :meth:`_first_key`: microseconds and no device program;
        ``in_flight``) → ``chunk_call`` (the jitted chunk call alone;
        ``program`` = the ``attention_impls`` key, ``chunk_512`` /
        ``final_chunk_512``, ``in_flight``; with a draft model a second
        one, ``draft=1``) → with a trie, on a final chunk ``trie_adopt`` →
        inside the ``device_wait``: ``fetch_tokens`` → ``fetch_counts``
        (one per intermediate chunk whose counts are due; the
        ``prefill_counts`` record follows it, outside it) →
        ``fetch_keys`` (a first token's fence reads its key before the
        counts). ``in_flight`` is :meth:`_in_flight`: the programs
        dispatched since the newest one a wait was made for, an upper
        bound on what lies ahead in the device's queue."""
        with self.tracer.span("engine_step", step=self.stats.steps):
            return self._step()

    # graftlint: hot-path
    def _step(self) -> list[RequestOutput]:
        outputs, self._late_outputs = self._late_outputs, []
        with self.tracer.span("sweep"):
            now = time.perf_counter()
            # A slot that owes its first token is swept as the decoding
            # request it is: take the token, then let the deadline end it.
            for slot, pend in self._owing():
                if self._expired(pend.req, now):
                    self._activate(slot, outputs)
            for slot, fl in enumerate(self._slots):
                if fl is not None and self._expired(fl.req, now):
                    outputs.append(self._finish(slot, "timeout"))
            for slot in list(self._pending):
                if self._expired(self._pending[slot].req, now):
                    outputs.append(self._cancel_pending(slot, "timeout"))
            # Queue-time deadline sweep: requests already dead stop
            # consuming queue capacity (and their tenant's EDF head) NOW,
            # not when a free slot happens to pop them.
            for req in self.queue.sweep_expired(now):
                outputs.append(self._timeout_unadmitted(req))
        self.last_step_prefill_tokens = 0
        self._step_prefill_budget = self.prefill_chunk_tokens
        # The rows this step decodes for: the slots occupied NOW. A slot
        # activated further down joins the next step's.
        rows = ([] if self.prefill_only else
                [slot for slot, fl in enumerate(self._slots)
                 if fl is not None])
        if not rows:
            self._admissions(outputs)
            if self.prefill_only:
                # Disaggregated prefill role: every slot that completed
                # admission this step is exported instead of decoded.
                # Requests that finished AT admission (EOS first token /
                # 1-token budget) are already terminal in ``outputs`` and
                # never ship.
                for slot, fl in enumerate(self._slots):
                    if fl is not None:
                        self._exports.append(
                            self.export_request_kv(fl.req.request_id))
            self._step_epilogue()
            return outputs
        # Decode-growth pages: a slot whose next write positions cross
        # into unmapped blocks claims from ITS reserved pages —
        # infallible by construction (reserved at admission), so growth
        # can never be starved by other admissions. A speculative step
        # writes up to spec_k positions past the cursor, but never past
        # the request's own budget (position n + max_new - 2 is the last
        # one any emitted token can occupy) — writes beyond that land in
        # the scratch page and the garbage selections they feed are
        # provably never emitted.
        with self.tracer.span("grow"):
            for slot in rows:
                fl = self._slots[slot]
                last = int(self._kv_lens[slot])
                if self.spec_k:
                    limit = len(fl.req.prompt) + fl.req.max_new_tokens - 2
                    last = min(last + self.spec_k, limit)
                for blk in range(int(self._kv_lens[slot]) // self.page_tokens,
                                 last // self.page_tokens + 1):
                    if self._tables[slot, blk] == 0:
                        self._tables[slot, blk] = (
                            self.pool.alloc_reserved(1)[0])
                        fl.grow_left -= 1
        inj = _faults.active()
        if inj is not None:
            inj.fire("serve_decode")
        flight_on = self.flight is not None and self.flight.enabled
        t_dec = time.perf_counter() if flight_on else 0.0
        if self.spec_k:
            self._spec_decode(rows, outputs)
        else:
            self._decode(rows, outputs)
        if flight_on:
            self._last_decode_ms = round(
                (time.perf_counter() - t_dec) * 1e3, 3)
        self._step_epilogue()
        return outputs

    # graftlint: hot-path
    def _admissions(self, outputs: list[RequestOutput]) -> None:
        """The step's admission work — under the decode, where one runs:
        take the first tokens earlier final chunks owe, then admit and
        prefill within the step's token budget."""
        for slot, _ in self._owing():
            self._activate(slot, outputs)
        flight_on = self.flight is not None and self.flight.enabled
        t_pf = time.perf_counter() if flight_on else 0.0
        # Admission and prefill alternate until neither makes progress:
        # a request that finishes AT admission (first token is EOS /
        # max_new_tokens == 1) frees its slot AND its pages for the next
        # queued request within the same iteration, budget permitting.
        while True:
            self._admit_free_slots(outputs)
            freed = self._run_prefills(outputs)
            if not (freed and len(self.queue)):
                break
        if flight_on and self.last_step_prefill_tokens:
            self._last_prefill_ms = round(
                (time.perf_counter() - t_pf) * 1e3, 3)

    def _wait(self, kind: str, seq: int):
        """The ``device_wait`` span around the blocking read of what program
        number *seq* hands back; counts whether the wait is covered."""
        covered = int(self._dispatches > seq)
        self.stats.record_fence(covered)
        # Nothing is dispatched inside a wait: by the next dispatch this
        # read has returned, and programs 1..seq are done.
        self._fenced = max(self._fenced, seq)
        return self.tracer.span("device_wait", kind=kind, covered=covered)

    def _in_flight(self) -> int:
        """The ``in_flight`` field of a ``decode_call`` / ``chunk_call`` /
        ``first_key`` span: programs dispatched since the newest one a wait
        has been made for, read BEFORE the span's own dispatch — what may
        lie ahead of it in the device's queue. An UPPER bound: the queue is
        in order, so everything up to the awaited program is done, and what
        was dispatched behind it may be done too. 0 on the first dispatch
        after a decode-only step's fence; 1 for a chunk dispatched behind
        this step's decode."""
        return self._dispatches - self._fenced

    def _count_sampled_rows(self) -> int:
        """Rows of the register file that sample (``_temps > 0``: a freed
        slot's is reset, a request's is set when it is activated), counted
        where a decode or spec-verify program is about to be dispatched. The
        program sees the same operand: with none it skips the sampler's sort
        (:func:`_sample_slots`)."""
        n = int(np.count_nonzero(self._temps > 0.0))
        self.stats.record_sampler_step(n)
        return n

    # graftlint: hot-path
    def _decode(self, rows: list[int], outputs: list[RequestOutput]) -> None:
        """Advance the occupied slots *rows* one token: one dispatch, the
        step's admission work behind it, one fence, then the host-side
        bookkeeping per slot."""
        active = len(rows)
        # rows: the live rows; context_tokens: the positions they attend in
        # all (a row at cursor n attends n + 1; other slots' cursors are 0);
        # sampled_rows: the rows the program's sampler sorts for, if any.
        with self.tracer.span(
                "decode", active=active, rows=active,
                context_tokens=int(self._kv_lens.sum()) + active,
                sampled_rows=self._count_sampled_rows(),
                **self._state_update_fields()) as span:
            with self.tracer.span("decode_call",
                                  in_flight=self._in_flight()):
                nxt, keys, self._cache = self._decode_step()
            seq = self._dispatches
            self._admissions(outputs)
            with self._wait("decode", seq):
                with self.tracer.span("fetch_tokens"):
                    # graftlint: disable=host-sync — the iteration's one
                    # honest sync: every slot's sampled token in one fence.
                    nxt = np.asarray(nxt)
                if nxt.size > self.num_slots:
                    # (a disabled tracer's span keeps no fields: the
                    # counters alone)
                    self._record_counts(getattr(span, "fields", {}),
                                        nxt[self.num_slots:])
                self._take_chunk_counts(seq)
                with self.tracer.span("fetch_keys"):
                    # graftlint: disable=host-sync — rides the same fence
                    keys = np.asarray(keys)
                # Only the decoded rows' keys: a slot activated under this
                # decode has had its own written since the dispatch.
                self._keys[rows] = keys[rows]
        with self.tracer.span("emit"):
            self.stats.record_step(active, self.num_slots)
            for slot in rows:
                fl = self._slots[slot]
                tok = int(nxt[slot])
                # The PREVIOUS token was just written at kv_lens; the
                # freshly sampled one becomes the next step's input.
                self._kv_lens[slot] += 1
                self._tokens[slot] = tok
                fl.tokens.append(tok)
                if fl.req.on_token is not None:
                    fl.req.on_token(tok)
                if self.eos_id is not None and tok == self.eos_id:
                    outputs.append(self._finish(slot, "eos"))
                elif len(fl.tokens) >= fl.req.max_new_tokens:
                    outputs.append(self._finish(slot, "length"))

    # graftlint: hot-path
    def _spec_decode(self, rows: list[int],
                     outputs: list[RequestOutput]) -> None:
        """One speculative serving iteration: ``spec_k`` greedy draft
        proposals per slot (scanned into one dispatch over the draft
        model's sibling paged cache), ONE multi-token verify pass through
        the target model, the step's admission work behind them, then
        host-side accept bookkeeping. Each slot
        emits the longest prefix of drafts matching the target's own
        selections plus the target's correction/bonus token (1 to
        spec_k + 1 tokens) — bit-identical to non-speculative decoding
        for every sampling config, because the accept rule is exact
        match against the target selection drawn with the slot's chained
        key (see :func:`_spec_verify_program`). Rollback is cursor
        truncation: rejected drafts stay in pages beyond the advanced
        cursor, never attended, overwritten in place by the next window
        before anything reads them."""
        with self.tracer.span("decode", active=len(rows),
                              spec_k=self.spec_k,
                              sampled_rows=self._count_sampled_rows()):
            with self.tracer.span("decode_call",
                                  in_flight=self._in_flight()):
                regs = self._registers()
                window, self._draft_cache = self._spec_draft_step(regs)
                sel, key_states, acc, self._cache = self._spec_verify_step(
                    window, regs)
            seq = self._dispatches
            self._admissions(outputs)
            with self._wait("spec", seq):
                with self.tracer.span("fetch_tokens"):
                    # graftlint: disable=host-sync — the iteration's one
                    # honest sync: every slot's window/selections in one
                    # fence.
                    window = np.asarray(window)
                    # graftlint: disable=host-sync — rides the same fence
                    sel = np.asarray(sel)
                    # graftlint: disable=host-sync — rides the same fence
                    acc = np.asarray(acc)
                with self.tracer.span("fetch_keys"):
                    # np.array (copy): the key register is written in place
                    # at admissions, and only the emitted-count column
                    # survives.
                    # graftlint: disable=host-sync — rides the same fence
                    key_states = np.array(key_states)
                self._take_chunk_counts(seq)
        with self.tracer.span("emit"):
            self._spec_emit(rows, outputs, window, sel, acc, key_states)

    def _spec_emit(self, rows: list[int], outputs: list[RequestOutput],
                   window, sel, acc, key_states) -> None:
        """The host-side accept bookkeeping of one speculative step."""
        emitted_total = 0
        proposed = 0
        accepted_counts: list[int] = []
        for slot in rows:
            fl = self._slots[slot]
            a = int(acc[slot])
            # Candidates in emission order: the accepted drafts, then the
            # target's correction (a < k) or bonus (a == k) token.
            cand = [int(window[slot, i]) for i in range(1, a + 1)]
            cand.append(int(sel[slot, a]))
            proposed += self.spec_k
            m = 0
            finished = None
            for tok in cand:
                m += 1
                fl.tokens.append(tok)
                if fl.req.on_token is not None:
                    fl.req.on_token(tok)
                if self.eos_id is not None and tok == self.eos_id:
                    finished = "eos"
                    break
                if len(fl.tokens) >= fl.req.max_new_tokens:
                    finished = "length"
                    break
            # Drafts among the emitted tokens (the final candidate is the
            # target's own selection, not a draft).
            acc_emitted = min(m, a)
            accepted_counts.append(acc_emitted)
            fl.spec_proposed += self.spec_k
            fl.spec_accepted += acc_emitted
            emitted_total += m
            # Cursor advance IS the accept/rollback: the m emitted
            # tokens' KV (all written this step) become live; everything
            # beyond kv_lens + m is dead by the col <= cursor mask.
            self._kv_lens[slot] += m
            self._tokens[slot] = cand[m - 1]
            self._keys[slot] = key_states[slot, m - 1]
            if finished is not None:
                outputs.append(self._finish(slot, finished))
        self.stats.record_step(len(rows), self.num_slots,
                               tokens=emitted_total)
        self.stats.record_spec_step(proposed, accepted_counts)

    def run(self, requests: Iterable[Request] | None = None,
            max_steps: int | None = None) -> list[RequestOutput]:
        """Submit *requests* (optional) and step until queue, prefills and
        slots are all drained. Returns outputs in completion order.

        Requests are FED as capacity frees rather than submitted upfront:
        a list longer than the queue bound pauses the feed on QueueFull
        and resumes after completions, instead of raising mid-run."""
        feed: deque[Request] = (deque(requests) if requests is not None
                                else deque())
        outputs: list[RequestOutput] = []
        steps = 0
        while True:
            while feed:
                try:
                    self.submit(feed[0])
                except QueueFull:
                    break            # back-pressure: resume after this step
                feed.popleft()
            if not (self.busy() or feed):
                break
            outs = self.step()
            outputs.extend(outs)
            if (not outs and len(self.queue) and not self._pending
                    and not any(s is not None for s in self._slots)):
                # Every queued tenant is rate-limited right now: nothing
                # decodes, so yield briefly while the buckets refill.
                time.sleep(0.001)
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return outputs

    def shutdown(self) -> list[RequestOutput]:
        """Abort everything: queued requests (no tokens), mid-prefill
        requests (pinned trie segments released, pages freed) and
        in-flight requests (partial tokens) all complete with
        finish_reason "aborted". The engine is reusable afterwards."""
        outs, self._late_outputs = self._late_outputs, []
        for slot, _ in self._owing():
            self._activate(slot, outs)
        now = time.perf_counter()
        for req in self.queue.drain():
            t0 = req._t_submit if req._t_submit is not None else now
            out = RequestOutput(
                request_id=req.request_id, prompt_len=len(req.prompt),
                tokens=[], finish_reason="aborted", queue_s=now - t0,
                ttft_s=None, latency_s=now - t0)
            outs.append(out)
            self._emit_request_trace(req, out)
            self._notify_finish(req, "aborted")
        for slot in list(self._pending):
            outs.append(self._cancel_pending(slot, "aborted"))
        for slot, fl in enumerate(self._slots):
            if fl is not None:
                outs.append(self._finish(slot, "aborted"))
        # Leak guard: everything above released its pages; anything still
        # live (after flushing the trie's cache retention) is a leak.
        # Runs on every shutdown — the breaker-trip evacuation path and
        # plain teardown both get the check for free.
        self._check_page_leaks("shutdown")
        return outs

    # ------------------------------------------------- program dispatch
    # The ONE seam between tp=0 (module-level jit programs, shared across
    # engines in the process) and tp>=1 (per-engine shard_map'd programs
    # over self._mesh). Signatures and semantics are identical on both
    # sides — everything above this seam (admission, trie, chunked
    # prefill, growth, migration, spec bookkeeping) is mode-blind.

    # Every dispatch counts itself (``_dispatches``): the number a program
    # got tells a later wait what lies behind it in the device's queue.

    def _registers(self) -> tuple:
        """COPIES of the per-slot register file (tokens, cursors, tables,
        temperatures, top-k, top-p, keys) for a program that runs while the
        host goes on writing the registers: jit may alias a numpy operand
        (the CPU backend does, zero-copy, when the buffer is aligned) or
        copy it to the device after the call returned, so what a dispatched
        program reads must not change under it. A few tens of KB a step."""
        return tuple(r.copy() for r in (
            self._tokens, self._kv_lens, self._tables, self._temps,
            self._top_ks, self._top_ps, self._keys))

    # graftlint: hot-path
    def _decode_step(self):
        self._dispatches += 1
        if self.tp:
            return self._tp_programs.decode(
                self.params, self._cache, *self._registers())
        return _decode_program(
            self.model, self.params, self._cache, *self._registers())

    # graftlint: hot-path
    def _spec_draft_step(self, regs: tuple):
        self._dispatches += 1
        tokens, kv_lens, tables = regs[:3]
        if self.tp:
            return self._tp_draft_programs.spec_draft(
                self.draft_params, self._draft_cache, tokens, kv_lens,
                tables)
        return _spec_draft_program(
            self.draft_model, self.draft_params, self._draft_cache,
            tokens, kv_lens, tables, steps=self.spec_k + 1)

    # graftlint: hot-path
    def _spec_verify_step(self, window, regs: tuple):
        self._dispatches += 1
        if self.tp:
            return self._tp_programs.spec_verify(
                self.params, self._cache, window, *regs[1:])
        return _spec_verify_program(
            self.model, self.params, self._cache, window, *regs[1:])

    def _state_update_fields(self) -> dict:
        """The ``state_rows`` / ``state_bytes_moved`` fields of a ``decode``
        span, for a model with per-slot state: the rows the step about to be
        dispatched advances (the slots with a cursor — what the program's own
        mask sees) and their state read and written once. No field for a
        model of pages only."""
        if not self._state_rows:
            return {}
        rows = int(np.count_nonzero(self._kv_lens > 0))
        self.stats.record_state_update(rows)
        return {"state_rows": rows,
                "state_bytes_moved": 2 * rows * self._slot_state_nbytes}

    def _state_slot(self, slot: int):
        """The chunk programs' ``slot`` operand: the arena row of *slot* for
        a model with per-slot state, None (no operand at all) otherwise."""
        return np.int32(slot) if self._state_rows else None

    @staticmethod
    def _first_key(seed: int) -> np.ndarray:
        """The final chunk's ``key`` operand: the request's first sampling
        key, bit for bit ``jax.random.PRNGKey(seed)`` under ``threefry2x32``
        (the constructor refuses any other implementation), made on the
        host: that call is two device programs and a read of their result,
        behind whatever the device has queued. As there, a seed beyond int64
        overflows, and without ``jax_enable_x64`` it is narrowed to 32 bits
        first, so the key's high word is 0."""
        seed = int(np.int64(seed))
        high = seed >> 32 if jax.config.jax_enable_x64 else 0
        return np.array([high & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)

    def _chunk_step(self, chunk, table, start, *, slot=None,
                    draft: bool = False):
        self._dispatches += 1
        if draft:
            if self.tp:
                return self._tp_draft_programs.chunk(
                    self.draft_params, self._draft_cache, chunk, table,
                    start)
            return _chunk_program(self.draft_model, self.draft_params,
                                  self._draft_cache, chunk, table, start)
        if self.tp:
            return self._tp_programs.chunk(
                self.params, self._cache, chunk, table, start)
        return _chunk_program(self.model, self.params, self._cache, chunk,
                              table, start, slot)

    def _final_chunk_step(self, chunk, table, start, length, temp, top_k,
                          top_p, key, slot=None):
        self._dispatches += 1
        if self.tp:
            return self._tp_programs.final_chunk(
                self.params, self._cache, chunk, table, start, length,
                temp, top_k, top_p, key)
        return _final_chunk_program(
            self.model, self.params, self._cache, chunk, table, start,
            length, temp, top_k, top_p, key, slot)

    def decode_cache_size(self) -> int:
        """Compiled-program count of the decode step (jit cache entries —
        shared across engines at tp=0, per-engine under tp) — the
        instrumentation behind the compiles-once acceptance test: run a
        workload, take the delta."""
        if self.tp:
            return self._tp_programs.decode._cache_size()
        return _decode_program._cache_size()

    @staticmethod
    def prefill_cache_size() -> int:
        """Compiled-program count of the final-chunk prefill step (≤ one
        per bucket — the same budget the monolithic prefill had)."""
        return _final_chunk_program._cache_size()

    @staticmethod
    def chunk_cache_size() -> int:
        """Compiled-program count of the intermediate-chunk step (≤ one
        per distinct chunk width)."""
        return _chunk_program._cache_size()

    @staticmethod
    def spec_cache_size() -> int:
        """Compiled-program count of the speculative draft + verify pair
        (one entry each per (model, spec_k) — the compiles-once check for
        the speculative path)."""
        return (_spec_draft_program._cache_size()
                + _spec_verify_program._cache_size())

    # ----------------------------------------------------------- internals

    @staticmethod
    def _expired(req: Request, now: float) -> bool:
        return (req.deadline_s is not None and req._t_submit is not None
                and now - req._t_submit > req.deadline_s)

    @staticmethod
    def _notify_finish(req: Request, reason: str) -> None:
        """Fire ``on_finish`` EXACTLY once per submission. Every terminal
        path funnels through here: shutdown racing a deadline expiry (or
        a second shutdown) must not tell a streaming client its request
        ended twice. The latch re-arms on resubmit."""
        if req._finished:
            return
        req._finished = True
        if req.on_finish is not None:
            req.on_finish(reason)

    def _take_chunk_counts(self, seq: int) -> None:
        """Inside a ``device_wait`` that has just returned what program
        number *seq* handed back: the intermediate chunks DISPATCHED BEFORE
        that program had no fence of their own, and are done now. Their
        counts are fetched here (one small copy a chunk). A chunk dispatched
        BEHIND the awaited program (this step's, behind this step's decode)
        is still running or queued: reading its counts here would wait for
        it with nothing queued behind it. It is read at the next step's
        fence."""
        while self._chunk_counts and self._chunk_counts[0][0] < seq:
            _, fields, counts = self._chunk_counts.popleft()
            with self.tracer.span("fetch_counts"):
                # graftlint: disable=host-sync — ran before the awaited one
                counts = np.asarray(counts)
            self._chunk_counts_record(fields, counts)

    def _chunk_counts_record(self, fields: dict, counts: np.ndarray) -> None:
        """A chunk's counts, known only after its ``prefill`` span closed:
        a ``prefill_counts`` record of their own beside the call's *fields*
        (``tokens``, ``start``, ``request_id``; ``chunk`` or, for a final
        one, ``bucket``) — nothing is written into a span that has
        closed."""
        self._record_counts(fields, counts)
        with self.tracer.span("prefill_counts", **fields):
            pass

    def _record_counts(self, fields: dict, counts: np.ndarray) -> None:
        """What a program's expert layers counted (:func:`_with_counts`: the
        decode and final-chunk programs hand it back BEHIND their tokens, so
        it rides their fetch), as the three numbers *fields* and the
        counters carry: ``moe_assignments`` (picks that landed on held
        experts, over every row the call computed), ``moe_experts_touched``
        (held experts with at least one row, summed over layers) and
        ``moe_max_rows`` (the fullest expert)."""
        a, touched, mx = (int(counts.sum()), int(np.count_nonzero(counts)),
                          int(counts.max()))
        self.stats.record_moe(a, touched, mx)
        fields.update(moe_assignments=a, moe_experts_touched=touched,
                      moe_max_rows=mx)

    def _record_pool_gauges(self, counters: dict | None = None) -> None:
        c = counters if counters is not None else self.pool.counters()
        self.stats.record_kv_pool(c["pages_total"], c["pages_used"],
                                  c["pages_shared"],
                                  by_owner=self.pool.owners_summary())

    def _step_epilogue(self) -> None:
        """Every :meth:`step` return path funnels here: refresh the pool
        gauges, append this step's flight-recorder snapshot, and — once a
        draining engine runs out of work — run the one-shot drain
        finalization (page-leak check + flight dump). The ``epilogue``
        span's fields are the step's gauges (the pool's fill among them; for
        a model with per-slot state also ``state_slots`` and ``state_bytes``),
        read before it opens: nothing writes to a span after the fact."""
        c = self.pool.counters()
        active = self.occupied_slots()
        state = {}
        if self._state_rows:
            # slots a request holds (decoding or mid-prefill) x bytes a slot
            held = active + len(self._pending)
            state = dict(state_slots=held,
                         state_bytes=held * self._slot_state_nbytes)
            self.stats.record_state(**state)
        with self.tracer.span(
                "epilogue", active=active,
                queued=len(self.queue),
                prefill_tokens=self.last_step_prefill_tokens,
                pages_used=c["pages_used"], pages_total=c["pages_total"],
                **state):
            self._epilogue(c)

    def _epilogue(self, counters: dict) -> None:
        self._record_pool_gauges(counters)
        fr = self.flight
        if fr is not None and fr.enabled:
            depths = getattr(self.queue, "depths", None)
            s = self.stats
            fr.record(
                f"engine:{self.replica_id or 'serve'}",
                step=s.steps,
                queued=len(self.queue),
                tenant_depths=depths() if depths is not None else {},
                pending_prefills=len(self._pending),
                occupied_slots=self.occupied_slots(),
                pool={"used": s.kv_pages_used, "total": s.kv_pages_total,
                      "shared": s.kv_pages_shared,
                      "reserved": self.pool.reserved},
                pool_owners=dict(s.kv_pages_by_owner),
                spec_proposed=s.spec_proposed_tokens,
                spec_accepted=s.spec_accepted_tokens,
                last_decode_ms=self._last_decode_ms,
                last_prefill_ms=self._last_prefill_ms,
                draining=self._draining)
        if self._draining and not self._drain_finalized and not self.busy():
            self._drain_finalized = True
            leak = self._check_page_leaks("drain")
            if fr is not None:
                fr.dump("drain", extra=self._flight_extra(leak))

    def _release_trie_page(self, page: int) -> None:
        """Trie eviction callback: drop the trie's pool reference and,
        when a decode slot still maps the page, hand the ledger
        attribution back to it (the slot's reference now owns the
        lifetime)."""
        self.pool.deref(page)
        if self.pool.refcount(page):
            self.pool.tag(page, "slot")

    def _check_page_leaks(self, origin: str) -> dict | None:
        """Drain/shutdown leak guard: once every request is terminal,
        nothing is pinned — flush the prefix trie (a cache is retention,
        not a leak; cold is correct on a replica about to die), then any
        page still live or reservation still outstanding is a genuine
        accounting leak. Emits a registry-checked ``kv_page_leak`` event
        with by-owner attribution and returns the leak record (None when
        clean)."""
        if self.prefix_cache is not None:
            while self.prefix_cache.evict_lru_unpinned():
                pass
        self._record_pool_gauges()
        c = self.pool.counters()
        if not c["pages_used"] and not self.pool.reserved:
            return None
        info = {"origin": origin,
                "replica": self.replica_id,
                "pages_leaked": c["pages_used"],
                "pages_reserved": self.pool.reserved,
                "by_owner": self.pool.owners_summary(),
                "pages_held": self.pool.held_pages()}
        if self.request_log is not None:
            self.request_log.emit("kv_page_leak", **info)
        return info

    def _flight_extra(self, leak: dict | None = None) -> dict:
        """Terminal context stamped into a flight-dump header: who holds
        the pool right now, by owner class and by page id."""
        extra = {"replica": self.replica_id,
                 "pool": self.pool.counters(),
                 "pages_by_owner": self.pool.owners_summary(),
                 "pages_held": self.pool.held_pages()}
        if leak is not None:
            extra["leak"] = leak
        return extra

    def _on_fault(self, site: str, action: str) -> None:
        """faults.add_fire_hook callback: an injected fault is about to
        execute (possibly ``os._exit``) — capture the black box NOW."""
        if self.flight is not None:
            self.flight.dump("fault", extra={
                "site": site, "action": action, **self._flight_extra()})

    def _timeout_unadmitted(self, req: Request) -> RequestOutput:
        """Terminal output for a request whose deadline expired while it
        was still queued — no slot, no tokens, no prefill spent on it."""
        now = time.perf_counter()
        t0 = req._t_submit if req._t_submit is not None else now
        out = RequestOutput(
            request_id=req.request_id, prompt_len=len(req.prompt),
            tokens=[], finish_reason="timeout", queue_s=now - t0,
            ttft_s=None, latency_s=now - t0)
        self._emit_request_trace(req, out)
        self._notify_finish(req, "timeout")
        return out

    def _sampled(self, request_id: str) -> bool:
        """Deterministic per-request sampling decision: a pure hash of the
        request id, so the same request traces (or doesn't) on every
        replica and rerun — correlatable across logs, and testable."""
        s = self.request_trace_sample
        if s <= 0.0 or self.request_log is None:
            return False
        if s >= 1.0:
            return True
        return zlib.crc32(request_id.encode()) < s * 2 ** 32

    def _emit_request_trace(self, req: Request, out: RequestOutput) -> None:
        """The lifecycle funnel: every terminal path (_finish,
        _cancel_pending, _timeout_unadmitted, shutdown's queued drain)
        lands here with the finished RequestOutput; sampled requests emit
        one ``request_trace`` JSONL event tying the whole journey —
        submit → queue → prefill chunks → decode → finish — to the
        request_id."""
        if not self._sampled(out.request_id):
            return
        n = len(out.tokens)
        priority = getattr(self.queue, "priority_of", None)
        self.request_log.emit(
            "request_trace",
            request_id=out.request_id,
            trace_id=req.trace_id,
            replica=self.replica_id,
            migrated_from=req.migrated_from,
            tenant=req.tenant,
            priority=priority(req.tenant) if priority is not None else None,
            prompt_len=out.prompt_len,
            cached_prompt_tokens=out.cached_prompt_tokens,
            prefill_chunks=out.prefill_chunks,
            queue_ms=round(out.queue_s * 1e3, 3),
            ttft_ms=(round(out.ttft_s * 1e3, 3)
                     if out.ttft_s is not None else None),
            latency_ms=round(out.latency_s * 1e3, 3),
            new_tokens=n,
            decode_steps=max(0, n - 1),
            tokens_per_s=(round(n / out.latency_s, 1)
                          if n and out.latency_s > 0 else None),
            spec_proposed=out.spec_proposed,
            spec_accepted=out.spec_accepted,
            kv_quant=self.kv_quant,
            weight_quant=self.weight_quant,
            finish_reason=out.finish_reason)
        self.stats.record_request_trace()

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_seq_len)

    def attention_impls(self) -> dict[str, str]:
        """Which paged-attention implementation each serving program
        resolves to (``"paged_flash"``, ``"latent_flash"`` for a latent pool,
        or ``"xla"``), keyed by program and
        query width: decode, the speculative verify window, the
        intermediate prefill chunk and every final-chunk bucket this
        engine can compile. Asks the model's own rule
        (``transformer.paged_attention_impl``), so it cannot drift from
        what the programs trace. Beside ``paged_flash`` stands the kernel's
        grid as its own rules set it for that program's call: for a call cut
        into blocks of queries (wider than 128 tokens a row:
        ``default_query_block``) the block, ``q_block=Q``; then the pages a
        cell attends (``default_pages_per_cell``) and the cells a call steps
        (rows x query blocks x cells a row). For a
        model with expert layers each entry ends in the dispatch that
        program's rows take (``experts=grouped`` / ``experts=dense``:
        :func:`models.moe.serving_dispatch`), and for one with state-space
        layers in what its one-token state update runs as (``ssm=kernel`` /
        ``ssm=xla``: :func:`models.transformer.ssm_update_impl`)."""
        slots = self.num_slots
        programs = {"decode": (1, slots)}        # name -> (sq, batch rows)
        if self.spec_k:
            programs["spec_verify"] = (self.spec_k + 1, slots)
        c = self.prefill_chunk_tokens
        if c:
            programs[f"chunk_{c}"] = (c, 1)
        top = self._bucket(c or self.max_seq_len)
        b = self.min_bucket
        while b < top:
            programs[f"final_chunk_{b}"] = (b, 1)
            b *= 2
        programs[f"final_chunk_{top}"] = (top, 1)
        cfg = self.model.cfg
        quant = self.kv_quant == "int8"
        q_itemsize = jnp.dtype(cfg.dtype).itemsize
        shard = max(self.tp, 1)                  # heads are split over tp
        latent = any(_leaf_name(path) == "cached_latent"
                     for path, _ in self._pool_rows(self._row_shapes))

        def report(sq: int, rows: int) -> str:
            if latent:
                # a latent pool: the absorbed kernel at decode widths, the
                # expanded one for a chunk, else XLA
                impl = transformer.latent_attention_impl(cfg)
                if impl != "latent_flash":
                    return impl
                if sq > pallas_latent_attn.MAX_QUERY_TOKENS:
                    return (f"{impl} expanded block_k="
                            f"{pallas_latent_attn.CHUNK_BLOCK_K} heads_per_cell="
                            f"{pallas_latent_attn.CHUNK_HEADS}")
                pages = pallas_latent_attn.default_pages_per_cell(
                    self.page_tokens, self.max_blocks)
                return (f"{impl} pages_per_cell={pages} "
                        f"cells={rows * -(-self.max_blocks // pages)}")
            impl = transformer.paged_attention_impl(cfg, sq)
            if impl != "paged_flash":
                return impl
            qb = pallas_paged_attn.default_query_block(
                sq, cfg.n_heads // cfg.resolved_kv_heads)
            pages = pallas_paged_attn.default_pages_per_cell(
                sq=qb, heads=cfg.n_heads // shard,
                hd=cfg.resolved_head_dim, page_tokens=self.page_tokens,
                kvhd=cfg.resolved_kv_heads * cfg.resolved_head_dim // shard,
                kv_itemsize=1 if quant else q_itemsize,
                q_itemsize=q_itemsize, n_blocks=self.max_blocks,
                quant=quant)
            cells = rows * (sq // qb) * -(-self.max_blocks // pages)
            return (impl + (f" q_block={qb}" if qb != sq else "")
                    + f" pages_per_cell={pages} cells={cells}")
        moe = moe_lib.moe_config_of(self.model)

        def experts(tokens: int) -> str:
            return ("" if moe is None else
                    f" experts={moe_lib.serving_dispatch(tokens, moe)}")
        mamba = transformer.mamba_config_of(self.model)
        ssm = ("" if mamba is None
               else f" ssm={transformer.ssm_update_impl(mamba)}")
        return {name: report(sq, rows) + experts(sq * rows) + ssm
                for name, (sq, rows) in programs.items()}

    def _fits(self, req: Request) -> bool:
        """Admission-time page probe (the scheduler calls this on its
        chosen head before popping): can the pool cover the request's
        worst-case need right now? Trie-only pages are reclaimable — evict
        unpinned LRU leaves until the request fits or the trie runs dry.
        False defers the request in place: no pop, no starvation (pages
        free monotonically as running slots finish)."""
        need = self._need_pages(req)
        while self.pool.available() < need:
            if (self.prefix_cache is None
                    or not self.prefix_cache.evict_lru_unpinned()):
                return False
        return True

    def _admit_free_slots(self, outputs: list[RequestOutput]) -> None:
        """Pop queued requests into free, non-pending slots (expired ones
        complete as "timeout" without costing prefill). ``pop() -> None``
        with a non-empty queue means every queued tenant is rate-,
        quota- or PAGE-blocked right now — no slot will do better, so
        stop."""
        for slot in range(self.num_slots):
            while (self._slots[slot] is None and slot not in self._pending
                   and len(self.queue)):
                req = self.queue.pop(fits=self._fits)
                if req is None:
                    return
                if self._expired(req, time.perf_counter()):
                    self.queue.release(req)   # popped = slot reserved
                    outputs.append(self._timeout_unadmitted(req))
                    continue        # expired in queue; try the next one
                self._begin_admission(slot, req)
                break

    def _begin_admission(self, slot: int, req: Request) -> None:
        """Reserve *slot* for *req*: map the longest trie-cached prefix
        into a PRIVATE block-table row (ZERO device copies — each matched
        node's page is ref'd and written into the row), allocate private
        pages for the uncached prompt tail, reserve worst-case decode
        growth, and park it as a pending prefill for :meth:`_run_prefills`.
        The row is installed engine-wide only at :meth:`_activate`
        — until then the slot stays all-scratch in ``self._tables`` so the
        decode program's rider write for this (stale-cursor) slot lands in
        the scratch page, not in the half-prefilled prompt.
        Allocation cannot fail here: the scheduler's ``fits`` probe
        guaranteed the (hit-blind, hence conservative) need before the
        pop, and nothing else allocates in between."""
        n = len(req.prompt)
        t_pop = time.perf_counter()
        prompt = np.asarray(req.prompt, np.int32)
        bt = self.page_tokens
        hit, nodes = 0, []
        table = np.zeros(self.max_blocks, np.int32)
        with self.tracer.span("admission", prompt_len=n, slot=slot,
                              request_id=req.request_id):
            if self.prefix_cache is not None:
                hit, nodes = self.prefix_cache.acquire(prompt.tolist())
                self.stats.record_prefix_lookup(hit, n)
                for j, node in enumerate(nodes):
                    self.pool.ref(node.page)
                    table[j] = node.page
            n_prompt_blocks = -(-n // bt)
            priv = self.pool.alloc(n_prompt_blocks - hit // bt)
            table[hit // bt:n_prompt_blocks] = priv
            grow = (-(-(n + req.max_new_tokens - 1) // bt)
                    - n_prompt_blocks)
            self.pool.reserve(grow)
        self._pending[slot] = _PendingPrefill(req, prompt, hit, hit, nodes,
                                              t_pop, grow, table)
        t0 = req._t_submit if req._t_submit is not None else t_pop
        self.stats.record_admission(queue_s=t_pop - t0, prompt_len=n)

    # graftlint: hot-path
    def _run_prefills(self, outputs: list[RequestOutput]) -> bool:
        """Advance pending prefills FIFO within this step's token budget.
        Intermediate chunks are exact C-token slices; the final chunk
        (bucketed) completes the admission. All chunks write straight into
        the slot's pool pages through its block table — there is no
        intermediate row cache and no splice. A final chunk's first token
        is taken here only where no decode will be dispatched behind it (no
        occupied slot, the ``prefill_only`` role); else the slot owes it
        until the next step's decode is in the queue. Returns True when a
        request finished AT admission and freed its slot."""
        freed = False
        for slot in list(self._pending):
            pend = self._pending.get(slot)
            if pend.first is not None:
                continue            # dispatched to its end: owes its token
            c = self.prefill_chunk_tokens
            table = pend.table[None, :]
            while pend is not None:
                rem = pend.n - pend.pos
                budget = self._step_prefill_budget
                if c is not None and rem > c:
                    if budget is not None and budget < c:
                        break       # out of budget; resume next iteration
                    program = f"chunk_{c}"
                    fields = dict(chunk=c, tokens=c, start=pend.pos,
                                  request_id=pend.req.request_id,
                                  attn=self._prefill_attn[program],
                                  **self._state_from(pend))
                    with self.tracer.span("prefill", slot=slot, **fields):
                        with self.tracer.span("chunk_operands"):
                            operands = (
                                np.ascontiguousarray(
                                    pend.prompt[None, pend.pos:pend.pos + c]),
                                np.ascontiguousarray(table),
                                np.int32(pend.pos))
                            state_slot = self._state_slot(slot)
                        with self.tracer.span("chunk_call", program=program,
                                              in_flight=self._in_flight()):
                            self._cache, moe = self._chunk_step(
                                *operands, slot=state_slot)
                        if moe is not None:
                            # no fence here: read at the next one behind it
                            self._chunk_counts.append((
                                self._dispatches, fields, moe))
                        if self.spec_k:
                            with self.tracer.span(
                                    "chunk_call", program=program,
                                    in_flight=self._in_flight(), draft=1):
                                self._draft_cache, _ = self._chunk_step(
                                    *operands, draft=True)
                    pend.pos += c
                    pend.chunks += 1
                    self._charge_prefill(c)
                    continue
                if budget is not None and rem > budget:
                    break
                self._dispatch_final_chunk(slot, pend)
                self._charge_prefill(rem)
                if self.prefill_only or not any(
                        fl is not None for fl in self._slots):
                    freed |= self._activate(slot, outputs)
                pend = None
        return freed

    def _state_from(self, pend: _PendingPrefill) -> dict:
        """The ``state_from`` field of a chunk's ``prefill`` span and
        ``prefill_counts`` record, for a model with per-slot state: ``zero``
        (the request's first chunk starts from zeros) or ``carried`` (from
        the chunk before it). No field for a model of pages only."""
        if not self._state_rows:
            return {}
        return {"state_from": "zero" if pend.pos == 0 else "carried"}

    def _charge_prefill(self, tokens: int) -> None:
        self.last_step_prefill_tokens += int(tokens)
        if self._step_prefill_budget is not None:
            self._step_prefill_budget = max(
                0, self._step_prefill_budget - int(tokens))

    # graftlint: hot-path
    def _dispatch_final_chunk(self, slot: int,
                              pend: _PendingPrefill) -> None:
        """Dispatch the final (sampling) chunk and adopt the prompt's pages
        into the trie; the first token stays on the device
        (``pend.first``) until :meth:`_activate` takes it. The chunk
        resumes at the prefill cursor RIGHT-PADDED to the bucket — the
        paged scatter writes each token at its absolute position, so the
        pad tail lands beyond the cursor (never attended) or in the
        scratch page (beyond the table), and positions before the cursor —
        including trie-shared pages — are never touched. The slot stays a
        pending prefill: its row is still private, so the decode that runs
        before the activation writes this slot's rider row to scratch."""
        req, n = pend.req, pend.n
        rem = n - pend.pos
        bucket = self._bucket(rem)
        sp = req.sampling
        program = f"final_chunk_{bucket}"
        fields = dict(bucket=bucket, tokens=rem, start=pend.pos,
                      request_id=req.request_id,
                      attn=self._prefill_attn[program],
                      **self._state_from(pend))
        with self.tracer.span("prefill", slot=slot, cached=pend.hit_tokens,
                              **fields):
            with self.tracer.span("chunk_operands"):
                chunk = np.full((1, bucket), self.pad_id, np.int32)
                chunk[0, :rem] = pend.prompt[pend.pos:]
                table = np.ascontiguousarray(pend.table[None, :])
                scalars = (np.int32(pend.pos), np.int32(rem),
                           np.float32(sp.temperature), np.int32(sp.top_k),
                           np.float32(sp.top_p))
                state_slot = self._state_slot(slot)
            with self.tracer.span("first_key", in_flight=self._in_flight()):
                # Host arithmetic alone: nothing is read back from the
                # device between the step's decode and this chunk's call.
                key = self._first_key(req.seed)
            with self.tracer.span("chunk_call", program=program,
                                  in_flight=self._in_flight()):
                tok, key, self._cache = self._final_chunk_step(
                    chunk, table, *scalars, key, slot=state_slot)
            pend.first = (tok, key, self._dispatches, fields)
            if self.spec_k:
                # Mirror the final chunk into the draft arena (logits
                # DCE'd): same padded chunk, same table, same positions
                # — pad writes land beyond the cursor or in scratch,
                # exactly as on the target path.
                with self.tracer.span("chunk_call",
                                      program=f"chunk_{bucket}",
                                      in_flight=self._in_flight(), draft=1):
                    self._draft_cache, _ = self._chunk_step(
                        chunk, table, scalars[0], draft=True)
            if self.prefix_cache is not None:
                with self.tracer.span("trie_adopt"):
                    self._adopt_into_trie(pend)

    def _adopt_into_trie(self, pend: _PendingPrefill) -> None:
        """Adopt whole prompt blocks into the trie by REFERENCE: the trie
        takes its own refcount on the slot's page, so the KV survives the
        slot and later requests map it with zero copies. Runs only for
        blocks the trie doesn't hold."""
        def page_for_block(i: int) -> int:
            page = int(pend.table[i])
            self.pool.ref(page)
            # Ledger: the trie's reference outlives the slot, so the
            # attribution moves with the longer lifetime.
            self.pool.tag(page, "trie")
            return page

        _, evicted = self.prefix_cache.insert(
            pend.prompt.tolist(), page_for_block)
        if evicted:
            self.stats.record_prefix_evictions(evicted)
        self.prefix_cache.release(pend.nodes)
        pend.nodes = []

    def _activate(self, slot: int, outputs: list[RequestOutput]) -> bool:
        """Take the first token *slot*'s final chunk owes and make the slot
        a decoding one: install its row engine-wide with the cursor at the
        prompt's length (BEFORE the next decode is dispatched, so the rider
        write lands past the prompt from now on), the sampling registers
        and the chained key. The final chunk's expert counts ride the
        token's fetch and become ONE ``prefill_counts`` record (its
        ``prefill`` span may have closed a step ago). Returns True when the
        request finished right here (first token was EOS, or the length
        budget is a single token): its output is appended to *outputs* and
        the slot is free again."""
        pend = self._pending.pop(slot)
        tok, key, seq, fields = pend.first
        req, n, sp = pend.req, pend.n, pend.req.sampling
        with self._wait("first_token", seq):
            with self.tracer.span("fetch_tokens"):
                # graftlint: disable=host-sync — the admission's one fence
                tok = np.asarray(tok).reshape(-1)
            with self.tracer.span("fetch_keys"):
                # graftlint: disable=host-sync — rides the same fence
                key = np.asarray(key)
            self._take_chunk_counts(seq)
            if tok.size > 1:
                self._chunk_counts_record(fields, tok[1:])
        first = int(tok[0])
        self._tables[slot, :] = pend.table
        now = time.perf_counter()
        fl = _InFlight(req, first, now)
        fl.t_admit = pend.t_pop
        fl.cached_prompt_tokens = pend.hit_tokens
        fl.prefill_chunks = pend.chunks + 1     # + the final sampling chunk
        fl.grow_left = pend.grow
        self._slots[slot] = fl
        self._tokens[slot] = first
        self._kv_lens[slot] = n          # next write position
        self._temps[slot] = sp.temperature
        self._top_ks[slot] = sp.top_k
        self._top_ps[slot] = sp.top_p
        self._keys[slot] = key
        self.stats.record_first_token(ttft_s=now - fl.t_submit)
        if req.on_token is not None:
            req.on_token(first)
        if self.eos_id is not None and first == self.eos_id:
            outputs.append(self._finish(slot, "eos"))
        elif req.max_new_tokens == 1:
            outputs.append(self._finish(slot, "length"))
        else:
            return False
        return True

    def _release_slot_pages(self, slot: int, grow_left: int,
                            row: np.ndarray | None = None) -> None:
        """Terminal page bookkeeping: deref every mapped page (freeing
        those the trie doesn't also hold), reset the table row to
        all-scratch, and return unused growth reservation. *row* is the
        still-private pending row for a request cancelled mid-prefill
        (its pages were never installed into ``self._tables``)."""
        if row is None:
            row = self._tables[slot]
        for j in range(self.max_blocks):
            page = int(row[j])
            if page:
                self.pool.deref(page)
        row[:] = 0
        if grow_left:
            self.pool.unreserve(grow_left)

    def _cancel_pending(self, slot: int, reason: str) -> RequestOutput:
        """Terminal output for a request cancelled mid-prefill (deadline /
        shutdown): release its pinned trie segments, free its pages and
        reservation, free the slot."""
        pend = self._pending.pop(slot)
        if self.prefix_cache is not None and pend.nodes:
            self.prefix_cache.release(pend.nodes)
            pend.nodes = []
        self._release_slot_pages(slot, pend.grow, row=pend.table)
        now = time.perf_counter()
        t0 = (pend.req._t_submit if pend.req._t_submit is not None else now)
        out = RequestOutput(
            request_id=pend.req.request_id, prompt_len=pend.n,
            tokens=[], finish_reason=reason, queue_s=pend.t_pop - t0,
            ttft_s=None, latency_s=now - t0,
            cached_prompt_tokens=pend.hit_tokens,
            prefill_chunks=pend.chunks)
        self.stats.record_completion(latency_s=out.latency_s, n_tokens=0,
                                     reason=reason)
        self.queue.release(pend.req)
        self._emit_request_trace(pend.req, out)
        self._notify_finish(pend.req, reason)
        return out

    def _finish(self, slot: int, reason: str) -> RequestOutput:
        fl = self._slots[slot]
        now = time.perf_counter()
        out = RequestOutput(
            request_id=fl.req.request_id, prompt_len=len(fl.req.prompt),
            tokens=list(fl.tokens), finish_reason=reason,
            queue_s=fl.t_admit - fl.t_submit,
            ttft_s=fl.t_first - fl.t_submit,
            latency_s=now - fl.t_submit,
            cached_prompt_tokens=fl.cached_prompt_tokens,
            prefill_chunks=fl.prefill_chunks,
            spec_proposed=fl.spec_proposed,
            spec_accepted=fl.spec_accepted)
        self._slots[slot] = None
        self._tokens[slot] = self.pad_id
        self._kv_lens[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._release_slot_pages(slot, fl.grow_left)
        self.stats.record_completion(latency_s=out.latency_s,
                                     n_tokens=len(out.tokens), reason=reason)
        if not fl.imported:
            # Imported requests never popped this engine's queue, so no
            # tenant slot is owed back here (the exporter released its own).
            self.queue.release(fl.req)
        self._emit_request_trace(fl.req, out)
        self._notify_finish(fl.req, reason)
        return out
