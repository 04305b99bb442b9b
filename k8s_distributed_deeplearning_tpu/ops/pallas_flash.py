"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The hot op of every transformer config in BASELINE.json. Design follows the
flash-attention recurrence (online softmax), mapped to TPU:

- grid (cells, S_q/block_q, S_k/superblock), a cell being the KV heads of
  one batch row that a grid cell owns (``_pack``: one, or a PAIR where the
  head size is 64 and K/V have as many heads as Q — BERT, ViT): K/V arrive
  in VMEM-resident SUPERBLOCKS (4096 positions) streamed through the innermost
  ("arbitrary") grid dim, and the kernel fori_loops over fine blocks inside
  each with the online-softmax carries in registers. Short sequences
  (S ≤ superblock) take exactly one grid step — a fully VMEM-resident fast
  path with zero streaming overhead; longer sequences carry (m, l, acc) in
  VMEM scratch across superblocks, so VMEM use is O(superblock) and
  sequence length is bounded by HBM only (64k+ measured on one chip). The
  S×S score matrix never exists in HBM either way;
- the op works on rows of heads, [B, S, H·d] — the projections' own
  layout, and what the custom VJP keeps for the backward pass. A pair of
  64-lane heads is one 128-lane column block of such a row, so that shape
  class reads and writes q, k, v, o and every gradient where they lie: no
  transpose in HBM, no half-empty VMEM tile;
- GQA is NATIVE: one grid cell owns one KV head and serves its whole
  query-head group from the single resident K/V superblock. Q rides as
  [B·Hkv, S, group·d] — a free reinterpretation of a row of heads
  (adjacent query heads of a group are adjacent in memory) plus the same
  batch×head transpose the MHA path pays — and the
  kernels unroll the group with per-head online-softmax carries. K/V are
  never repeated to query-head count (the round-3 kernel materialized the
  repeat in HBM: 3× K/V footprint, residual traffic, and per-head re-reads
  on the 12q/4kv flagship), and dK/dV accumulate the head-group sum
  in-kernel, emerging at KV-head count with no post-hoc reduction;
- causal work is skipped twice over: whole superblocks beyond the diagonal
  frontier skip via ``pl.when``, and the fine-block loop inside clips its
  trip count to the frontier — the causal pass does ~half the FLOPs,
  matching the mask's sparsity;
- the backward pass recomputes P from (Q, K, lse) per block — the standard
  flash trade: O(S) extra FLOPs for never storing P — with separate dQ and
  dK/dV kernels so each accumulates over its own grid without races, and
  ONE kernel where one query block and one key block hold the sequence
  (``_resident``: nothing accumulates across cells, so one recomputation
  feeds dQ, dK and dV);
- the kernels are named ``flash_attn_fwd``, ``flash_attn_bwd`` (resident)
  and ``flash_attn_dq`` / ``flash_attn_dkv``: a device trace and the
  benchmark's ``flash_attn_roofline`` find them as ``jit_step/.*flash_attn``;
- off-TPU (CPU CI) the same kernels run with ``interpret=True``, so tests
  exercise the identical code path the TPU compiles.

Used via ``ops.attention.multi_head_attention(..., impl="flash")`` or the
transformer configs' ``attention_impl="flash"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

NEG_INF = -1e30


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two block ≤ target dividing s."""
    b = 1
    while b * 2 <= min(s, target) and s % (b * 2) == 0:
        b *= 2
    return b


def _block_sizes(sq: int, sk: int) -> tuple[int, int]:
    """Largest power-of-two block sizes ≤ the swept targets dividing the seq
    lengths. 512/512 won the v5e sweep at S=2048-8192 and head size 128
    (round 4, "flash block sweep"), and again at head size 64 in pairs (PR
    30, forward + backward, ms at blocks of 512 / 256 / 128: S = 512
    0.71 / 1.86 / 3.41, S = 1,024 3.91 / 6.49 / 12.9): the head size does
    not enter. Fine blocks tile WITHIN a superblock."""
    return (min(_pick_block(sq, _BLOCK_Q), _superblock(sq)),
            min(_pick_block(sk, _BLOCK_K), _superblock(sk)))


# Fine-block size targets (power-of-two caps; clipped to divide S). What a
# sweep, or a test that wants a streamed sequence at a small size, changes.
_BLOCK_Q = 512
_BLOCK_K = 512


# K/V (and in the dK/dV pass, Q/dO) ride into VMEM in SUPERBLOCKS of this
# many positions; the kernels fori_loop over fine blocks inside. Short
# sequences (S <= superblock) hit the fast resident path — one grid step,
# loop carries in registers; longer sequences stream superblocks through an
# "arbitrary" grid dim with the online stats in VMEM scratch. 4096 positions
# x 128 head_dim x bf16 = 1 MiB per tensor per buffer — comfortably inside
# the VMEM budget with double buffering.
_SUPERBLOCK = 4096


def _superblock(s: int) -> int:
    return _pick_block(s, _SUPERBLOCK)


def _diag_split(causal: bool, off: int, resident: bool, segments: bool,
                block_q: int, block_k: int) -> bool:
    """Static predicate for the diagonal-split causal specialization (the
    flagship self-attention shape): with square blocks and aligned
    diagonals, EVERY fine block is either fully visible (no mask work) or
    THE diagonal block, whose mask is one fixed triangle ADDED as a bias —
    computed once per grid cell instead of two iotas + compare + select per
    block. The kernels are VPU-bound, so dropping those per-block passes is
    the win (round 3, chip-measured)."""
    return resident and _stream_split(causal, off, segments,
                                      block_q, block_k)


def _causal_tri(block_q: int, block_k: int) -> jax.Array:
    """The [block_q, block_k] lower-triangle additive bias (0 on/below the
    diagonal, NEG_INF above) for the diagonal block. Shared by every head
    of a GQA group — rows are positions, never folded."""
    return jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1),
        0.0, NEG_INF)


def _stream_split(causal: bool, off: int, segments: bool,
                  block_q: int, block_k: int) -> bool:
    """Streaming variant of :func:`_diag_split` (same static conditions
    minus residency): inside the superblock holding the diagonal, the
    boundary fine block is THE diagonal block; every other executed block
    is fully visible."""
    return causal and off == 0 and not segments and block_q == block_k


def _pack(h: int, hkv: int, d: int) -> int:
    """KV heads one grid cell owns: 2 at head size 64 when K/V have as many
    heads as Q (BERT, ViT), else 1. A PAIR of 64-lane heads is one 128-lane
    column block of the projection's own [B, S, H*D] rows, so the kernels
    take q, k, v, o and every gradient as they lie in HBM — no transpose on
    the way in or out — and no VMEM tile is half empty. Head size 128 and
    the GQA shapes (a KV head's block would be 64 lanes of a wider row:
    not a legal block) go through :func:`_cells`'s folded view."""
    return 2 if d == 64 and h == hkv and h % 2 == 0 else 1


def _cells(x: jax.Array, hkv: int, pack: int) -> jax.Array:
    """The kernels' view of a [B, S, H*D] operand: [rows, S, width], a grid
    cell reading one (1, block, pack*group*D) column block of a row.

    ``pack > 1``: *x* itself; a row holds ``hkv // pack`` cells side by
    side (:func:`_cell_index`).
    ``pack == 1``: [B*hkv, S, group*D], one cell a row. The query heads of
    one KV group are adjacent in a row of *x*, so regrouping it into (hkv,
    group*D) is a free reinterpretation; the data movement is the batch x
    head transpose. Head t of a cell lives in feature columns
    [t*D, (t+1)*D) either way — the kernels slice it statically."""
    if pack > 1:
        return x
    b, s, f = x.shape
    return x.reshape(b, s, hkv, f // hkv).transpose(0, 2, 1, 3).reshape(
        b * hkv, s, f // hkv)


def _uncells(x: jax.Array, b: int, hkv: int, pack: int) -> jax.Array:
    """Inverse of :func:`_cells`: back to [B, S, H*D]."""
    if pack > 1:
        return x
    _, s, w = x.shape
    return x.reshape(b, hkv, s, w).transpose(0, 2, 1, 3).reshape(
        b, s, hkv * w)


def _cell_index(hkv: int, pack: int):
    """(cell, block) -> block index into a :func:`_cells` view."""
    if pack > 1:
        per_row = hkv // pack
        return lambda g, blk: (g // per_row, blk, g % per_row)
    return lambda g, blk: (g, blk, 0)


def _kv_heads(k_ref, v_ref, rows, pack: int, d: int) -> list:
    """[(k, v)] per KV head of the cell: *rows* of its static d columns."""
    return [(k_ref[0, rows, u * d:(u + 1) * d],
             v_ref[0, rows, u * d:(u + 1) * d]) for u in range(pack)]


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, block_k: int, sb: int,
                n_sb: int, off: int, segments: bool, group: int, pack: int,
                d: int):
    """One (cell, q-block, K/V-superblock) grid cell; a cell is ``pack`` KV
    heads of one batch row (:func:`_pack`). The superblock (sb positions of
    K and V) is VMEM-resident and serves the WHOLE query-head group of each
    of its KV heads: q_ref is [1, block_q, pack*group*d] and the kernel
    unrolls the heads, each slicing its static feature columns (query head
    t reads KV head t // group) and carrying its own online-softmax
    (m, l, acc) — so under GQA each K/V byte fetched from HBM feeds
    ``group`` heads of work. Masks are built
    once per fine block and shared across the group (positions are
    head-independent). Short sequences (Sk <= superblock) take exactly one
    grid step — the fast resident path; longer sequences stream superblocks
    through the innermost ("arbitrary") grid dim with the per-head stats
    carried across steps in VMEM scratch, so VMEM use is O(superblock),
    never O(S)."""
    if segments:
        segq_ref, segk_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q = q_ref.shape[1]
    base = kb * sb                       # first K column of this superblock
    resident = n_sb == 1                 # static: whole Sk fits one step
    last_row = qi * block_q + block_q - 1 + off
    # Matmul inputs stay in the storage dtype (bf16 rides the MXU's native
    # path; f32 inputs would run the systolic array below peak) with f32
    # accumulation via preferred_element_type; the softmax scale applies to
    # the f32 scores.
    heads = pack * group
    qh = [q_ref[0, :, t * d:(t + 1) * d] for t in range(heads)]

    def n_inner():
        if causal:
            # Fine blocks inside the superblock up to the causal frontier
            # (col <= row + off; off = Sk - Sq, the decode alignment
            # matching ops/attention.py's reference mask).
            return jnp.clip((last_row - base) // block_k + 1,
                            0, sb // block_k)
        return sb // block_k

    diag_split = _diag_split(causal, off, resident, segments,
                             block_q, block_k)

    def make_body(general_mask: bool, bias):
        def body(j, carry):
            kv = _kv_heads(k_ref, v_ref, pl.ds(j * block_k, block_k), pack, d)
            mask = None                  # shared by every head of the cell
            if general_mask:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                col = base + j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask = row + off >= col
            if segments:
                sq_ids = segq_ref[0, 0]                           # [bq]
                sk_ids = segk_ref[0, 0, pl.ds(j * block_k, block_k)]
                seg_ok = sq_ids[:, None] == sk_ids[None, :]
                mask = seg_ok if mask is None else mask & seg_ok
            out = []
            for t in range(heads):
                m, l, acc = carry[t]
                k, v = kv[t // group]
                s = jax.lax.dot_general(
                    qh[t], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if bias is not None:
                    s = s + bias
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                bm = jnp.max(s, axis=-1)
                m_new = jnp.maximum(m, bm)
                p = jnp.exp(s - m_new[:, None])
                if segments or off < 0:
                    # A fully-masked row has m == NEG_INF and would
                    # exp(0) = 1; zero it. Possible under segment masks,
                    # and under causal with sq > sk (off < 0: leading rows
                    # see no columns). In the common causal sk >= sq case
                    # every row sees at least column 0, so masked entries
                    # underflow to exactly 0 on their own — skip the pass.
                    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
                alpha = jnp.exp(m - m_new)
                l_new = alpha * l + jnp.sum(p, axis=-1)
                # P rides the MXU in the storage dtype too — the same trade
                # the XLA path makes (probs.astype(v.dtype) before PV).
                acc_new = alpha[:, None] * acc + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out.append((m_new, l_new, acc_new))
            return tuple(out)
        return body

    def emit(carry):
        for t in range(heads):
            m, l, acc = carry[t]
            norm = jnp.maximum(l, 1e-30)
            o_ref[0, :, t * d:(t + 1) * d] = (
                acc / norm[:, None]).astype(o_ref.dtype)
            lse_ref[0, t] = m + jnp.log(norm)

    if resident:
        # Fast path (statically selected): carries live in registers, no
        # scratch traffic, no grid predicates — identical to a single-pass
        # whole-KV kernel.
        init = tuple((jnp.full((block_q,), NEG_INF, jnp.float32),
                      jnp.zeros((block_q,), jnp.float32),
                      jnp.zeros((block_q, d), jnp.float32))
                     for _ in range(heads))
        if diag_split:
            tri = _causal_tri(block_q, block_k)
            carry = jax.lax.fori_loop(0, qi, make_body(False, None), init)
            carry = make_body(False, tri)(qi, carry)
        else:
            carry = jax.lax.fori_loop(0, n_inner(),
                                      make_body(causal, None), init)
        emit(carry)
        return

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    run = base <= last_row if causal else True
    stream_split = _stream_split(causal, off, segments, block_q, block_k)

    def read_carry():
        return tuple((m_s[t], l_s[t], acc_s[t]) for t in range(heads))

    def write_carry(carry):
        for t in range(heads):
            m_s[t], l_s[t], acc_s[t] = carry[t]

    @pl.when(run)
    def _superblock_body():
        carry = read_carry()
        if stream_split:
            has_diag = jnp.logical_and(base <= qi * block_q,
                                       qi * block_q < base + sb)
            carry = jax.lax.fori_loop(
                0, n_inner() - has_diag.astype(jnp.int32),
                make_body(False, None), carry)
            tri = _causal_tri(block_q, block_k)
            carry = jax.lax.cond(
                has_diag,
                lambda c: make_body(False, tri)(n_inner() - 1, c),
                lambda c: c, carry)
        else:
            carry = jax.lax.fori_loop(0, n_inner(), make_body(causal, None),
                                      carry)
        write_carry(carry)

    @pl.when(kb == n_sb - 1)
    def _emit():
        emit(read_carry())


def _seg_specs(per_row: int, block_q: int, sb_k: int):
    """BlockSpecs for segment-id arrays on the (cells, q-blocks,
    k-superblocks) grid: q ids per q block, k ids per K superblock (ids are
    per-batch — the ``per_row`` cells of a batch row share them).

    Segments ride as [B, 1, S]: TPU block rules constrain the LAST TWO dims
    (8/128-divisible or full), so a [B, S] layout would make the B dim a
    "second-last" dim with block 1 — illegal for B not in {1, 8k}. The
    length-1 middle dim absorbs that constraint (same trick as lse).
    """
    return [
        pl.BlockSpec((1, 1, block_q), lambda g, i, j: (g // per_row, 0, i)),
        pl.BlockSpec((1, 1, sb_k), lambda g, i, j: (g // per_row, 0, j)),
    ]


def _compiler_params(interpret):
    # Cells are embarrassingly parallel; the q/k block dims carry
    # scratch state across iterations, so they stay sequential. The scoped
    # VMEM limit is raised above the 16 MiB default: the unrolled heads of
    # a cell (per-head f32 score/prob tiles plus double-buffered
    # superblocks) legitimately peak past 16 MiB on the 12/4 flagship,
    # well within the chip's physical VMEM.
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _fwd(q, k, v, segs, d, causal, scale, interpret):
    """q [B, Sq, H*d], k and v [B, Sk, Hkv*d], *segs* the segment-id pair
    or () -> (o [B, Sq, H*d], lse [B, H, Sq])."""
    b, sq, f = q.shape
    sk, h, hkv = k.shape[1], f // d, k.shape[2] // d
    group = h // hkv                 # query heads sharing one KV head
    pack = _pack(h, hkv, d)
    heads, n_cells = pack * group, b * hkv // pack
    block_q, block_k = _block_sizes(sq, sk)
    sb = _superblock(sk)
    n_sb = sk // sb
    # K/V are NEVER repeated to query-head count in either view.
    qt, kt, vt = (_cells(x, hkv, pack) for x in (q, k, v))
    at = _cell_index(hkv, pack)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, sb=sb, n_sb=n_sb,
                               off=sk - sq, segments=bool(segs), group=group,
                               pack=pack, d=d)
    q_spec = pl.BlockSpec((1, block_q, heads * d), lambda g, i, j: at(g, i))
    kv_spec = pl.BlockSpec((1, sb, pack * d), lambda g, i, j: at(g, j))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qt, kt, vt]
    if segs:
        in_specs += _seg_specs(hkv // pack, block_q, sb)
        operands += [x[:, None, :] for x in segs]          # [B,1,S] layout
    o, lse = pl.pallas_call(
        kernel,
        grid=(n_cells, sq // block_q, n_sb),
        in_specs=in_specs,
        out_specs=[
            q_spec,
            # lse rides as [cells, heads, sq] with a (1, heads, block_q)
            # block: the last two dims are (full, 128-multiple) — legal —
            # and head t writes row t.
            pl.BlockSpec((1, heads, block_q), lambda g, i, j: (g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((n_cells, heads, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q), jnp.float32),     # running max m
            pltpu.VMEM((heads, block_q), jnp.float32),     # running sum l
            pltpu.VMEM((heads, block_q, d), jnp.float32),  # unnormalized acc
        ],
        compiler_params=_compiler_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq * sk * d // (2 if causal else 1),
            bytes_accessed=(qt.size + kt.size + vt.size) * qt.dtype.itemsize,
            transcendentals=b * h * sq * sk),
        interpret=interpret,
        name="flash_attn_fwd",
    )(*operands)
    return _uncells(o, b, hkv, pack), lse.reshape(b, h, sq)


# ---------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale: float, causal: bool, block_k: int, sb: int,
                   n_sb: int, off: int, segments: bool, group: int,
                   pack: int, d: int):
    """dQ on the (cells, q-blocks, K/V-superblocks) grid: one grid cell
    serves every query head of its KV heads from the resident K/V
    superblock — q/do are [1, block_q, pack*group*d] with static per-head
    feature slices, lse/delta are [1, pack*group, block_q] rows; the
    per-head dq accumulators
    carry across superblocks in VMEM scratch; fine k blocks loop inside
    the resident superblock (registers)."""
    if segments:
        segq_ref, segk_ref, dq_ref, dq_s = rest
    else:
        dq_ref, dq_s = rest
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    block_q = q_ref.shape[1]
    base = kb * sb
    resident = n_sb == 1
    last_row = qi * block_q + block_q - 1 + off
    # bf16 matmul inputs / f32 accumulation (see _fwd_kernel); the softmax
    # scale folds into ds once instead of pre-scaling q and post-scaling dq.
    heads = pack * group
    qh = [q_ref[0, :, t * d:(t + 1) * d] for t in range(heads)]
    doh = [do_ref[0, :, t * d:(t + 1) * d] for t in range(heads)]
    lse = [lse_ref[0, t] for t in range(heads)]
    delta = [delta_ref[0, t] for t in range(heads)]

    def n_inner():
        if causal:
            return jnp.clip((last_row - base) // block_k + 1,
                            0, sb // block_k)
        return sb // block_k

    diag_split = _diag_split(causal, off, resident, segments,
                             block_q, block_k)

    def make_body(general_mask: bool, bias):
        def body(j, dq):
            kv = _kv_heads(k_ref, v_ref, pl.ds(j * block_k, block_k), pack, d)
            mask = None
            if general_mask:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                col = base + j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask = row + off >= col
            if segments:
                sq_ids = segq_ref[0, 0]
                sk_ids = segk_ref[0, 0, pl.ds(j * block_k, block_k)]
                seg_ok = sq_ids[:, None] == sk_ids[None, :]
                mask = seg_ok if mask is None else mask & seg_ok
            out = []
            for t in range(heads):
                k, v = kv[t // group]
                s = jax.lax.dot_general(
                    qh[t], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if bias is not None:
                    s = s + bias
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                p = jnp.exp(s - lse[t][:, None])
                if segments or off < 0:
                    # Fully-masked rows (segment masks, or causal sq > sk —
                    # see _fwd_kernel) have a degenerate lse; force zeros.
                    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
                dp = jax.lax.dot_general(
                    doh[t], v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = (p * (dp - delta[t][:, None]) * scale).astype(k.dtype)
                out.append(dq[t] + jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            return tuple(out)
        return body

    def emit(dq):
        for t in range(heads):
            dq_ref[0, :, t * d:(t + 1) * d] = dq[t].astype(dq_ref.dtype)

    if resident:
        init = tuple(jnp.zeros((block_q, d), jnp.float32)
                     for _ in range(heads))
        if diag_split:
            tri = _causal_tri(block_q, block_k)
            dq = jax.lax.fori_loop(0, qi, make_body(False, None), init)
            dq = make_body(False, tri)(qi, dq)
        else:
            dq = jax.lax.fori_loop(0, n_inner(), make_body(causal, None),
                                   init)
        emit(dq)
        return

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    run = base <= last_row if causal else True

    @pl.when(run)
    def _superblock_body():
        carry = tuple(dq_s[t] for t in range(heads))
        # Streaming diagonal-split mirrors _fwd_kernel's.
        if _stream_split(causal, off, segments, block_q, block_k):
            has_diag = jnp.logical_and(base <= qi * block_q,
                                       qi * block_q < base + sb)
            carry = jax.lax.fori_loop(
                0, n_inner() - has_diag.astype(jnp.int32),
                make_body(False, None), carry)
            tri = _causal_tri(block_q, block_k)
            carry = jax.lax.cond(
                has_diag,
                lambda c: make_body(False, tri)(n_inner() - 1, c),
                lambda c: c, carry)
        else:
            carry = jax.lax.fori_loop(0, n_inner(),
                                      make_body(causal, None), carry)
        for t in range(heads):
            dq_s[t] = carry[t]

    @pl.when(kb == n_sb - 1)
    def _emit():
        emit(tuple(dq_s[t] for t in range(heads)))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale: float, causal: bool, block_q: int, sb: int,
                    n_sb: int, off: int, segments: bool, group: int,
                    pack: int, d: int):
    """dK/dV on the (cells, k-blocks, Q-superblocks) grid: each grid cell
    owns its KV heads' k block; the streamed Q/dO superblocks carry the
    WHOLE query-head group of each in the feature dim ([1, sb,
    pack*group*d], static per-head slices), so dk/dv accumulate the full
    GQA head-group sum in one pass — written once at KV-head count with no
    post-hoc reduction.
    Fine q blocks loop inside the resident superblock; dk/dv accumulate in
    VMEM scratch across superblocks. Masks are built once per fine block
    and shared across the group."""
    if segments:
        segq_ref, segk_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        dk_ref, dv_ref, dk_s, dv_s = rest
    ki = pl.program_id(1)
    qb = pl.program_id(2)
    block_k = k_ref.shape[1]
    base = qb * sb                     # first Q row of this superblock
    resident = n_sb == 1
    first_col = ki * block_k
    # bf16 matmul inputs / f32 accumulation; scale folds into ds (see
    # _bwd_dq_kernel).
    kv = _kv_heads(k_ref, v_ref, slice(None), pack, d)

    def first_inner():
        if causal:
            # First fine q block inside the superblock whose last row
            # reaches this k block's first column.
            return jnp.clip((first_col - off - base) // block_q, 0,
                            sb // block_q)
        return 0

    diag_split = _diag_split(causal, off, resident, segments,
                             block_q, block_k)

    def make_body(general_mask: bool, bias):
        def body(i, carry):
            mask = None
            if general_mask:
                row = base + i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                col = first_col + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                mask = row + off >= col
            if segments:
                sq_ids = segq_ref[0, 0, pl.ds(i * block_q, block_q)]
                sk_ids = segk_ref[0, 0]
                seg_ok = sq_ids[:, None] == sk_ids[None, :]
                mask = seg_ok if mask is None else mask & seg_ok
            carry = list(carry)
            for t in range(pack * group):
                (k, v), (dk, dv) = kv[t // group], carry[t // group]
                q = q_ref[0, pl.ds(i * block_q, block_q), t * d:(t + 1) * d]
                do = do_ref[0, pl.ds(i * block_q, block_q), t * d:(t + 1) * d]
                lse = lse_ref[0, t, pl.ds(i * block_q, block_q)]
                delta = delta_ref[0, t, pl.ds(i * block_q, block_q)]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if bias is not None:
                    s = s + bias
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                p = jnp.exp(s - lse[:, None])
                if segments or off < 0:
                    # Fully-masked rows (segment masks, or causal sq > sk —
                    # see _fwd_kernel) have a degenerate lse; force zeros.
                    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
                dv = dv + jax.lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(
                    do, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
                dk = dk + jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                carry[t // group] = (dk, dv)
            return tuple(carry)
        return body

    def emit(carry):
        for u, (dk, dv) in enumerate(carry):
            dk_ref[0, :, u * d:(u + 1) * d] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, u * d:(u + 1) * d] = dv.astype(dv_ref.dtype)

    if resident:
        zero = jnp.zeros((block_k, d), jnp.float32)
        init = tuple((zero, zero) for _ in range(pack))
        if diag_split:
            # Diagonal q block i == ki (triangular bias), full blocks after.
            tri = _causal_tri(block_q, block_k)
            carry = make_body(False, tri)(ki, init)
            carry = jax.lax.fori_loop(ki + 1, sb // block_q,
                                      make_body(False, None), carry)
        else:
            carry = jax.lax.fori_loop(first_inner(), sb // block_q,
                                      make_body(causal, None), init)
        emit(carry)
        return

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # The superblock contributes iff its LAST row can see this k block's
    # first column (row + off >= col for some pair).
    run = base + sb - 1 + off >= first_col if causal else True

    @pl.when(run)
    def _superblock_body():
        carry = tuple((dk_s[u], dv_s[u]) for u in range(pack))
        # Streaming diagonal-split: the diagonal q block (when this Q
        # superblock holds it) is exactly first_inner(); later blocks see
        # this k block in full.
        if _stream_split(causal, off, segments, block_q, block_k):
            has_diag = jnp.logical_and(base <= ki * block_k,
                                       ki * block_k < base + sb)
            tri = _causal_tri(block_q, block_k)
            carry = jax.lax.cond(
                has_diag,
                lambda c: make_body(False, tri)(first_inner(), c),
                lambda c: c, carry)
            carry = jax.lax.fori_loop(
                first_inner() + has_diag.astype(jnp.int32), sb // block_q,
                make_body(False, None), carry)
        else:
            carry = jax.lax.fori_loop(first_inner(), sb // block_q,
                                      make_body(causal, None), carry)
        for u in range(pack):
            dk_s[u], dv_s[u] = carry[u]

    @pl.when(qb == n_sb - 1)
    def _emit():
        emit(tuple((dk_s[u], dv_s[u]) for u in range(pack)))


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, *rest,
                      scale: float, causal: bool, off: int, segments: bool,
                      group: int, pack: int, d: int):
    """dQ, dK and dV of one cell whose WHOLE sequence is resident (one query
    block, one key block): nothing accumulates across grid cells, so the
    split into a dQ and a dK/dV kernel — which exists to avoid races across
    the grid — would only recompute the scores and dP a second time. One
    recomputation of the probabilities per head feeds all three gradients
    (five products of S x S x d a head where the two kernels make seven).
    With a whole row of P and dP in hand, the softmax Jacobian's row term is
    rowsum(P * dP) — what the einsum path's backward computes — so neither
    the output nor a delta array is read."""
    if segments:
        segq_ref, segk_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        dq_ref, dk_ref, dv_ref = rest
    sq, sk = q_ref.shape[1], k_ref.shape[1]
    mask = None                          # shared by every head of the cell
    if causal:
        mask = (jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + off
                >= jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1))
    if segments:
        seg_ok = segq_ref[0, 0][:, None] == segk_ref[0, 0][None, :]
        mask = seg_ok if mask is None else mask & seg_ok
    for u, (k, v) in enumerate(_kv_heads(k_ref, v_ref, slice(None), pack, d)):
        dk = jnp.zeros((sk, d), jnp.float32)
        dv = jnp.zeros((sk, d), jnp.float32)
        for t in range(u * group, (u + 1) * group):
            q = q_ref[0, :, t * d:(t + 1) * d]
            do = do_ref[0, :, t * d:(t + 1) * d]
            # bf16 matmul inputs / f32 accumulation; scale folds into ds
            # (see _bwd_dq_kernel).
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, t][:, None])
            if segments or off < 0:
                # Fully-masked rows have a degenerate lse (see _fwd_kernel).
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            delta = jnp.sum(p * dp, axis=-1, keepdims=True)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            dq_ref[0, :, t * d:(t + 1) * d] = jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(dq_ref.dtype)
            dk = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_ref[0, :, u * d:(u + 1) * d] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, u * d:(u + 1) * d] = dv.astype(dv_ref.dtype)


def _resident(sq: int, sk: int) -> bool:
    """One query block and one key block hold the sequence: the backward
    pass is ONE kernel and the forward's output is not among its residuals.
    """
    return (sq, sk) == _block_sizes(sq, sk)


def _bwd(q, k, v, segs, g, lse, o, d, causal, scale, interpret):
    """-> (dq, dk, dv) in the operands' [B, S, heads*d] layout. *o*, the
    forward's output, is read only where the sequence is not
    :func:`_resident` (None there)."""
    b, sq, f = q.shape
    sk, h, hkv = k.shape[1], f // d, k.shape[2] // d
    group = h // hkv
    pack = _pack(h, hkv, d)
    heads, n_cells = pack * group, b * hkv // pack
    block_q, block_k = _block_sizes(sq, sk)
    sb_k, sb_q = _superblock(sk), _superblock(sq)
    segments = bool(segs)
    per_row = hkv // pack

    qt, kt, vt, dot = (_cells(x, hkv, pack) for x in (q, k, v, g))
    at = _cell_index(hkv, pack)
    lse = lse.reshape(n_cells, heads, sq)
    seg_operands = [x[:, None, :] for x in segs]
    statics = dict(scale=scale, causal=causal, off=sk - sq,
                   segments=segments, group=group, pack=pack, d=d)
    q_shape = jax.ShapeDtypeStruct(qt.shape, q.dtype)
    kv_shapes = [jax.ShapeDtypeStruct(kt.shape, k.dtype),
                 jax.ShapeDtypeStruct(vt.shape, v.dtype)]

    if _resident(sq, sk):
        q_spec = pl.BlockSpec((1, sq, heads * d), lambda g_, i, j: at(g_, 0))
        kv_spec = pl.BlockSpec((1, sk, pack * d), lambda g_, i, j: at(g_, 0))
        row_spec = pl.BlockSpec((1, heads, sq), lambda g_, i, j: (g_, 0, 0))
        specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec]
        if segments:
            specs += _seg_specs(per_row, sq, sk)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **statics),
            grid=(n_cells, 1, 1),
            in_specs=specs,
            out_specs=[q_spec, kv_spec, kv_spec],
            out_shape=[q_shape] + kv_shapes,
            compiler_params=_compiler_params(interpret),
            interpret=interpret,
            name="flash_attn_bwd",
        )(qt, kt, vt, dot, lse, *seg_operands)
        return tuple(_uncells(x, b, hkv, pack) for x in (dq, dk, dv))

    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term,
    # per head: [cells, heads, sq] rows match the lse layout.
    delta = jnp.sum(
        (g.astype(jnp.float32) * o.astype(jnp.float32)).reshape(b, sq, h, d),
        axis=-1).transpose(0, 2, 1).reshape(n_cells, heads, sq)
    operands = [qt, kt, vt, dot, lse, delta] + seg_operands

    # One dq grid cell per (batch, KV heads of a cell): q/do carry every
    # query head in the feature dim, K/V load once per group.
    q_spec = pl.BlockSpec((1, block_q, heads * d), lambda g_, i, j: at(g_, i))
    kv_spec = pl.BlockSpec((1, sb_k, pack * d), lambda g_, i, j: at(g_, j))
    row_spec = pl.BlockSpec((1, heads, block_q), lambda g_, i, j: (g_, 0, i))
    dq_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    if segments:
        dq_specs += _seg_specs(per_row, block_q, sb_k)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=block_k, sb=sb_k,
                          n_sb=sk // sb_k, **statics),
        grid=(n_cells, sq // block_q, sk // sb_k),
        in_specs=dq_specs,
        out_specs=q_spec,
        out_shape=q_shape,
        scratch_shapes=[pltpu.VMEM((heads, block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_attn_dq",
    )(*operands)

    # dK/dV: grid dim 0 owns a cell's KV heads; k blocks in the middle dim;
    # Q/dO superblocks stream innermost carrying every query head in the
    # feature dim, so dk/dv accumulate the GQA sum in scratch and are
    # written once at KV-head count.
    q_spec = pl.BlockSpec((1, sb_q, heads * d), lambda g_, j, i: at(g_, i))
    kv_spec = pl.BlockSpec((1, block_k, pack * d), lambda g_, j, i: at(g_, j))
    row_spec = pl.BlockSpec((1, heads, sb_q), lambda g_, j, i: (g_, 0, i))
    dkv_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    if segments:
        dkv_specs += [
            pl.BlockSpec((1, 1, sb_q), lambda g_, j, i: (g_ // per_row, 0, i)),
            pl.BlockSpec((1, 1, block_k),
                         lambda g_, j, i: (g_ // per_row, 0, j)),
        ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, sb=sb_q,
                          n_sb=sq // sb_q, **statics),
        grid=(n_cells, sk // block_k, sq // sb_q),
        in_specs=dkv_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=kv_shapes,
        scratch_shapes=[
            pltpu.VMEM((pack, block_k, d), jnp.float32),
            pltpu.VMEM((pack, block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name="flash_attn_dkv",
    )(*operands)
    return tuple(_uncells(x, b, hkv, pack) for x in (dq, dk, dv))


# ---------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, segs, d, causal, scale, interpret):
    return _fwd(q, k, v, segs, d, causal, scale, interpret)[0]


def _flash_fwd(q, k, v, segs, d, causal, scale, interpret):
    o, lse = _fwd(q, k, v, segs, d, causal, scale, interpret)
    keep_o = not _resident(q.shape[1], k.shape[1])
    return o, (q, k, v, segs, o if keep_o else None, lse)


def _flash_bwd(d, causal, scale, interpret, res, g):
    q, k, v, segs, o, lse = res
    dq, dk, dv = _bwd(q, k, v, segs, g, lse, o, d, causal, scale, interpret)
    return dq, dk, dv, tuple(np.zeros(x.shape, jax.dtypes.float0)
                             for x in segs)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    softmax_scale: float | None = None,
                    q_segment_ids: jax.Array | None = None,
                    kv_segment_ids: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Flash attention, [B,S,H,D] layout, native GQA (KV heads stay shared).

    ``k``/``v`` may carry fewer heads than ``q`` (num_q_heads %
    num_kv_heads == 0): one grid cell owns one KV head and serves its whole
    query-head group from a single resident K/V superblock — K/V are never
    repeated to query-head count, so GQA pays KV-head HBM footprint in the
    forward residuals and dK/dV accumulate the head-group sum in-kernel
    (3x less K/V memory on the 12q/4kv flagship than the round-3
    repeat-based path, and one K/V fetch feeds the whole group).

    ``q_segment_ids``/``kv_segment_ids`` ([B, S] int32) restrict attention to
    equal segment ids — the packed-sequence mask (multiple documents per row)
    and, with a sentinel id on pad positions, the padding mask. Composes with
    ``causal``. Both must be given together.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (CPU CI runs the same kernels). Sequence lengths must be divisible by the
    chosen power-of-two block sizes (always true for the usual 2^k lengths).
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("q_segment_ids and kv_segment_ids must be given "
                         "together")
    if q_segment_ids is not None:
        if q_segment_ids.shape != q.shape[:2]:
            raise ValueError(f"q_segment_ids {q_segment_ids.shape} must be "
                             f"[B, Sq] = {q.shape[:2]}")
        if kv_segment_ids.shape != k.shape[:2]:
            raise ValueError(f"kv_segment_ids {kv_segment_ids.shape} must be "
                             f"[B, Sk] = {k.shape[:2]}")
        q_segment_ids = q_segment_ids.astype(jnp.int32)
        kv_segment_ids = kv_segment_ids.astype(jnp.int32)
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv:
        raise ValueError(f"{hq} q heads not divisible by {hkv} kv heads")
    if v.shape[2] != hkv:
        raise ValueError(f"k has {hkv} heads but v has {v.shape[2]}")
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not on_tpu()
    segs = () if q_segment_ids is None else (q_segment_ids, kv_segment_ids)
    # The op works on rows of heads, [B, S, H*D] — the projections' own
    # layout, and what the custom VJP keeps for the backward pass.
    (b, sq, _, d), sk = q.shape, k.shape[1]
    o = _flash(q.reshape(b, sq, hq * d), k.reshape(b, sk, hkv * d),
               v.reshape(b, sk, hkv * d), segs, d, causal, scale, interpret)
    return o.reshape(q.shape)
