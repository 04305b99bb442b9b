"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

The hot op of every transformer config in BASELINE.json. Design follows the
flash-attention recurrence (online softmax), mapped to TPU:

- grid (batch·KV-heads, S_q/block_q, S_k/superblock): K/V arrive in
  VMEM-resident SUPERBLOCKS (4096 positions) streamed through the innermost
  ("arbitrary") grid dim, and the kernel fori_loops over fine blocks inside
  each with the online-softmax carries in registers. Short sequences
  (S ≤ superblock) take exactly one grid step — a fully VMEM-resident fast
  path with zero streaming overhead; longer sequences carry (m, l, acc) in
  VMEM scratch across superblocks, so VMEM use is O(superblock) and
  sequence length is bounded by HBM only (64k+ measured on one chip). The
  S×S score matrix never exists in HBM either way;
- GQA is NATIVE: one grid cell owns one KV head and serves its whole
  query-head group from the single resident K/V superblock. Q rides as
  [B·Hkv, S, group·d] — a free reinterpretation of the projection's
  [B, S, H, d] layout (adjacent query heads of a group are adjacent in
  memory) plus the same batch×head transpose the MHA path pays — and the
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
  dK/dV kernels so each accumulates over its own grid without races;
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
    lengths. 512/512 won the v5e sweep at S=2048-8192 (round 4, "flash
    block sweep"); the knobs exist so future sweeps don't edit the kernel."""
    return _pick_block(sq, _BLOCK_Q), _pick_block(sk, _BLOCK_K)


# Fine-block size targets (power-of-two caps; clipped to divide S).
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


def _fold_q(x: jax.Array, hkv: int) -> jax.Array:
    """[B, S, H, D] -> [B*hkv, S, group*D].

    Adjacent query heads of one KV group are adjacent in the last two dims
    of the projection layout, so regrouping H into (hkv, group*D) is a free
    reinterpretation; the only data movement is the same batch×head
    transpose the plain MHA fold pays (with group× longer contiguous runs).
    Head t of a group lives in feature columns [t*D, (t+1)*D) — the kernels
    slice it statically."""
    b, s, h, d = x.shape
    group = h // hkv
    return x.reshape(b, s, hkv, group * d).transpose(0, 2, 1, 3).reshape(
        b * hkv, s, group * d)


def _unfold_q(x: jax.Array, b: int, hkv: int, s: int) -> jax.Array:
    """Inverse of :func:`_fold_q` (back to [B, S, H, D] given head_dim from
    the caller's reshape)."""
    gd = x.shape[-1]
    return x.reshape(b, hkv, s, gd).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale: float, causal: bool, block_k: int, sb: int,
                n_sb: int, off: int, segments: bool, group: int, d: int):
    """One (batch·KV-head, q-block, K/V-superblock) grid cell. The
    superblock (sb positions of K and V) is VMEM-resident and serves the
    WHOLE query-head group: q_ref is [1, block_q, group*d] and the kernel
    unrolls the group, each head slicing its static feature columns and
    carrying its own online-softmax (m, l, acc) — so under GQA each K/V
    byte fetched from HBM feeds ``group`` heads of work. Masks are built
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
    qh = [q_ref[0, :, t * d:(t + 1) * d] for t in range(group)]

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
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            mask = None                  # shared by the whole head group
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
            for t in range(group):
                m, l, acc = carry[t]
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
        for t in range(group):
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
                     for _ in range(group))
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
        return tuple((m_s[t], l_s[t], acc_s[t]) for t in range(group))

    def write_carry(carry):
        for t in range(group):
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


def _seg_specs(hkv: int, block_q: int, sb_k: int):
    """BlockSpecs for segment-id arrays on the (b*hkv, q-blocks,
    k-superblocks) grid: q ids per q block, k ids per K superblock (ids are
    per-batch — every head of the group shares them).

    Segments ride as [B, 1, S]: TPU block rules constrain the LAST TWO dims
    (8/128-divisible or full), so a [B, S] layout would make the B dim a
    "second-last" dim with block 1 — illegal for B not in {1, 8k}. The
    length-1 middle dim absorbs that constraint (same trick as lse).
    """
    return [
        pl.BlockSpec((1, 1, block_q), lambda g, i, j: (g // hkv, 0, i)),
        pl.BlockSpec((1, 1, sb_k), lambda g, i, j: (g // hkv, 0, j)),
    ]


def _compiler_params(interpret):
    # batch×heads is embarrassingly parallel; the q/k block dims carry
    # scratch state across iterations, so they stay sequential. The scoped
    # VMEM limit is raised above the 16 MiB default: the GQA group-unrolled
    # blocks (per-head f32 score/prob tiles plus double-buffered
    # superblocks) legitimately peak past 16 MiB on the 12/4 flagship,
    # well within the chip's physical VMEM.
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _fwd(q, k, v, segq, segk, *, causal, scale, interpret):
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv                 # query heads sharing one KV head
    block_q, block_k = _block_sizes(sq, sk)
    sb = _superblock(sk)
    block_k = min(block_k, sb)      # fine blocks tile WITHIN the superblock
    n_sb = sk // sb
    # Kernel layout: Q folds its KV group into the feature dim (_fold_q —
    # same transpose cost as the plain MHA fold); K/V fold batch×KV-heads
    # and are NEVER repeated to query-head count.
    qt = _fold_q(q, hkv)                              # [b*hkv, sq, group*d]
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    segments = segq is not None

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, sb=sb, n_sb=n_sb,
                               off=sk - sq, segments=segments, group=group,
                               d=d)
    in_specs = [
        pl.BlockSpec((1, block_q, group * d), lambda g, i, j: (g, i, 0)),
        pl.BlockSpec((1, sb, d), lambda g, i, j: (g, j, 0)),
        pl.BlockSpec((1, sb, d), lambda g, i, j: (g, j, 0)),
    ]
    operands = [qt, kt, vt]
    if segments:
        in_specs += _seg_specs(hkv, block_q, sb)
        operands += [segq[:, None, :], segk[:, None, :]]   # [B,1,S] layout
    o, lse = pl.pallas_call(
        kernel,
        grid=(b * hkv, sq // block_q, n_sb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, group * d), lambda g, i, j: (g, i, 0)),
            # lse rides as [b*hkv, group, sq] with a (1, group, block_q)
            # block: the last two dims are (full, 128-multiple) — legal —
            # and head t writes row t.
            pl.BlockSpec((1, group, block_q), lambda g, i, j: (g, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sq, group * d), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, group, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, block_q), jnp.float32),     # running max m
            pltpu.VMEM((group, block_q), jnp.float32),     # running sum l
            pltpu.VMEM((group, block_q, d), jnp.float32),  # unnormalized acc
        ],
        compiler_params=_compiler_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq * sk * d // (2 if causal else 1),
            bytes_accessed=(qt.size + kt.size + vt.size) * qt.dtype.itemsize,
            transcendentals=b * h * sq * sk),
        interpret=interpret,
    )(*operands)
    return _unfold_q(o, b, hkv, sq).reshape(b, sq, h, d), lse


# ---------------------------------------------------------------- backward

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale: float, causal: bool, block_k: int, sb: int,
                   n_sb: int, off: int, segments: bool, group: int, d: int):
    """dQ on the (b*h_kv, q-blocks, K/V-superblocks) grid: one grid cell
    serves the whole query-head group from the resident K/V superblock —
    q/do are [1, block_q, group*d] with static per-head feature slices,
    lse/delta are [1, group, block_q] rows; the per-head dq accumulators
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
    qh = [q_ref[0, :, t * d:(t + 1) * d] for t in range(group)]
    doh = [do_ref[0, :, t * d:(t + 1) * d] for t in range(group)]
    lse = [lse_ref[0, t] for t in range(group)]
    delta = [delta_ref[0, t] for t in range(group)]

    def n_inner():
        if causal:
            return jnp.clip((last_row - base) // block_k + 1,
                            0, sb // block_k)
        return sb // block_k

    diag_split = _diag_split(causal, off, resident, segments,
                             block_q, block_k)

    def make_body(general_mask: bool, bias):
        def body(j, dq):
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
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
            for t in range(group):
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
        for t in range(group):
            dq_ref[0, :, t * d:(t + 1) * d] = dq[t].astype(dq_ref.dtype)

    if resident:
        init = tuple(jnp.zeros((block_q, d), jnp.float32)
                     for _ in range(group))
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
        carry = tuple(dq_s[t] for t in range(group))
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
        for t in range(group):
            dq_s[t] = carry[t]

    @pl.when(kb == n_sb - 1)
    def _emit():
        emit(tuple(dq_s[t] for t in range(group)))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale: float, causal: bool, block_q: int, sb: int,
                    n_sb: int, off: int, segments: bool, group: int, d: int):
    """dK/dV on the (b*h_kv, k-blocks, Q-superblocks) grid: each grid cell
    owns one KV head's k block; the streamed Q/dO superblocks carry the
    WHOLE query-head group in the feature dim ([1, sb, group*d], static
    per-head slices), so dk/dv accumulate the full GQA head-group sum in
    one pass — written once at KV-head count with no post-hoc reduction.
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
    k = k_ref[0]
    v = v_ref[0]

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
            dk, dv = carry
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
            for t in range(group):
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
            return dk, dv
        return body

    if resident:
        zero = lambda a: jnp.zeros(a.shape, jnp.float32)
        init = (zero(k), zero(v))
        if diag_split:
            # Diagonal q block i == ki (triangular bias), full blocks after.
            tri = _causal_tri(block_q, block_k)
            dk, dv = make_body(False, tri)(ki, init)
            dk, dv = jax.lax.fori_loop(ki + 1, sb // block_q,
                                       make_body(False, None), (dk, dv))
        else:
            dk, dv = jax.lax.fori_loop(first_inner(), sb // block_q,
                                       make_body(causal, None), init)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)
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
        carry = (dk_s[...], dv_s[...])
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
        dk_s[...], dv_s[...] = carry

    @pl.when(qb == n_sb - 1)
    def _emit():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd(causal, scale, interpret, res, g):
    q, k, v, segq, segk, o, lse = res
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q, block_k = _block_sizes(sq, sk)
    sb_k, sb_q = _superblock(sk), _superblock(sq)
    block_k = min(block_k, sb_k)    # fine blocks tile WITHIN the superblock
    block_q = min(block_q, sb_q)
    segments = segq is not None

    kvfold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hkv, x.shape[1], d)
    qt, dot = _fold_q(q, hkv), _fold_q(g, hkv)
    kt, vt = kvfold(k), kvfold(v)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term,
    # per head: [b*hkv, group, sq] rows match the lse layout.
    delta = jnp.sum(
        dot.astype(jnp.float32).reshape(b * hkv, sq, group, d)
        * _fold_q(o, hkv).astype(jnp.float32).reshape(b * hkv, sq, group, d),
        axis=-1).transpose(0, 2, 1)

    # One dq grid cell per (batch, KV head): q/do carry the whole query-head
    # group in the feature dim, K/V load once per group.
    dq_specs = [
        pl.BlockSpec((1, block_q, group * d), lambda g_, i, j: (g_, i, 0)),
        pl.BlockSpec((1, sb_k, d), lambda g_, i, j: (g_, j, 0)),
        pl.BlockSpec((1, sb_k, d), lambda g_, i, j: (g_, j, 0)),
        pl.BlockSpec((1, block_q, group * d), lambda g_, i, j: (g_, i, 0)),
        pl.BlockSpec((1, group, block_q), lambda g_, i, j: (g_, 0, i)),
        pl.BlockSpec((1, group, block_q), lambda g_, i, j: (g_, 0, i)),
    ]
    dq_operands = [qt, kt, vt, dot, lse, delta]
    if segments:
        dq_specs += _seg_specs(hkv, block_q, sb_k)
        dq_operands += [segq[:, None, :], segk[:, None, :]]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, sb=sb_k, n_sb=sk // sb_k,
                          off=sk - sq, segments=segments, group=group, d=d),
        grid=(b * hkv, sq // block_q, sk // sb_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, group * d),
                               lambda g_, i, j: (g_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, sq, group * d), q.dtype),
        scratch_shapes=[pltpu.VMEM((group, block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*dq_operands)

    # dK/dV: grid dim 0 owns one KV head; k blocks in the middle dim; Q/dO
    # superblocks stream innermost carrying the whole query-head group in
    # the feature dim, so dk/dv accumulate the GQA sum in scratch and are
    # written once at KV-head count.
    dkv_specs = [
        pl.BlockSpec((1, sb_q, group * d), lambda g_, j, i: (g_, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda g_, j, i: (g_, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda g_, j, i: (g_, j, 0)),
        pl.BlockSpec((1, sb_q, group * d), lambda g_, j, i: (g_, i, 0)),
        pl.BlockSpec((1, group, sb_q), lambda g_, j, i: (g_, 0, i)),
        pl.BlockSpec((1, group, sb_q), lambda g_, j, i: (g_, 0, i)),
    ]
    dkv_operands = [qt, kt, vt, dot, lse, delta]
    if segments:
        dkv_specs += [
            pl.BlockSpec((1, 1, sb_q), lambda g_, j, i: (g_ // hkv, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda g_, j, i: (g_ // hkv, 0, j)),
        ]
        dkv_operands += [segq[:, None, :], segk[:, None, :]]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, sb=sb_q, n_sb=sq // sb_q,
                          off=sk - sq, segments=segments, group=group, d=d),
        grid=(b * hkv, sk // block_k, sq // sb_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda g_, j, i: (g_, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda g_, j, i: (g_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(*dkv_operands)

    kvunfold = lambda x: x.reshape(b, hkv, sk, d).transpose(0, 2, 1, 3)
    none_seg = None if segq is None else np.zeros(segq.shape,
                                                  jax.dtypes.float0)
    none_segk = None if segk is None else np.zeros(segk.shape,
                                                   jax.dtypes.float0)
    return (_unfold_q(dq, b, hkv, sq).reshape(b, sq, h, d),
            kvunfold(dk), kvunfold(dv), none_seg, none_segk)


# ---------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, segq, segk, causal, scale, interpret):
    o, _ = _fwd(q, k, v, segq, segk, causal=causal, scale=scale,
                interpret=interpret)
    return o


def _flash_fwd(q, k, v, segq, segk, causal, scale, interpret):
    o, lse = _fwd(q, k, v, segq, segk, causal=causal, scale=scale,
                  interpret=interpret)
    return o, (q, k, v, segq, segk, o, lse)


_flash.defvjp(_flash_fwd,
              lambda causal, scale, interpret, res, g:
              _bwd(causal, scale, interpret, res, g))


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
    return _flash(q, k, v, q_segment_ids, kv_segment_ids, causal, scale,
                  interpret)
