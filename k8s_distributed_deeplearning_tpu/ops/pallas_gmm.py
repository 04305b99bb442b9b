"""Grouped matrix multiply (ragged GEMM) as a Pallas TPU kernel.

The MoE expert-compute hot path. The capacity-buffer formulation
(``models/moe.py`` index/einsum dispatch) pads every expert to
``capacity_factor·k·T/E`` rows, so at cf=1.25 ≥20% of the expert MXU work
multiplies zeros before any load imbalance — and genuinely hot experts
DROP tokens. This kernel removes both: tokens are laid out in one flat
``[M, d]`` buffer sorted by expert (dropless — every (token, choice) pair
is computed), each expert's rows rounded up to the row-block size, and the
kernel streams row blocks through the MXU with the expert id of each block
SCALAR-PREFETCHED so the right expert's weight block is resident before
the block arrives. Per-expert work is proportional to real tokens
(± one block of round-up), not padded capacity.

Design notes (TPU-first):

- grid (N/bn, M/bm) with the row dim INNERMOST: the rhs BlockSpec index
  map reads ``block_expert[m]`` (a prefetched scalar), which is
  non-decreasing — consecutive row blocks of one expert revisit the same
  weight block, so Pallas re-fetches weights only at expert boundaries
  (E fetches per column sweep, not M/bm);
- one K pass per block (K = model/mlp dim fits VMEM whole), f32 MXU
  accumulation via ``preferred_element_type``, no scratch carries;
- fully-dead row blocks (round-up slack, empty experts) skip the matmul
  via a prefetched liveness flag — they write zeros (their rows are never
  gathered back anyway, the buffer's padding rows are zero by
  construction);
- the backward is two more grouped products with the same layout:
  ``dlhs = gmm(dout, rhsᵀ)`` (reusing this kernel on a transposed weight
  view) and ``drhs = tgmm(lhs, dout)`` — a separate kernel that
  accumulates ``lhs_blockᵀ · dout_block`` into the owning expert's
  ``[K, N]`` gradient across that expert's contiguous run of row blocks
  (out-block revisiting keeps the accumulator in VMEM; it spills to HBM
  once per expert per column sweep);
- serving programs take forward-only products with no gradient rule:
  ``gmm_forward`` (one weight) and ``gmm_act``, the experts' hidden
  activation as the product's EPILOGUE — ``relu(x W_up)^2`` from one weight,
  ``silu(x W_gate) * (x W_up)`` from two whose blocks are indexed by the same
  prefetched expert, so a row block is read once for both; the activation is
  computed on the f32 accumulators and rounded once, and a dead block's zeros
  are its activation (act(0) = 0). No ``[M_pad, N]`` product goes to HBM and
  back, and the compiler has no pair of outputs to place in VMEM;
- their column block (:func:`wide_block`) is the widest multiple of 128
  dividing N whose weight blocks — one or two, each double-buffered — fit
  ``_WIDE_BLOCK_BYTES``: the whole width where it fits (one column step: each
  row block and each expert's weights read once);
- every forward kernel's name keeps ``moe_gmm`` (``moe_gmm``,
  ``moe_gmm_act``): the benchmark's expert-product readers (e.g.
  ``moe_gmm_roofline``) find the device time by that name, and count the work
  from the serving counters whatever kernel does it — a kernel named
  otherwise would drop its time from the share;
- off-TPU the kernels run with ``interpret=True`` — CI exercises the
  exact code path TPUs compile (same convention as ``pallas_flash``).

No counterpart in the reference (its MoE story is absent; SURVEY.md §2c).
Parity against the capacity paths is tested with capacities large enough
that they too drop nothing.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu


# Row-block size. The on-chip sweep (round 5) measured the
# MLP pair at bm 512/256/128 = 0.410/0.614/0.731 ms — 512 wins; the
# round-up slack per expert stays < bm rows (≤ 8·511 ≈ 2.5% of the
# flagship's M = 16384, and those blocks SKIP compute via the live flag).
_BLOCK_M = 512
# Column block cap, clipped to divide N. Full-width columns won the sweep
# decisively (bn=N 0.611 ms vs bn=1024 0.715 at bm=512, "arbitrary"):
# with one column step, expert weight blocks are fetched at most E times
# total. 2048 covers the flagship dims while bounding VMEM (lhs 0.75M·2 +
# rhs 3M·2 + out 2M·2 ≈ 11.5 MiB at bm=512, K=768).
_BLOCK_N = 2048


def _pick_block(n: int, target: int) -> int:
    """Largest power-of-two ≤ target dividing n (shared convention)."""
    b = 1
    while b * 2 <= min(n, target) and n % (b * 2) == 0:
        b *= 2
    return b


def _compiler_params(interpret):
    if interpret:
        return None
    # Column steps are independent ("parallel" — worth 0.611 → 0.410 ms on
    # the MLP-pair sweep even at a single column step, evidently unlocking
    # a better Mosaic schedule); row steps stay "arbitrary": the rhs/out
    # index maps read prefetched scalars indexed by the row step, and the
    # tgmm accumulator carries state across a group's row blocks. The
    # scoped-VMEM limit is raised above the 16 MiB default (flash-kernel
    # convention): tgmm's double-buffered f32 [K, bn] accumulator plus its
    # streamed operands legitimately peaks at ~17.5 MiB on the flagship
    # dims, well within physical VMEM.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)


class GroupedLayout(NamedTuple):
    """Per-step routing layout consumed by :func:`gmm` / the dispatcher.

    All shapes are static; values are data-dependent (traced).

    - ``row_offset`` [E]: first row of each expert's block-aligned span.
    - ``block_expert`` [tiles_m] int32: owning expert of each row block
      (tail blocks past the last span clip to E-1; they are dead).
    - ``block_live`` [tiles_m] int32 (0/1): block contains ≥1 real row.
    - ``block_first`` [tiles_m] int32 (0/1): first block of its expert's
      span (tgmm initializes its accumulator here).
    - ``m_pad``: static padded row count (tiles_m · block_m).
    - ``block_m``: the row-block size the layout was built for.
    """

    row_offset: jax.Array
    block_expert: jax.Array
    block_live: jax.Array
    block_first: jax.Array
    m_pad: int
    block_m: int


def padded_rows(total_rows: int, num_experts: int,
                block_m: int = _BLOCK_M) -> int:
    """Static padded row count: every expert's span rounds up to a whole
    block (empty experts still own one dead block), so the worst case is
    ``ceil(total/bm) + E`` blocks."""
    return (-(-total_rows // block_m) + num_experts) * block_m


def grouped_layout(group_sizes: jax.Array, total_rows: int,
                   block_m: int = _BLOCK_M) -> GroupedLayout:
    """Build the block-aligned ragged layout from per-expert row counts.

    ``group_sizes`` [E] int32 with ``sum == total_rows`` (static bound).
    """
    e = group_sizes.shape[0]
    m_pad = padded_rows(total_rows, e, block_m)
    tiles_m = m_pad // block_m
    blocks = jnp.maximum(1, -(-group_sizes // block_m))     # ceil, ≥1
    ends = jnp.cumsum(blocks * block_m)                     # span ends [E]
    row_offset = (ends - blocks * block_m).astype(jnp.int32)
    first_row = jnp.arange(tiles_m, dtype=jnp.int32) * block_m
    # Block b belongs to expert e iff ends[e-1] <= b·bm < ends[e].
    block_expert = jnp.clip(
        jnp.searchsorted(ends, first_row, side="right"), 0, e - 1
    ).astype(jnp.int32)
    live_end = row_offset[block_expert] + group_sizes[block_expert]
    block_live = (first_row < live_end).astype(jnp.int32)
    block_first = (first_row == row_offset[block_expert]).astype(jnp.int32)
    return GroupedLayout(row_offset, block_expert, block_live, block_first,
                         m_pad, block_m)


# ---------------------------------------------------------------------------
# Forward kernel: out[m_block] = lhs[m_block] @ rhs[expert(m_block)], or with
# an activation as its epilogue, act(lhs[m_block] @ w[expert(m_block)] for
# each weight w)
# ---------------------------------------------------------------------------

# The experts' hidden activations, by the weights each one multiplies:
# "relu2" = relu(x W_up)^2, "swiglu" = silu(x W_gate) * (x W_up).
ACT_WEIGHTS = {"relu2": 1, "swiglu": 2}


def _activation(act: str, *products):
    if act == "relu2":
        (up,) = products
        return jnp.square(jnp.maximum(up, 0.0))
    gate, up = products
    return jax.nn.silu(gate) * up


def _gmm_kernel(act, expert_ref, live_ref, first_ref, lhs_ref, *refs):
    del expert_ref, first_ref
    *w_refs, out_ref = refs
    m = pl.program_id(1)

    # Each product stays in f32 until the activation: one rounding, at the end.
    @pl.when(live_ref[m] == 1)
    def _compute():
        x = lhs_ref[:]
        products = [jax.lax.dot_general(x, w_ref[0], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                    for w_ref in w_refs]
        out = products[0] if act is None else _activation(act, *products)
        out_ref[:] = out.astype(out_ref.dtype)

    # act(0) = 0 for every activation: a dead block's zeros are its output.
    @pl.when(live_ref[m] == 0)
    def _dead():
        out_ref[:] = jnp.zeros_like(out_ref)


def _gmm_call(lhs, weights, layout: GroupedLayout, interpret: bool,
              block_n: int | None = None, act: str | None = None):
    m_pad, k = lhs.shape
    e, k2, n = weights[0].shape
    assert k == k2 and all(w.shape == weights[0].shape for w in weights), (
        lhs.shape, [w.shape for w in weights])
    bm = layout.block_m
    bn = block_n or _pick_block(n, _BLOCK_N)
    tiles_m, tiles_n = m_pad // bm, n // bn
    grid = (tiles_n, tiles_m)   # row dim innermost: weight blocks revisit
    # every weight's block is the same prefetched expert's: a row block is
    # read once for all of them
    w_spec = pl.BlockSpec((1, k, bn), lambda j, m, be, bl, bf: (be[m], 0, j))

    return pl.pallas_call(
        functools.partial(_gmm_kernel, act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, k), lambda j, m, be, bl, bf: (m, 0))]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((bm, bn),
                                   lambda j, m, be, bl, bf: (m, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), lhs.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        # the name the device trace carries for the kernel's events; both keep
        # "moe_gmm", which the benchmark's expert-product readers match
        name="moe_gmm" if act is None else "moe_gmm_act",
    )(layout.block_expert, layout.block_live, layout.block_first, lhs,
      *weights)


# ---------------------------------------------------------------------------
# Weight-gradient kernel: drhs[e] = Σ_{m in group e} lhs[m]ᵀ @ dout[m]
# ---------------------------------------------------------------------------


def _tgmm_kernel(expert_ref, live_ref, first_ref, lhs_ref, dout_ref,
                 out_ref, acc_ref):
    m = pl.program_id(1)
    nm = pl.num_programs(1)
    live, first = live_ref[m] == 1, first_ref[m] == 1

    # lhsᵀ·dout contracting the row-block dim, accumulated in an f32 VMEM
    # scratch across the expert's contiguous run of row blocks. Dead
    # blocks hold zero lhs rows, so skipping them is pure perf.
    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accum():
        acc_ref[:] += jax.lax.dot_general(
            lhs_ref[:], dout_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Flush once per (expert, column block): the last row block of the
    # expert's span (tail blocks past the final span clip to the last
    # expert and stay part of its run, adding zeros before its flush).
    is_last = jnp.where(m + 1 < nm,
                        first_ref[jnp.minimum(m + 1, nm - 1)] == 1,
                        True)
    @pl.when(is_last)
    def _flush():
        out_ref[0] = acc_ref[:].astype(out_ref.dtype)


def _tgmm_call(lhs, dout, num_experts: int, layout: GroupedLayout,
               interpret: bool):
    m_pad, k = lhs.shape
    m_pad2, n = dout.shape
    assert m_pad == m_pad2
    bm = layout.block_m
    bn = _pick_block(n, _BLOCK_N)
    tiles_m, tiles_n = m_pad // bm, n // bn
    grid = (tiles_n, tiles_m)   # row dim innermost: expert runs contiguous

    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, k), lambda j, m, be, bl, bf: (m, 0)),
                pl.BlockSpec((bm, bn), lambda j, m, be, bl, bf: (m, j)),
            ],
            out_specs=pl.BlockSpec((1, k, bn),
                                   lambda j, m, be, bl, bf: (be[m], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), lhs.dtype),
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(layout.block_expert, layout.block_live, layout.block_first, lhs, dout)


# ---------------------------------------------------------------------------
# Differentiable wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _gmm(lhs, rhs, row_offset, block_expert, block_live, block_first,
         meta, interpret):
    layout = GroupedLayout(row_offset, block_expert, block_live,
                           block_first, *meta)
    return _gmm_call(lhs, (rhs,), layout, interpret)


def _gmm_fwd(lhs, rhs, row_offset, block_expert, block_live, block_first,
             meta, interpret):
    out = _gmm(lhs, rhs, row_offset, block_expert, block_live, block_first,
               meta, interpret)
    return out, (lhs, rhs, row_offset, block_expert, block_live,
                 block_first)


def _gmm_bwd(meta, interpret, res, g):
    lhs, rhs, row_offset, block_expert, block_live, block_first = res
    layout = GroupedLayout(row_offset, block_expert, block_live,
                           block_first, *meta)
    g = g.astype(lhs.dtype)
    # dlhs: the same grouped product against the transposed weight view.
    # The explicit swapaxes materializes E·N·K·2 bytes once per backward —
    # measured noise next to the three grouped matmuls (round 5).
    dlhs = _gmm_call(g, (jnp.swapaxes(rhs, 1, 2),), layout, interpret)
    drhs = _tgmm_call(lhs, g, rhs.shape[0], layout, interpret)
    def zero_ct(a):  # integer primals carry float0 cotangents
        return np.zeros(a.shape, jax.dtypes.float0)
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype),
            zero_ct(row_offset), zero_ct(block_expert),
            zero_ct(block_live), zero_ct(block_first))


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, layout: GroupedLayout,
        interpret: bool | None = None) -> jax.Array:
    """Grouped matmul: rows of ``lhs`` [M_pad, K] laid out per
    :func:`grouped_layout` times the owning expert's ``rhs`` [E, K, N]
    weight → [M_pad, N]. Differentiable wrt lhs and rhs."""
    if interpret is None:
        interpret = not on_tpu()
    meta = (layout.m_pad, layout.block_m)
    return _gmm(lhs, rhs, layout.row_offset, layout.block_expert,
                layout.block_live, layout.block_first, meta, interpret)


# What the weight blocks of one step of a forward-only product may take in
# VMEM, double-buffered, beside the row block, the output block and the f32
# products (inside the 64 MiB limit of ``_compiler_params``).
_WIDE_BLOCK_BYTES = 32 * 1024 * 1024


def wide_block(n: int, k: int, itemsize: int = 2, weights: int = 1) -> int:
    """The column block of the forward-only products: the widest multiple of
    128 that divides ``n`` with ``weights`` double-buffered ``[k, block]``
    weight blocks inside ``_WIDE_BLOCK_BYTES`` — all of ``n`` where that fits,
    so that a row block is read once and an expert's weights once a run of its
    blocks. The power-of-two rule of :func:`_pick_block` gives 128 for
    n = 2,688 = 21 x 128: 21 column steps, each reading every row block
    again; it stays for an ``n`` that no multiple of 128 divides."""
    for blocks in range(n // 128, 0, -1):
        bn = blocks * 128
        if n % bn == 0 and 2 * weights * k * bn * itemsize <= _WIDE_BLOCK_BYTES:
            return bn
    return _pick_block(n, _BLOCK_N)


def gmm_forward(lhs: jax.Array, rhs: jax.Array, layout: GroupedLayout,
                interpret: bool | None = None) -> jax.Array:
    """:func:`gmm` for a serving program: the forward product alone (no
    gradient rule), at :func:`wide_block` columns a step."""
    if interpret is None:
        interpret = not on_tpu()
    return _gmm_call(lhs, (rhs,), layout, interpret,
                     wide_block(rhs.shape[2], rhs.shape[1], rhs.dtype.itemsize))


def gmm_act(lhs: jax.Array, weights: tuple[jax.Array, ...],
            layout: GroupedLayout, act: str,
            interpret: bool | None = None) -> jax.Array:
    """The experts' hidden activation in one grouped product, for a serving
    program (no gradient rule): ``act`` of the rows of ``lhs`` [M_pad, K] times
    their expert's weights — ``(w_up,)`` for ``"relu2"``, ``(w_gate, w_up)``
    for ``"swiglu"``, each [E, K, N] — → [M_pad, N]. The products meet in
    VMEM: no [M_pad, N] product goes to HBM and back before the activation.
    The column block is :func:`wide_block`'s, counted for all the weights."""
    if len(weights) != ACT_WEIGHTS[act]:
        raise ValueError(f"{act!r} takes {ACT_WEIGHTS[act]} weights, "
                         f"got {len(weights)}")
    if interpret is None:
        interpret = not on_tpu()
    _, k, n = weights[0].shape
    return _gmm_call(lhs, weights, layout, interpret,
                     wide_block(n, k, weights[0].dtype.itemsize, len(weights)),
                     act)


def gmm_reference(lhs: jax.Array, rhs: jax.Array,
                  layout: GroupedLayout) -> jax.Array:
    """Dense reference for tests: every row multiplied by its block's
    expert weight (O(M·E) memory — test sizes only)."""
    e_of_row = jnp.repeat(layout.block_expert, layout.block_m)
    return jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                      rhs[e_of_row].astype(jnp.float32)).astype(lhs.dtype)
