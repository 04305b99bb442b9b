"""Pallas TPU paged decode attention over a LATENT page pool (absorbed
multi-head latent attention, DeepSeek-V2 §2.1).

The pool holds one row a token, ``[num_pages, page_tokens, lanes]``: the
normed latent ``c`` (``rank`` lanes) followed by the shared rope key (and
whatever the layout pads after them). In the absorbed form every query head
attends ONE key that is also the value: per head ``score = q̃·c + q^R·k^R``
and ``u = Σ p·c``, so a cell's tile is read once and used twice — ONE
``[heads·sq, lanes] x [lanes, T]`` product for the scores of all heads and one
``[heads·sq, T] x [T, rank]`` product for the values, from the same VMEM tile.

The grid, the scalar-prefetched block table, the kernel's own page copies
(one DMA a page into a contiguous double-buffered tile, the next live cell's
copies started under this cell's arithmetic), the cursor mask and the online
softmax are :mod:`ops.pallas_paged_attn`'s (PR 26); what differs is that
there is no KV-head loop and no V pool. Work per row follows its LIVE length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu
from k8s_distributed_deeplearning_tpu.ops.pallas_paged_attn import (
    NEG_INF, VMEM_LIMIT_BYTES)

# Widest query chunk the ABSORBED kernel is selected for: decode and a verify
# window. A prefill chunk takes the EXPANDED form (:func:`latent_chunk_
# attention`), which costs a third of the absorbed form's operations a key.
MAX_QUERY_TOKENS = 8
# Tokens a decode cell's tile holds. Timed alone on a v5e at the docs-backlog
# cell's shape (32 rows, 64 heads, ~9.7 k live tokens a row, least time 0.44
# ms; PERF.md 6, PR 27): 512 tokens a cell 0.81 ms, 1,024 0.66, 2,048 0.62 —
# fewer grid steps, 2.6 MB of copies in flight.
CELL_TOKENS = 2048
# The chunk kernel: KV tokens a grid step, heads a grid cell.
CHUNK_BLOCK_K = 512
CHUNK_HEADS = 4


def default_impl(platform: str | None = None) -> str:
    """``attention_impl="auto"`` for a latent block-table call: the kernels
    on TPU (absorbed up to :data:`MAX_QUERY_TOKENS` queries a row, expanded
    beyond), XLA elsewhere."""
    tpu = on_tpu() if platform is None else platform == "tpu"
    return "latent_flash" if tpu else "xla"


def default_pages_per_cell(page_tokens: int, n_blocks: int) -> int:
    """Pages a decode grid cell attends: :data:`CELL_TOKENS` tokens' worth,
    never more than the table."""
    return max(1, min(CELL_TOKENS // page_tokens, n_blocks))


def _kernel(tables_ref, last_ref, q_ref, pos_ref, src, o_ref, m_s, l_s,
            acc_s, buf, sems, slot_s, *, rank, page_tokens, pages, scale):
    """One (batch row, run of ``pages`` virtual blocks) grid cell.

    ``q_ref`` ``[1, rows, lanes]`` holds, per query row (head-major,
    ``rows = heads·sq``), the absorbed query ``W_UKᵀ q^N`` in the first
    ``rank`` lanes, the rope query after it and zeros in the pad lanes;
    ``src`` is the pool in HBM. State ``m_s``/``l_s`` ``[rows, 1]`` and
    ``acc_s`` ``[rows, rank]`` (f32) carries across a row's cells."""
    i, j = pl.program_id(0), pl.program_id(1)
    b, n_cells = pl.num_programs(0), pl.num_programs(1)
    rows = q_ref.shape[1]
    t_cell = pages * page_tokens

    def copies(row, cell, slot, do):
        first = cell * pages
        live = jnp.clip(last_ref[row] - first + 1, 0, pages)

        def one(p, carry):
            page = tables_ref[row, first + p]
            dst = pl.ds(pl.multiple_of(p * page_tokens, page_tokens),
                        page_tokens)
            do(pltpu.make_async_copy(src.at[page], buf.at[slot, dst],
                                     sems.at[slot]))
            return carry
        jax.lax.fori_loop(0, live, one, 0)

    start = lambda dma: dma.start()
    wait = lambda dma: dma.wait()

    @pl.when((i == 0) & (j == 0))
    def _first():
        slot_s[0] = 0
        buf[...] = jnp.zeros_like(buf)
        copies(0, 0, 0, start)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j * pages <= last_ref[i])
    def _cell():
        slot = slot_s[0]
        more = (j + 1) * pages <= last_ref[i]
        nxt_row = jnp.where(more, i, i + 1)

        @pl.when(nxt_row < b)
        def _prefetch():
            copies(nxt_row, jnp.where(more, j + 1, 0), 1 - slot, start)
            slot_s[0] = 1 - slot

        copies(i, j, slot, wait)
        tile = buf[slot]                                       # [T, lanes]
        col = (j * t_cell
               + jax.lax.broadcasted_iota(jnp.int32, (rows, t_cell), 1))
        allow = col <= pos_ref[0]                              # [rows, T]
        s = jax.lax.dot_general(
            q_ref[0], tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [rows, T]
        s = jnp.where(allow, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(tile.dtype), tile[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [rows, rank]
        acc_s[...] = acc_s[...] * alpha + pv
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new

    @pl.when(j == n_cells - 1)
    def _emit():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def latent_decode_attention(q_abs: jax.Array, pool: jax.Array,
                            block_tables: jax.Array, positions: jax.Array,
                            *, rank: int, softmax_scale: float,
                            pages_per_cell: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Absorbed latent decode attention straight off the page pool.

    q_abs: ``[B, sq, H, lanes]`` — per head the absorbed query (``rank``
    lanes), the rope query, zeros up to the pool's lane width; pool:
    ``[num_pages, page_tokens, lanes]`` (written BEFORE the call);
    block_tables ``[B, n_blocks]`` int32 (0 = the scratch page); positions
    ``[B, sq]`` int32 — query ``i`` of row ``b`` attends virtual columns
    ``<= positions[b, i]``. Returns ``u`` ``[B, sq, H, rank]`` in q's dtype:
    each head's probability-weighted latent, for the caller's ``W_UV``."""
    if q_abs.ndim != 4 or pool.ndim != 3:
        raise ValueError(f"q_abs [B, sq, H, lanes] / pool [pages, page_tokens,"
                         f" lanes] expected, got {q_abs.shape} / {pool.shape}")
    b, sq, h, lanes = q_abs.shape
    _, page_tokens, pool_lanes = pool.shape
    if lanes != pool_lanes or rank > lanes:
        raise ValueError(f"query lanes {lanes} / rank {rank} do not fit the "
                         f"pool's {pool_lanes} lanes")
    if block_tables.shape[0] != b or positions.shape != (b, sq):
        raise ValueError(f"block_tables [B={b}, n_blocks] and positions "
                         f"[B={b}, sq={sq}] expected, got "
                         f"{block_tables.shape} / {positions.shape}")
    if interpret is None:
        interpret = not on_tpu()
    n_blocks = block_tables.shape[1]
    pages = pages_per_cell or default_pages_per_cell(page_tokens, n_blocks)
    if not 1 <= pages <= n_blocks:
        raise ValueError(f"pages_per_cell must be in [1, {n_blocks}], got {pages}")
    n_cells = -(-n_blocks // pages)
    t_cell = pages * page_tokens
    rows = h * sq
    s_virt = n_blocks * page_tokens

    qg = q_abs.transpose(0, 2, 1, 3).reshape(b, rows, lanes)   # head-major
    pos = jnp.minimum(positions.astype(jnp.int32), s_virt - 1)
    pos_rows = jnp.tile(pos, (1, h))[:, :, None]               # [B, rows, 1]
    last = jnp.max(pos, axis=1) // page_tokens
    tables = block_tables.astype(jnp.int32)

    row_spec = lambda i, j, tbl, last: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_cells),
        in_specs=[pl.BlockSpec((1, rows, lanes), row_spec),
                  pl.BlockSpec((1, rows, 1), row_spec),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, rows, rank), row_spec),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, rank), jnp.float32),
                        pltpu.VMEM((2, t_cell, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_kernel, rank=rank, page_tokens=page_tokens,
                          pages=pages, scale=softmax_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q_abs.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * rows * s_virt * (lanes + rank),
            bytes_accessed=(b * s_virt * lanes * pool.dtype.itemsize
                            + qg.size * qg.dtype.itemsize
                            + b * rows * rank * q_abs.dtype.itemsize),
            transcendentals=b * rows * s_virt),
        interpret=interpret,
        # the name the device trace carries for the kernel's events
        name="latent_attn",
    )(tables, last, qg, pos_rows, pool)
    return out.reshape(b, h, sq, rank).transpose(0, 2, 1, 3)


def _chunk_kernel(last_ref, qn_ref, qr_ref, pos_ref, lat_ref, wuk_ref, wuv_ref,
                  o_ref, m_s, l_s, acc_s, *, rank, rope, block_k, scale):
    """One (row, group of heads, block of ``block_k`` cache positions) grid
    cell of the EXPANDED form: the block's latent rows ``[block_k, lanes]``
    are up-projected IN VMEM to this group's keys and values (``W_UK,h``,
    ``W_UV,h``: ``[rank, dn]``, ``[rank, dv]`` a head) — the expanded K and V
    never exist in HBM — then flash attention: scores of the chunk's queries
    (``q^N·k^N + q^R·k^R``), cursor mask from the queries' own positions,
    online softmax in f32 scratch carried over the row's blocks."""
    i, j = pl.program_id(0), pl.program_id(2)
    heads, sq = qn_ref.shape[1], qn_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(j <= last_ref[i])
    def _block():
        lat = lat_ref[0]                                       # [block_k, lanes]
        c, k_r = lat[:, :rank], lat[:, rank:rank + rope]
        col = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (sq, block_k), 1)
        allow = col <= pos_ref[0]                              # [sq, block_k]
        nt = (((1,), (1,)), ((), ()))                          # a @ b.T
        for h in range(heads):
            k_n = jnp.dot(c, wuk_ref[h], preferred_element_type=jnp.float32
                          ).astype(lat.dtype)                  # [block_k, dn]
            v = jnp.dot(c, wuv_ref[h], preferred_element_type=jnp.float32
                        ).astype(lat.dtype)                    # [block_k, dv]
            s = (jax.lax.dot_general(qn_ref[0, h], k_n, nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[0, h], k_r, nt,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            s = jnp.where(allow, s, NEG_INF)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            acc_s[h] = acc_s[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            m_s[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        for h in range(heads):
            o_ref[0, h] = (acc_s[h] / jnp.maximum(l_s[h], 1e-30)
                           ).astype(o_ref.dtype)


def latent_chunk_attention(q_n: jax.Array, q_r: jax.Array, lat: jax.Array,
                           w_uk: jax.Array, w_uv: jax.Array,
                           positions: jax.Array, *, rank: int,
                           softmax_scale: float, block_k: int | None = None,
                           heads_per_cell: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Expanded latent attention of a prefill chunk over its row's cache.

    q_n ``[B, sq, H, dn]``, q_r ``[B, sq, H, dr]``; lat ``[B, S, lanes]`` —
    the row's pages gathered in table order (written BEFORE the call), S a
    multiple of ``block_k``; w_uk ``[rank, H, dn]``, w_uv ``[rank, H, dv]``;
    positions ``[B, sq]`` int32 — query ``i`` attends columns ``<=
    positions[b, i]``. Blocks past a row's last position are neither fetched
    anew nor computed. Returns ``[B, sq, H, dv]`` in q's dtype."""
    b, sq, h, dn = q_n.shape
    dr, dv = q_r.shape[-1], w_uv.shape[-1]
    s_virt, lanes = lat.shape[1], lat.shape[2]
    bk = block_k or min(CHUNK_BLOCK_K, s_virt)
    g = heads_per_cell or (CHUNK_HEADS if h % CHUNK_HEADS == 0 else 1)
    if s_virt % bk or h % g:
        raise ValueError(f"{s_virt} cache positions / {h} heads do not "
                         f"divide into blocks of {bk} / groups of {g}")
    if interpret is None:
        interpret = not on_tpu()
    pos = jnp.minimum(positions.astype(jnp.int32), s_virt - 1)
    last = jnp.max(pos, axis=1) // bk                          # [B]
    head_major = lambda x: x.transpose(0, 2, 1, 3)             # [B, H, sq, d]
    w_head = lambda w: w.transpose(1, 0, 2)                    # [H, rank, d]
    row = lambda i, c, j, last: (i, c, 0, 0)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, rank=rank, rope=dr, block_k=bk,
                          scale=softmax_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // g, s_virt // bk),
            in_specs=[
                pl.BlockSpec((1, g, sq, dn), row),
                pl.BlockSpec((1, g, sq, dr), row),
                pl.BlockSpec((1, sq, 1), lambda i, c, j, last: (i, 0, 0)),
                # a dead block names the last live one: no new copy
                pl.BlockSpec((1, bk, lanes), lambda i, c, j, last: (
                    i, jnp.minimum(j, last[i]), 0)),
                pl.BlockSpec((g, rank, dn), lambda i, c, j, last: (c, 0, 0)),
                pl.BlockSpec((g, rank, dv), lambda i, c, j, last: (c, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, g, sq, dv), row),
            scratch_shapes=[pltpu.VMEM((g, sq, 1), jnp.float32),
                            pltpu.VMEM((g, sq, 1), jnp.float32),
                            pltpu.VMEM((g, sq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q_n.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * s_virt * (sq * (dn + dr + dv) + rank * (dn + dv)),
            bytes_accessed=(b * (h // g) * s_virt * lanes * lat.dtype.itemsize
                            + 2 * b * h * sq * (dn + dr + dv)),
            transcendentals=b * h * sq * s_virt),
        interpret=interpret,
        name="latent_chunk_attn",
    )(last, head_major(q_n), head_major(q_r), pos[:, :, None], lat,
      w_head(w_uk), w_head(w_uv))
    return out.transpose(0, 2, 1, 3)
