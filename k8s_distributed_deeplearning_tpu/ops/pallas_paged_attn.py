"""Pallas TPU paged decode-attention: fused gather+attend over pool pages.

The serving engine's paged decode branch (models/transformer.py) stores KV
as ONE pool of fixed-size pages ``[num_pages, page_tokens, kv·head_dim]``
(vLLM's PagedAttention layout, serve/page_pool.py) and, on the XLA path,
materializes each row's virtual sequence with a
``pool[block_tables]`` gather before calling plain attention — a
``[B, n_blocks·page_tokens, kv, hd]`` HBM round-trip per decode step that
exists only to feed the softmax. This kernel fuses the two. The grid walks
``(batch row, cell)``, a cell being a run of P consecutive virtual blocks
(:func:`default_pages_per_cell`: 32 pages = 1024 tokens for decode at the
benchmark cell's geometry, so a 128-block table is 4 cells a row, not 128).
The pools stay in HBM; a cell reads the SCALAR-PREFETCHED block table and
copies its pages — one DMA a page, wherever they lie in the pool — into one
contiguous VMEM tile ``[P·page_tokens, kv·hd]`` per K and V, double-buffered
so that the next live cell's copies run under this cell's arithmetic. Per KV
head the cell then does ONE product of the group's query rows ``[group·sq, hd]``
against the tile's ``[P·page_tokens, hd]`` slice and one online-softmax
update (flash-attention style, state in VMEM scratch across the row's
cells). No K or V proportional to the virtual sequence ever lands in HBM
(under int8 pages their f32 scales do: 1/32 of the pages' bytes at hd 128,
gathered by XLA because Mosaic copies no slab narrower than 128 lanes).

Same contract as the XLA path it replaces:

- grouped-query decode attention: q ``[B, sq, H, hd]`` (``sq`` is 1 for
  classic decode, or a small speculative verify window), KV heads folded
  into the page lane dim (``kv·hd``), q head ``h`` attends KV head
  ``h // (H/kv)``;
- per-row causal cursor masking: query ``i`` of row ``b`` attends virtual
  columns ``col <= positions[b, i]`` — stale KV beyond a row's cursor
  (freed-slot garbage, rejected speculative drafts) is never read, and the
  scratch page (table entries 0) is always masked out by the same rule;
- pages past a row's cursor are never copied and cells wholly past it do
  nothing (``pl.when``), so the work per row is proportional to its LIVE
  length, not the table width: a dead cell costs its grid step alone.

Off-TPU the kernel runs in the Pallas interpreter (``interpret`` defaults
to ``not on_tpu()``), so CPU CI exercises the exact same code path —
tier-1 keeps the XLA gather as its default via the ``attention_impl``
selection (:func:`default_impl`) and opts into the kernel explicitly
(``"paged_flash"``) for parity tests.

What ONE grid row carries is bounded by ``sq`` (:data:`MAX_QUERY_TOKENS`):
the KV-head loop is python-unrolled over ``[group·sq, P·page_tokens]`` score
tiles and the state is ``H·sq·(hd + 2·128)`` f32 in VMEM, so both Mosaic's
compile time and the VMEM footprint grow with the query width. Decode (1), a
speculative verify window (k+1) and a 128-token prefill chunk are one grid
row a batch row. A wider call — a 512-token chunk, a monolithic prefill
bucket — is cut into BLOCKS OF QUERIES (:func:`default_query_block`: ``Q``
tokens, ``group·Q`` = 512 query rows a KV head), and each block is to the
kernel what a batch row is: its own query slab, cursor column and last live
block, the row's table. The kernel's own rules then give the causal saving —
a query block copies only pages at or before its own last position, a cell
past it costs a grid step — and the VMEM a call holds is that of ``sq = Q``.
K/V pages are read once per query block that can see them. A width that is
no whole number of blocks takes the XLA gather path (:func:`default_impl`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

NEG_INF = -1e30

# Most query tokens ONE grid row carries. The KV-head loop is unrolled over
# [group·sq, T] score tiles, so Mosaic's compile time follows rows x T, and
# the f32 state and the q/out blocks follow H·sq. Compiling the 32q/8kv
# hd-128 kernel for a v5e takes 0.8 s at sq=1 (32 pages a cell) and 2.7 s at
# sq=128 (8 pages a cell; the one-page-a-cell kernel it replaces took 2.0 s
# and 2.5 s); at 256 state and blocks are 20 MiB and leave the rule one page
# a cell (3.6 s), and at 512 they pass VMEM_LIMIT_BYTES and Mosaic refuses
# (Mosaic for a described v5e, PR 26). 128 covers decode, every verify
# window and a 128-token prefill chunk in one row; a wider call is cut into
# blocks of queries, each a row of its own (default_query_block).
MAX_QUERY_TOKENS = 128
# Query rows a KV head (group · Q) that a block of a wider call is cut to:
# the rows the kernel runs at for a 128-token chunk at a group of 4 (the
# Mistral and lfm2 cells), where SCORE_TILE_ELEMS gives it 256 tokens a cell.
QUERY_BLOCK_ROWS = 512


def default_query_block(sq: int, group: int) -> int:
    """Query tokens one grid row of a call carries, from what the call can
    see (its width, and the query heads a KV head serves). Up to
    :data:`MAX_QUERY_TOKENS` the call itself: one row a batch row. Wider, the
    largest power of two ``Q`` with ``group·Q <= QUERY_BLOCK_ROWS`` (128 at a
    group of 4 or fewer, 32 at 16) where ``sq`` is a whole number of them;
    else ``sq`` again — ONE block of a width Mosaic may refuse, which
    :func:`default_impl` therefore never picks."""
    if sq <= MAX_QUERY_TOKENS:
        return sq
    q = 1
    while 2 * q <= min(MAX_QUERY_TOKENS, QUERY_BLOCK_ROWS // group):
        q *= 2
    return sq if sq % q else q


def default_impl(sq: int, platform: str | None = None, *,
                 group: int = 1) -> str:
    """The ``attention_impl="auto"`` rule for block-table (paged) calls,
    from what the call can observe: the Pallas kernel on TPU for a call of
    one grid row a batch row (``sq <= MAX_QUERY_TOKENS``: decode, a verify
    window, a 128-token chunk) and for any wider call that is a whole number
    of query blocks (:func:`default_query_block` at the model's *group* of
    query heads a KV head: a 512-token chunk, a monolithic bucket); the XLA
    gather-attend everywhere else — a width off the block grid, and every
    shape off TPU, where the kernel would run in the (slow) interpreter."""
    tpu = on_tpu() if platform is None else platform == "tpu"
    served = (sq <= MAX_QUERY_TOKENS
              or default_query_block(sq, group) != sq)
    return "paged_flash" if tpu and served else "xla"


# What one kernel call may hold in VMEM by the rule's own accounting
# (:func:`cell_vmem_bytes`): the double-buffered K/V tiles, the q/out/cursor
# blocks, the softmax state and the score intermediates. The limit handed to
# Mosaic leaves room above it for what the accounting does not see (spills,
# relayout temporaries); a v5e core has 128 MiB of VMEM, 16 MiB of it scoped
# to a kernel unless the call asks for more.
VMEM_BUDGET_BYTES = 20 << 20
VMEM_LIMIT_BYTES = 32 << 20
# Tokens a cell's K/V tile is grown toward, and the query-rows x tokens a
# cell's score tile may cover; the page count is the smaller of the two over
# the page size, then cut to the VMEM budget and the table's width. Both
# from the kernel timed alone on a v5e at the benchmark cell's shape (PERF.md
# section 6, PR 26): decode (4 query rows a KV head) is fastest at 1024
# tokens a cell, where fewer cells are stepped and 4 MiB of copies are in
# flight; a 128-token chunk (512 rows) at 256, where the unrolled score
# passes — and Mosaic's compile time, which follows them — stay the parent's.
CELL_TOKENS = 1024
SCORE_TILE_ELEMS = 512 * 256


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def cell_vmem_bytes(pages: int, *, sq: int, heads: int, hd: int,
                    page_tokens: int, kvhd: int, kv_itemsize: int,
                    q_itemsize: int, quant: bool = False) -> int:
    """VMEM one call holds with ``pages`` pages per grid cell, as the tiles
    lie there: the last dim padded to 128 lanes, the one before it to a
    32-byte sublane group."""
    hkv = kvhd // hd
    rows = (heads // hkv) * sq
    t = pages * page_tokens
    lanes = lambda n: _pad(n, 128)
    subl = lambda n, itemsize: _pad(n, 32 // itemsize)
    kv = 2 * 2 * subl(t, kv_itemsize) * lanes(kvhd) * kv_itemsize
    if quant:                       # scale pages + one head's f32 K and V
        kv += 2 * 2 * subl(t, 4) * lanes(hkv) * 4 + 2 * t * lanes(hd) * 4
    qo = 2 * 2 * hkv * subl(rows, q_itemsize) * lanes(hd) * q_itemsize
    pos = 2 * subl(rows, 4) * 128 * 4
    state = hkv * subl(rows, 4) * (lanes(hd) + 2 * 128) * 4
    scores = 3 * subl(rows, 4) * lanes(t) * 4       # s, p, p in V's dtype
    return kv + qo + pos + state + scores


def default_pages_per_cell(*, sq: int, heads: int, hd: int, page_tokens: int,
                           kvhd: int, kv_itemsize: int, q_itemsize: int,
                           n_blocks: int, quant: bool = False) -> int:
    """How many pages one grid cell attends, from what the call can see:
    the most that stay within :data:`CELL_TOKENS` tokens and
    :data:`SCORE_TILE_ELEMS` scores a KV head, fit
    :data:`VMEM_BUDGET_BYTES` and exist in the table — never fewer than one.
    A pool narrower than a lane tile (``kv·hd`` not a multiple of 128: one
    local hd-64 KV head under tp) gets one: Mosaic copies whole 128-lane
    tiles only, and one page a cell is read through the block pipeline."""
    if kvhd % 128:
        return 1
    fits = lambda p: cell_vmem_bytes(
        p, sq=sq, heads=heads, hd=hd, page_tokens=page_tokens, kvhd=kvhd,
        kv_itemsize=kv_itemsize, q_itemsize=q_itemsize,
        quant=quant) <= VMEM_BUDGET_BYTES
    rows = heads // (kvhd // hd) * sq
    tokens = min(CELL_TOKENS, SCORE_TILE_ELEMS // rows)
    p = max(1, min(tokens // page_tokens, n_blocks))
    while p > 1 and not fits(p):
        p -= 1
    return p


def _compiler_params(interpret):
    # Both dims sequential: the block dim carries the online-softmax state,
    # and every live cell starts the NEXT live cell's page copies — across
    # rows too — so the cells must run in grid order on one core.
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _kernel(tables_ref, last_ref, q_ref, pos_ref, k_src, v_src, *rest,
            hkv, hd, page_tokens, pages, scale, quant):
    """One (batch row, run of ``pages`` virtual blocks) grid cell.

    ``tables_ref`` [B, n_blocks] and ``last_ref`` [B] (each row's last live
    block) are scalar-prefetched. With ``pages > 1`` the pools (``k_src``,
    ``v_src``) stay in HBM; a live cell's pages are copied, one DMA a page,
    into ONE contiguous VMEM tile ``[pages·page_tokens, kv·hd]`` per K and
    V — two tiles each, so that while a cell computes, the next live cell's
    copies (same row, or the next row's first) are already in flight. Only
    pages at or before the row's cursor are copied: a cell past the cursor
    starts none and computes nothing, and tile rows no copy reached hold
    finite leftovers (the tiles are zeroed once) that the cursor mask turns
    into exact zeros. With ``pages == 1`` the tile is the cell's one page,
    brought by the block pipeline (the K/V index maps read the table).

    Per KV head ONE product: the group's query rows ``[group·sq, hd]``
    (``q_ref`` is ``[1, hkv, group·sq, hd]``, row ``t·sq + i`` = group
    member ``t``, query token ``i``) against the tile's ``[T, hd]`` lane
    slice, f32 scores, one online-softmax update of ``m_s``/``l_s``
    ``[hkv, group·sq, 1]`` and ``acc_s`` ``[hkv, group·sq, hd]`` (f32,
    carried across the row's cells), ``p`` cast to the page dtype for
    ``p·v``. Under ``quant`` two more refs follow v_src: the cell's
    per-token-per-head scales ``[1, 1, T, hkv]``, and the dequant
    (``int8 → f32 × scale``) happens on the lane slice in VMEM: dequantized
    K/V never exist in HBM.
    """
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_s, l_s, acc_s, *copy_state = rest
    i, j = pl.program_id(0), pl.program_id(1)
    b, n_cells = pl.num_programs(0), pl.num_programs(1)
    rows = q_ref.shape[2]
    t_cell = pages * page_tokens

    if copy_state:
        k_buf, v_buf, sems, slot_s = copy_state
        streams = ((k_src, k_buf), (v_src, v_buf))

        def copies(row, cell, slot, do):
            """``do`` (start, or wait for) each page copy of (row, cell) into
            tile ``slot``: its pages up to the row's last live block."""
            first = cell * pages
            live = jnp.clip(last_ref[row] - first + 1, 0, pages)

            def one(p, carry):
                page = tables_ref[row, first + p]
                dst = pl.ds(pl.multiple_of(p * page_tokens, page_tokens),
                            page_tokens)
                for hbm, buf in streams:
                    do(pltpu.make_async_copy(
                        hbm.at[page], buf.at[slot, dst], sems.at[slot]))
                return carry
            jax.lax.fori_loop(0, live, one, 0)

        start = lambda dma: dma.start()
        wait = lambda dma: dma.wait()

        @pl.when((i == 0) & (j == 0))
        def _first():
            slot_s[0] = 0
            for _, buf in streams:
                buf[...] = jnp.zeros_like(buf)
            copies(0, 0, 0, start)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # A cell is live when its first block is at or before the row's cursor
    # (cell 0 always is: positions are >= 0). Work per row is proportional
    # to its LIVE length, not the table width.
    @pl.when(j * pages <= last_ref[i])
    def _cell():
        if copy_state:
            slot = slot_s[0]
            more = (j + 1) * pages <= last_ref[i]
            nxt_row = jnp.where(more, i, i + 1)

            @pl.when(nxt_row < b)
            def _prefetch():
                copies(nxt_row, jnp.where(more, j + 1, 0), 1 - slot, start)
                slot_s[0] = 1 - slot

            copies(i, j, slot, wait)
            k_tile, v_tile, at = k_buf, v_buf, slot
        else:
            k_tile, v_tile, at = k_src, v_src, 0
        col = (j * t_cell
               + jax.lax.broadcasted_iota(jnp.int32, (rows, t_cell), 1))
        allow = col <= pos_ref[0]                              # [rows, T]
        for h in range(hkv):
            lanes = slice(h * hd, (h + 1) * hd)
            k_h, v_h = k_tile[at, :, lanes], v_tile[at, :, lanes]   # [T, hd]
            if quant:
                k_h = k_h.astype(jnp.float32) * ks_ref[0, 0, :, h][:, None]
                v_h = v_h.astype(jnp.float32) * vs_ref[0, 0, :, h][:, None]
            s = jax.lax.dot_general(
                q_ref[0, h], k_h, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [rows, T]
            s = jnp.where(allow, s, NEG_INF)
            m_prev = m_s[h]                                    # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # Masked columns contribute exactly zero (not exp(0) where a
            # query's cursor sits before this whole cell).
            p = jnp.where(allow, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(
                p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [rows, hd]
            acc_s[h] = acc_s[h] * alpha + pv
            l_s[h] = alpha * l_s[h] + jnp.sum(p, axis=1, keepdims=True)
            m_s[h] = m_new

    @pl.when(j == n_cells - 1)
    def _emit():
        for h in range(hkv):
            o_ref[0, h] = (acc_s[h] / jnp.maximum(l_s[h], 1e-30)
                           ).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           softmax_scale: float | None = None,
                           pages_per_cell: int | None = None,
                           query_block: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Grouped-query decode attention straight off the page pool.

    q: ``[B, sq, H, hd]`` (``sq`` = 1 for classic decode, the speculative
    verify-window width, or a prefill chunk's); pool_k/pool_v:
    ``[num_pages, page_tokens, kv·hd]`` (the engine's folded-head page
    layout — written BEFORE this is called, so window tokens see each
    other); block_tables: ``[B, n_blocks]`` int32 mapping each row's
    virtual blocks onto pool pages (0 = the never-attended scratch page);
    positions: ``[B, sq]`` int32 absolute cursor per query token — row
    ``b`` query ``i`` attends virtual columns ``<= positions[b, i]``.
    Returns ``[B, sq, H, hd]`` in q's dtype. ``interpret=None`` picks the
    real kernel on TPU and the Pallas interpreter elsewhere.
    ``pages_per_cell=None`` takes the rule's choice
    (:func:`default_pages_per_cell`) and ``query_block=None`` likewise
    (:func:`default_query_block`); the tests and timings force others. A
    call wider than its query block is folded: ``[B, sq] -> [B·sq/Q, Q]``,
    the table repeated per block, the output unfolded — each block a grid
    row with its own cursors and last live block.

    ``k_scale``/``v_scale`` (both or neither) switch on the graftquant
    int8 path: pool_k/pool_v hold int8 rows and the scales
    ``[num_pages, page_tokens, kv]`` hold each token's per-head absmax
    factor; the kernel dequantizes tile slices in VMEM, fused into the
    online softmax.
    """
    if q.ndim != 4:
        raise ValueError(f"q must be [B, sq, H, hd], got {q.shape}")
    if pool_k.ndim != 3 or pool_k.shape != pool_v.shape:
        raise ValueError(
            f"pool_k/pool_v must be identical [num_pages, page_tokens, "
            f"kv*hd], got {pool_k.shape} / {pool_v.shape}")
    b, sq, h, hd = q.shape
    _, page_tokens, kvhd = pool_k.shape
    if kvhd % hd:
        raise ValueError(
            f"pool lane dim {kvhd} is not a multiple of head_dim {hd}")
    hkv = kvhd // hd
    if h % hkv:
        raise ValueError(
            f"{h} q heads not divisible by {hkv} kv heads")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables must be [B={b}, n_blocks], "
            f"got {block_tables.shape}")
    if positions.shape != (b, sq):
        raise ValueError(
            f"positions must be [B={b}, sq={sq}], got {positions.shape}")
    quant = k_scale is not None or v_scale is not None
    if quant:
        if k_scale is None or v_scale is None:
            raise ValueError("k_scale and v_scale must be passed together")
        want = pool_k.shape[:2] + (hkv,)
        if k_scale.shape != want or v_scale.shape != want:
            raise ValueError(
                f"k_scale/v_scale must be {want} (per-token-per-head), "
                f"got {k_scale.shape} / {v_scale.shape}")
    if interpret is None:
        interpret = not on_tpu()
    group = h // hkv
    qb = query_block or default_query_block(sq, group)
    if qb != sq:
        if sq % qb:
            raise ValueError(
                f"query_block {qb} does not divide the {sq} query tokens")
        return _query_blocks(
            q, pool_k, pool_v, block_tables, positions, k_scale, v_scale,
            softmax_scale=softmax_scale, pages_per_cell=pages_per_cell,
            query_block=qb, interpret=interpret)
    rows = group * sq
    n_blocks = block_tables.shape[1]
    s_virt = n_blocks * page_tokens
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    pages = pages_per_cell
    if pages is None:
        pages = default_pages_per_cell(
            sq=sq, heads=h, hd=hd, page_tokens=page_tokens, kvhd=kvhd,
            kv_itemsize=pool_k.dtype.itemsize, q_itemsize=q.dtype.itemsize,
            n_blocks=n_blocks, quant=quant)
    if not 1 <= pages <= n_blocks:
        raise ValueError(
            f"pages_per_cell must be in [1, n_blocks={n_blocks}], "
            f"got {pages}")
    n_cells = -(-n_blocks // pages)
    t_cell = pages * page_tokens

    # One [group·sq, hd] slab of query rows per KV head: row t·sq + i is
    # group member t, query token i (a free reshape at sq = 1).
    qg = q.reshape(b, sq, hkv, group, hd).transpose(0, 2, 3, 1, 4).reshape(
        b, hkv, rows, hd)
    # Columns exist up to the table's width; each query row carries its
    # cursor as a [rows, 1] column, and each batch row its last live block.
    pos = jnp.minimum(positions.astype(jnp.int32), s_virt - 1)
    pos_rows = jnp.tile(pos, (1, group))[:, :, None]           # [B, rows, 1]
    last = jnp.max(pos, axis=1) // page_tokens                 # [B]
    tables = block_tables.astype(jnp.int32)

    row_spec = lambda i, j, tbl, last: (i, 0, 0, 0)
    if pages == 1:      # the cell's page, by the block pipeline
        page_spec = pl.BlockSpec((1, page_tokens, kvhd),
                                 lambda i, j, tbl, last: (tbl[i, j], 0, 0))
        copy_state = []
    else:               # the pool, for the kernel's own page copies
        page_spec = pl.BlockSpec(memory_space=pl.ANY)
        copy_state = [pltpu.VMEM((2, t_cell, kvhd), pool_k.dtype),
                      pltpu.VMEM((2, t_cell, kvhd), pool_v.dtype),
                      pltpu.SemaphoreType.DMA((2,)),
                      pltpu.SMEM((1,), jnp.int32)]
    in_specs = [
        pl.BlockSpec((1, hkv, rows, hd), row_spec),
        pl.BlockSpec((1, rows, 1), lambda i, j, tbl, last: (i, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [qg, pos_rows, pool_k, pool_v]
    if quant:
        # The scales of each row's virtual sequence, gathered by XLA and cut
        # into the cells' [T, hkv] blocks (1/32 of the int8 K/V bytes at
        # hd = 128): a page's [page_tokens, hkv] f32 slab is too narrow for
        # Mosaic to copy on its own.
        def cells(sc):
            sc = sc.astype(jnp.float32)[tables]     # [B, n_blocks, pt, hkv]
            sc = jnp.pad(sc, ((0, 0), (0, n_cells * pages - n_blocks),
                              (0, 0), (0, 0)))
            return sc.reshape(b, n_cells, t_cell, hkv)
        cell_spec = pl.BlockSpec((1, 1, t_cell, hkv),
                                 lambda i, j, tbl, last: (i, j, 0, 0))
        in_specs += [cell_spec, cell_spec]
        operands += [cells(k_scale), cells(v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_cells),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, rows, hd), row_spec),
        scratch_shapes=[
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, hd), jnp.float32),
            *copy_state,
        ],
    )
    scale_bytes = (2 * b * s_virt * hkv * 4) if quant else 0
    kernel = functools.partial(_kernel, hkv=hkv, hd=hd,
                               page_tokens=page_tokens, pages=pages,
                               scale=scale, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq * s_virt * hd,
            bytes_accessed=(2 * qg.size * qg.dtype.itemsize
                            + 2 * b * s_virt * kvhd * pool_k.dtype.itemsize
                            + scale_bytes),
            transcendentals=b * h * sq * s_virt),
        interpret=interpret,
        # The name the device trace carries for the kernel's events: chosen
        # here, not inherited from whichever module scope calls the kernel.
        name="paged_attn",
    )(tables, last, *operands)
    return out.reshape(b, hkv, group, sq, hd).transpose(0, 3, 1, 2, 4).reshape(
        b, sq, h, hd)


@functools.partial(jax.jit, static_argnames=(
    "softmax_scale", "pages_per_cell", "query_block", "interpret"))
def _query_blocks(q, pool_k, pool_v, block_tables, positions, k_scale,
                  v_scale, *, softmax_scale, pages_per_cell, query_block,
                  interpret):
    """A call of ``sq / query_block`` blocks of queries, each a grid row:
    ``[B, sq] -> [B·nq, Q]``, the row's table repeated per block. A jitted
    function of its own, so that a program whose layers all make this call
    traces and lowers the kernel ONCE and calls it from each layer: traced
    per layer, six instances in the lfm2 cell's two chunk programs took its
    warm set-up from 46.9 to 52 s on the chip's host (PERF.md section 6, PR
    34). The narrower calls above are not wrapped: their programs' lowered
    text was not this change's to move (ROADMAP speed item 8)."""
    b, sq, h, hd = q.shape
    nq = sq // query_block
    fold = lambda a: a.reshape((b * nq, query_block) + a.shape[2:])
    return paged_decode_attention(
        fold(q), pool_k, pool_v, jnp.repeat(block_tables, nq, axis=0),
        fold(positions), k_scale=k_scale, v_scale=v_scale,
        softmax_scale=softmax_scale, pages_per_cell=pages_per_cell,
        query_block=query_block, interpret=interpret).reshape(b, sq, h, hd)
