"""Pallas TPU paged decode-attention: fused gather+attend over pool pages.

The serving engine's paged decode branch (models/transformer.py) stores KV
as ONE pool of fixed-size pages ``[num_pages, page_tokens, kv·head_dim]``
(vLLM's PagedAttention layout, serve/page_pool.py) and, on the XLA path,
materializes each row's virtual sequence with a
``pool[block_tables]`` gather before calling plain attention — a
``[B, n_blocks·page_tokens, kv, hd]`` HBM round-trip per decode step that
exists only to feed the softmax. This kernel fuses the two: the grid walks
``(batch, block)``, the block index map reads the SCALAR-PREFETCHED block
table to pull exactly the page each row's block maps to, and an
online-softmax (flash-attention style, carried in VMEM scratch across the
block dimension) attends it in place. Nothing proportional to the virtual
sequence ever lands in HBM.

Same contract as the XLA path it replaces:

- grouped-query decode attention: q ``[B, sq, H, hd]`` (``sq`` is 1 for
  classic decode, or a small speculative verify window), KV heads folded
  into the page lane dim (``kv·hd``), q head ``h`` attends KV head
  ``h // (H/kv)``;
- per-row causal cursor masking: query ``i`` of row ``b`` attends virtual
  columns ``col <= positions[b, i]`` — stale KV beyond a row's cursor
  (freed-slot garbage, rejected speculative drafts) is never read, and the
  scratch page (table entries 0) is always masked out by the same rule;
- blocks wholly past every query's cursor are skipped (``pl.when``), so
  the work per row is proportional to its LIVE length, not the table
  width.

Off-TPU the kernel runs in the Pallas interpreter (``interpret`` defaults
to ``not on_tpu()``), so CPU CI exercises the exact same code path —
tier-1 keeps the XLA gather as its default via the ``attention_impl``
selection (:func:`default_impl`) and opts into the kernel explicitly
(``"paged_flash"``) for parity tests.

What the kernel serves is bounded by ``sq`` (:data:`MAX_QUERY_TOKENS`): the
head loops are python-unrolled over ``[sq, page_tokens]`` score tiles and
the accumulators are ``H·sq·hd`` f32 in VMEM, so both Mosaic's compile time
and the VMEM footprint grow with the query-chunk width. Decode (1), a
speculative verify window (k+1) and a chunked-prefill slice fit; a
monolithic prefill bucket does not, and takes the XLA gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

NEG_INF = -1e30

# Widest query chunk the kernel is selected for. Compiling the 12q/4kv hd-64
# kernel for a v5e took 1 s at sq=128, 11 s at 256, 54 s at 512 and 276 s at
# 1024 (Mosaic, PR 21; the unrolled head loops scale the program with sq),
# and at 32 heads x hd 128 the f32 accumulator alone is 16 MiB at sq=1024.
# 128 covers decode, every verify window and a 128-token prefill chunk.
MAX_QUERY_TOKENS = 128


def default_impl(sq: int, platform: str | None = None) -> str:
    """The ``attention_impl="auto"`` rule for block-table (paged) calls,
    from what the call can observe: the Pallas kernel on TPU for query
    chunks it serves (``sq <= MAX_QUERY_TOKENS``), the XLA gather-attend
    everywhere else — wider prefill buckets on TPU, and every shape off
    TPU, where the kernel would run in the (slow) interpreter."""
    tpu = on_tpu() if platform is None else platform == "tpu"
    return "paged_flash" if tpu and sq <= MAX_QUERY_TOKENS else "xla"


def _compiler_params(interpret):
    # batch is embarrassingly parallel; the block dim carries the
    # online-softmax scratch, so it stays sequential.
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _kernel(tables_ref, q_ref, k_ref, v_ref, *rest,
            hkv, group, hd, page_tokens, scale, quant):
    """One (batch row, virtual block) grid cell.

    ``tables_ref`` is the scalar-prefetched block table — consumed by the
    K/V index maps (which page this cell reads), unused in the body.
    Scratch ``m_s``/``l_s`` are [H, sq] f32 and ``acc_s`` is [H, sq, hd]
    f32, carried across the (sequential) block dimension. Head loops are
    python-static: each (kv head, group member) pair is a static lane
    slice of the folded refs — the pallas_flash per-head idiom, one level
    up. Under ``quant`` two extra refs follow v_ref — the int8 pages'
    per-token-per-head scale pages ``[1, page_tokens, hkv]``, indexed by
    the SAME prefetched table entry — and the dequant
    (``int8 → f32 × scale``) happens on the lane slice in VMEM, fused
    into the attention math: dequantized K/V never exist in HBM.
    """
    if quant:
        ks_ref, vs_ref, pos_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        pos_ref, o_ref, m_s, l_s, acc_s = rest
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    sq = q_ref.shape[1]
    h_all = hkv * group

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    pos = pos_ref[0, 0, :]                                     # [sq] int32
    # Skip blocks wholly beyond every query's cursor: the first virtual
    # column of block j is j·page_tokens; nothing in a later block can be
    # attended by any row of this batch element.
    @pl.when(j * page_tokens <= jnp.max(pos))
    def _block():
        col = (j * page_tokens
               + jax.lax.broadcasted_iota(jnp.int32, (sq, page_tokens), 1))
        allow = col <= pos[:, None]                            # [sq, bt]
        for h in range(hkv):
            k_h = k_ref[0, :, h * hd:(h + 1) * hd]             # [bt, hd]
            v_h = v_ref[0, :, h * hd:(h + 1) * hd]
            if quant:
                k_h = k_h.astype(jnp.float32) * ks_ref[0, :, h][:, None]
                v_h = v_h.astype(jnp.float32) * vs_ref[0, :, h][:, None]
            for t in range(group):
                qi = h * group + t
                q_t = q_ref[0, :, qi * hd:(qi + 1) * hd]       # [sq, hd]
                s = jax.lax.dot_general(
                    q_t, k_h, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(allow, s, NEG_INF)
                m_prev = m_s[qi, :]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
                p = jnp.exp(s - m_new[:, None])
                # Fully-masked guard: a row whose cursor sits before this
                # block contributes exactly zero (not exp(0) rows).
                p = jnp.where(s <= NEG_INF / 2, 0.0, p)
                alpha = jnp.exp(m_prev - m_new)
                pv = jax.lax.dot_general(
                    p.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)        # [sq, hd]
                acc_s[qi] = acc_s[qi] * alpha[:, None] + pv
                l_s[qi, :] = alpha * l_s[qi, :] + jnp.sum(p, axis=1)
                m_s[qi, :] = m_new

    @pl.when(j == n_blocks - 1)
    def _emit():
        for qi in range(h_all):
            norm = jnp.maximum(l_s[qi, :], 1e-30)
            o_ref[0, :, qi * hd:(qi + 1) * hd] = (
                acc_s[qi] / norm[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, pool_k: jax.Array,
                           pool_v: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           softmax_scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Grouped-query decode attention straight off the page pool.

    q: ``[B, sq, H, hd]`` (``sq`` = 1 for classic decode or the
    speculative verify-window width); pool_k/pool_v:
    ``[num_pages, page_tokens, kv·hd]`` (the engine's folded-head page
    layout — written BEFORE this is called, so window tokens see each
    other); block_tables: ``[B, n_blocks]`` int32 mapping each row's
    virtual blocks onto pool pages (0 = the never-attended scratch page);
    positions: ``[B, sq]`` int32 absolute cursor per query token — row
    ``b`` query ``i`` attends virtual columns ``<= positions[b, i]``.
    Returns ``[B, sq, H, hd]`` in q's dtype. ``interpret=None`` picks the
    real kernel on TPU and the Pallas interpreter elsewhere.

    ``k_scale``/``v_scale`` (both or neither) switch on the graftquant
    int8 path: pool_k/pool_v hold int8 rows and the scales
    ``[num_pages, page_tokens, kv]`` hold each token's per-head absmax
    factor; the kernel dequantizes page slices in VMEM, fused into the
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
    n_blocks = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5

    qf = q.reshape(b, sq, h * hd)
    # [B, 1, sq]: the length-1 middle dim keeps the last-two-dims tiling
    # legal for any B (same trick as pallas_flash's segment/lse specs).
    pos3 = positions.astype(jnp.int32)[:, None, :]
    tables = block_tables.astype(jnp.int32)

    page_spec = lambda i, j, tbl: (tbl[i, j], 0, 0)
    in_specs = [
        pl.BlockSpec((1, sq, h * hd), lambda i, j, tbl: (i, 0, 0)),
        pl.BlockSpec((1, page_tokens, kvhd), page_spec),
        pl.BlockSpec((1, page_tokens, kvhd), page_spec),
    ]
    operands = [qf, pool_k, pool_v]
    if quant:
        # Scale pages ride the same prefetched table entry as their int8
        # pages — one (page, scale-page) pair per grid cell.
        in_specs += [pl.BlockSpec((1, page_tokens, hkv), page_spec),
                     pl.BlockSpec((1, page_tokens, hkv), page_spec)]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    in_specs.append(pl.BlockSpec((1, 1, sq), lambda i, j, tbl: (i, 0, 0)))
    operands.append(pos3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, sq, h * hd), lambda i, j, tbl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, sq), jnp.float32),
            pltpu.VMEM((h, sq), jnp.float32),
            pltpu.VMEM((h, sq, hd), jnp.float32),
        ],
    )
    s_virt = n_blocks * page_tokens
    scale_bytes = (2 * b * s_virt * hkv * 4) if quant else 0
    kernel = functools.partial(_kernel, hkv=hkv, group=group, hd=hd,
                               page_tokens=page_tokens, scale=scale,
                               quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, h * hd), q.dtype),
        compiler_params=_compiler_params(interpret),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq * s_virt * hd,
            bytes_accessed=(qf.size * qf.dtype.itemsize
                            + 2 * b * s_virt * kvhd * pool_k.dtype.itemsize
                            + scale_bytes),
            transcendentals=b * h * sq * s_virt),
        interpret=interpret,
        # The name the device trace carries for the kernel's events: chosen
        # here, not inherited from whichever module scope calls the kernel.
        name="paged_attn",
    )(tables, *operands)
    return out.reshape(b, sq, h, hd)
