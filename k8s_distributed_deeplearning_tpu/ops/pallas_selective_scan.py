"""Pallas TPU selective scan: a Mamba-1 mixer's state advanced over a chunk of
tokens, and one token IN PLACE over every live slot of the arena.

A Mamba-1 mixer (Jamba's) carries per channel ``e`` a state of ``N`` columns:

    h_t[n, e] = exp(dt_t[e] A[n, e]) h_(t-1)[n, e] + dt_t[e] B_t[n] u_t[e]
    y_t[e]    = sum_n h_t[n, e] C_t[n]

The decay differs for every (state column, channel) pair, so the chunked
matrix-product form of a Mamba-2 mixer (one scalar decay a head) does not
apply: over a prefill chunk the recurrence is a true scan. XLA's fallbacks
either walk the tokens one program step at a time or (an associative scan)
write ``[L, N, E]`` float32 intermediates to HBM on every one of its passes.

The state is laid out ``[N, E]`` — the CHANNELS on the 128 lanes, the 16
state columns on the sublanes: ``[E, N]`` with 16 on the lanes would pad
eightfold. The serving engine's arena leaf ``ssm_state`` is ``[slots, N, E]``
float32 (serve/engine.py), and ``A`` is handed over as ``[N, E]``.

- :func:`selective_scan` (a chunk program): the grid walks tiles of channels;
  each tile's ``[N, tile]`` state stays in registers while the kernel walks
  the chunk's tokens, eight at a time. ``u``, ``dt``, ``B`` and ``C`` are
  read once and ``y`` is written once; the state enters from the slot's row
  and leaves after the last token. A pad moves no state: its ``dt`` is 0.
- :func:`selective_update` (the decode program): one token for every LIVE
  row, the arena aliased to its output (``input_output_aliases``), the row
  order scalar-prefetched with the live rows first; a grid step past the
  last live row maps to the block before it (no copy, nothing computed), so
  an idle or mid-prefill slot's state is never read, written or copied — as
  :mod:`ops.pallas_ssm` does for a Mamba-2 arena. Compiled into the decode
  program, XLA's memory-space assignment stages a layer's 42 MB arena
  through VMEM around the call (four async slices in, one async copy back,
  overlapped with the other layers' weight stream): the kernel's own time is
  then VMEM-fed and holds none of the state's HBM traffic (PERF.md §5).

Each has a ``jax.numpy`` reference on the same layout
(:func:`selective_scan_reference`, :func:`selective_update_reference`): the
kernels' test reference and the path off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

# Tokens one scan call walks: a longer call is cut into segments of this many
# (the state carried between them), so the VMEM a call holds is that of one
# 512-token chunk whatever the prompt: u, dt and y blocks of [512, tile] f32.
SCAN_TOKENS = 512
# Channels a grid step of the scan carries: [N, 512] f32 state = 8 vregs at
# N = 16, beside the decay's 8 and a group's u and dt rows.
SCAN_LANES = 512
# Tokens a scan step takes from VMEM at once (one sublane tile of u and dt).
GROUP = 8


def selective_update_reference(state, u, dt, a, bmat, cmat, live):
    """One token in ``jax.numpy``. *state* ``[B, N, E]``, *u* / *dt* ``[B, E]``
    (*dt* after the softplus), *a* ``[N, E]`` f32 (negative), *bmat* / *cmat*
    ``[B, N]``, *live* ``[B]`` bool. -> (state with the live rows advanced,
    the others as they were; ``y`` ``[B, E]`` f32 without the ``D u`` term, 0
    in rows not live)."""
    f32 = jnp.float32
    s = state.astype(f32)
    dt, u = dt.astype(f32), u.astype(f32)
    new = (jnp.exp(dt[:, None, :] * a[None]) * s
           + bmat.astype(f32)[:, :, None] * (dt * u)[:, None, :])
    y = jnp.sum(new * cmat.astype(f32)[:, :, None], axis=1)
    keep = live.reshape(-1, 1, 1)
    return (jnp.where(keep, new, s).astype(state.dtype),
            jnp.where(live[:, None], y, 0.0))


def selective_scan_reference(u, dt, a, bmat, cmat, h0):
    """The recurrence over a chunk in ``jax.numpy``, a ``lax.scan`` over
    tokens. *u* / *dt* ``[B, L, E]`` (*dt* 0 where a token must not move the
    state), *a* ``[N, E]``, *bmat* / *cmat* ``[B, L, N]``, *h0* ``[B, N, E]``.
    -> (``y`` ``[B, L, E]`` f32 without the ``D u`` term, the state after the
    last token, f32)."""
    f32 = jnp.float32

    def one_token(h, now):
        u_t, d_t, b_t, c_t = now
        h = (jnp.exp(d_t[:, None, :] * a) * h
             + b_t[:, :, None] * (d_t * u_t)[:, None, :])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    to_steps = lambda v: jnp.moveaxis(v.astype(f32), 1, 0)
    h_end, y = jax.lax.scan(one_token, h0.astype(f32),
                            tuple(map(to_steps, (u, dt, bmat, cmat))))
    return jnp.moveaxis(y, 0, 1), h_end


def _lanes(e: int, want: int) -> int:
    """Channels a grid step carries: the largest multiple of 128 up to
    *want* that divides *e*; *e* itself where none does (one block)."""
    for t in range(want, 127, -128):
        if e % t == 0:
            return t
    return e


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref):
    """One (row, tile of channels) grid cell: the tile's state walks the
    call's tokens, GROUP at a time. ``b_ref`` / ``c_ref`` are
    ``[1, L / GROUP, N, GROUP]``: a group's B and C with the state columns on
    the sublanes, so that token ``j``'s column is a static lane slice."""
    a = a_ref[...]                                           # [N, T]

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        u8 = u_ref[0, pl.ds(t0, GROUP), :]                  # [GROUP, T]
        dt8 = dt_ref[0, pl.ds(t0, GROUP), :]
        bg, cg = b_ref[0, g], c_ref[0, g]                    # [N, GROUP]
        rows = []
        for j in range(GROUP):
            d = dt8[j:j + 1, :]                              # [1, T]
            h = jnp.exp(d * a) * h + bg[:, j:j + 1] * (d * u8[j:j + 1, :])
            rows.append(jnp.sum(h * cg[:, j:j + 1], axis=0, keepdims=True))
        y_ref[0, pl.ds(t0, GROUP), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[0] = jax.lax.fori_loop(0, u_ref.shape[1] // GROUP, group, h0_ref[0])


def _scan_call(u, dt, a, bmat, cmat, h0, *, interpret: bool):
    """One call of at most :data:`SCAN_TOKENS` tokens, a multiple of GROUP."""
    b, length, e = u.shape
    n = a.shape[0]
    lanes = _lanes(e, SCAN_LANES)
    cols = lambda v: v.reshape(b, length // GROUP, GROUP, n).transpose(0, 1, 3, 2)
    tok_spec = pl.BlockSpec((1, length, lanes), lambda i, j: (i, 0, j))
    col_spec = pl.BlockSpec((1, length // GROUP, n, GROUP), lambda i, j: (i, 0, 0, 0))
    state_spec = pl.BlockSpec((1, n, lanes), lambda i, j: (i, 0, j))
    return pl.pallas_call(
        _scan_kernel,
        grid=(b, e // lanes),
        in_specs=[tok_spec, tok_spec, pl.BlockSpec((n, lanes), lambda i, j: (0, j)),
                  col_spec, col_spec, state_spec],
        out_specs=[tok_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, length, e), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, e), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * length * n * e, transcendentals=b * length * n * e,
            bytes_accessed=4 * (3 * b * length * e + 2 * b * n * e + n * e
                                + 2 * b * length * n)),
        interpret=interpret,
        # the name the device trace carries for the kernel's events
        name="selective_scan",
    )(u, dt, a, cols(bmat), cols(cmat), h0)


def selective_scan(u, dt, a, bmat, cmat, h0, *, interpret: bool | None = None):
    """:func:`selective_scan_reference` as a kernel: the call's tokens padded
    to a multiple of GROUP (``dt`` 0 there: a pad moves no state) and cut
    into segments of :data:`SCAN_TOKENS`, the state carried between them."""
    if interpret is None:
        interpret = not on_tpu()
    f32 = jnp.float32
    b, length, e = u.shape
    seg = min(SCAN_TOKENS, -(-length // GROUP) * GROUP)
    nseg = -(-length // seg)
    pad = nseg * seg - length
    u, dt, bmat, cmat = (jnp.pad(v.astype(f32), ((0, 0), (0, pad), (0, 0)))
                         for v in (u, dt, bmat, cmat))
    call = functools.partial(_scan_call, a=a.astype(f32), interpret=interpret)
    if nseg == 1:
        y, h_end = call(u, dt, bmat=bmat, cmat=cmat, h0=h0.astype(f32))
        return y[:, :length], h_end
    cut = lambda v: jnp.moveaxis(v.reshape((b, nseg, seg) + v.shape[2:]), 1, 0)

    def one(h, xs):
        su, sd, sb, sc = xs
        y, h = call(su, sd, bmat=sb, cmat=sc, h0=h)
        return h, y
    h_end, ys = jax.lax.scan(one, h0.astype(f32), tuple(map(cut, (u, dt, bmat, cmat))))
    return jnp.moveaxis(ys, 0, 1).reshape(b, nseg * seg, e)[:, :length], h_end


def _update_kernel(order_ref, n_live_ref, s_ref, u_ref, dt_ref, a_ref, b_ref,
                   c_ref, o_ref, y_ref):
    n_live = n_live_ref[0]

    @pl.when(pl.program_id(0) < n_live)
    def _advance():
        d = dt_ref[0]                                        # [1, T]
        new = jnp.exp(d * a_ref[...]) * s_ref[0] + b_ref[0] * (d * u_ref[0])
        o_ref[0] = new
        y_ref[0] = jnp.sum(new * c_ref[0], axis=0, keepdims=True)

    @pl.when(n_live == 0)
    def _nothing_live():
        # the one block the grid maps to is written back: as it was
        o_ref[...] = s_ref[...]


# A row's state in one grid step where it fits this (at the benchmark cell's
# widths 16 x 5,120 f32 = 320 KiB: one step a row).
UPDATE_BLOCK_BYTES = 1 << 20


def selective_update(state, u, dt, a, bmat, cmat, live, *,
                     interpret: bool | None = None):
    """:func:`selective_update_reference` as one kernel over the live rows,
    the arena aliased to the new arena. *state* must be float32."""
    if interpret is None:
        interpret = not on_tpu()
    f32 = jnp.float32
    b, n, e = state.shape
    if state.dtype != f32 or a.shape != (n, e) or u.shape != (b, e):
        raise ValueError(
            f"selective_update wants a float32 arena [B, N, E] with u [B, E] "
            f"and A [N, E], got {state.dtype} {state.shape}, u {u.shape}, "
            f"A {a.shape}")
    lanes = _lanes(e, max(128, UPDATE_BLOCK_BYTES // (4 * n) // 128 * 128))
    tiles = e // lanes
    live = live.astype(bool)
    n_live = jnp.sum(live).astype(jnp.int32)
    # the rows in the order the grid walks them: the live ones first, each
    # group in its own order (two running counts and a scatter: no sort)
    place = jnp.where(live, jnp.cumsum(live) - 1, n_live + jnp.cumsum(~live) - 1)
    order = jnp.zeros((b,), jnp.int32).at[place].set(jnp.arange(b, dtype=jnp.int32))

    def at(i, j, order, n_live):
        """Grid step -> (row, tile): past the last live row, the step
        before's — the same block again, so nothing moves."""
        past = i >= n_live[0]
        last = jnp.maximum(n_live[0] - 1, 0)
        return order[jnp.where(past, last, i)], jnp.where(past, tiles - 1, j)

    def spec(rows):
        return pl.BlockSpec((1, rows, lanes), lambda i, j, order, n_live: (
            at(i, j, order, n_live)[0], 0, at(i, j, order, n_live)[1]))

    col = pl.BlockSpec((1, n, 1), lambda i, j, order, n_live: (
        at(i, j, order, n_live)[0], 0, 0))
    new, y = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, tiles),
            in_specs=[spec(n), spec(1), spec(1),
                      pl.BlockSpec((n, lanes), lambda i, j, order, n_live: (
                          0, at(i, j, order, n_live)[1])),
                      col, col],
            out_specs=[spec(n), spec(1)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((b, 1, e), f32)],
        # operands count the two prefetched scalars: the arena is the third
        input_output_aliases={2: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * n * e, transcendentals=b * n * e,
            bytes_accessed=4 * (2 * b * n * e + 3 * b * e + n * e)),
        interpret=interpret,
        # the name the device trace carries for the kernel's events
        name="selective_update",
    )(order, n_live.reshape(1), state, u.astype(f32).reshape(b, 1, e),
      dt.astype(f32).reshape(b, 1, e), a.astype(f32),
      bmat.astype(f32).reshape(b, n, 1), cmat.astype(f32).reshape(b, n, 1))
    # rows not live were never visited: their y is whatever the buffer held
    return new, jnp.where(live[:, None], y.reshape(b, e), 0.0)
