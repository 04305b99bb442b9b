"""Pallas TPU state-space decode update: every live slot's state advanced one
token IN PLACE, in one pass over the arena.

A Mamba-2 mixer's decode step is, per head ``h`` with state ``S_h`` ``[P, N]``:

    S_h <- exp(dt_h A_h) S_h + (dt_h x_h) (x) B_g        y_h = S_h C_g + D x_h

bound by reading and writing ``S`` (at the benchmark cell's widths 4 MiB a
slot a layer in float32, 0.5 GiB a layer over 128 slots). The serving engine
keeps every slot's state in one arena leaf (``ssm_state``, serve/engine.py)
that the decode program donates; this kernel aliases that arena to its output
(``input_output_aliases``) and walks ONLY the live rows: the row order comes
scalar-prefetched (live rows first), a step past the last live row maps to
the block before it — no copy in, none out — and does nothing, so an idle or
mid-prefill slot's state is never read, written or copied.

The arena's layout (:func:`state_shape`) is chosen for the update, not for
the equations: ``[slots, (H / pack) * N, pack * P]`` with ``pack`` heads side
by side on the 128 lanes (two at P = 64), so that what differs per lane —
``exp(dt A)`` and ``dt x`` — is a lane vector, and what differs per row of a
head's tile — ``B[n]`` and ``C[n]`` — is one ``[N, lanes]`` tile a GROUP,
made once a block (a block is one group's heads) by a transpose. ``y`` is
then a reduction over rows, which leaves a lane vector again.

:func:`ssm_update_reference` is the same function in ``jax.numpy`` on the
same layout: the kernel's test reference and the path off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from k8s_distributed_deeplearning_tpu.backend import on_tpu

LANES = 128


def head_pack(heads_per_group: int, head_dim: int) -> int:
    """Heads laid side by side on one row of the state: as many as fill the
    128 lanes (2 at head_dim 64), where a group's heads divide into such
    packs; else 1."""
    pack = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 else 1
    return pack if heads_per_group % pack == 0 else 1


def state_shape(heads: int, head_dim: int, state: int, groups: int) -> tuple:
    """``(rows, lanes)`` of one slot's state in the arena's layout: row
    ``(h // pack) * N + n``, lane ``(h % pack) * P + p`` holds ``S_h[p, n]``."""
    pack = head_pack(heads // groups, head_dim)
    return (heads // pack * state, pack * head_dim)


def pack_state(s: jax.Array, groups: int) -> jax.Array:
    """``[..., H, P, N]`` (the equations' order) -> the arena's layout."""
    *lead, h, p, n = s.shape
    pack = head_pack(h // groups, p)
    s = s.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // pack * n, pack * p)


def unpack_state(s: jax.Array, heads: int, head_dim: int, groups: int
                 ) -> jax.Array:
    """The arena's layout -> ``[..., H, P, N]``."""
    *lead, rows, lanes = s.shape
    pack = lanes // head_dim
    n = rows * pack // heads
    s = s.reshape(*lead, heads // pack, n, pack, head_dim)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, heads, head_dim, n)


def _lane_vectors(x, dt, a, heads: int, head_dim: int, groups: int):
    """What the update needs per lane, ``[B, H / pack, pack * P]`` f32 each:
    ``exp(dt A)`` (a head's, over its P lanes) and ``dt x``."""
    pack = head_pack(heads // groups, head_dim)
    b = x.shape[0]
    decay = jnp.exp(dt * a)                                   # [B, H]
    decay = jnp.broadcast_to(decay[:, :, None], (b, heads, head_dim))
    dtx = dt[:, :, None] * x.astype(jnp.float32)              # [B, H, P]
    shape = (b, heads // pack, pack * head_dim)
    return decay.reshape(shape), dtx.reshape(shape)


def ssm_update_reference(state, x, dt, a, bmat, cmat, live):
    """The update in ``jax.numpy``. *state* ``[B, rows, lanes]`` (the arena's
    layout), *x* ``[B, H, P]``, *dt* ``[B, H]`` f32 (after the softplus), *a*
    ``[H]`` f32 (negative), *bmat* / *cmat* ``[B, G, N]``, *live* ``[B]``
    bool. -> (state with the live rows advanced, the others as they were;
    ``y`` ``[B, H, P]`` f32 without the ``D x`` term, 0 in rows not live)."""
    b, h, p = x.shape
    g, n = bmat.shape[1:]
    pack = head_pack(h // g, p)
    decay, dtx = _lane_vectors(x, dt, a, h, p, g)
    s = state.astype(jnp.float32).reshape(b, h // pack, n, pack * p)
    per_group = h // pack // g
    bg = jnp.repeat(bmat.astype(jnp.float32), per_group, axis=1)  # [B, H/pack, N]
    cg = jnp.repeat(cmat.astype(jnp.float32), per_group, axis=1)
    new = s * decay[:, :, None, :] + bg[..., None] * dtx[:, :, None, :]
    y = jnp.sum(new * cg[..., None], axis=2).reshape(b, h, p)
    keep = live.reshape(-1, 1, 1)
    new = jnp.where(keep, new.reshape(state.shape), state.astype(jnp.float32))
    return new.astype(state.dtype), jnp.where(keep, y, 0.0)


def _kernel(order_ref, n_live_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref,
            o_ref, y_ref, *, n: int, per_block: int):
    i, j = pl.program_id(0), pl.program_id(1)
    n_live = n_live_ref[0]
    lanes = s_ref.shape[-1]

    @pl.when(i < n_live)
    def _advance():
        # B[n], C[n] of this block's group down the rows of an [N, lanes] tile
        mine = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape[1:], 0) == j
        brow = jnp.sum(jnp.where(mine, b_ref[0], 0.0), axis=0, keepdims=True)
        crow = jnp.sum(jnp.where(mine, c_ref[0], 0.0), axis=0, keepdims=True)
        side = max(n, lanes)
        bt = jnp.broadcast_to(jnp.pad(brow, ((0, 0), (0, side - n))),
                              (side, side)).T[:n, :lanes]
        ct = jnp.broadcast_to(jnp.pad(crow, ((0, 0), (0, side - n))),
                              (side, side)).T[:n, :lanes]
        for q in range(per_block):
            rows = slice(q * n, (q + 1) * n)
            new = (s_ref[0, rows, :] * decay_ref[0, q:q + 1, :]
                   + bt * dtx_ref[0, q:q + 1, :])
            o_ref[0, rows, :] = new
            y_ref[0, q:q + 1, :] = jnp.sum(new * ct, axis=0, keepdims=True)

    @pl.when(n_live == 0)
    def _nothing_live():
        # the one block the grid maps to is written back: as it was
        o_ref[...] = s_ref[...]


def ssm_update(state, x, dt, a, bmat, cmat, live, *,
               interpret: bool | None = None):
    """:func:`ssm_update_reference` as one kernel over the live rows, the
    arena aliased to the new arena. *state* must be float32."""
    if interpret is None:
        interpret = not on_tpu()
    b, h, p = x.shape
    g, n = bmat.shape[1:]
    pack = head_pack(h // g, p)
    per_block = h // pack // g                   # packed heads of one group
    lanes = pack * p
    if state.dtype != jnp.float32 or state.shape != (b, h // pack * n, lanes):
        raise ValueError(
            f"ssm_update wants a float32 arena {(b, h // pack * n, lanes)}, "
            f"got {state.dtype} {state.shape}")
    decay, dtx = _lane_vectors(x, dt, a, h, p, g)
    live = live.astype(bool)
    n_live = jnp.sum(live).astype(jnp.int32)
    # the rows in the order the grid walks them: the live ones first, each
    # group in its own order (two running counts and a scatter: no sort)
    place = jnp.where(live, jnp.cumsum(live) - 1, n_live + jnp.cumsum(~live) - 1)
    order = jnp.zeros((b,), jnp.int32).at[place].set(jnp.arange(b, dtype=jnp.int32))
    n_live = n_live.reshape(1)

    def at(i, j, order, n_live):
        """Grid step -> (row, block): past the last live row, the step
        before's — the same block again, so nothing moves."""
        past = i >= n_live[0]
        last = jnp.maximum(n_live[0] - 1, 0)
        return (order[jnp.where(past, last, i)], jnp.where(past, g - 1, j))

    def rows_spec(width):
        return pl.BlockSpec(
            (1, width, lanes),
            lambda i, j, order, n_live: at(i, j, order, n_live) + (0,))

    whole = pl.BlockSpec(
        (1, g, n), lambda i, j, order, n_live: (at(i, j, order, n_live)[0], 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_kernel, n=n, per_block=per_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, g),
            in_specs=[rows_spec(per_block * n), rows_spec(per_block),
                      rows_spec(per_block), whole, whole],
            out_specs=[rows_spec(per_block * n), rows_spec(per_block)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h // pack, lanes), jnp.float32)],
        # operands count the two prefetched scalars: the arena is the third
        input_output_aliases={2: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        # the name the device trace carries for the kernel's events
        name="ssm_update",
    )(order, n_live, state, decay, dtx, bmat.astype(jnp.float32),
      cmat.astype(jnp.float32))
    # rows not live were never visited: their y is whatever the buffer held
    return new, jnp.where(live[:, None, None], y.reshape(b, h, p), 0.0)
