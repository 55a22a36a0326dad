"""The selective scan of a state-space layer (Mamba-1) over one slot's
prompt chunk — the Pallas TPU kernel ``ssm_chunk_scan`` — and the decode
lane's one-row update of every slot's state in plain XLA.

The recurrence, per channel ``d`` of ``d_inner`` and state index ``n``::

    S_t[d, n] = exp(D_t[d] A[d, n]) S_{t-1}[d, n] + D_t[d] c_t[d] B_t[n]
    y_t[d]    = sum_n S_t[d, n] C_t[n] + D_skip[d] c_t[d]

``c`` is the convolved, activated input, ``D`` the step (after its
softplus), ``B`` / ``C`` the input and output projections of the row.

As ``lax.associative_scan`` the ``[rows, d_inner, state]`` planes of
``exp(D A)`` and ``D c B`` are written and read several times a layer;
the recurrence itself moves ``rows x d_inner`` values in and out.  The
kernel keeps the state in registers instead.

Layout — chosen so that every broadcast is one the vector unit has:
the channels are cut into TILES of ``8 * lanes`` (``lanes`` = 128 on the
chip: one tile of channels is one (8, 128) register), channel ``d`` of a
row sits at ``[d // (8 lanes), (d // lanes) % 8, d % lanes]``, and a
state is ``[tiles, state, 8, lanes]``: ``S[:, n]`` is one register a
tile.  A row's ``c`` / ``D`` are one register a tile as well, and its
``B_t[n]`` / ``C_t[n]`` are SCALARS (read from SMEM, splat over the
register): no lane-to-sublane move anywhere.  :func:`to_tiles` /
:func:`from_tiles` are the (copying) reshapes between ``[.., d_inner]``
and ``[.., tiles, 8, lanes]``; :func:`state_to_tiles` /
:func:`state_from_tiles` between the equations' ``[.., d_inner, state]``
and the stored form.  The serving engine stores a slot's state tiled and
never converts it.

Grid ``(tile, row block)``: the row blocks of one tile run in order, the
state carried from block to block in a VMEM scratch (resident across the
walk over rows), written out after the last.  Rows at or past the valid
length must leave the state alone: the wrapper zeroes their step, so
``exp(0 A) = 1`` and ``0 c B = 0``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

LANES = 128
SUBLANES = 8
#: rows of one grid step: its c, D and y blocks are ``rows x 4 KB`` each,
#: double-buffered
_ROW_BLOCK = 128


def tile_lanes(d_inner: int) -> int:
    """Lanes of one channel tile: 128, or all of a small test width's."""
    return min(LANES, max(1, d_inner // SUBLANES))


def tiling(d_inner: int) -> Tuple[int, int]:
    """``(channel tiles, lanes of a tile)`` of ``d_inner`` channels."""
    lanes = tile_lanes(d_inner)
    if d_inner % (SUBLANES * lanes):
        raise ValueError(
            f"d_inner {d_inner} is not a whole number of {SUBLANES} x "
            f"{lanes} channel tiles")
    return d_inner // (SUBLANES * lanes), lanes


def to_tiles(x: jax.Array) -> jax.Array:
    """``[.., d_inner]`` -> ``[.., tiles, 8, lanes]``."""
    tiles, lanes = tiling(x.shape[-1])
    return x.reshape(*x.shape[:-1], tiles, SUBLANES, lanes)


def from_tiles(x: jax.Array) -> jax.Array:
    """``[.., tiles, 8, lanes]`` -> ``[.., d_inner]``."""
    return x.reshape(*x.shape[:-3], -1)


def state_to_tiles(s: jax.Array) -> jax.Array:
    """The equations' ``[.., d_inner, state]`` -> the stored ``[.., tiles,
    state, 8, lanes]``."""
    n = s.shape[-1]
    tiles, lanes = tiling(s.shape[-2])
    s = s.reshape(*s.shape[:-2], tiles, SUBLANES, lanes, n)
    return jnp.moveaxis(s, -1, -3)


def state_from_tiles(s: jax.Array) -> jax.Array:
    """The stored ``[.., tiles, state, 8, lanes]`` -> ``[.., d_inner,
    state]``."""
    s = jnp.moveaxis(s, -3, -1)                 # [.., tiles, 8, lanes, n]
    return s.reshape(*s.shape[:-4], -1, s.shape[-1])


def _kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, dskip_ref, s0_ref, y_ref,
            s1_ref, s_scr, *, rows, nstate):
    """One row block of one channel tile.  ``b_ref`` / ``c_ref`` (SMEM)
    hold every row's ``B_t`` / ``C_t`` flat, row-major; ``x_ref`` /
    ``dt_ref`` / ``y_ref`` ``[rows, 8, lanes]``; ``a_ref`` / ``s0_ref`` /
    ``s1_ref`` / ``s_scr`` ``[state, 8, lanes]``."""
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _load():
        s_scr[...] = s0_ref[...]

    a = [a_ref[n] for n in range(nstate)]
    dskip = dskip_ref[...]
    row0 = blk * rows

    def row(t, state):
        x, dt = x_ref[t], dt_ref[t]
        u = dt * x
        at = (row0 + t) * nstate
        y = dskip * x
        new = []
        for n in range(nstate):
            s = jnp.exp(dt * a[n]) * state[n] + b_ref[at + n] * u
            y = y + c_ref[at + n] * s
            new.append(s)
        y_ref[t] = y
        return tuple(new)

    state = jax.lax.fori_loop(
        0, rows, row, tuple(s_scr[n] for n in range(nstate)))
    for n in range(nstate):
        s_scr[n] = state[n]

    @pl.when(blk == pl.num_programs(1) - 1)
    def _store():
        s1_ref[...] = s_scr[...]


def ssm_chunk_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
                   a: jax.Array, d_skip: jax.Array, state: jax.Array,
                   valid_rows=None, interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """One slot's chunk through the recurrence, from ``state``.

    x, dt ``[T, d_inner]`` (the convolved input and the step, any float
    type; computed in float32), b, c ``[T, state]``, ``a [d_inner,
    state]`` (negative), ``d_skip [d_inner]``, ``state [tiles, state, 8,
    lanes]`` float32 (:func:`state_to_tiles`); ``valid_rows`` int32
    scalar: rows at or past it leave the state alone (their ``y`` is
    ``d_skip x``).  Returns ``(y [T, d_inner] float32, the state after
    the last valid row, tiled)``."""
    t, di = x.shape
    nstate = b.shape[1]
    tiles, lanes = tiling(di)
    if state.shape != (tiles, nstate, SUBLANES, lanes):
        raise ValueError(
            f"ssm_chunk_scan: state must be {(tiles, nstate, SUBLANES, lanes)}"
            f" (state_to_tiles of [{di}, {nstate}]), got {state.shape}")
    interpret = resolve_interpret(interpret)
    if not interpret and lanes != LANES:
        raise ValueError(
            f"ssm_chunk_scan: compiled for the TPU, d_inner ({di}) must be "
            f"a whole number of {SUBLANES * LANES}-channel tiles")
    rows = min(_ROW_BLOCK, t)
    if t % rows:
        raise ValueError(f"ssm_chunk_scan: {t} rows are not whole blocks "
                         f"of {rows}")
    f32 = jnp.float32
    dt = dt.astype(f32)
    if valid_rows is not None:
        dt = jnp.where(jnp.arange(t)[:, None] < valid_rows, dt, 0.0)
    # [T, d_inner] -> [tiles, T, 8, lanes]: a tile's rows are contiguous
    xt = jnp.moveaxis(to_tiles(x.astype(f32)), 1, 0)
    dtt = jnp.moveaxis(to_tiles(dt), 1, 0)
    at = state_to_tiles(a.astype(f32))
    dsk = to_tiles(d_skip.astype(f32))

    def rows_spec():
        return pl.BlockSpec((None, rows, SUBLANES, lanes),
                            lambda i, j: (i, j, 0, 0))

    def tile_spec(lead):
        return pl.BlockSpec((None,) + lead + (SUBLANES, lanes),
                            lambda i, j: (i,) + (0,) * (len(lead) + 2))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, rows=rows, nstate=nstate),
        grid=(tiles, t // rows),
        in_specs=[smem, smem, rows_spec(), rows_spec(),
                  tile_spec((nstate,)), tile_spec(()), tile_spec((nstate,))],
        out_specs=[rows_spec(), tile_spec((nstate,))],
        out_shape=[jax.ShapeDtypeStruct((tiles, t, SUBLANES, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=[pltpu.VMEM((nstate, SUBLANES, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_chunk_scan",
    )(b.astype(f32).reshape(-1), c.astype(f32).reshape(-1), xt, dtt, at,
      dsk, state.astype(f32))
    return from_tiles(jnp.moveaxis(y, 0, 1)), new_state


def ssm_decode_update(x: jax.Array, dt: jax.Array, b: jax.Array,
                      c: jax.Array, a: jax.Array, d_skip: jax.Array,
                      state: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: x, dt ``[S, d_inner]``, b, c ``[S, state]``,
    ``state [S, tiles, state, 8, lanes]`` float32.  Elementwise XLA:
    ``(y [S, d_inner] float32, the new states)``."""
    f32 = jnp.float32
    xt, dtt = to_tiles(x.astype(f32)), to_tiles(dt.astype(f32))
    at = state_to_tiles(a.astype(f32))                  # [tiles, n, 8, l]
    bb = b.astype(f32)[:, None, :, None, None]          # [S, 1, n, 1, 1]
    cc = c.astype(f32)[:, None, :, None, None]
    new = (jnp.exp(dtt[:, :, None] * at[None]) * state
           + bb * (dtt * xt)[:, :, None])
    y = jnp.sum(new * cc, axis=2) + to_tiles(d_skip.astype(f32)) * xt
    return from_tiles(y), new


def ssm_scan_reference(x, dt, b, c, a, d_skip, state, valid_rows=None):
    """The recurrence as a loop over rows, float32, in the equations'
    own shapes: ``state [d_inner, state]``.  ``(y [T, d_inner], the state
    after the last valid row)``."""
    f32 = jnp.float32
    t = x.shape[0]
    valid_rows = t if valid_rows is None else valid_rows

    def row(s, xs):
        xt, dtt, bt, ct, i = xs
        new = (jnp.exp(dtt[:, None] * a) * s
               + (dtt * xt)[:, None] * bt[None, :])
        new = jnp.where(i < valid_rows, new, s)
        return new, new @ ct + d_skip * xt
    s, y = jax.lax.scan(
        row, state.astype(f32),
        (x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32),
         jnp.arange(t)))
    return y, s
