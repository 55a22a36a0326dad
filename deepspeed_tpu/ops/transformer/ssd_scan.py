"""The recurrence of a state-space layer with a SCALAR decay a head and a
MATRIX state a head (Mamba-2 / SSD), both lanes of the serving step, in
plain XLA.

Per head ``h`` of ``H``, with ``x_t^h [P]`` the head's slice of the
convolved input, ``B_t`` / ``C_t [N]`` shared by every head (one group),
``D_t^h`` the step after its softplus and ``A^h < 0`` a scalar::

    S_t^h = exp(D_t^h A^h) S_{t-1}^h + D_t^h x_t^h (x) B_t       [P, N]
    y_t^h = S_t^h C_t + D_skip^h x_t^h                            [P]

The two lanes have opposite bounds.

``ssd_chunk_scan`` — one slot's prompt chunk.  A loop over rows would be
``T`` sequential passes over ``H x P x N`` state elements on the vector
unit; the decay being a scalar a head, a BLOCK of ``Q`` rows goes through
the matrix unit instead (the blocked, "state-space dual" form).  With
``cum_t = sum_{s <= t} D_s A`` inside a block that starts from ``S_0``::

    L_ts = exp(cum_t - cum_s)  (s <= t, else 0)
    Y    = ((C B^T) o L) (D o X)  +  exp(cum) o (C S_0)
    S_Q  = exp(cum_Q) S_0 + sum_s exp(cum_Q - cum_s) D_s x_s (x) B_s

and the blocks are chained through ``S``.  ``cum``, ``L`` and the state
are float32; ``L_ts`` is the exponential of the DIFFERENCE (``cum`` falls
monotonically, so ``cum_t - cum_s <= 0`` where it is used, and never
``exp(cum_t) * exp(-cum_s)``, whose second factor overflows within a
block); the products that make ``Y`` take their inputs in the
activations' type and accumulate in float32.  The ONE product that makes
the state (the sum in ``S_Q``) keeps float32 inputs at the highest
precision — 0.5 GFLOP a layer a chunk: every later token of the session
is computed from it, and what the attention layers downstream write
came 6 % nearer the reference for it (``PERF.md`` section 4).  No loop
over rows.

``ssd_decode_update`` — every slot's one row: no product worth the matrix
unit, and the WHOLE state read and written.  It is one elementwise pass
over ``[slots, H, P, N]`` with ``N`` on the lanes, written so that the
caller's ``dynamic_slice`` of a layer's slots out of the step's state
buffer and the ``dynamic_update_slice`` back fuse around it: the buffer
is read once and written once, in place.

``ssd_scan_reference`` is the loop over rows in the equations' own
shapes: the tests' yardstick.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

#: rows to a block of the blocked form: the ``[H, Q, Q]`` decay planes are
#: ``4 H Q^2`` bytes a block (4.2 MB at 128, 16.8 MB at the published 256)
#: and ``4 H Q T`` a chunk, so a chunk's bytes fall with ``Q`` while its
#: products stay deep enough for the matrix unit
BLOCK_ROWS = 128


def ssd_chunk_scan(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
                   a: jax.Array, d_skip: jax.Array, state: jax.Array,
                   valid_rows=None, block_rows: int = BLOCK_ROWS,
                   product_dtype=None) -> Tuple[jax.Array, jax.Array]:
    """One slot's chunk through the recurrence, from ``state``.

    ``x [T, H, P]``, ``dt [T, H]`` (the step, after its softplus), ``b``,
    ``c [T, N]``, ``a [H]`` (negative), ``d_skip [H]``, ``state [H, P,
    N]`` float32; ``valid_rows`` an int32 scalar: rows at or past it get
    step 0, so they leave the state alone; ``product_dtype``: the input
    type of the products that make ``y`` (``x``'s own by default).
    Returns ``(y [T, H, P] float32, the state after the last valid
    row)``."""
    t, h, p = x.shape
    n = b.shape[1]
    q = min(block_rows, t)
    if t % q:
        raise ValueError(f"ssd_chunk_scan: {t} rows are not whole blocks of "
                         f"{q}")
    if state.shape != (h, p, n):
        raise ValueError(f"ssd_chunk_scan: state must be {(h, p, n)}, got "
                         f"{state.shape}")
    f32, act = jnp.float32, product_dtype or x.dtype
    nb = t // q
    dt = dt.astype(f32)
    if valid_rows is not None:
        dt = jnp.where(jnp.arange(t)[:, None] < valid_rows, dt, 0.0)
    dt = dt.reshape(nb, q, h)
    cum = jnp.cumsum(dt * a.astype(f32), axis=1)             # [nb, Q, H]
    xb = x.reshape(nb, q, h, p)
    bb, cb = b.astype(act).reshape(nb, q, n), c.astype(act).reshape(nb, q, n)
    xd32 = dt[..., None] * xb.astype(f32)                    # D o X
    xd = xd32.astype(act)
    # inside a block: ((C B^T) o L) (D o X)
    cbt = jnp.einsum("bqn,bsn->bqs", cb, bb, preferred_element_type=f32)
    by_head = cum.transpose(0, 2, 1)                         # [nb, H, Q]
    diff = by_head[:, :, :, None] - by_head[:, :, None, :]   # [nb, H, Q, Q]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    y = jnp.einsum("bhqs,bshp->bqhp", (cbt[:, None] * decay).astype(act), xd,
                   preferred_element_type=f32)
    # what each block adds to the state by its end, from zero
    to_end = jnp.exp(cum[:, -1:, :] - cum)                   # [nb, Q, H]
    added = jnp.einsum(
        "bshp,bsn->bhpn", to_end[..., None] * xd32,
        b.astype(f32).reshape(nb, q, n), precision=jax.lax.Precision.HIGHEST)
    # the blocks chained through the state (nb is 2 to 4: unrolled)
    whole = jnp.exp(cum[:, -1, :])                           # [nb, H]
    state = state.astype(f32)
    starts = []
    for k in range(nb):
        starts.append(state)
        state = whole[k][:, None, None] * state + added[k]
    carried = jnp.einsum("bqn,bhpn->bqhp", cb,
                         jnp.stack(starts).astype(act),
                         preferred_element_type=f32)
    y = (y + jnp.exp(cum)[..., None] * carried
         + d_skip.astype(f32)[:, None] * xb.astype(f32))
    return y.reshape(t, h, p), state


def ssd_decode_update(x: jax.Array, dt: jax.Array, b: jax.Array,
                      c: jax.Array, a: jax.Array, d_skip: jax.Array,
                      state: jax.Array, active: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: ``x [S, H, P]``, ``dt [S, H]``, ``b``, ``c [S,
    N]``, ``state [S, H, P, N]`` float32 (``N`` on the lanes); ``active
    [S]`` bool: a slot that is not gets step 0 and keeps its state.
    Elementwise XLA: ``(y [S, H, P] float32, the new states)``."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    if active is not None:
        dt = jnp.where(active[:, None], dt, 0.0)
    x = x.astype(f32)
    keep = jnp.exp(dt * a.astype(f32))                       # [S, H]
    new = (keep[:, :, None, None] * state
           + (dt[:, :, None] * x)[..., None]
           * b.astype(f32)[:, None, None, :])
    y = (jnp.sum(new * c.astype(f32)[:, None, None, :], axis=-1)
         + d_skip.astype(f32)[:, None] * x)
    return y, new


def ssd_scan_reference(x, dt, b, c, a, d_skip, state, valid_rows=None):
    """The recurrence as a loop over rows, float32, in the equations'
    shapes: ``(y [T, H, P], the state after the last valid row)``."""
    f32 = jnp.float32
    t = x.shape[0]
    valid_rows = t if valid_rows is None else valid_rows
    a, d_skip = a.astype(f32), d_skip.astype(f32)

    def row(s, xs):
        xt, dtt, bt, ct, i = xs
        new = (jnp.exp(dtt * a)[:, None, None] * s
               + (dtt[:, None] * xt)[..., None] * bt[None, None, :])
        new = jnp.where(i < valid_rows, new, s)
        return new, new @ ct + d_skip[:, None] * xt
    s, y = jax.lax.scan(
        row, state.astype(f32),
        (x.astype(f32), dt.astype(f32), b.astype(f32), c.astype(f32),
         jnp.arange(t)))
    return y, s
