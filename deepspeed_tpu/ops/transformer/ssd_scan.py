"""The recurrence of a state-space layer with a SCALAR decay a head and a
MATRIX state a head (Mamba-2 / SSD), both lanes of the serving step: the
chunk's blocked form in plain XLA, the decode rows' update a Pallas TPU
kernel over the step's state buffer, in place.

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
unit, and the WHOLE state of a layer read and written.  The kernel is
handed the step's whole buffer ``[layers x slots, H, P, N]`` (aliased to
its result) and the layer's first row, streams that layer's slots through
on-chip memory in blocks of ``DECODE_HEADS`` heads and writes each back
where it lay: one read, one write, ``y`` from the same pass, whatever XLA
fuses around the call.  Nothing else of the buffer is touched.

The state has ``N`` on the lanes and ``(head, p)`` on the sublanes, while
``x``, ``D x`` and ``y`` have ``p`` on the LANES; the two places where
they meet go through the matrix unit, which transposes for nothing:

* the rank-1 term ``(D x) (x) B`` is a transposed-left product over a
  contraction of 16, ``L[16, hp]^T R[16, N]``.  Both factors are float32,
  and one bfloat16 pass would round them; so each is cut into three
  bfloat16 pieces that add up to it (:func:`_bf16_pieces`) and the nine
  products of a piece with a piece are the nine live rows of the
  contraction: ONE pass of exact products summed in float32, where
  ``precision=HIGHEST`` would take six passes over ``hp x N`` and held the
  kernel a quarter over its bound (``PERF.md`` section 6, PR 52);
* ``y = S C`` is the ``q k^T`` form ``C[8, N] . S[hp, N]^T -> [8, hp]``
  (row 0 live) at ``precision=HIGHEST``: hidden behind the block's DMA.

The decay ``exp(D A)`` is a scalar a (slot, head), read from SMEM and
splat over the head's ``[P, N]``.

``ssd_scan_reference`` is the loop over rows in the equations' own
shapes: the tests' yardstick.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

#: rows to a block of the blocked form: the ``[H, Q, Q]`` decay planes are
#: ``4 H Q^2`` bytes a block (4.2 MB at 128, 16.8 MB at the published 256)
#: and ``4 H Q T`` a chunk, so a chunk's bytes fall with ``Q`` while its
#: products stay deep enough for the matrix unit
BLOCK_ROWS = 128
#: heads to a grid step of the decode kernel: a block of the state is
#: ``heads x P x N`` float32 (1 MB at 32 x 64 x 128), double-buffered in
#: and out: with the products' temporaries 6-8 MB of on-chip memory, under
#: the 16 MB a kernel gets unasked.  At 16 the grid's steps show beside
#: the products (0.437 ms a layer against 0.412 at 32 and at 64, which is
#: what a kernel that only scales the state takes: ``PERF.md`` section 6,
#: PR 52)
DECODE_HEADS = 32
LANES = 128
SUBLANES = 8


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


def _bf16_pieces(v: jax.Array):
    """Three float32 arrays, each a bfloat16's value, that add up to
    ``v`` to 2^-24 of it."""
    bf, f32 = jnp.bfloat16, jnp.float32
    hi = v.astype(bf).astype(f32)
    rest = v - hi
    mid = rest.astype(bf).astype(f32)
    return hi, mid, (rest - mid).astype(bf).astype(f32)


def _decode_kernel(first_ref, keep_ref, dx_ref, skip_ref, b_ref, c_ref,
                   s_ref, new_ref, y_ref, *, heads: int, p: int):
    """One block of ``heads`` heads of one slot.  ``keep_ref`` (SMEM)
    holds every (slot, head)'s decay, flat; ``dx_ref`` (``D x``) /
    ``skip_ref`` (``D_skip x``) / ``y_ref`` ``[rows, heads * p]`` and
    ``b_ref`` / ``c_ref`` ``[rows, N]`` hold the rows of the slot's group
    of ``rows`` slots (resident while the grid walks the group); ``s_ref``
    / ``new_ref`` ``[heads, p, N]``, the same rows of the same buffer."""
    del first_ref                      # the index maps' alone
    f32, bf = jnp.float32, jnp.bfloat16
    n = b_ref.shape[-1]
    m = heads * p
    blk, slot = pl.program_id(0), pl.program_id(1)
    head0 = (slot * pl.num_programs(0) + blk) * heads
    row = pl.ds(slot % dx_ref.shape[0], 1)
    # row 3 i + j of the contraction: piece i of D x, piece j of B
    d, b = _bf16_pieces(dx_ref[row, :]), _bf16_pieces(b_ref[row, :])
    km = jax.lax.broadcasted_iota(jnp.int32, (16, m), 0)
    kn = jax.lax.broadcasted_iota(jnp.int32, (16, n), 0)
    left = jnp.where(km < 3, d[0], jnp.where(
        km < 6, d[1], jnp.where(km < 9, d[2], 0.0)))
    right = jnp.where(kn >= 9, 0.0, jnp.where(
        kn % 3 == 0, b[0], jnp.where(kn % 3 == 1, b[1], b[2])))
    added = jax.lax.dot_general(
        left.astype(bf), right.astype(bf), (((0,), (0,)), ((), ())),
        preferred_element_type=f32)                          # [m, N]
    for h in range(heads):
        new_ref[h] = (keep_ref[head0 + h] * s_ref[h]
                      + added[h * p:(h + 1) * p])
    c8 = jnp.where(kn[:SUBLANES] == 0, c_ref[row, :], 0.0)
    y8 = jax.lax.dot_general(
        c8, new_ref[...].reshape(m, n), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=f32)                          # [8, m]
    y_ref[row, :] = y8[0:1] + skip_ref[row, :]


def ssd_decode_update(x: jax.Array, dt: jax.Array, b: jax.Array,
                      c: jax.Array, a: jax.Array, d_skip: jax.Array,
                      state: jax.Array, active: Optional[jax.Array] = None,
                      first=0, interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: ``x [S, H, P]``, ``dt [S, H]``, ``b``, ``c [S,
    N]`` (any float type; computed in float32); ``state [rows >= S, H, P,
    N]`` float32 (``N`` on the lanes): the ``S`` slots' states are its
    rows ``first .. first + S`` (``first`` an int32 scalar, traced or
    not) and no other row is read or written; ``active [S]`` bool: a slot
    that is not gets step 0 and keeps its state.  Returns ``(y [S, H, P]
    float32, state with those rows updated)`` — the kernel's result IS
    its operand's buffer (``input_output_aliases``): donate it."""
    s, h, p = x.shape
    n = b.shape[1]
    if (state.ndim != 4 or state.shape[1:] != (h, p, n)
            or state.shape[0] < s):
        raise ValueError(f"ssd_decode_update: state must be [rows >= {s}, "
                         f"{h}, {p}, {n}], got {state.shape}")
    heads = max(k for k in range(1, min(h, DECODE_HEADS) + 1) if h % k == 0)
    interpret = resolve_interpret(interpret)
    if not interpret and (n % LANES or p % SUBLANES
                          or (heads * p) % LANES):
        raise ValueError(
            f"ssd_decode_update: compiled for the TPU, the state ({n}) must "
            f"be whole {LANES}-lane tiles and a block of {heads} heads of "
            f"{p} whole {LANES}-lane rows of y")
    f32 = jnp.float32
    dt = dt.astype(f32)
    if active is not None:
        dt = jnp.where(active[:, None], dt, 0.0)
    x = x.astype(f32)
    keep = jnp.exp(dt * a.astype(f32))                       # [S, H]
    dx = (dt[:, :, None] * x).reshape(s, h * p)
    skip = (d_skip.astype(f32)[:, None] * x).reshape(s, h * p)
    # the slots' rows arrive and leave eight slots a block, as they lie in
    # (8, 128) tiles: the grid walks a group's slots before the next head
    # block, so a block of rows is fetched (and y's written) once a group
    rows = SUBLANES if s % SUBLANES == 0 else s
    hp_spec = pl.BlockSpec((rows, heads * p),
                           lambda j, i, first: (i // rows, j))
    n_spec = pl.BlockSpec((rows, n), lambda j, i, first: (i // rows, 0))
    state_spec = pl.BlockSpec(
        (None, heads, p, n), lambda j, i, first: (first[0] + i, j, 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h // heads, s),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), hp_spec,
                      hp_spec, n_spec, n_spec, state_spec],
            out_specs=[state_spec, hp_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((s, h * p), f32)],
        # operands count from the scalar prefetch: 6 is the state
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssd_decode_update",
    )(jnp.asarray(first, jnp.int32).reshape(1), keep.reshape(-1), dx, skip,
      b.astype(f32), c.astype(f32), state)
    return y.reshape(s, h, p), new


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
