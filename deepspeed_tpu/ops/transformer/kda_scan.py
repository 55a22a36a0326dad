"""The recurrence of a gated DELTA rule with a decay a CHANNEL (Kimi Delta
Attention, KDA), both lanes of the serving step: the chunk's blocked form
in plain XLA, the decode rows' update a Pallas TPU kernel over the step's
state buffer, in place.

Per head, with ``q_t``, ``k_t [K]`` (``k_t`` of unit length), ``v_t [V]``,
``g_t [K] <= 0`` the log of the decay ``a_t = exp(g_t)`` a channel and
``beta_t`` in (0, 1)::

    S'  = diag(a_t) S_{t-1}                                       [K, V]
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                                               [V]

What is written depends on what the state already holds (``S'^T k_t``),
and the decay is a vector: neither ``ssd_scan.py`` nor ``ssm_scan.py``
computes it.

THE STATE IS STORED VALUE-MAJOR: every function here takes and returns
``S^T [.., H, V, K]``, the key channel on the lanes, so that the decay is a
lane vector (a sublane broadcast, free) and ``S'^T k`` a reduction over
lanes.  ``models/kda_latent_moe.py::slot_state`` swaps the axes back for
a reader who wants the equations' ``[K, V]``.

``kda_chunk_scan`` — one slot's prompt chunk, the blocked (WY) form.  For
a block of ``C`` rows from ``S_0``, ``G_t = sum_{i <= t} g_i``::

    A[t, i] = beta_i sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])     (i <  t)
    P[t, i] = beta_i sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])     (i <= t)
    (I + A) W = V - (exp(G) * K) S_0          (unit lower triangular)
    O   = (exp(G) * Q) S_0 + P W
    S_C = diag(exp(G_C)) S_0 + sum_i beta_i (exp(G_C - G_i) * k_i) w_i^T

Every exponent is of ``G_later - G_earlier <= 0``: ``1 / exp(G_i)`` over
a block is never formed (it overflows under a strong gate).  Inside a
sub-block of ``SUB_ROWS`` rows the differences are taken directly (a
``[rows, rows, K]`` reduction); across sub-blocks they factor through the
LATER sub-block's first row ``r``: ``exp(G_t - G_r) exp(G_r - G_i)``,
both factors at most 1, so those parts of ``A`` and ``P`` are matrix
products.  The solve is split: ``(I + A)^-1 [V | exp(G) * K]`` needs no
state, so it runs for every block at once (forward substitution by rows
inside a sub-block's diagonal, by sub-blocks across them), and the chain
through ``S`` is three products a block.  ``G``, ``A``, the solve, ``W``
and the state are float32, their products at precision ``highest``; the
two products that make ``O`` (``(exp(G) * Q) S_0`` and ``P W``) take
their inputs in ``product_dtype`` (the activations' type) and accumulate
in float32.

``kda_decode_update`` — every slot's one row: the WHOLE state of a layer
read and written, 64 KB a head.  The kernel is handed the step's whole
buffer ``[layers x slots, H, V, K]`` (aliased to its result) and the
layer's first row (a multiple of the slots), streams that layer's slots
through on-chip memory in blocks of ``DECODE_STATES`` head-states (8
slots x 2 heads) and writes each back where it lay.  A
head's pass: the decay (lanes) times the state; ONE product at precision
``highest`` of ``[k; q]`` against ``S'^T`` (the ``q k^T`` form: rows of
the result lie on the lanes as ``v`` and ``o`` do), which gives ``S'^T k``
and ``S'^T q`` together — ``o = S'^T q + beta (k . q) u`` with ``u = v -
S'^T k`` needs no second pass over the new state; and the rank-1 term
``u (x) beta k`` as a transposed-left product over a contraction of 16
whose factors are cut into three bfloat16 pieces each, the nine products
of a piece with a piece exact and summed in float32
(``ssd_scan.py::_bf16_pieces``).  An idle slot gets decay 1 and ``beta``
0: its state passes through unchanged.

``kda_scan_reference`` is the loop over rows: the tests' yardstick.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from .ssd_scan import LANES, SUBLANES, _bf16_pieces

#: rows to a block of the blocked form (a unit triangular system of this
#: size a head a block) and to a sub-block (differences taken directly)
BLOCK_ROWS = 64
SUB_ROWS = 16
#: head-states to a grid step of the decode kernel (a group of 8 slots x
#: their next 2 heads): a block of the state is 1 MB at ``V = K = 128``
#: float32, double-buffered in and out
DECODE_STATES = 16

_HIGHEST = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for ``a [.., n, n]`` strictly lower triangular, by
    forward substitution over the rows (``n`` static steps)."""
    n = a.shape[-1]
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    for t in range(1, n):
        row = x[..., t, :] - jnp.einsum("...i,...ij->...j", a[..., t, :], x,
                                        precision=_HIGHEST)
        x = x.at[..., t, :].set(row)
    return x


def kda_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, state: jax.Array, valid_rows=None,
                   block_rows: int = BLOCK_ROWS, sub_rows: int = SUB_ROWS,
                   product_dtype=None) -> Tuple[jax.Array, jax.Array]:
    """One slot's chunk through the recurrence, from ``state``.

    ``q``, ``k``, ``g [T, H, K]`` (``g <= 0``, the decay's log), ``v [T,
    H, V]``, ``beta [T, H]``, ``state [H, V, K]`` float32 (value-major);
    ``valid_rows`` an int32 scalar: rows at or past it get decay 1 and
    ``beta`` 0, so they leave the state alone; ``product_dtype``: the
    input type of the two products that make ``o`` (float32 by default).
    Returns ``(o [T, H, V] float32, the state after the last valid
    row)``."""
    t, h, kd = q.shape
    vd = v.shape[-1]
    c = min(block_rows, t)
    r = min(sub_rows, c)
    if t % c or c % r:
        raise ValueError(f"kda_chunk_scan: {t} rows are not whole blocks of "
                         f"{c} in sub-blocks of {r}")
    if state.shape != (h, vd, kd):
        raise ValueError(f"kda_chunk_scan: state must be {(h, vd, kd)} "
                         f"(value-major), got {state.shape}")
    f32, act = jnp.float32, product_dtype or jnp.float32
    nb, ns = t // c, c // r
    g, beta = g.astype(f32), beta.astype(f32)
    if valid_rows is not None:
        live = jnp.arange(t) < valid_rows
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    # [nb, ns, r, H, ..]: block, sub-block, row
    shape = (nb, ns, r, h)
    gc = jnp.cumsum(g.reshape(nb, c, h, kd), axis=1)         # G, a block
    gs = gc.reshape(*shape, kd)
    qs, ks = q.reshape(*shape, kd), k.reshape(*shape, kd)
    first = gs[:, :, :1]                                     # G at row r0
    # rows' side, from their sub-block's first row; both <= 0
    k_row = ks * jnp.exp(gs - first)
    q_row = qs * jnp.exp(gs - first)
    # columns' side, to a LATER sub-block I's first row: [nb, I, J, r, H, K]
    to_first = first[:, :, None] - gs[:, None]               # G_I0 - G_i
    later = (jnp.arange(ns)[:, None] > jnp.arange(ns)[None, :]
             )[None, :, :, None, None, None]
    k_col = jnp.where(later, ks[:, None] * jnp.exp(
        jnp.where(later, to_first, 0.0)), 0.0)
    # across sub-blocks: [nb, H, I, t, J, i]
    a_off = jnp.einsum("bithc,bijshc->bhitjs", k_row, k_col,
                       precision=_HIGHEST)
    p_off = jnp.einsum("bithc,bijshc->bhitjs", q_row, k_col,
                       precision=_HIGHEST)
    # inside a sub-block, the differences directly: [nb, I, t, i, H, K]
    diff = gs[:, :, :, None] - gs[:, :, None]
    tri = (jnp.arange(r)[:, None] >= jnp.arange(r)[None, :]
           )[None, None, :, :, None, None]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    a_in = jnp.sum(ks[:, :, :, None] * ks[:, :, None] * decay, axis=-1)
    p_in = jnp.sum(qs[:, :, :, None] * ks[:, :, None] * decay, axis=-1)
    eye = jnp.eye(ns, dtype=f32)[None, None, :, None, :, None]

    def whole(off, inside):
        """The block's ``[nb, H, C, C]`` matrix, column ``i`` times
        ``beta_i``."""
        inside = inside.transpose(0, 4, 1, 2, 3)             # [nb,H,I,t,i]
        m = off + inside[:, :, :, :, None, :] * eye
        return m.reshape(nb, h, c, c) * beta.reshape(nb, c, h).transpose(
            0, 2, 1)[:, :, None, :]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    a_mat = jnp.where(strict, whole(a_off, a_in), 0.0)
    p_mat = whole(p_off, p_in)             # lower with its diagonal: i <= t
    # (I + A)^-1 [V | exp(G) * K], every block at once
    kg = (k.reshape(nb, c, h, kd) * jnp.exp(gc)).transpose(0, 2, 1, 3)
    rhs = jnp.concatenate(
        [v.reshape(nb, c, h, vd).transpose(0, 2, 1, 3), kg], axis=-1)
    a_sub = a_mat.reshape(nb, h, ns, r, ns, r)
    inv = _unit_lower_inverse(jnp.stack(
        [a_sub[:, :, i, :, i, :] for i in range(ns)], axis=2))
    solved = []
    for i in range(ns):
        part = rhs[:, :, i * r:(i + 1) * r]
        if i:
            part = part - jnp.einsum(
                "bhtx,bhxw->bhtw",
                a_mat[:, :, i * r:(i + 1) * r, :i * r],
                jnp.concatenate(solved, axis=2), precision=_HIGHEST)
        solved.append(jnp.einsum("bhts,bhsw->bhtw", inv[:, :, i], part,
                                 precision=_HIGHEST))
    solved = jnp.concatenate(solved, axis=2)                 # [nb,H,C,V+K]
    u, wk = solved[..., :vd], solved[..., vd:]
    # what a block's rows leave in the state by its end
    k_end = (k.reshape(nb, c, h, kd) * jnp.exp(gc[:, -1:] - gc)
             * beta.reshape(nb, c, h, 1)).transpose(0, 2, 1, 3)
    qg = (q.reshape(nb, c, h, kd) * jnp.exp(gc)).transpose(0, 2, 1, 3)
    end = jnp.exp(gc[:, -1])                                 # [nb, H, K]

    def block(s, xs):
        u_b, wk_b, p_b, qg_b, k_end_b, end_b = xs
        w = u_b - jnp.einsum("htk,hvk->htv", wk_b, s, precision=_HIGHEST)
        o = (jnp.einsum("htk,hvk->htv", qg_b.astype(act), s.astype(act),
                        preferred_element_type=f32)
             + jnp.einsum("hts,hsv->htv", p_b.astype(act), w.astype(act),
                          preferred_element_type=f32))
        s = end_b[:, None, :] * s + jnp.einsum(
            "htv,htk->hvk", w, k_end_b, precision=_HIGHEST)
        return s, o
    state, o = jax.lax.scan(block, state.astype(f32),
                            (u, wk, p_mat, qg, k_end, end))
    return o.transpose(0, 2, 1, 3).reshape(t, h, vd), state


def _decode_kernel(first_ref, beta_ref, kq_ref, q_ref, k_ref, v_ref, a_ref,
                   s_ref, new_ref, o_ref, *, heads: int, kd: int, vd: int,
                   all_heads: int):
    """One block: ``heads`` heads of each of a group of ``rows`` slots.
    ``beta_ref`` / ``kq_ref`` (SMEM) hold every (slot, head)'s ``beta``
    and ``beta (k . q)``, flat; ``q_ref`` / ``k_ref`` / ``a_ref [rows,
    heads * K]`` and ``v_ref`` / ``o_ref [rows, heads * V]`` the group's
    rows; ``s_ref`` / ``new_ref [rows, heads, V, K]``, the same rows of
    the same buffer.  Every index is static: a (slot, head)'s ``[1, K]``
    piece of a row is an aligned load."""
    del first_ref                      # the index maps' alone
    f32, bf = jnp.float32, jnp.bfloat16
    rows = q_ref.shape[0]
    group, blk = pl.program_id(0), pl.program_id(1)
    r8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, kd), 0)
    ru = jax.lax.broadcasted_iota(jnp.int32, (16, vd), 0)
    rk = jax.lax.broadcasted_iota(jnp.int32, (16, kd), 0)
    for r in range(rows):
        for h in range(heads):
            row = slice(r, r + 1)
            kc = slice(h * kd, (h + 1) * kd)
            vc = slice(h * vd, (h + 1) * vd)
            at = (group * rows + r) * all_heads + blk * heads + h
            k_row, q_row = k_ref[row, kc], q_ref[row, kc]
            decayed = a_ref[row, kc] * s_ref[r, h]           # [V, K]
            # rows 0 and 1 of the result: S'^T k and S'^T q, on the lanes
            both = jax.lax.dot_general(
                jnp.where(r8 == 0, k_row, jnp.where(r8 == 1, q_row, 0.0)),
                decayed, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=f32)                  # [8, V]
            u = v_ref[row, vc] - both[0:1]
            o_ref[row, vc] = both[1:2] + kq_ref[at] * u
            # u (x) beta k: row 3 i + j of the contraction is piece i of
            # u against piece j of beta k
            d, b = _bf16_pieces(u), _bf16_pieces(beta_ref[at] * k_row)
            left = jnp.where(ru < 3, d[0], jnp.where(
                ru < 6, d[1], jnp.where(ru < 9, d[2], 0.0)))
            right = jnp.where(rk >= 9, 0.0, jnp.where(
                rk % 3 == 0, b[0], jnp.where(rk % 3 == 1, b[1], b[2])))
            new_ref[r, h] = decayed + jax.lax.dot_general(
                left.astype(bf), right.astype(bf), (((0,), (0,)), ((), ())),
                preferred_element_type=f32)                  # [V, K]


def kda_decode_update(q: jax.Array, k: jax.Array, v: jax.Array,
                      g: jax.Array, beta: jax.Array, state: jax.Array,
                      active: Optional[jax.Array] = None, first=0,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot: ``q``, ``k``, ``g [S, H, K]``, ``v [S, H, V]``,
    ``beta [S, H]`` (any float type; computed in float32); ``state [rows
    >= S, H, V, K]`` float32 (value-major, ``K`` on the lanes): the ``S``
    slots' states are its rows ``first .. first + S`` (``first`` an int32
    scalar, traced or not) and no other row is read or written; ``active
    [S]`` bool: a slot that is not gets decay 1 and ``beta`` 0 and keeps
    its state.  Returns ``(o [S, H, V] float32, state with those rows
    updated)`` — the kernel's result IS its operand's buffer
    (``input_output_aliases``): donate it."""
    s, h, kd = q.shape
    vd = v.shape[-1]
    if (state.ndim != 4 or state.shape[1:] != (h, vd, kd)
            or state.shape[0] < s):
        raise ValueError(f"kda_decode_update: state must be [rows >= {s}, "
                         f"{h}, {vd}, {kd}] (value-major), got {state.shape}")
    # the slots' rows arrive and leave eight slots a block, as they lie in
    # (8, 128) tiles, and a block of the state is those slots' next few
    # heads: ``DECODE_STATES`` head-states, 1 MB at 128 x 128
    rows = SUBLANES if s % SUBLANES == 0 else s
    heads = max(n for n in range(1, h + 1)
                if h % n == 0 and (n == 1 or n * rows <= DECODE_STATES))
    interpret = resolve_interpret(interpret)
    if not interpret and (kd % LANES or vd % LANES):
        raise ValueError(
            f"kda_decode_update: compiled for the TPU, a head's key ({kd}) "
            f"and value ({vd}) widths must be whole {LANES}-lane tiles")
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    g, beta = g.astype(f32), beta.astype(f32)
    if active is not None:
        g = jnp.where(active[:, None, None], g, 0.0)
        beta = jnp.where(active[:, None], beta, 0.0)
    kq = beta * jnp.sum(k * q, axis=-1)                      # [S, H]
    k_spec = pl.BlockSpec((rows, heads * kd), lambda i, j, first: (i, j))
    v_spec = pl.BlockSpec((rows, heads * vd), lambda i, j, first: (i, j))
    # (``first`` is a layer's first row, a multiple of the slots and so of
    # the group)
    state_spec = pl.BlockSpec(
        (rows, heads, vd, kd),
        lambda i, j, first: (first[0] // rows + i, j, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    new, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, kd=kd, vd=vd,
                          all_heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s // rows, h // heads),
            in_specs=[smem, smem, k_spec, k_spec, v_spec, k_spec,
                      state_spec],
            out_specs=[state_spec, v_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((s, h * vd), f32)],
        # operands count from the scalar prefetch: 7 is the state
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_update",
    )(jnp.asarray(first, jnp.int32).reshape(1), beta.reshape(-1),
      kq.reshape(-1), q.reshape(s, h * kd), k.reshape(s, h * kd),
      v.reshape(s, h * vd), jnp.exp(g).reshape(s, h * kd), state)
    return o.reshape(s, h, vd), new


def kda_scan_reference(q, k, v, g, beta, state, valid_rows=None):
    """The recurrence as a loop over rows, float32: ``(o [T, H, V], the
    state after the last valid row)``; ``state [H, V, K]`` value-major,
    as everywhere in this file."""
    f32 = jnp.float32
    t = q.shape[0]
    valid_rows = t if valid_rows is None else valid_rows

    def row(s, xs):
        qt, kt, vt, gt, bt, i = xs
        decayed = jnp.exp(gt)[:, None, :] * s                # [H, V, K]
        u = vt - jnp.einsum("hvk,hk->hv", decayed, kt, precision=_HIGHEST)
        new = decayed + (bt[:, None] * u)[:, :, None] * kt[:, None, :]
        new = jnp.where(i < valid_rows, new, s)
        return new, jnp.einsum("hvk,hk->hv", new, qt, precision=_HIGHEST)
    s, o = jax.lax.scan(
        row, state.astype(f32),
        (q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
         beta.astype(f32), jnp.arange(t)))
    return o, s
