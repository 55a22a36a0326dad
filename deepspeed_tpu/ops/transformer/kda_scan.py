"""The recurrence of a gated DELTA rule with a decay a CHANNEL (Kimi Delta
Attention, KDA), both lanes of the serving step: the chunk's blocked form
in plain XLA, the decode rows' update ONE Pallas TPU kernel over the step's
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
read and written, 64 KB a head, and nothing of the lane outside the
kernel.  The kernel is handed the step's whole buffer ``[layers x slots,
H, V, K]`` (aliased to its result) and the layer's first row (a multiple
of the slots), streams that layer's slots through on-chip memory in blocks
of ``DECODE_STATES`` head-states (8 slots x 2 heads) and writes each back
where it lay; the slots' rows ``q``, ``k``, ``v``, ``g`` come as the
projections write them, ``[S, H K]``, eight slots a block, ``beta`` and
the mask from SMEM.  A head of a block, for its eight slots at once on
whole ``[8, 128]`` registers: the mask, ``exp(g)``, ``k . q`` (one lane
reduction), and the three bfloat16 pieces that add up to ``k`` and to
``q`` (``ssd_scan.py::_bf16_pieces``).  Then a head-state's pass: the
decay (lanes) times the state, cut into ITS three pieces; six rows — the
pieces of ``k`` and of ``q`` — against each piece of ``S'^T`` in ONE
bfloat16 pass each (the ``q k^T`` form: rows of the result lie on the
lanes as ``v`` and ``o`` do), nine exact piece-by-piece products a row
summed in float32, which give ``S'^T k`` and ``S'^T q`` together — ``o =
S'^T q + beta (k . q) u`` with ``u = v - S'^T k`` needs no second pass
over the new state; and the rank-1 term ``u (x) beta k`` as a
transposed-left product over a contraction of 16 whose nine live rows are
the products of a piece of ``u`` with a piece of ``beta k``.  Four
single passes a head-state where a product at precision ``highest`` took
six for ``[k; q] S'^T`` alone, each with the state as the stationary
operand — and a head's eight first products are all issued before its
first rank-1 term: where every head-state's chain closes before the next
one's opens, the matrix unit's latency, not its work, bounds the kernel
(509 us a layer against 316, and 315 for a kernel that only decays the
state in place: PR 57, chip).  An idle slot gets decay 1 and ``beta`` 0:
its state passes through unchanged.

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


def _pieces(x: jax.Array) -> jax.Array:
    """``x [n, w]`` float32 -> its three bfloat16 pieces one under the
    other, ``[3 n, w]`` float32 (piece ``j`` of row ``r`` at ``j n +
    r``)."""
    return jnp.concatenate(_bf16_pieces(x), axis=0)


def _row(a: jax.Array, at: int) -> jax.Array:
    """Row ``at`` of ``a``, kept a row: ``[1, w]``."""
    return jax.lax.slice_in_dim(a, at, at + 1, axis=0)


# A head-state's two halves are jitted: a kernel's body is traced anew at
# every call of the layer, fourteen times a step program's two shapes, and
# a jitted helper is traced once a process and inlined when the kernel is
# lowered (the same instructions, in the same order).
@functools.partial(jax.jit, static_argnums=0)
def _state_products(r: int, decay, kq, state, sk, sq):
    """Slot ``r``'s ``S'^T k`` and ``S'^T q`` put into row ``r`` of ``sk``
    / ``sq [rows, V]``: ``decay [rows, K]``, ``kq [6 rows, K]`` the
    pieces of ``k`` over those of ``q`` (:func:`_pieces`), ``state [V,
    K]``.  Six rows — the pieces of ``k``, then of ``q`` — against each
    piece of the decayed state in one bfloat16 pass: nine exact products
    a row pair, summed in float32."""
    f32, bf = jnp.float32, jnp.bfloat16
    rows = decay.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (16, kq.shape[1]), 0)
    six = jnp.zeros(at.shape, f32)
    for j in range(6):
        six = jnp.where(at == j, _row(kq, j * rows + r), six)
    p0, p1, p2 = (jax.lax.dot_general(
        six.astype(bf), piece.astype(bf), (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
        for piece in _bf16_pieces(_row(decay, r) * state))    # [16, V]
    both = p0 + p1 + p2
    mine = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) == r
    return (jnp.where(mine, _row(both, 0) + _row(both, 1) + _row(both, 2), sk),
            jnp.where(mine, _row(both, 3) + _row(both, 4) + _row(both, 5), sq))


@functools.partial(jax.jit, static_argnums=0)
def _state_update(r: int, decay, state, u, bk):
    """Slot ``r``'s new state ``S' + u (x) beta k``: ``decay [rows, K]``,
    ``state [V, K]``, ``u [3 rows, V]`` and ``bk [3 rows, K]`` the pieces
    of ``u`` and of ``beta k`` (:func:`_pieces`).  The rank-1 term is a
    transposed-left product over a contraction of 16: row ``3 i + j`` is
    piece ``i`` of ``u`` against piece ``j`` of ``beta k``."""
    f32, bf = jnp.float32, jnp.bfloat16
    rows = decay.shape[0]
    au = jax.lax.broadcasted_iota(jnp.int32, (16, u.shape[1]), 0)
    ak = jax.lax.broadcasted_iota(jnp.int32, (16, bk.shape[1]), 0)
    left, right = jnp.zeros(au.shape, f32), jnp.zeros(ak.shape, f32)
    for i in range(3):
        left = jnp.where((au >= 3 * i) & (au < 3 * i + 3),
                         _row(u, i * rows + r), left)
        right = jnp.where((ak < 9) & (ak % 3 == i),
                          _row(bk, i * rows + r), right)
    # (the decayed state again: 16 products, not a store and a load of 64
    # KB)
    return _row(decay, r) * state + jax.lax.dot_general(
        left.astype(bf), right.astype(bf), (((0,), (0,)), ((), ())),
        preferred_element_type=f32)                          # [V, K]


def _decode_kernel(first_ref, act_ref, beta_ref, q_ref, k_ref, v_ref, g_ref,
                   s_ref, new_ref, o_ref, *, heads: int, kd: int, vd: int):
    """One block: ``heads`` heads of each of a group of ``rows`` slots.
    ``act_ref [S]`` (SMEM, int32: a slot that is live) and ``beta_ref [S,
    H]`` (SMEM) hold every slot's; ``q_ref`` / ``k_ref`` / ``g_ref [rows,
    heads * K]`` and ``v_ref`` / ``o_ref [rows, heads * V]`` the group's
    rows, a slot a sublane; ``s_ref`` / ``new_ref [rows, heads, V, K]``,
    the same rows of the same buffer.  A head at a time: what is a row a
    slot (mask, decay, ``k . q``, ``u``, ``o`` and every cut into pieces)
    is computed once for the group on whole ``[rows, 128]`` registers,
    and the head's ``rows`` first products are all issued before its
    first rank-1 term, so that no head-state's matrix-unit latency
    stands in the next one's way.  Every index into a row or a state is
    static; only the SMEM scalars sit at traced places."""
    del first_ref                      # the index maps' alone
    f32 = jnp.float32
    rows = q_ref.shape[0]
    slot0, head0 = pl.program_id(0) * rows, pl.program_id(1) * heads
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)

    def column(scalar_of):
        """``[rows, 1]``: row ``r`` the SMEM scalar ``scalar_of(r)``."""
        out = jnp.zeros((rows, 1), f32)
        for r in range(rows):
            out = jnp.where(slot == r, scalar_of(r), out)
        return out
    live = column(lambda r: act_ref[slot0 + r].astype(f32)) > 0.0
    for h in range(heads):
        kc = slice(h * kd, (h + 1) * kd)
        vc = slice(h * vd, (h + 1) * vd)
        k, q = k_ref[:, kc], q_ref[:, kc]
        # an idle slot: decay 1, beta 0
        decay = jnp.where(live, jnp.exp(g_ref[:, kc]), 1.0)
        beta = jnp.where(
            live, column(lambda r: beta_ref[slot0 + r, head0 + h]), 0.0)
        kq = jnp.concatenate([_pieces(k), _pieces(q)], axis=0)
        sk = sq = jnp.zeros((rows, vd), f32)
        for r in range(rows):
            sk, sq = _state_products(r, decay, kq, s_ref[r, h], sk, sq)
        u = v_ref[:, vc] - sk                                # [rows, V]
        o_ref[:, vc] = sq + beta * jnp.sum(k * q, axis=-1, keepdims=True) * u
        u, bk = _pieces(u), _pieces(beta * k)
        for r in range(rows):
            new_ref[r, h] = _state_update(r, decay, s_ref[r, h], u, bk)


def kda_decode_update(q: jax.Array, k: jax.Array, v: jax.Array,
                      g: jax.Array, beta: jax.Array, state: jax.Array,
                      active: Optional[jax.Array] = None, first=0,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot, the rows as the projections write them — a slot a
    row, a head's channels side by side on the lanes: ``q``, ``k``, ``g
    [S, H K]``, ``v [S, H V]``, ``beta [S, H]`` (any float type; computed
    in float32); ``state [rows >= S, H, V, K]`` float32 (value-major,
    ``K`` on the lanes): the ``S`` slots' states are its rows ``first ..
    first + S`` (``first`` an int32 scalar, traced or not) and no other
    row is read or written; ``active [S]`` bool: a slot that is not gets
    decay 1 and ``beta`` 0 and keeps its state.  Returns ``(o [S, H V]
    float32, state with those rows updated)`` — the kernel's result IS
    its operand's buffer (``input_output_aliases``): donate it.  Nothing
    is computed outside the kernel: mask, ``exp(g)`` and ``k . q`` are
    its own."""
    s, h = beta.shape
    vd, kd = state.shape[-2:]
    if (state.ndim != 4 or state.shape[1] != h or state.shape[0] < s
            or q.shape != (s, h * kd) or k.shape != q.shape
            or g.shape != q.shape or v.shape != (s, h * vd)):
        raise ValueError(
            f"kda_decode_update: {s} slots' rows of {h} heads must be q, k, "
            f"g [{s}, {h} K], v [{s}, {h} V] beside a state [rows >= {s}, "
            f"{h}, V, K] (value-major); got {q.shape}, {k.shape}, "
            f"{g.shape}, {v.shape}, {state.shape}")
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
    if active is None:
        active = jnp.ones((s,), jnp.int32)
    k_spec = pl.BlockSpec((rows, heads * kd), lambda i, j, *_: (i, j))
    v_spec = pl.BlockSpec((rows, heads * vd), lambda i, j, *_: (i, j))
    # (``first`` is a layer's first row, a multiple of the slots and so of
    # the group)
    state_spec = pl.BlockSpec(
        (rows, heads, vd, kd),
        lambda i, j, first, act: (first[0] // rows + i, j, 0, 0))
    new, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, kd=kd, vd=vd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s // rows, h // heads),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), k_spec, k_spec,
                      v_spec, k_spec, state_spec],
            out_specs=[state_spec, v_spec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((s, h * vd), f32)],
        # operands count from the scalar prefetch: 7 is the state
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_update",
    )(jnp.asarray(first, jnp.int32).reshape(1), active.astype(jnp.int32),
      beta.astype(f32), q.astype(f32), k.astype(f32), v.astype(f32),
      g.astype(f32), state)
    return o, new


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
