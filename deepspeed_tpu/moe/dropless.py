"""Dropless top-k routing over routed and zero-compute experts, and the
expert layer of ONE CHIP'S SHARE of the routed experts.

The router keeps its published width: scores over ``E`` routed experts
and ``Z`` identity ("zero-compute") experts — a softmax over the outputs
or each output's sigmoid — the ``k`` chosen are the top ``k`` of ``score +
bias`` (the bias, where the gate has one, moves the choice and never the
weight), and a pick's weight is ``scale * score``, renormalised over the
row's picks where the configuration says so (:func:`route`).  No capacity,
no drops, no auxiliary loss.

The expert layer is told which contiguous range of the routed experts it
holds (``experts_held``).  It computes, for the rows routed to a held
expert, that expert's weighted output, plus the identity experts' part
``w * u`` for the rows that live here; what the absent experts would add
is left out and that partial sum goes on.  On one chip there is no
exchange and nothing stands in for one.

Held experts run as ONE grouped matrix product over rows laid out by
expert: every pick of a held expert gets a row in a buffer whose tiles of
``TILE_ROWS`` rows each belong to one expert (an expert's rows start on a
tile boundary), so a tile needs one expert's weights and nothing else.
The buffer is walked in passes of ``pass_rows`` rows under a loop whose
trip count follows the load: a balanced batch takes one pass, a batch
routed wholly to one expert takes as many as its rows need, and no pick
is ever dropped.  The product is :func:`grouped_matmul` (device trace name
``moe_grouped_matmul``), three calls a pass, each skipping the pass's dead
tiles.  The row-wise work around them — the gather of the picks' rows,
the activation between the products, the weighting and the float32
scatter-add back — follows the live tiles too: it walks the pass in
blocks of ``BLOCK_ROWS`` rows under loops whose trip count is the blocks
that hold a live tile, so a dead row of a pass costs nothing: it is
neither gathered, nor passed over, nor added (counter
``moe_rows_moved``: the rows those blocks hold).

Where every pick sits in that buffer — each row's token and weight, each
tile's expert, the live tiles, the experts' counts — is ONE device
operation (:func:`_layout`, kernel ``moe_layout``): a counting pass that
ranks each expert's picks (running counts as products with triangles of
ones), adds up the experts' tiles into their first rows, and inverts
pick -> row by comparing the LIVE rows with the picks' destinations (no
running sum, search or scatter is left to XLA: PERF.md, PR 64).  It
differentiates in the picks' weights.

It differentiates (``custom_vjp``): the rows' gradient is the same kernel
walked against the weights' other axis, the weights' a second kernel
(``moe_grouped_matmul_dw``) that sums ``x_tile^T dy_tile`` over each
expert's tiles.  A loop whose trip count follows the load cannot be
differentiated in reverse, so a training call asks for ONE pass over the
whole buffer (``pass_rows=None``), in which a dead tile still starts no
DMA, and for row tiles deep enough to fill the matrix unit
(``tile_rows``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.overlap import scoped
from ..ops import resolve_interpret

#: rows to a tile of the grouped product: one bf16 operand tile's sublanes
TILE_ROWS = 16
#: rows of the buffer one pass walks: what ONE call of the grouped product
#: covers, so that an expert's weights cross HBM once however its tiles
#: lie in the pass.  A pass costs its LIVE rows: a decode-only dispatch
#: fills a quarter of it and pays for a quarter (``BLOCK_ROWS``)
PASS_ROWS = 1024
#: rows of a pass that one step of its row-wise work moves (the gather, the
#: activation, the weighted scatter-add).  XLA's scatter-add costs more a
#: row the more rows one call holds, steeply so for wide rows: at 7,680
#: columns 17 us a block of 64 rows, 23-70 us of 128, 1.6 ms of 256; at
#: 2,048 columns a full pass is cheapest in large blocks (8 us a block of
#: 128 against 47 us for the whole pass in one).  128 keeps every served
#: width on the cheap side and a full pass within a tenth of one call
#: (PERF.md, PR 61: the chip's readings at 64, 128, 256 and 512)
BLOCK_ROWS = 128
#: scoped VMEM the grouped product asks for (two halves of one weight
#: block of up to ``_WEIGHT_BLOCK_BYTES``, the row tiles, the result)
_VMEM_LIMIT_BYTES = 48 << 20
_WEIGHT_BLOCK_BYTES = 8 << 20
#: one float32 ``[tk, tn]`` block of the weights' gradient
_DW_BLOCK_BYTES = 8 << 20
#: the counters :func:`expert_share` returns, in order
COUNTERS = ("moe_picks", "moe_picks_held", "moe_picks_zero",
            "moe_rows_max_expert", "moe_experts_touched", "moe_rows_moved")


class Routing(NamedTuple):
    index: jax.Array       # [T, k] int32 in [0, E + Z)
    weight: jax.Array      # [T, k] float32 = scale * (renormalised) score


@scoped("router")
def route_logits(logits: jax.Array, bias: Optional[jax.Array], k: int,
                 scale: float, scoring: str = "softmax",
                 renormalize: bool = False) -> Routing:
    """``logits [T, E + Z]`` float32 -> the ``k`` picks of every row, in
    the gate's form the model's configuration names: a ``softmax`` over
    the outputs, or each output's own ``sigmoid``.  The ``k`` chosen are
    the top ``k`` of ``score + bias`` (``bias`` None: of the score).  A
    pick's weight is ``scale * score``, or with ``renormalize`` ``scale *
    score / (the row's chosen scores' sum + 1e-20)``."""
    if scoring == "softmax":
        p = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        p = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"route: scoring {scoring!r} is neither 'softmax' "
                         f"nor 'sigmoid'")
    _, index = jax.lax.top_k(
        p if bias is None else p + bias.astype(jnp.float32), k)
    weight = jnp.take_along_axis(p, index, axis=-1)
    if renormalize:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return Routing(index.astype(jnp.int32), scale * weight)


@scoped("router")
def route(u: jax.Array, router_kernel: jax.Array,
          bias: Optional[jax.Array], k: int, scale: float,
          scoring: str = "softmax", renormalize: bool = False) -> Routing:
    """``u [T, h]`` -> :func:`route_logits` of the gate that is one matrix
    product: float32 logits over all ``E + Z`` outputs (the operands keep
    their stored type; products accumulate in float32)."""
    logits = jnp.einsum("th,he->te", u, router_kernel.astype(u.dtype),
                        preferred_element_type=jnp.float32)
    return route_logits(logits, bias, k, scale, scoring, renormalize)


def _tile_n(k_dim: int, n: int, itemsize: int) -> int:
    """Columns of one weight block ``[K, tn]``: all of them, or the widest
    128-multiple divisor of ``n`` whose block fits the budget."""
    return _divisor_block(n, _WEIGHT_BLOCK_BYTES // (k_dim * itemsize))


def _product(x, w, tile_expert, live_tiles, interpret, transpose_w=False):
    """``x [M, K]`` times each tile's expert's ``w[e] [K, N]`` — or, with
    ``transpose_w``, ``x [M, N]`` times ``w[e]^T``: the weights are read
    where they lie, blocked along their other axis by the index map, and
    contracted on their last (no transposed copy in HBM)."""
    m, k_dim = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tiles = tile_expert.shape[0]
    if m % tiles or (m // tiles) % TILE_ROWS:
        raise ValueError(f"grouped_matmul: {m} rows are not {tiles} whole "
                         f"tiles of a multiple of {TILE_ROWS}")
    tile = m // tiles
    tn = _tile_n(k_dim, n, w.dtype.itemsize)

    def kernel(te_ref, live_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < live_ref[0])
        def _live():
            if transpose_w:
                o_ref[...] = jax.lax.dot_general(
                    x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)
            else:
                o_ref[...] = jnp.dot(
                    x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)

    def w_block(j, t, te, live):
        e = te[jnp.minimum(t, jnp.maximum(live[0] - 1, 0))]
        return (e, j, 0) if transpose_w else (e, 0, j)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                # a dead tile's blocks are the last live tile's: no DMA
                pl.BlockSpec(
                    (tile, k_dim), lambda j, t, te, live: (
                        jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), 0)),
                pl.BlockSpec(
                    (None, tn, k_dim) if transpose_w else (None, k_dim, tn),
                    w_block),
            ],
            out_specs=pl.BlockSpec(
                (tile, tn), lambda j, t, te, live: (
                    jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(live_tiles, jnp.int32).reshape(1), x, w.astype(x.dtype))


def _divisor_block(dim: int, most: int) -> int:
    """The widest block of ``dim`` of at most ``most``: all of it, or a
    128-multiple that divides it."""
    block = dim
    while block > most and block % 256 == 0:
        block //= 2
    return block


def _product_dw(x, dy, tile_expert, live_tiles, experts, interpret):
    """``dw[e] = sum over e's live tiles of x_tile^T dy_tile``, float32
    ``[experts, K, N]``.  Grid ``(K blocks, N blocks, tiles)``, tiles
    innermost: an expert's tiles are consecutive, so its ``[K, N]`` block
    stays in VMEM while they add to it and crosses to HBM once.  An
    expert with no live tile is never visited: its block is UNDEFINED —
    the caller zeroes it."""
    m, k_dim = x.shape
    n = dy.shape[1]
    tiles = tile_expert.shape[0]
    tile = m // tiles
    tn = _divisor_block(n, 2048)
    tk = _divisor_block(k_dim, max(_DW_BLOCK_BYTES // (4 * tn), 128))

    def kernel(te_ref, live_ref, x_ref, dy_ref, o_ref):
        t = pl.program_id(2)
        live = t < live_ref[0]
        first = (t == 0) | (te_ref[t] != te_ref[jnp.maximum(t - 1, 0)])

        @pl.when(live & first)
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(live)
        def _add():
            o_ref[...] += jax.lax.dot_general(
                x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k_dim // tk, n // tn, tiles),
            in_specs=[
                # a dead tile's blocks are the last live tile's: no DMA
                pl.BlockSpec((tile, tk), lambda i, j, t, te, live: (
                    jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), i)),
                pl.BlockSpec((tile, tn), lambda i, j, t, te, live: (
                    jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), j)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda i, j, t, te, live: (
                    te[jnp.minimum(t, jnp.maximum(live[0] - 1, 0))], i,
                    j)),
        ),
        out_shape=jax.ShapeDtypeStruct((experts, k_dim, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name="moe_grouped_matmul_dw",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(live_tiles, jnp.int32).reshape(1), x, dy.astype(x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped(x, w, tile_expert, live_tiles, interpret):
    return _product(x, w, tile_expert, live_tiles, interpret)


def _grouped_fwd(x, w, tile_expert, live_tiles, interpret):
    return (_product(x, w, tile_expert, live_tiles, interpret),
            (x, w, tile_expert, live_tiles))


def _grouped_bwd(interpret, res, dy):
    """Rows of dead tiles are undefined going in (``x``, ``dy``) and
    coming out (``dx``), as the forward's are; ``dw`` is exact: only live
    tiles add to it, and a live tile's padding rows are zero rows."""
    x, w, tile_expert, live_tiles = res
    with jax.named_scope("experts"):
        dx = _product(dy, w, tile_expert, live_tiles, interpret,
                      transpose_w=True)
        dw = _product_dw(x, dy, tile_expert, live_tiles, w.shape[0],
                         interpret)
        tiles = tile_expert.shape[0]
        touched = jnp.zeros((w.shape[0],), bool).at[tile_expert].max(
            jnp.arange(tiles) < live_tiles)
        dw = jnp.where(touched[:, None, None], dw, 0.0).astype(w.dtype)
    return dx, dw, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@scoped("experts")
def grouped_matmul(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
                   live_tiles: jax.Array,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``x [M, K]`` in as many equal tiles (of a multiple of ``TILE_ROWS``
    rows) as ``tile_expert`` has entries, tile ``t`` wholly of expert
    ``tile_expert[t]``; ``w [E, K, N]``.  Returns ``[M, N]`` in ``x``'s
    type.  Only the first ``live_tiles`` tiles are computed: a dead tile
    starts no DMA (its blocks are the last live tile's) and leaves its
    rows of the result UNDEFINED — the caller masks them.

    Grid ``(N blocks, tiles)``, tiles innermost: consecutive tiles of one
    expert reuse the weight block already in VMEM, so each held expert's
    weights cross HBM once per call however its rows split over tiles.

    Differentiable in ``x`` and ``w`` (module docstring): ``dx`` is this
    kernel over ``w``'s other axis, ``dw`` is ``moe_grouped_matmul_dw``
    with an expert that got no row written as zeros."""
    return _grouped(x, w, tile_expert, jnp.asarray(live_tiles, jnp.int32),
                    interpret)


class _Layout(NamedTuple):
    """Where every held pick sits in the row buffer."""
    row_token: jax.Array     # [M] int32: the pick's token, T for no pick
    row_weight: jax.Array    # [M] float32
    tile_expert: jax.Array   # [M / tile] int32 (local expert id)
    live_tiles: jax.Array    # int32 scalar
    counts: jax.Array        # [held] int32 rows of each held expert


#: picks to a chunk, rows to a block of the layout kernel: a vector's lanes
_LANES = 128


def _running_sum(x, axis):
    """Inclusive running sum of an int32 value along ``axis`` by doubling
    rolls (no ``reduce-window``)."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    shift = 1
    while shift < x.shape[axis]:
        x = x + jnp.where(at >= shift, pltpu.roll(x, shift, axis), 0)
        shift *= 2
    return x


def _ones_where(mask):
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


def _layout_kernel(local_ref, weight_ref, token_ref, row_weight_ref,
                   tile_expert_ref, dest_ref, counts_ref, live_ref,
                   rank_ref, ends_ref, *, held, tile, picks, k):
    """A counting pass over ``local_ref`` / ``weight_ref [C, 128]`` (pick
    ``i`` at ``[i // 128, i % 128]``; ``held`` and 0.0 in the padding).

    The experts' one-hots lie stacked in ``rank_ref [held C, 128]``; one
    running count over them (a product with a triangle of ones along the
    lanes, a second along each expert's chunks) is every pick's rank among
    its expert's and, in an expert's last chunk, its count.  The counts'
    tiles, summed over the experts, are where each expert's rows start;
    rank and start give every pick's destination (``dest_ref``, the
    buffer's end for a pick not held).  The inverse needs no scatter: a
    block of 128 rows is compared with the destinations of 128 picks a
    step, and only the blocks that hold a live tile are visited — a row
    holds one pick at most, so a hit overwrites.  Every other row keeps
    the fill."""
    i32 = jnp.int32
    chunks = local_ref.shape[0]
    no_row = token_ref.shape[0] * _LANES
    loc = local_ref[...]

    def of_expert(e):
        return pl.ds(pl.multiple_of(e * chunks, 8), chunks)

    def mark(e, _):
        rank_ref[of_expert(e), :] = jnp.where(loc == e, 1, 0).astype(i32)
        return 0
    jax.lax.fori_loop(0, held, mark, 0)
    # counts of at most 128 a chunk are exact in bfloat16, their sums in
    # float32: both running counts are products with a triangle of ones
    mine = rank_ref[...].astype(jnp.float32).astype(jnp.bfloat16)
    lanes = (_LANES, _LANES)
    within = jnp.dot(
        mine, _ones_where(jax.lax.broadcasted_iota(i32, lanes, 0)
                          <= jax.lax.broadcasted_iota(i32, lanes, 1)),
        preferred_element_type=jnp.float32)
    total = jnp.broadcast_to(within[:, _LANES - 1:], within.shape)
    # ... and over the chunks before it of the same expert
    row = jax.lax.broadcasted_iota(i32, (held * chunks, 1), 0)
    col = jax.lax.broadcasted_iota(i32, (1, held * chunks), 1)
    same = jax.lax.div(row, i32(chunks)) == jax.lax.div(col, i32(chunks))
    upto = jnp.dot(_ones_where(same & (col <= row)),
                   total.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    within, total, upto = (a.astype(i32) for a in (within, total, upto))
    rank_ref[...] = upto - total + within - 1
    # ``ends_ref``: first the running totals (an expert's last chunk holds
    # its count), then, in its first ``held`` rows, the experts' first tiles
    ends_ref[...] = upto
    counts = ends_ref[pl.ds(chunks - 1, held, stride=chunks), :]
    counts_ref[...] = counts
    tiles_of = jax.lax.div(counts + (tile - 1), i32(tile))
    ends = _running_sum(tiles_of, 0)          # the first tile past each
    ends_ref[pl.ds(0, held), :] = ends - tiles_of
    live_tiles = jnp.max(ends)
    live_ref[0] = live_tiles
    # a tile's expert: the experts but the last that end at or under it
    shape = (held, tile_expert_ref.shape[1])
    ended = ((jax.lax.broadcasted_iota(i32, shape, 1) >= ends[:, :1])
             & (jax.lax.broadcasted_iota(i32, shape, 0) < held - 1))
    tile_expert_ref[...] = jnp.sum(jnp.where(ended, 1, 0).astype(i32),
                                   axis=0, keepdims=True)

    def place(e, dest):
        return jnp.where(loc == e, ends_ref[pl.ds(e, 1), :] * tile
                         + rank_ref[of_expert(e), :], dest)
    dest_ref[...] = jax.lax.fori_loop(0, held, place,
                                      jnp.full(loc.shape, no_row, i32))
    token_ref[...] = jnp.full(token_ref.shape, picks // k, i32)
    row_weight_ref[...] = jnp.zeros(row_weight_ref.shape, jnp.float32)

    row_at = jax.lax.broadcasted_iota(i32, (_LANES, _LANES), 0)
    pick_at = jax.lax.broadcasted_iota(i32, (_LANES, _LANES), 1)

    def rows_block(b, _):
        rows_b = row_at + b * _LANES

        def chunk(c, found):
            pick, weight = found
            hit = dest_ref[pl.ds(c, 1), :] == rows_b
            return (jnp.where(hit, pick_at + c * _LANES, pick),
                    jnp.where(hit, weight_ref[pl.ds(c, 1), :], weight))
        pick, weight = jax.lax.fori_loop(
            0, -(-picks // _LANES), chunk,
            (jnp.full((_LANES, _LANES), picks, i32),
             jnp.zeros((_LANES, _LANES), jnp.float32)))
        # [rows, picks] -> a row of 128 rows: one lane of a row is set
        token_ref[pl.ds(b, 1), :] = jax.lax.div(
            jnp.min(pick.T, axis=0, keepdims=True), i32(k))
        row_weight_ref[pl.ds(b, 1), :] = jnp.sum(weight.T, axis=0,
                                                 keepdims=True)
        return 0
    jax.lax.fori_loop(0, (live_tiles * tile + _LANES - 1) // _LANES,
                      rows_block, 0)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layout_call(local, weight, held, rows, tile, interpret):
    """``(row_token, row_weight, tile_expert, live_tiles, counts, dest
    [T, k])`` of kernel ``moe_layout``.  (Jitted: a call outside any
    program builds its kernel once a shape, not once a call.)"""
    t, k = local.shape
    picks = t * k
    chunks = -(-picks // (8 * _LANES)) * 8
    blocks = -(-rows // _LANES)

    def by_chunks(a, fill):
        # a few KB, padded by the fusion that makes it: no round trip
        # dstpu: ignore[PALLAS004] -- fused into the operand's producer
        return jnp.pad(a.reshape(-1), (0, chunks * _LANES - picks),
                       constant_values=fill).reshape(chunks, _LANES)
    vmem, smem = (pl.BlockSpec(memory_space=m)
                  for m in (pltpu.VMEM, pltpu.SMEM))
    token, row_weight, tile_expert, dest, counts, live = pl.pallas_call(
        functools.partial(_layout_kernel, held=held, tile=tile,
                          picks=picks, k=k),
        in_specs=[vmem, vmem],
        out_specs=[vmem, vmem, vmem, vmem, vmem, smem],
        out_shape=[jax.ShapeDtypeStruct((blocks, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((blocks, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((1, rows // tile), jnp.int32),
                   jax.ShapeDtypeStruct((chunks, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((held, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((held * chunks, _LANES), jnp.int32)] * 2,
        interpret=interpret,
        name="moe_layout",
    )(by_chunks(local, held), by_chunks(weight, 0.0))
    return (token.reshape(-1)[:rows], row_weight.reshape(-1)[:rows],
            tile_expert[0], live[0], counts[:, 0],
            dest.reshape(-1)[:picks].reshape(t, k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _laid_out(local, weight, held, rows, tile, interpret):
    return _layout_call(local, weight, held, rows, tile, interpret)[:5]


def _laid_out_fwd(local, weight, held, rows, tile, interpret):
    *lay, dest = _layout_call(local, weight, held, rows, tile, interpret)
    return tuple(lay), dest


def _laid_out_bwd(held, rows, tile, interpret, dest, given):
    """A pick's weight went to its row and nowhere else."""
    return None, given[1].at[dest].get(mode="fill", fill_value=0.0)


_laid_out.defvjp(_laid_out_fwd, _laid_out_bwd)


def _layout(local: jax.Array, weight: jax.Array, held: int, rows: int,
            tile: int = TILE_ROWS) -> _Layout:
    """``local [T, k]`` — the held experts' local ids, ``held`` for a pick
    that is not dispatched here.  Each expert's picks go to consecutive
    rows from a boundary of the tiles of ``tile`` rows on, experts in
    order, picks in token order.  ONE device operation, kernel
    ``moe_layout``; differentiable in ``weight``."""
    return _Layout(*_laid_out(local, weight.astype(jnp.float32), held, rows,
                              tile, resolve_interpret(None)))


def split_experts(layers: dict):
    """A stack of expert layers ``{.., "moe": {"router", .., "experts"}}``
    as ``(the stack without its experts, the experts)``.  The expert stack
    stays out of a layer scan's ``xs``: sliced per layer it would be
    copied whole, every step, to reach the kernel — :func:`expert_share`
    takes the whole stack and the layer's index instead (``layer=``)."""
    moe = layers["moe"]
    return (dict(layers, moe={k: v for k, v in moe.items()
                              if k != "experts"}), moe["experts"])


@scoped("expert_layout")
def expert_share(experts: dict, u: jax.Array, routing: Routing,
                 num_routed: int, experts_held: Tuple[int, int],
                 row_valid: Optional[jax.Array] = None,
                 pass_rows: Optional[int] = PASS_ROWS,
                 layer: Optional[jax.Array] = None,
                 tile_rows: int = TILE_ROWS
                 ) -> Tuple[jax.Array, jax.Array]:
    """This chip's part of the MoE sublayer's output.

    ``experts``: ``w_gate`` / ``w_up`` ``[held, h, f]``, ``w_down``
    ``[held, f, h]`` — the held experts ``lo .. hi - 1`` of the
    ``num_routed``; ``u [T, h]``; ``routing`` from :func:`route`.
    ``row_valid [T]`` masks rows that carry no token (an idle slot,
    chunk padding): they are routed nowhere and counted nowhere.
    With ``layer`` (an int32 scalar) the weights are EVERY layer's,
    ``[L, held, ..]``, and the kernel reads layer ``layer``'s experts
    where they lie: a layer scan that sliced the stack would copy a
    whole layer's experts every step to hand them to a kernel.
    ``pass_rows`` None is ONE pass over the whole buffer, the form that
    differentiates; ``tile_rows`` (a multiple of ``TILE_ROWS``) is how
    many rows of one expert a step of the grouped product takes.
    Returns ``(y [T, h] in u's type, counters int32 [len(COUNTERS)])``.
    """
    lo, hi = experts_held
    held = hi - lo
    t, h = u.shape
    k = routing.index.shape[1]
    valid = (jnp.ones((t,), bool) if row_valid is None
             else row_valid.astype(bool))[:, None]
    index = routing.index
    weight = jnp.where(valid, routing.weight, 0.0)
    is_held = valid & (index >= lo) & (index < hi)
    is_zero = valid & (index >= num_routed)

    # the row buffer: every held pick and each expert's tile padding
    per_token = min(k, held)
    tile = int(tile_rows)
    rows = t * per_token + held * (tile - 1)
    step = -(-min(int(pass_rows or rows), rows) // tile) * tile
    block = step                   # ONE pass moves its buffer whole
    if pass_rows is not None:      # a pass is whole blocks of whole tiles
        block = min(-(-BLOCK_ROWS // tile) * tile, step)
        step = -(-step // block) * block
    rows = -(-rows // step) * step
    lay = _layout(jnp.where(is_held, index - lo, held).astype(jnp.int32),
                  weight, held, rows, tile)
    u_pad = jnp.concatenate([u, jnp.zeros((1, h), u.dtype)])
    first = 0                      # this layer's first expert in the stack
    if layer is not None:
        experts = {n: w.reshape(-1, *w.shape[2:])
                   for n, w in experts.items()}
        first = layer * held

    def activation(gate, up):
        return (jax.nn.silu(gate.astype(jnp.float32))
                * up.astype(jnp.float32)).astype(u.dtype)

    def weighted_add(y, token, w_row, out):
        # a dead tile's rows are undefined, a padding row's are zero rows
        # of a real expert: both are no pick, and 0 * garbage is not 0
        out = jnp.where((token < t)[:, None],
                        out.astype(jnp.float32) * w_row[:, None], 0.0)
        return y.at[token].add(out, mode="drop")

    def part(b, a):
        return jax.lax.dynamic_slice_in_dim(a, b * block, block)

    def put(b, a, rows_b):
        return jax.lax.dynamic_update_slice_in_dim(a, rows_b, b * block, 0)

    def one_pass(p, carry):
        y, moved = carry
        at, tile_at = p * step, p * (step // tile)
        token = jax.lax.dynamic_slice_in_dim(lay.row_token, at, step)
        w_row = jax.lax.dynamic_slice_in_dim(lay.row_weight, at, step)
        te = first + jax.lax.dynamic_slice_in_dim(
            lay.tile_expert, tile_at, step // tile)
        live = jnp.clip(lay.live_tiles - tile_at, 0, step // tile)
        # the row-wise work follows the live tiles as the products do: it
        # walks the blocks that hold one.  Rows past the last of them are
        # never written and never read, in any of the pass's buffers.  The
        # form that differentiates takes its one block in one step
        blocks = 1 if pass_rows is None else -(-(live * tile) // block)

        def walk(body, init):
            if pass_rows is None:
                return body(0, init)
            return jax.lax.fori_loop(0, blocks, body, init)
        xs = walk(lambda b, xs: put(b, xs, u_pad[part(b, token)]),
                  jnp.zeros((step, h), u.dtype))
        gate = grouped_matmul(xs, experts["w_gate"], te, live)
        up = grouped_matmul(xs, experts["w_up"], te, live)
        with jax.named_scope("experts"):
            mid = walk(lambda b, gate: put(b, gate, activation(
                part(b, gate), part(b, up))), gate)
        out = grouped_matmul(mid, experts["w_down"], te, live)
        y = walk(lambda b, y: weighted_add(
            y, part(b, token), part(b, w_row), part(b, out)), y)
        return y, moved + blocks * block

    carry = jnp.zeros((t, h), jnp.float32), jnp.int32(0)
    if rows == step:
        y, moved = one_pass(0, carry)
    else:
        y, moved = jax.lax.fori_loop(
            0, -(-(lay.live_tiles * tile) // step), one_pass, carry)
    y = y + u.astype(jnp.float32) * jnp.sum(
        jnp.where(is_zero, weight, 0.0), axis=-1, keepdims=True)
    counters = jnp.stack([
        valid.sum(dtype=jnp.int32) * k, is_held.sum(dtype=jnp.int32),
        is_zero.sum(dtype=jnp.int32), lay.counts.max(),
        (lay.counts > 0).sum(dtype=jnp.int32), moved])
    return y.astype(u.dtype), counters


def init_experts(rng, held: int, d_model: int, d_ff: int, stddev: float,
                 out_stddev: float, dtype) -> dict:
    """The held experts' SwiGLU weights, stacked on a leading axis."""
    kg, ku, kd = jax.random.split(rng, 3)

    def normal(key, shape, std):
        return (std * jax.random.normal(key, shape)).astype(dtype)
    return {"w_gate": normal(kg, (held, d_model, d_ff), stddev),
            "w_up": normal(ku, (held, d_model, d_ff), stddev),
            "w_down": normal(kd, (held, d_ff, d_model), out_stddev)}
