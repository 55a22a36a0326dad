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
``moe_grouped_matmul``), a forward-only kernel: the block that uses it
serves and does not train (``LatentMoELM.training_refusal``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.overlap import scoped
from ..ops import resolve_interpret

#: rows to a tile of the grouped product: one bf16 operand tile's sublanes
TILE_ROWS = 16
#: rows of the buffer one pass of the grouped product walks (a balanced
#: batch of the serving cell's 560 rows needs a quarter of it)
PASS_ROWS = 1024
#: scoped VMEM the grouped product asks for (two halves of one weight
#: block of up to ``_WEIGHT_BLOCK_BYTES``, the row tiles, the result)
_VMEM_LIMIT_BYTES = 48 << 20
_WEIGHT_BLOCK_BYTES = 8 << 20
#: the counters :func:`expert_share` returns, in order
COUNTERS = ("moe_picks", "moe_picks_held", "moe_picks_zero",
            "moe_rows_max_expert", "moe_experts_touched")


class Routing(NamedTuple):
    index: jax.Array       # [T, k] int32 in [0, E + Z)
    weight: jax.Array      # [T, k] float32 = scale * (renormalised) score


@scoped("router")
def route(u: jax.Array, router_kernel: jax.Array,
          bias: Optional[jax.Array], k: int, scale: float,
          scoring: str = "softmax", renormalize: bool = False) -> Routing:
    """``u [T, h]`` -> the ``k`` picks of every row, in the gate's form
    the model's configuration names.  Scores are float32 over all
    ``E + Z`` outputs (the operands keep their stored type; products
    accumulate in float32): a ``softmax`` over the outputs, or each
    output's own ``sigmoid``.  The ``k`` chosen are the top ``k`` of
    ``score + bias`` (``bias`` None: of the score).  A pick's weight is
    ``scale * score``, or with ``renormalize`` ``scale * score / (the
    row's chosen scores' sum + 1e-20)``."""
    logits = jnp.einsum("th,he->te", u, router_kernel.astype(u.dtype),
                        preferred_element_type=jnp.float32)
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


def _tile_n(k_dim: int, n: int, itemsize: int) -> int:
    """Columns of one weight block ``[K, tn]``: all of them, or the widest
    128-multiple divisor of ``n`` whose block fits the budget."""
    if k_dim * n * itemsize <= _WEIGHT_BLOCK_BYTES or n % 128:
        return n
    tn = n
    while tn % 256 == 0 and k_dim * tn * itemsize > _WEIGHT_BLOCK_BYTES:
        tn //= 2
    return tn


@scoped("experts")
def grouped_matmul(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
                   live_tiles: jax.Array,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``x [M, K]`` in tiles of ``TILE_ROWS`` rows, tile ``t`` wholly of
    expert ``tile_expert[t]``; ``w [E, K, N]``.  Returns ``[M, N]`` in
    ``x``'s type.  Only the first ``live_tiles`` tiles are computed: a
    dead tile starts no DMA (its blocks are the last live tile's) and
    leaves its rows of the result UNDEFINED — the caller masks them.

    Grid ``(N blocks, tiles)``, tiles innermost: consecutive tiles of one
    expert reuse the weight block already in VMEM, so each held expert's
    weights cross HBM once per call however its rows split over tiles."""
    m, k_dim = x.shape
    n = w.shape[2]
    if m % TILE_ROWS:
        raise ValueError(f"grouped_matmul: {m} rows are not whole tiles of "
                         f"{TILE_ROWS}")
    tn = _tile_n(k_dim, n, w.dtype.itemsize)
    tiles = m // TILE_ROWS

    def kernel(te_ref, live_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < live_ref[0])
        def _live():
            o_ref[...] = jnp.dot(
                x_ref[...], w_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                # a dead tile's blocks are the last live tile's: no DMA
                pl.BlockSpec(
                    (TILE_ROWS, k_dim), lambda j, t, te, live: (
                        jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), 0)),
                pl.BlockSpec(
                    (None, k_dim, tn), lambda j, t, te, live: (
                        te[jnp.minimum(t, jnp.maximum(live[0] - 1, 0))], 0,
                        j)),
            ],
            out_specs=pl.BlockSpec(
                (TILE_ROWS, tn), lambda j, t, te, live: (
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


class _Layout(NamedTuple):
    """Where every held pick sits in the row buffer."""
    row_token: jax.Array     # [M] int32: the pick's token, T for no pick
    row_weight: jax.Array    # [M] float32
    tile_expert: jax.Array   # [M / TILE_ROWS] int32 (local expert id)
    live_tiles: jax.Array    # int32 scalar
    counts: jax.Array        # [held] int32 rows of each held expert


def _layout(local: jax.Array, weight: jax.Array, held: int, rows: int
            ) -> _Layout:
    """``local [T, k]`` — the held experts' local ids, ``held`` for a pick
    that is not dispatched here.  Each expert's picks go to consecutive
    rows from a tile boundary on, experts in order, picks in token
    order."""
    t, k = local.shape
    flat = local.reshape(-1)
    onehot = flat[:, None] == jnp.arange(held, dtype=flat.dtype)[None, :]
    counts = onehot.sum(0, dtype=jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1,
        jnp.minimum(flat, held - 1)[:, None], axis=1)[:, 0]
    tiles_of = -(-counts // TILE_ROWS)
    first_tile = jnp.cumsum(tiles_of) - tiles_of
    dest = jnp.where(
        flat < held,
        first_tile[jnp.minimum(flat, held - 1)] * TILE_ROWS + rank, rows)
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.full((rows,), t, jnp.int32).at[dest].set(
        token, mode="drop")
    row_weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        weight.reshape(-1), mode="drop")
    tile_expert = jnp.clip(
        jnp.searchsorted(jnp.cumsum(tiles_of),
                         jnp.arange(rows // TILE_ROWS, dtype=jnp.int32),
                         side="right"), 0, held - 1).astype(jnp.int32)
    return _Layout(row_token, row_weight, tile_expert, tiles_of.sum(),
                   counts)


@scoped("expert_layout")
def expert_share(experts: dict, u: jax.Array, routing: Routing,
                 num_routed: int, experts_held: Tuple[int, int],
                 row_valid: Optional[jax.Array] = None,
                 pass_rows: int = PASS_ROWS,
                 layer: Optional[jax.Array] = None
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
    rows = t * per_token + held * (TILE_ROWS - 1)
    step = -(-min(int(pass_rows), rows) // TILE_ROWS) * TILE_ROWS
    rows = -(-rows // step) * step
    lay = _layout(jnp.where(is_held, index - lo, held).astype(jnp.int32),
                  weight, held, rows)
    u_pad = jnp.concatenate([u, jnp.zeros((1, h), u.dtype)])
    first = 0                      # this layer's first expert in the stack
    if layer is not None:
        experts = {n: w.reshape(-1, *w.shape[2:])
                   for n, w in experts.items()}
        first = layer * held

    def one_pass(p, y):
        at, tile_at = p * step, p * (step // TILE_ROWS)
        token = jax.lax.dynamic_slice_in_dim(lay.row_token, at, step)
        w_row = jax.lax.dynamic_slice_in_dim(lay.row_weight, at, step)
        te = first + jax.lax.dynamic_slice_in_dim(
            lay.tile_expert, tile_at, step // TILE_ROWS)
        live = jnp.clip(lay.live_tiles - tile_at, 0, step // TILE_ROWS)
        xs = u_pad[token]
        gate = grouped_matmul(xs, experts["w_gate"], te, live)
        up = grouped_matmul(xs, experts["w_up"], te, live)
        with jax.named_scope("experts"):
            mid = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(u.dtype)
        out = grouped_matmul(mid, experts["w_down"], te, live)
        # a dead tile's rows are undefined, a padding row's are zero rows
        # of a real expert: both are no pick, and 0 * garbage is not 0
        out = jnp.where((token < t)[:, None],
                        out.astype(jnp.float32) * w_row[:, None], 0.0)
        return y.at[token].add(out, mode="drop")

    y = jnp.zeros((t, h), jnp.float32)
    if rows == step:
        y = one_pass(0, y)
    else:
        y = jax.lax.fori_loop(
            0, -(-(lay.live_tiles * TILE_ROWS) // step), one_pass, y)
    y = y + u.astype(jnp.float32) * jnp.sum(
        jnp.where(is_zero, weight, 0.0), axis=-1, keepdims=True)
    counters = jnp.stack([
        valid.sum(dtype=jnp.int32) * k, is_held.sum(dtype=jnp.int32),
        is_zero.sum(dtype=jnp.int32), lay.counts.max(),
        (lay.counts > 0).sum(dtype=jnp.int32)])
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
