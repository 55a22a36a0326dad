"""Selected-context latent attention over the paged pools: the indexer's
score kernel, the exact top-k selection, and latent attention over the
selected tokens only.

A model with learned sparse attention (``models/sparse_latent_moe.py``)
keeps TWO kinds of row a token under one block table: the latent row
``[c | k_rope | 0]`` of ``paged_decode_attention.py``'s latent kernel, and
in the layers that compute a selection an INDEXER KEY of ``D`` values.
A query row scores every earlier token against its indexer key,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s]),

keeps the ``k`` largest, and attends to those tokens' latent rows only.

  * :func:`dsa_index_scores` — Pallas, kernel ``dsa_index_scores``: the
    page walk of the latent kernel over the INDEXER pool for a walker of
    query rows (a decode slot's one position x every index head; a tile
    of a chunk's positions, head by head), writing the float32 score
    plane ``[rows, context]``.  Dead steps (past the walker's own length)
    start no DMA, so a short context does not pay for the table's width.
    A page group whose pool blocks are CONSECUTIVE — a document prefilled
    into a fresh pool, the shared prefix of every request that follows —
    is ONE DMA instead of one a page (``paged_decode_attention.py``'s
    ``_grouped_tables`` / ``_fetch_group`` with the whole group as the
    run): at 4 KB a page the walk is bound by descriptors, not bytes.
  * :func:`kth_largest` / :func:`select_positions` — plain XLA, exact:
    the ``k``-th largest score of a row by bisection over the float's
    bits (32 counting passes, no sort), then for decode rows the
    positions at or over it, compacted in order with cumulative sums and
    one-hot products (no sort, no scatter).  Scores equal to the
    threshold fill the set in position order (a chunk row's mask keeps
    them all); nothing is approximated.  A chunk's passes run through
    :func:`narrowed`: over a quarter, a half or all of the plane by the
    chunk's own context.
  * :func:`gathered_latent_attention` — plain XLA: a decode row's
    selected tokens are gathered by TOKEN from the latent pool (one
    gather of ``[slots, k]`` rows: XLA's gather moves a 1,280-byte row in
    a fifth of the time a Pallas descriptor a row costs) and attended in
    the absorbed form.
  * :func:`dsa_sparse_prefill_attention` — Pallas, kernel
    ``dsa_sparse_attention``: a chunk's rows share the pages they fetch.
    The latent kernel's walk for tiles of the chunk's positions, with the
    selection as a per-row mask built in the kernel from the score plane
    and each row's threshold — the union of a tile's selections is most
    of the context, so fetching by token would fetch every page many
    times.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from .paged_decode_attention import (LANES, MASK_VALUE, _MLA_TILE_ROWS,
                                     _VMEM_LIMIT_BYTES, _grouped_tables,
                                     _mla_pages_per_program, _walk_step,
                                     _walkers)

#: chunk positions one walker of the index kernel scores, and the keys
#: (pages x block) one of its grid steps covers
_INDEX_TILE = 128
_INDEX_KEYS = 2048
#: positions a block of the selection's compaction covers
_COMPACT = 128


def _index_pages(npages: int, block: int, override: Optional[int]) -> int:
    """Pages to a grid step of the index kernel: ``_INDEX_KEYS`` keys'
    worth, a power of two no wider than the table needs."""
    pp = override or max(1, _INDEX_KEYS // block)
    return min(pp, 1 << (npages - 1).bit_length())


def plane_width(npages: int, block: int,
                pages_per_program: Optional[int] = None) -> int:
    """Columns of the score plane :func:`dsa_index_scores` returns for a
    table of ``npages`` pages: whole grid steps' keys."""
    pp = _index_pages(npages, block, pages_per_program)
    return -(-npages // pp) * pp * block


# ---------------------------------------------------------------------------
# the indexer's scores
# ---------------------------------------------------------------------------
def _index_kernel(meta_ref, bt_ref, run_ref, q_ref, w_ref, pool_hbm, o_ref,
                  buf, sem, *, block, pp, heads, tp):
    """One walker's scores against one page group of the indexer pool:
    ``sum_j w_j relu(q_j . k)`` in float32.  A decode walker (``tp`` 1) is
    one contraction of all heads' rows and a sum over them; a chunk
    walker goes head by head over its ``tp`` positions."""
    i, g = pl.program_id(0), pl.program_id(1)
    nwalk = pl.num_programs(0)

    @pl.when(g < meta_ref[2, i])
    def _live():
        half = _walk_step(i, g, nwalk, meta_ref, bt_ref, run_ref, pool_hbm,
                          buf, sem, block=block, pp=pp, run=pp)
        keys = buf[half].reshape(pp * block, buf.shape[-1])
        dims = (((1,), (1,)), ((), ()))
        if tp == 1:
            s = jax.lax.dot_general(q_ref[...], keys, dims,
                                    preferred_element_type=jnp.float32)
            o_ref[...] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0,
                                 keepdims=True)
        else:
            w = w_ref[...]                                    # [tp, heads]
            acc = jnp.zeros(o_ref.shape, jnp.float32)
            for j in range(heads):
                s = jax.lax.dot_general(q_ref[j], keys, dims,
                                        preferred_element_type=jnp.float32)
                acc = acc + jnp.maximum(s, 0.0) * w[:, j:j + 1]
            o_ref[...] = acc


def dsa_index_scores(q_idx, w_idx, ipool, base, total, block_tables, *,
                     interpret: Optional[bool] = None,
                     pages_per_program: Optional[int] = None):
    """The indexer's scores of ``C`` query positions a slot against the
    slot's own context.  ``q_idx [B, C, J, D]`` (``J`` index heads),
    ``w_idx [B, C, J]`` float32 head weights, ``ipool [num_blocks, block,
    D]`` indexer keys; slot ``b``'s positions are ``base[b] ..`` and it
    holds ``total[b]`` keys (0 = dead).  Returns float32 ``[B, C, P]``
    with ``P >= pages * block`` a whole number of grid steps' keys;
    entry ``[b, c, s]`` is defined for ``s < min(total[b], the last
    position of c's tile + 1)`` and undefined (never NaN-safe: mask it by
    position) elsewhere."""
    b, c, heads, d = q_idx.shape
    block = ipool.shape[1]
    interpret = resolve_interpret(interpret)
    if ipool.ndim != 3 or ipool.shape[2] != d:
        raise ValueError(f"dsa_index_scores: the indexer pool must be "
                         f"[num_blocks, block, {d}], got {ipool.shape}")
    if not interpret and d % LANES:
        raise ValueError(f"dsa_index_scores: compiled for the TPU, an "
                         f"indexer key ({d}) must be whole {LANES}-lane "
                         f"tiles")
    npages = block_tables.shape[1]
    pp = _index_pages(npages, block, pages_per_program)
    keys = pp * block
    tp = 1 if c == 1 else min(c, _INDEX_TILE)
    while c % tp:
        tp -= 1
    ntile = c // tp
    nwalk = b * ntile
    base = jnp.asarray(base, jnp.int32).reshape(b)
    total = jnp.asarray(total, jnp.int32).reshape(b)
    tables, runs = _grouped_tables(block_tables, total, pp, block, pp)
    ngroups = runs.shape[1]
    meta = _walkers(base, total, ntile, tp, keys, ngroups)
    dtype = ipool.dtype
    if tp == 1:
        q = q_idx.astype(dtype).reshape(nwalk, heads, d)
        w = w_idx.astype(jnp.float32).reshape(nwalk, heads, 1)
        q_spec = pl.BlockSpec((None, heads, d), lambda i, g, *_: (i, 0, 0))
        w_spec = pl.BlockSpec((None, heads, 1), lambda i, g, *_: (i, 0, 0))
    else:
        # head-major inside a tile: a head's rows are one operand
        q = q_idx.astype(dtype).reshape(b, ntile, tp, heads, d).transpose(
            0, 1, 3, 2, 4).reshape(nwalk, heads, tp, d)
        w = w_idx.astype(jnp.float32).reshape(nwalk, tp, heads)
        q_spec = pl.BlockSpec((None, heads, tp, d),
                              lambda i, g, *_: (i, 0, 0, 0))
        w_spec = pl.BlockSpec((None, tp, heads), lambda i, g, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_index_kernel, block=block, pp=pp, heads=heads,
                          tp=tp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nwalk, ngroups),
            in_specs=[q_spec, w_spec, pl.BlockSpec(memory_space=pl.ANY)],
            # a dead step writes nothing back: its block is the walker's
            # last live one
            out_specs=pl.BlockSpec(
                (None, tp, keys),
                lambda i, g, meta, *_: (i, 0, jnp.maximum(
                    jnp.minimum(g, meta[2, i] - 1), 0))),
            scratch_shapes=[pltpu.VMEM((2, pp, block, d), dtype),
                            pltpu.SemaphoreType.DMA((2, 1))],
        ),
        out_shape=jax.ShapeDtypeStruct((nwalk, tp, ngroups * keys),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dsa_index_scores",
    )(meta, tables, runs, q, w, ipool)
    return out.reshape(b, c, ngroups * keys)


def index_scores_reference(q_idx, w_idx, ipool, block_tables):
    """float32 jnp reference of :func:`dsa_index_scores` over the whole
    table (no masking): ``[B, C, pages * block]``."""
    npages, block = block_tables.shape[1], ipool.shape[1]

    def one(q, w, table):
        k = ipool[table].reshape(npages * block, -1).astype(jnp.float32)
        s = jnp.einsum("cjd,sd->cjs", q.astype(jnp.float32), k)
        return jnp.einsum("cjs,cj->cs", jnp.maximum(s, 0.0),
                          w.astype(jnp.float32))
    return jax.vmap(one)(q_idx, w_idx, block_tables)


# ---------------------------------------------------------------------------
# the selection: exact top-k, no sort
# ---------------------------------------------------------------------------
def _ordered_bits(x):
    """float32 -> uint32 with the same order (``-inf`` lowest, and above
    0: no score's bits are 0)."""
    i = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def _from_ordered_bits(u):
    """The float32 whose :func:`_ordered_bits` are ``u``."""
    i = jax.lax.bitcast_convert_type(u ^ jnp.uint32(1 << 31), jnp.int32)
    return jax.lax.bitcast_convert_type(
        i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def kth_largest(scores, visible, k: int):
    """``scores [R, P]`` float32, ``visible [R, P]`` bool -> ``[R]``
    float32: a row's ``k``-th largest visible score, ``-inf`` where it
    has at most ``k`` — so ``visible & (scores >= it)`` is the row's top
    ``k``, all of them where they are few.  Exact: found bit by bit over
    the float's ordered bits, 32 counting passes over the plane and no
    sort; scores equal to it are all kept.  Entries that are not
    ``visible`` may hold anything."""
    bits = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))

    def bit(at, floor):
        cand = floor | (jnp.uint32(1) << (31 - at).astype(jnp.uint32))
        enough = jnp.sum(bits >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, floor)
    floor = jax.lax.fori_loop(0, 32, bit,
                              jnp.zeros((scores.shape[0],), jnp.uint32))
    few = jnp.sum(visible, axis=1) <= k
    return jnp.where(few, -jnp.inf, _from_ordered_bits(floor))


def narrowed(width: int, longest, fn):
    """``fn(w)`` at the narrowest ``w`` of a quarter, a half or all of
    ``width`` columns that still holds a context of ``longest`` tokens (a
    traced scalar): a chunk's 32 counting passes over ``[chunk, width]``
    run over as much of its score plane as its own context fills, not
    over what a table can hold.  ``fn`` returns the same shapes at every
    ``w``.  A conditional: what runs inside it is not fused with what
    feeds it, so it pays only where the passes dominate — a chunk's 512
    rows, not a decode lane's 32."""
    widths = [-(-width // n) for n in (4, 2, 1)]
    return jax.lax.switch(
        jnp.searchsorted(jnp.asarray(widths[:-1], jnp.int32), longest),
        [functools.partial(fn, w) for w in widths])


def select_positions(scores, visible, k: int):
    """``(positions [R, k] int32, count [R] int32)``: the positions of a
    row's ``k`` largest visible scores in ascending order, the first
    ``count`` of them valid (``count < k`` where the row sees fewer).
    Exact and sort-free: :func:`kth_largest`, then the chosen positions
    compacted block by block — a block's count places it among the
    outputs, a one-hot product fetches its running count, and a compare
    finds the position inside it."""
    r, p = scores.shape
    pad = -p % _COMPACT
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad)))
        visible = jnp.pad(visible, ((0, 0), (0, pad)))
    kth = kth_largest(scores, visible, k)[:, None]
    over = visible & (scores > kth)
    # scores equal to the k-th largest fill what the larger ones leave,
    # in position order
    tie = visible & (scores == kth)
    room = k - jnp.sum(over, axis=1, keepdims=True)
    chosen = over | (tie & (jnp.cumsum(tie, axis=1) <= room))
    nb = (p + pad) // _COMPACT
    within = jnp.cumsum(chosen.reshape(r, nb, _COMPACT).astype(jnp.int32),
                        axis=-1)                   # running count in a block
    upto = jnp.cumsum(within[..., -1], axis=-1)    # chosen through block b
    out = jnp.arange(k, dtype=jnp.int32)
    blk = jnp.minimum(jnp.sum(upto[:, None, :] <= out[None, :, None],
                              axis=-1), nb - 1)    # the block output j is in
    onehot = blk[..., None] == jnp.arange(nb, dtype=jnp.int32)[None, None]
    before = jnp.sum(jnp.where(
        onehot, (upto - within[..., -1])[:, None, :], 0), axis=-1)
    # counts are at most 128: exact in bfloat16, one term a sum
    running = jnp.einsum("rjb,rbc->rjc", onehot.astype(jnp.bfloat16),
                         within.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    rank = (out[None] - before + 1).astype(jnp.float32)
    inside = jnp.sum(running < rank[..., None], axis=-1).astype(jnp.int32)
    count = jnp.minimum(upto[:, -1], k)
    positions = blk * _COMPACT + jnp.minimum(inside, _COMPACT - 1)
    return jnp.where(out[None] < count[:, None], positions, 0), count


def pool_rows_of(positions, block_tables, block: int):
    """The flat pool row (``block id * block + offset``, before any
    layer's offset) of each selected position: ``positions [B, k]`` of
    slot ``b`` through ``block_tables[b]``.  The table is read with a
    one-hot product in exact float32 — 65 k scalar gathers cost more
    than the selection itself."""
    b, npages = block_tables.shape
    group = max(1, _COMPACT // block)
    ng = -(-npages // group)
    tables = jnp.pad(block_tables, ((0, 0), (0, ng * group - npages)))
    page = positions // block
    onehot = (page // group)[..., None] == jnp.arange(
        ng, dtype=jnp.int32)[None, None]
    cand = jnp.einsum("bjg,bgc->bjc", onehot.astype(jnp.float32),
                      tables.reshape(b, ng, group).astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    pick = (page % group)[..., None] == jnp.arange(
        group, dtype=jnp.int32)[None, None]
    bid = jnp.sum(jnp.where(pick, cand, 0.0), axis=-1).astype(jnp.int32)
    return bid * block + positions % block


# ---------------------------------------------------------------------------
# attention over the selected tokens
# ---------------------------------------------------------------------------
def gathered_latent_attention(q_lat, q_rope, pool, rows, count,
                              sm_scale: float):
    """Decode rows over their selected tokens, absorbed form, plain XLA.
    ``q_lat [B, H, R]``, ``q_rope [B, H, Dr]``; ``pool [blocks, block,
    W]`` latent rows ``[c | k_rope | 0]``; ``rows [B, k]`` flat pool rows
    of slot ``b``'s selected tokens, the first ``count[b]`` valid (0 =
    dead slot: zero rows back).  Returns ``o_lat [B, H, R]``."""
    b, h, lat = q_lat.shape
    lanes = pool.shape[-1]
    picked = pool.reshape(-1, lanes)[rows]                   # [B, k, W]
    # the query laid out like a pool row: [q_lat | q_rope | 0]
    q = jnp.concatenate(
        [q_lat, q_rope,
         jnp.zeros((b, h, lanes - lat - q_rope.shape[-1]), q_lat.dtype)],
        axis=-1).astype(pool.dtype)
    s = jnp.einsum("bhd,bsd->bhs", q, picked,
                   preferred_element_type=jnp.float32) * sm_scale
    valid = jnp.arange(rows.shape[1])[None] < count[:, None]
    s = jnp.where(valid[:, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    # stale rows of masked entries are zeroed, not down-weighted
    c = jnp.where(valid[..., None], picked[..., :lat], 0)
    o = jnp.einsum("bhs,bsr->bhr", p.astype(pool.dtype), c,
                   preferred_element_type=jnp.float32)
    return jnp.where((count > 0)[:, None, None], o, 0.0).astype(q_lat.dtype)


def _sparse_kernel(meta_ref, bt_ref, run_ref, q_ref, plane_ref, floor_ref,
                   spread_ref, pool_hbm, o_ref, buf, m_scr, l_scr, acc_scr,
                   sem, *, sm_scale, block, pp, lat):
    """``paged_decode_attention._mla_kernel``'s step for a tile of a
    chunk's positions, with the selection as a mask: position ``t`` of
    the tile sees key ``s`` only where ``plane[t, s] >= floor[t]``.  The
    mask is made for the tile's ``tp`` positions — selection, causality
    and the slot's length at once — as an additive bias, and one product
    with ``spread`` (row ``r`` -> position ``r // H``) gives every query
    row its position's: the ``[tp * H, keys]`` score tile is touched by
    the scale-and-bias and the online softmax alone (the vector unit, not
    the MXU, bounds a step).  A row whose keys so far are all masked
    accumulates finite garbage that the first chosen key's ``alpha = 0``
    wipes; rows that never see one (past the chunk's length) return it,
    and callers ignore them."""
    i, g = pl.program_id(0), pl.program_id(1)
    nwalk, ng = pl.num_programs(0), pl.num_programs(1)
    keys = pp * block
    tp = plane_ref.shape[0]
    base, total, live_groups = meta_ref[0, i], meta_ref[1, i], meta_ref[2, i]

    @pl.when(g < live_groups)
    def _live():
        half = _walk_step(i, g, nwalk, meta_ref, bt_ref, run_ref, pool_hbm,
                          buf, sem, block=block, pp=pp, run=pp)

        @pl.when(g == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        pos = g * keys + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        qpos = base + jax.lax.broadcasted_iota(jnp.int32, (tp, 1), 0)
        seen = ((plane_ref[...] >= floor_ref[...]) & (pos <= qpos)
                & (pos < total))                               # [tp, keys]
        bias = jax.lax.dot_general(
            spread_ref[...],
            jnp.where(seen, 0.0, MASK_VALUE).astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [R, keys]
        v_valid = g * keys + jax.lax.broadcasted_iota(
            jnp.int32, (keys, 1), 0) < total                   # [keys, 1]
        # stale or unfetched rows past the length: zeroed, not
        # down-weighted (whatever they hold must not reach a product)
        rows = buf[half].reshape(keys, buf.shape[-1])
        rows = jnp.where(v_valid, rows, jnp.zeros_like(rows))
        s = jax.lax.dot_general(q_ref[...], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale + bias
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :lat], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(g == ng - 1)
    def _out():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = jnp.where(live_groups > 0, inv * acc_scr[...],
                               0.0).astype(o_ref.dtype)


def dsa_sparse_prefill_attention(q_lat, q_rope, pool, plane, floor, base,
                                 chunk_len, block_table, sm_scale: float,
                                 interpret: Optional[bool] = None,
                                 pages_per_program: Optional[int] = None):
    """Latent causal chunk of ONE slot over each row's selected tokens:
    ``q_lat [C, H, R]``, ``q_rope [C, H, Dr]`` at positions ``base ..``;
    ``plane [C, P]`` the rows' indexer scores (:func:`dsa_index_scores`)
    and ``floor [C]`` each row's threshold (:func:`kth_largest`): row
    ``t`` attends key ``s <= base + t`` where ``plane[t, s] >=
    floor[t]``.  The chunk's own rows are already in the pool.  Rows at
    or past ``chunk_len`` come back as finite garbage or zeros; callers
    ignore them."""
    c, h, lat = q_lat.shape
    rope = q_rope.shape[-1]
    block, lanes = pool.shape[1:]
    interpret = resolve_interpret(interpret)
    if pool.ndim != 3 or lanes < lat + rope:
        raise ValueError(
            f"dsa_sparse_prefill_attention: the latent pool must be "
            f"[num_blocks, block, >= {lat} + {rope}], got {pool.shape}")
    if not interpret and (lat % LANES or lanes % LANES):
        raise ValueError(
            f"dsa_sparse_prefill_attention: compiled for the TPU, the "
            f"latent part ({lat}) and the whole pool row ({lanes}) must be "
            f"whole {LANES}-lane tiles")
    tp = max(1, min(c, _MLA_TILE_ROWS // h))
    while c % tp:
        tp -= 1
    ntile, rows = c // tp, tp * h
    npages = block_table.shape[0]
    pp = _mla_pages_per_program(block, lanes, lat, pool.dtype.itemsize, rows,
                                npages, pages_per_program)
    keys = pp * block
    if plane.ndim != 2 or plane.shape[0] != c \
            or plane.shape[1] < npages * block:
        raise ValueError(
            f"dsa_sparse_prefill_attention: the score plane must be "
            f"[{c}, >= {npages * block}], got {plane.shape}")
    base = jnp.asarray(base, jnp.int32).reshape(1)
    total = base + jnp.asarray(chunk_len, jnp.int32)
    tables, runs = _grouped_tables(block_table[None], total, pp, block, pp)
    ngroups = runs.shape[1]
    meta = _walkers(base, total, ntile, tp, keys, ngroups)
    dtype = pool.dtype
    q = jnp.concatenate(
        [q_lat.astype(dtype), q_rope.astype(dtype),
         jnp.zeros((c, h, lanes - lat - rope), dtype)], axis=-1
    ).reshape(ntile, rows, lanes)
    spread = (jnp.arange(rows, dtype=jnp.int32)[:, None] // h
              == jnp.arange(tp, dtype=jnp.int32)[None]
              ).astype(jnp.bfloat16)                            # [R, tp]

    def qspec(width):
        return pl.BlockSpec((None, rows, width), lambda i, g, *_: (i, 0, 0))

    def fixed(shape):
        return pl.BlockSpec(shape, lambda i, g, *_: (0, 0))

    out = pl.pallas_call(
        functools.partial(_sparse_kernel, sm_scale=sm_scale, block=block,
                          pp=pp, lat=lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ntile, ngroups),
            in_specs=[qspec(lanes),
                      # a dead step fetches no new block of the plane
                      pl.BlockSpec((tp, keys), lambda i, g, meta, *_: (
                          i, jnp.maximum(jnp.minimum(g, meta[2, i] - 1),
                                         0))),
                      pl.BlockSpec((tp, 1), lambda i, g, *_: (i, 0)),
                      fixed((rows, tp)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec(lat),
            scratch_shapes=[
                pltpu.VMEM((2, pp, block, lanes), dtype),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, lat), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 1))],
        ),
        out_shape=jax.ShapeDtypeStruct((ntile, rows, lat), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="dsa_sparse_attention",
    )(meta, tables, runs, q, plane.astype(jnp.float32),
      floor.astype(jnp.float32).reshape(c, 1), spread, pool)
    return out.reshape(c, h, lat).astype(q_lat.dtype)
