"""Batched paged attention — the Pallas TPU kernel behind serving.

A continuous-batching server holds no contiguous per-sequence cache:
sequences join and leave the batch between iterations, their lengths
diverge, and their KV lives in fixed-size blocks of a shared pool
indexed through per-sequence block tables (PagedAttention, Kwon et al.
SOSP '23; `inference/serving/` builds the allocator).  ONE kernel
serves both lanes of the mixed step: decode is a one-query chunk per
slot, chunked prefill is a C-query causal chunk of one slot.

Pool layout — what the Mosaic compiler accepts.  A DMA slab must be a
whole number of 128-lane tiles, so the pool is TOKEN-MAJOR with every
kv head's features side by side in the lane dimension:

    pool_k / pool_v   [num_blocks, block, Hkv * De]
    k_scale / v_scale [num_blocks, Hkv, 1, block]   f32 (quantized only)

``De`` is the stored width of one head's row: ``D`` (bf16/f32/int8) or
``D // 2`` (packed int4).  The kernel walks the lane dimension in
chunks of ``W = lcm(De, 128)`` lanes — a PACK of ``W // De`` kv heads
(2 at head_dim 64, 1 at 128, 4 at 96) — and fetches one ``[block, W]``
slab per (page, pack).  Scale rows are stored lane-major, one
``[1, block]`` row per (page, head), so they broadcast over score
columns.  Compiled for the TPU this needs ``Hkv * De`` to be a multiple
of ``W`` and, for a quantized pool, ``block % 128 == 0``; the
interpreter (CPU tests) takes any shape.

Kernel design:

  * grid ``(slot, pack, page_group)`` with MULTIPLE pages per program;
    pages past a slot's valid prefix are never fetched (their DMA is
    predicated off), so a short sequence's ragged tail costs no HBM
    traffic.
  * DOUBLE-BUFFERED manual block fetches: the pools stay in HBM
    (``memory_space=ANY``); while page group *g* is consumed, the next
    grid position's group (next group, next pack, next slot) is already
    in flight into the other half of the VMEM scratch.
  * the heads of a pack share one MXU contraction: the wrapper lays the
    pack's queries out BLOCK-DIAGONALLY (head j's rows are non-zero only
    in head j's lane window), so ``Q x slab^T`` yields every head's
    scores with no in-kernel lane slicing; the off-diagonal output
    windows are discarded by the wrapper.  GQA query groups and prefill
    chunk rows are simply more rows.
  * FUSED DEQUANT: an int8 / packed-int4 pool crosses HBM compressed;
    the per-row scales are applied in the SCORE domain (``(q . k_int) *
    k_scale`` and ``(p * v_scale) . v_int``), which is the same product
    as ``ops/quantizer/kv_dequantize`` re-associated.  int4 is
    feature-split packed (byte ``j`` = features ``j`` and ``j + D//2``),
    so the wrapper splits q in halves and the kernel never concatenates.
  * inactive slots (length 0) fetch nothing and return zero rows; masked
    v rows and scales are ZEROED, not just down-weighted — ``0 x NaN``
    from a recycled quarantined block must never reach the accumulator
    (the PR 6 invariant, pinned by the NaN-garbage parity tests).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128
#: rows per page group the wrapper aims for: enough work per grid step
#: to hide its overhead
_TARGET_GROUP_ROWS = 1024
#: cap on concurrently in-flight page DMAs per buffer half
_MAX_PAGES_PER_PROGRAM = 16
#: f32 score tiles a program keeps live between its max and exp passes
_SCORE_BYTES = 2 << 20


def _pages_per_program(block: int, npages: int, rows: int,
                       override: Optional[int]) -> int:
    if override is not None:
        if override < 1:
            raise ValueError(
                f"pages_per_program must be >= 1, got {override}")
        return min(override, npages)
    pp = max(1, _TARGET_GROUP_ROWS // block)
    # a [rows, block] f32 score tile occupies whole (8, 128) vregs
    tile = (-(-rows // 8) * 8) * (-(-block // LANES) * LANES) * 4
    return max(1, min(pp, _MAX_PAGES_PER_PROGRAM, npages,
                      _SCORE_BYTES // tile))


def _head_pack(kv_heads: int, d_eff: int) -> int:
    """kv heads that share one lane chunk of the pool: the fewest whose
    rows fill whole 128-lane tiles (capped by what ``kv_heads`` allows —
    the compiled kernel then rejects the shape, the interpreter runs
    it)."""
    return math.gcd(kv_heads, math.lcm(d_eff, LANES) // d_eff)


def _page_group_dma(start, hbm, bufs, sem, bt_ref, row, pack, total, group,
                    buf, *, block, pp, width, hp):
    """Start (or wait on) the DMAs of one page group: for each valid
    page ``group * pp + j`` of slot ``row``, the pack's ``[block, W]``
    k and v slabs — and, for a quantized pool, each of its heads'
    ``[1, block]`` scale rows — from pool block ``bt[row, page]`` into
    slot ``j`` of buffer half ``buf``.  Start and wait MUST evaluate the
    same predicates, so both go through here."""
    npages = bt_ref.shape[1]
    lane0 = pl.multiple_of(pack * width, width)
    for j in range(pp):
        p = group * pp + j
        bid = bt_ref[row, jnp.minimum(p, npages - 1)]
        copies = [pltpu.make_async_copy(
            hbm[op].at[bid, :, pl.ds(lane0, width)], bufs[op].at[buf, j],
            sem.at[buf, op]) for op in (0, 1)]
        for op in range(2, len(hbm)):
            copies += [pltpu.make_async_copy(
                hbm[op].at[bid, pack * hp + t], bufs[op].at[buf, j, t],
                sem.at[buf, op]) for t in range(hp)]

        @pl.when((p < npages) & (p * block < total))
        def _():
            for c in copies:
                c.start() if start else c.wait()


def _unpack(x, kv_bits):
    """One pool slab ``[block, W]`` → the matmul operand per q split:
    the slab itself, its int8 values, or its (low, high) nibbles."""
    if kv_bits == 0:
        return [x]
    xi = x.astype(jnp.int32)
    if kv_bits == 8:
        return [xi.astype(jnp.float32)]
    return [(((xi & 0xF) ^ 8) - 8).astype(jnp.float32),
            (xi >> 4).astype(jnp.float32)]


def _kernel(meta_ref, bt_ref, coff_ref, rhead_ref, q_ref, *refs, sm_scale,
            block, pp, kv_bits, width, hp):
    """Online-softmax walk over one (slot, pack)'s page groups.

    ``meta_ref [B, 2]`` = (base, total) per slot: query row ``c`` sits
    at absolute position ``base + c``, sees keys ``<=`` its own
    position, and nothing at or past ``total`` is attended.  q_ref
    ``[nsplit, R, W]`` block-diagonal queries (``R = hp * G * C`` rows;
    ``coff_ref``/``rhead_ref`` ``[R, 1]`` give each row's chunk offset
    and head-within-pack); VMEM slabs kbuf/vbuf ``[2, pp, block, W]`` in
    the pool dtype (+ ksbuf/vsbuf ``[2, pp, hp, 1, block]`` f32 when
    quantized); scratch m/l ``[R, 1]``, acc ``[nsplit, R, W]`` f32; one
    DMA semaphore per (buffer half, operand)."""
    nops = 2 if kv_bits == 0 else 4
    hbm = refs[:nops]
    o_ref = refs[nops]
    bufs = refs[nops + 1:nops + 1 + nops]
    m_scr, l_scr, acc_scr, sem = refs[nops + 1 + nops:]

    i, hh, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nh, ng = pl.num_programs(1), pl.num_programs(2)
    rows = pp * block
    base, total = meta_ref[i, 0], meta_ref[i, 1]
    step = (i * nh + hh) * ng + g
    buf = jax.lax.rem(step, 2)

    def fetch(row, pack, group, into_buf, start):
        _page_group_dma(start, hbm, bufs, sem, bt_ref, row, pack,
                        meta_ref[row, 1], group, into_buf, block=block,
                        pp=pp, width=width, hp=hp)

    @pl.when(step == 0)
    def _cold_start():
        fetch(i, hh, g, buf, start=True)

    # issue the NEXT grid position's fetch before waiting on ours: the
    # pipeline stays full across page-group, pack, and slot boundaries
    g1 = g + 1
    h1 = hh + g1 // ng
    i1 = i + h1 // nh
    g1, h1 = jax.lax.rem(g1, ng), jax.lax.rem(h1, nh)

    @pl.when(i1 < pl.num_programs(0))
    def _prefetch_next():
        fetch(i1, h1, g1, jax.lax.rem(step + 1, 2), start=True)

    fetch(i, hh, g, buf, start=False)

    @pl.when(g == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(g * rows < total)
    def _body():
        nsplit = q_ref.shape[0]
        qs = [q_ref[s] if kv_bits == 0 else q_ref[s].astype(jnp.float32)
              for s in range(nsplit)]
        qpos = base + coff_ref[...]                       # [R, 1]

        def row_scale(sbuf, j):
            # the pack's per-head [1, block] scale rows -> [R, block],
            # each query row taking its own head's
            s = sbuf[buf, j, 0]
            for t in range(1, hp):
                s = jnp.where(rhead_ref[...] == t, sbuf[buf, j, t], s)
            return s

        # pass 1: every page's masked scores, and the group's row max
        scores, in_range = [], []
        m_prev = m_scr[...]                               # [R, 1]
        m_new = m_prev
        for j in range(pp):
            s = sum(jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                for q, k in zip(qs, _unpack(bufs[0][buf, j], kv_bits)))
            if kv_bits:
                s = s * row_scale(bufs[2], j)
            pos = (g * pp + j) * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            in_range.append(pos < total)
            s = jnp.where((pos <= qpos) & in_range[j], s * sm_scale,
                          MASK_VALUE)                     # [R, block]
            scores.append(s)
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        # pass 2: one rescale of the running state, then accumulate
        alpha = jnp.exp(m_prev - m_new)                   # [R, 1]
        l_new = alpha * l_scr[...]
        acc = [alpha * acc_scr[s] for s in range(nsplit)]
        for j in range(pp):
            p = jnp.exp(scores[j] - m_new)                # [R, block]
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
            # masked rows get probability ~0, but 0 * NaN = NaN: zero
            # the v rows (and scales) past the valid length so a
            # recycled pool block holding a quarantined request's
            # non-finite KV cannot re-poison its next owner — unfetched
            # pages also leave stale garbage in the buffer
            rowpos = (g * pp + j) * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            v = bufs[1][buf, j]
            v = jnp.where(rowpos < total, v, jnp.zeros_like(v))
            if kv_bits:
                p = p * jnp.where(in_range[j], row_scale(bufs[3], j), 0.0)
            for s, vv in enumerate(_unpack(v, kv_bits)):
                acc[s] = acc[s] + jax.lax.dot_general(
                    p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [R, W]
        l_scr[...] = l_new
        m_scr[...] = m_new
        for s in range(nsplit):
            acc_scr[s] = acc[s]

    @pl.when(g == ng - 1)
    def _out():
        # a slot that never ran a group (length 0, idle prefill lane):
        # l stays 0 and the clamp yields zero rows instead of 0/0
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)        # [R, 1]
        for s in range(q_ref.shape[0]):
            o_ref[s] = (inv * acc_scr[s]).astype(o_ref.dtype)


def _check_args(q_heads, d, pool_k, pool_v, k_scale, v_scale, kv_bits,
                what):
    """Validate the pool/scale operands; returns (kv_heads, d_eff)."""
    if kv_bits not in (0, 4, 8):
        raise ValueError(f"kv_bits must be 0, 4 or 8, got {kv_bits}")
    if pool_k.ndim != 3 or pool_v.shape != pool_k.shape:
        raise ValueError(
            f"{what}: pools must be [num_blocks, block, Hkv * De] and "
            f"match, got pool_k {pool_k.shape} pool_v {pool_v.shape}")
    if kv_bits == 4 and d % 2:
        raise ValueError(f"{what}: packed int4 needs even head_dim {d}")
    d_eff = d // 2 if kv_bits == 4 else d
    if pool_k.shape[2] % d_eff:
        raise ValueError(
            f"{what}: pool last dim {pool_k.shape[2]} is not a whole "
            f"number of {d_eff}-wide head rows (kv_bits={kv_bits}, "
            f"head_dim {d})")
    hkv = pool_k.shape[2] // d_eff
    if q_heads % hkv:
        raise ValueError(
            f"{what}: query heads {q_heads} not a multiple of kv heads "
            f"{hkv}")
    if kv_bits == 0:
        if k_scale is not None or v_scale is not None:
            raise ValueError(f"{what}: scales given but kv_bits=0")
        return hkv, d_eff
    want = (pool_k.shape[0], hkv, 1, pool_k.shape[1])
    if k_scale is None or v_scale is None:
        raise ValueError(f"{what}: kv_bits={kv_bits} needs k_scale and "
                         f"v_scale [num_blocks, Hkv, 1, block] f32")
    if pool_k.dtype != jnp.int8:
        raise ValueError(
            f"{what}: quantized pool must be int8, got {pool_k.dtype}")
    for name, scale in (("k_scale", k_scale), ("v_scale", v_scale)):
        if scale.shape != want:
            raise ValueError(
                f"{what}: {name} shape {scale.shape} != {want}")
    return hkv, d_eff


def _paged_attention(q, pool_k, pool_v, base, total, block_tables, *,
                     sm_scale, interpret, k_scale, v_scale, kv_bits,
                     pages_per_program, what):
    """q [B, C, H, D] — C query rows per slot at absolute positions
    ``base[b] .. base[b] + C - 1``; ``total[b]`` bounds the attended
    prefix; block_tables [B, pages].  Returns [B, C, H, D]."""
    b, c, h, d = q.shape
    hkv, d_eff = _check_args(h, d, pool_k, pool_v, k_scale, v_scale,
                             kv_bits, what)
    block = pool_k.shape[1]
    nsplit = d // d_eff                   # 2 for packed int4, else 1
    groups = h // hkv
    hp = _head_pack(hkv, d_eff)
    width = hp * d_eff
    npacks = hkv // hp
    interpret = resolve_interpret(interpret)
    if not interpret:
        if width % LANES:
            raise ValueError(
                f"{what}: compiled for the TPU, the {hkv} kv heads' "
                f"{d_eff}-wide rows must tile whole {LANES}-lane chunks "
                f"(head_dim {d}, kv_bits={kv_bits}: groups of "
                f"{math.lcm(d_eff, LANES) // d_eff} heads)")
        if kv_bits and block % LANES:
            raise ValueError(
                f"{what}: compiled for the TPU, a quantized pool needs "
                f"kv_block_size % {LANES} == 0 (scale rows are DMA'd "
                f"[1, block]), got {block}")
    npages = block_tables.shape[1]
    rows = hp * groups * c
    pp = _pages_per_program(block, npages, rows, pages_per_program)
    ngroups = -(-npages // pp)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    meta = jnp.stack([jnp.asarray(base, jnp.int32).reshape(b),
                      jnp.asarray(total, jnp.int32).reshape(b)], axis=1)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if kv_bits == 0:
        q = q.astype(pool_k.dtype)
    # [B, C, H, D] -> [B, pack, split, (head-in-pack, group, c), W] with
    # head j's rows non-zero only in lane window j (block-diagonal).
    # Query head (pack * hp + j) * G + g reads kv head pack * hp + j.
    qg = q.reshape(b, c, npacks, hp, groups, nsplit, d_eff)
    qg = qg.transpose(0, 2, 5, 3, 4, 1, 6)              # b P s j g c e
    eye = jnp.eye(hp, dtype=q.dtype)
    qg = jnp.einsum("bpsjgce,jk->bpsjgcke", qg, eye)
    qg = qg.reshape(b, npacks, nsplit, rows, width)
    coff = jnp.tile(jnp.arange(c, dtype=jnp.int32),
                    hp * groups).reshape(rows, 1)
    rhead = jnp.repeat(jnp.arange(hp, dtype=jnp.int32),
                       groups * c).reshape(rows, 1)

    nops = 2 if kv_bits == 0 else 4
    operands = [coff, rhead, qg, pool_k, pool_v]
    scratch = [pltpu.VMEM((2, pp, block, width), pool_k.dtype),
               pltpu.VMEM((2, pp, block, width), pool_v.dtype)]
    if kv_bits:
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, pp, hp, 1, block), jnp.float32),
                    pltpu.VMEM((2, pp, hp, 1, block), jnp.float32)]
    scratch += [pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((nsplit, rows, width), jnp.float32),
                pltpu.SemaphoreType.DMA((2, nops))]
    qspec = pl.BlockSpec((None, None, nsplit, rows, width),
                         lambda i, hh, g, *_: (i, hh, 0, 0, 0))
    rspec = pl.BlockSpec((rows, 1), lambda i, hh, g, *_: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, block=block, pp=pp,
                          kv_bits=kv_bits, width=width, hp=hp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, npacks, ngroups),
            in_specs=[rspec, rspec, qspec]
            + [pl.BlockSpec(memory_space=pl.ANY)] * nops,
            out_specs=qspec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(meta, block_tables, *operands)
    # keep each head's own lane window of its rows
    out = out.reshape(b, npacks, nsplit, hp, groups, c, hp, d_eff)
    out = jnp.einsum("bpsjgcke,jk->bpsjgce", out, eye)
    return out.transpose(0, 5, 1, 3, 4, 2, 6).reshape(b, c, h, d)


def paged_decode_attention(q: jnp.ndarray, pool_k: jnp.ndarray,
                           pool_v: jnp.ndarray, lengths: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           kv_bits: int = 0,
                           pages_per_program: Optional[int] = None
                           ) -> jnp.ndarray:
    """q [B, H, D] (one new token per slot); pool_k/v [num_blocks,
    block, Hkv * De]; lengths [B] int32 (valid tokens per slot INCLUDING
    the just-written one, 0 = inactive); block_tables [B, pages] int32
    (pool block ids; unused entries must hold a VALID id — the allocator
    pads with the reserved null block 0).  With ``kv_bits`` 8 or 4 the
    pools are int8 (``De = D`` or ``D // 2`` packed) and
    ``k_scale``/``v_scale`` [num_blocks, Hkv, 1, block] f32 ride along;
    dequant fuses into the page loop so only compressed bytes cross
    HBM.  Returns [B, H, D] in q's dtype; inactive slots come back as
    zero rows.

    The caller guarantees ``lengths[i] <= pages * block`` and that every
    table entry below ``ceil(lengths[i]/block)`` points at that slot's
    own blocks.  ``pages_per_program`` overrides the auto-picked group
    width.
    """
    if block_tables.ndim != 2 or block_tables.shape[0] != q.shape[0]:
        raise ValueError(
            f"block_tables must be [B={q.shape[0]}, pages], got "
            f"{block_tables.shape}")
    lengths = jnp.asarray(lengths, jnp.int32).reshape(q.shape[0])
    out = _paged_attention(
        q[:, None], pool_k, pool_v, lengths - 1, lengths, block_tables,
        sm_scale=sm_scale, interpret=interpret, k_scale=k_scale,
        v_scale=v_scale, kv_bits=kv_bits,
        pages_per_program=pages_per_program, what="paged_decode_attention")
    return out[:, 0].astype(q.dtype)


def paged_prefill_attention(q: jnp.ndarray, pool_k: jnp.ndarray,
                            pool_v: jnp.ndarray, base: jnp.ndarray,
                            chunk_len: jnp.ndarray,
                            block_table: jnp.ndarray,
                            sm_scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            k_scale: Optional[jnp.ndarray] = None,
                            v_scale: Optional[jnp.ndarray] = None,
                            kv_bits: int = 0,
                            pages_per_program: Optional[int] = None
                            ) -> jnp.ndarray:
    """Causal chunked-prefill attention for ONE slot through its block
    table (the Sarathi-Serve mixed-batch building block).

    q [C, H, D] — a chunk of C query tokens at absolute rows
    ``base .. base+C-1`` (rotary already applied); pools and scales as
    in :func:`paged_decode_attention`; ``base`` int32 scalar (rows of
    prior context already in the pool); ``chunk_len`` int32 scalar
    (valid queries; rows past it are padding — finite garbage out,
    callers ignore them); block_table [pages] int32 (the slot's pages,
    padded with the reserved null block 0).  The chunk's OWN k/v must
    already be scattered into the pool at rows base.. (the model does
    this immediately before the call), so the kernel reads every key —
    prior and in-chunk — through one uniform double-buffered page walk.
    Returns [C, H, D] in q's dtype.
    """
    if block_table.ndim != 1:
        raise ValueError(
            f"block_table must be [pages], got {block_table.shape}")
    base = jnp.asarray(base, jnp.int32)
    out = _paged_attention(
        q[None], pool_k, pool_v, base, base + jnp.asarray(chunk_len,
                                                          jnp.int32),
        block_table[None], sm_scale=sm_scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, kv_bits=kv_bits,
        pages_per_program=pages_per_program,
        what="paged_prefill_attention")
    return out[0].astype(q.dtype)


def _reference_cache(pool_k, pool_v, k_scale, v_scale, kv_bits, d):
    """The pools as plain ``[num_blocks, block, Hkv, D]`` arrays for the
    jnp references — ``kv_dequantize`` is the math the kernel fuses."""
    d_eff = d // 2 if kv_bits == 4 else d
    nb, block, lanes = pool_k.shape
    shape = (nb, block, lanes // d_eff, d_eff)
    pool_k, pool_v = pool_k.reshape(shape), pool_v.reshape(shape)
    if kv_bits == 0:
        return pool_k, pool_v
    from ..quantizer.quantizer import kv_dequantize

    def rows(scale):                       # [nb, Hkv, 1, block] -> row-major
        return scale[:, :, 0].transpose(0, 2, 1)
    return (kv_dequantize(pool_k, rows(k_scale), kv_bits),
            kv_dequantize(pool_v, rows(v_scale), kv_bits))


def _reference(q, pool_k, pool_v, base, total, block_tables, k_scale,
               v_scale, kv_bits):
    """Readable float32 jnp reference for the kernel (tests and the
    on-chip smoke pin against this): per slot, dequantize if needed,
    gather the table's pages into a contiguous cache and run masked
    dense attention.  q [B, C, H, D]."""
    b, c, h, d = q.shape
    pool_k, pool_v = _reference_cache(pool_k, pool_v, k_scale, v_scale,
                                      kv_bits, d)
    block, hkv = pool_k.shape[1], pool_k.shape[2]
    npages = block_tables.shape[1]
    g = h // hkv

    def one(qi, table, bs, tot):
        k = pool_k[table].reshape(npages * block, hkv, d)
        v = pool_v[table].reshape(npages * block, hkv, d)
        if g > 1:
            k = jnp.repeat(k, g, axis=1)
            v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("chd,shd->chs", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) / math.sqrt(d)
        pos = jnp.arange(npages * block)
        qpos = bs + jnp.arange(c)[:, None, None]
        s = jnp.where((pos <= qpos) & (pos < tot), s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        v = jnp.where((pos < tot)[:, None, None], v, 0.0)  # NaN-safe
        return jnp.einsum("chs,shd->chd", p, v.astype(jnp.float32))

    return jax.vmap(one)(q, block_tables, base, total)


def paged_prefill_reference(q, pool_k, pool_v, base, chunk_len,
                            block_table, k_scale=None, v_scale=None,
                            kv_bits=0):
    """jnp reference for :func:`paged_prefill_attention`.  Padding
    queries (index >= chunk_len) are returned as zeros."""
    base = jnp.asarray(base, jnp.int32)
    out = _reference(q[None], pool_k, pool_v, base[None],
                     (base + chunk_len)[None], block_table[None], k_scale,
                     v_scale, kv_bits)[0]
    valid = (jnp.arange(q.shape[0]) < chunk_len)[:, None, None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)


def paged_attention_reference(q, pool_k, pool_v, lengths, block_tables,
                              k_scale=None, v_scale=None, kv_bits=0):
    """jnp reference for :func:`paged_decode_attention`.
    O(B·pages·block) gather — test-scale only."""
    lengths = jnp.asarray(lengths, jnp.int32)
    out = _reference(q[:, None], pool_k, pool_v, lengths - 1, lengths,
                     block_tables, k_scale, v_scale, kv_bits)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


def supports(head_dim: int) -> bool:
    """Sublane-aligned head dim; lengths and batch are unbounded (KV
    pages stream through VMEM).  The lane-tiling conditions depend on
    the kv head count and ``kv_bits`` as well and are checked — with a
    message — when the kernel is built for the TPU."""
    return head_dim % 8 == 0
