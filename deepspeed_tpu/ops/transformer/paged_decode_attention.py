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
``D // 2`` (packed int4).  A pool block — a PAGE — is contiguous in HBM
(64 KB at 16 tokens x 2,048 bf16 lanes) and is always fetched whole.
The smallest lane chunk of whole heads is a PACK of ``lcm(De, 128) //
De`` kv heads (2 at head_dim 64, 1 at 128, 4 at 96).  Scale rows are
stored lane-major, one ``[1, block]`` row per (page, head), so they
broadcast over score columns.  Compiled for the TPU this needs ``Hkv *
De`` to be a multiple of the pack's lanes and, for a quantized pool,
``block % 128 == 0``; the interpreter (CPU tests) takes any shape.

Kernel design:

  * grid ``(slot, page_group)``: one step fetches a group of
    ``pages_per_program`` pages and contracts its live part for EVERY
    head.  A step at or past its slot's length (an inactive slot, the
    idle prefill lane, the tail of a short sequence) is DEAD: one
    predicate, no descriptor, no table look-up.  The wrapper computes from the
    lengths what a step needs to know of the walk — live groups per
    slot, live steps before it, the next live slot — and hands it over
    as scalar prefetch.
  * the unit of the walk is the RUN: ``PAGE_RUN`` consecutive pages of a
    slot's table.  Where a run's table entries are consecutive pool
    blocks that all hold attended keys — which the cache manager makes
    the rule — it is ONE DMA per operand, ``pool[bid : bid + PAGE_RUN]``
    into slots ``j ..`` of a ``[2, pp, block, Hkv * De]`` VMEM scratch
    (the scale rows of a quantized pool likewise, ``[PAGE_RUN, Hkv, 1,
    block]`` in one copy); any other run — a slot's open last run, one a
    window's first position falls in, one the allocator had to break —
    is one DMA per operand a live PAGE, ``pool[bid]`` into slot ``j``,
    issued by a loop over the run's live pages only.  The wrapper makes
    the choice from the table and the lengths (``_grouped_tables``: flags
    ``[B, pages / PAGE_RUN]``, a third scalar-prefetch operand), so start
    and wait agree.  A page of 16 KB costs its descriptor, not its bytes:
    80 starts and 80 waits a slot a layer at 40 pages where runs take 17.
    A pool whose page is ``_RUN_PAGE_BYTES`` (64 KB) an operand or more
    walks page by page and is handed no flags: there the bytes outlast
    the descriptors.
    DOUBLE-BUFFERED across steps: the pools stay in HBM
    (``memory_space=ANY``); while one group is contracted, the next LIVE
    step's group — of this slot or of the next live one, however many
    dead steps lie between — is already in flight into the other half.
  * a step contracts its group in PARTS of whole runs (``_part_pages``)
    under a loop whose trip count follows the length: the parts that hold
    keys the slot attends (below ``total``; under a window, at or after
    the walk's first position) and no others.  A group sized for VMEM is
    the whole table of a narrow pool (128 pages = 2,048 keys at 512
    lanes), and a key contracted costs the same fetched or not.
  * heads are walked in WINDOWS of whole packs, 128-lane-aligned slices
    of the slab in VMEM, under a ``fori_loop`` (traced once — the mixed
    step is traced and lowered in every serving set-up — and unrolled
    when it is lowered, so that the windows' independent chains
    overlap).  A
    window's heads share one MXU contraction over the group's ``pp x
    block`` keys: the wrapper lays their queries out BLOCK-DIAGONALLY
    (head j's rows are non-zero only in head j's lanes), so ``Q x
    slab^T`` yields every head's scores with no in-kernel lane slicing;
    the off-diagonal output windows are discarded by the wrapper.  The
    window grows while its rows fit ``_WINDOW_ROWS``: a decode call (one
    row a head) stacks ALL heads into one contraction a step, a 256-row
    chunk walks one pack at a time.  GQA query groups and chunk rows
    are simply more rows.  Online-softmax state (m, l, acc) is kept per
    window.
  * ``pages_per_program`` follows from a stated VMEM budget
    (``_VMEM_GROUP_BYTES`` of the ``_VMEM_LIMIT_BYTES`` the kernel asks
    for): what a page costs in both buffer halves plus what a
    contraction of the whole group would keep per key (a part keeps
    less: the budget is an upper bound).
  * FUSED DEQUANT: an int8 / packed-int4 pool crosses HBM compressed;
    the per-row scales are applied in the SCORE domain (``(q . k_int) *
    k_scale`` and ``(p * v_scale) . v_int``), which is the same product
    as ``ops/quantizer/kv_dequantize`` re-associated.  int4 is
    feature-split packed (byte ``j`` = features ``j`` and ``j + D//2``),
    so the wrapper splits q in halves and the kernel never concatenates.
  * dead slots return zero rows; masked v rows and scales are ZEROED,
    not just down-weighted — ``0 x NaN`` from a recycled quarantined
    block, or from whatever an unfetched page left in the buffer, must
    never reach the accumulator (the PR 6 invariant, pinned by the
    NaN-garbage parity tests).
  * VISIBILITY BY BLOCKS (``block_rows``, static; generation by diffusion
    over blocks): a row's offset is rounded up to the end of the block of
    ``block_rows`` rows it stands in before the kernel sees it, so row
    ``c`` sees every key up to ``base + (c // block_rows) * block_rows +
    block_rows - 1`` (bounded by ``total`` as ever) — its own block both
    ways, every earlier block whole.  The kernel is the same program: only
    the offsets it is handed differ.  ``base`` must be a block's start.
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
#: scoped VMEM the kernel asks the compiler for ...
_VMEM_LIMIT_BYTES = 32 << 20
#: ... and the part of it a step's page buffers and per-group
#: temporaries may plan to fill (the rest: the q / o blocks, the
#: accumulators, what the compiler keeps for itself)
_VMEM_GROUP_BYTES = 12 << 20
#: query rows one head window may stack block-diagonally: a bf16 operand
#: tile has 16 sublanes, so an MXU pass costs the same for 1 row as for 16
_WINDOW_ROWS = 16
#: pages of a RUN: the cache manager (``inference/serving/
#: block_allocator.py``) hands a sequence's pages ``k * PAGE_RUN .. (k + 1)
#: * PAGE_RUN - 1`` out as consecutive pool blocks wherever it can, and
#: every walk here — the plain kernel's k and v, the latent kernel's one
#: operand — fetches such a run with ONE DMA an operand.  8 pages = 128
#: tokens = 160 KB of latent rows, 128 KB of k at 4 kv heads of 128
PAGE_RUN = 8
#: a page this large an operand is fetched page by page whatever the table
#: holds: its transfer (80 ns at 64 KB) outlasts its descriptor (38 ns,
#: PERF.md PR 55), the run walk returned nothing there on the chip
#: (Pythia's decode, PR 59) and its flags cost an XLA fusion a layer; at 40
#: KB (phi-4's window layers) it still returns 8 %
_RUN_PAGE_BYTES = 64 << 10
#: keys one PART of a page group holds at most: a step contracts the
#: parts that hold attended keys.  A part is one pass of the chain QK ->
#: max -> exp -> PV whose latency (some 0.4 us a head window on the v5e,
#: whatever the keys) is paid a part: 1,024 keys halve a 2,048-key group
#: for a slot that fills a third of it; parts of 128 cost twice the
#: whole group (PERF.md, PR 59)
_PART_KEYS = 1024


def _head_pack(kv_heads: int, d_eff: int) -> int:
    """kv heads that share one lane chunk of the pool: the fewest whose
    rows fill whole 128-lane tiles (capped by what ``kv_heads`` allows —
    the compiled kernel then rejects the shape, the interpreter runs
    it)."""
    return math.gcd(kv_heads, math.lcm(d_eff, LANES) // d_eff)


def _window_heads(kv_heads: int, hp: int, rows_per_head: int) -> int:
    """kv heads whose queries one contraction stacks block-diagonally:
    whole packs, doubled while they divide the heads and the stacked
    rows stay within ``_WINDOW_ROWS`` — every head of a decode call
    (one row each), one pack of a 256-row chunk."""
    heads = hp
    while (kv_heads % (2 * heads) == 0
           and 2 * heads * rows_per_head <= _WINDOW_ROWS):
        heads *= 2
    return heads


def _pages_per_program(pool, kv_heads: int, kv_bits: int, rows: int,
                       width: int, npages: int,
                       override: Optional[int]) -> int:
    """Pages one grid step fetches and contracts: as many as
    ``_VMEM_GROUP_BYTES`` holds of what a page costs in VMEM — its k and
    v rows in both buffer halves, its scale rows (a ``[1, block]`` row
    occupies whole (8, 128) tiles), and per key the f32 score and
    probability rows of ``rows`` queries plus the operands one window
    pass makes of the slab (the zeroed v rows; both f32 copies, per q
    split, of a quantized pool's k and v) — rounded down to a power of
    two."""
    if override is not None:
        if override < 1:
            raise ValueError(
                f"pages_per_program must be >= 1, got {override}")
        return min(override, npages)
    _, block, lanes = pool.shape
    page = 4 * block * lanes * pool.dtype.itemsize
    per_key = 2 * 4 * (-(-rows // 8) * 8) + width * pool.dtype.itemsize
    if kv_bits:
        nsplit = 2 if kv_bits == 4 else 1
        page += 4 * kv_heads * 8 * (-(-block // LANES) * LANES) * 4
        per_key += 2 * nsplit * width * 4
    pp = max(1, _VMEM_GROUP_BYTES // (page + block * per_key))
    return min(1 << (pp.bit_length() - 1), npages)


def _part_pages(pp: int, run: int, block: int) -> int:
    """Pages of one PART of a page group — what a step contracts at a
    time: whole runs, doubled while they divide the group and hold no more
    than ``_PART_KEYS`` keys."""
    part = run
    while pp % (2 * part) == 0 and 2 * part * block <= _PART_KEYS:
        part *= 2
    return part


def _page_group_dma(start, hbm, bufs, sem, bt_ref, row, total, group, half,
                    *, block, pp, first=0, span=None):
    """Start (or wait on) the DMAs of one live page group: for each page
    ``group * pp + j`` of slot ``row`` that holds keys below ``total``,
    the WHOLE pool block ``bt[row, page]`` — ``[block, Hkv * De]`` of k
    and of v, and for a quantized pool its ``[Hkv, 1, block]`` scale
    rows — into slot ``j`` of buffer half ``half``, one copy per operand.
    The loop runs over the live pages only, so start and wait count
    alike; a wait needs the copy's shape and semaphore, not its source.
    With a WINDOW the walk has a ``first`` position as well: pages that
    end at or below it are dead too (their blocks may be another slot's
    by now), and neither started nor waited on.  ``span = (j0, n)``
    narrows the loop to the group's pages ``j0 .. j0 + n - 1``."""
    def page(j, carry):
        bid = bt_ref[row, group * pp + j] if start else 0
        for op, (src, dst) in enumerate(zip(hbm, bufs)):
            copy = pltpu.make_async_copy(src.at[bid], dst.at[half, j],
                                         sem.at[half, op])
            copy.start() if start else copy.wait()
        return carry
    live = jnp.clip(-(-(total - group * pp * block) // block), 0, pp)
    dead = 0 if isinstance(first, int) and first == 0 else jnp.clip(
        (first - group * pp * block) // block, 0, pp)
    if span is not None:
        dead = jnp.maximum(dead, span[0])
        live = jnp.minimum(live, span[0] + span[1])
    jax.lax.fori_loop(dead, live, page, 0)


def _grouped_tables(block_tables, total, pp: int, block: int, run: int,
                    first=None):
    """``(tables padded to whole groups of pp pages, runs [B, groups * pp //
    run])``: a group is ``pp // run`` RUNS of ``run`` pages, and ``runs`` is
    1 where a run's pages are consecutive pool blocks and all hold keys
    below the slot's ``total`` — such a run is one DMA.  (A run that is
    dead or partly dead reads 0, so nothing past the length is fetched by
    it.)  Under a window the walk has a ``first [B]`` position too, and a
    run with a page wholly before it reads 0 as well: what the table holds
    there is not the slot's any more."""
    b, npages = block_tables.shape
    ngroups = -(-npages // pp)
    nruns = ngroups * pp // run
    tables = jnp.pad(jnp.asarray(block_tables, jnp.int32),
                     ((0, 0), (0, ngroups * pp - npages)))
    t = tables.reshape(b, nruns, run)
    consecutive = jnp.all(t[..., 1:] == t[..., :-1] + 1, axis=-1)
    r = jnp.arange(nruns, dtype=jnp.int32)[None]
    whole = (r + 1) * (run * block) <= total[:, None]
    if first is not None:
        whole = whole & (r * run >= (first // block)[:, None])
    return tables, (consecutive & whole).astype(jnp.int32)


def page_runs(block_tables, total, block: int):
    """``[B, ceil(pages / PAGE_RUN)]`` int32: 1 where pages ``j * PAGE_RUN
    .. (j + 1) * PAGE_RUN - 1`` of slot ``b``'s table are consecutive pool
    blocks that all hold keys below ``total[b]`` — the runs a latent walk
    fetches with one DMA each (what a program counts its run share
    from)."""
    return _grouped_tables(block_tables, total, PAGE_RUN, block, PAGE_RUN)[1]


def walk_pages(block_tables, total, block: int, first=None):
    """``(pages, pages in runs)`` of the walks over ``block_tables [B,
    pages]`` up to ``total [B]`` (from ``first [B]`` under a window): the
    pages that hold attended keys, and those of them in runs the kernel
    fetches with one DMA an operand (the flags it is handed, ``page_runs``
    of the same tables and lengths) — what a program counts its
    ``kv_pages_read`` / ``kv_pages_in_runs`` from."""
    total = jnp.asarray(total, jnp.int32)
    pages = -(-total // block)
    if first is not None:
        pages = jnp.maximum(pages - first // block, 0)
    runs = _grouped_tables(block_tables, total, PAGE_RUN, block, PAGE_RUN,
                           first)[1]
    return jnp.sum(pages), PAGE_RUN * jnp.sum(runs)


def _fetch_group(start, hbm, bufs, sem, bt_ref, run_ref, slot, total,
                 group, half, *, block, pp, run, first=0):
    """Start (or wait on) one page group of ``slot`` into buffer half
    ``half``, run by run and for every operand of ``hbm`` / ``bufs`` (the
    latent pool alone; k and v, and a quantized pool's two scale arrays):
    ONE DMA an operand of ``run`` consecutive pool blocks where ``run_ref``
    says so, else ``_page_group_dma``'s one DMA a live page of that run.
    The choice is made from the table and the lengths alone
    (``_grouped_tables``), the same at start and at wait.  Runs past the
    length — and, under a window, before the walk's ``first`` position —
    are not looked at."""
    nruns = pp // run

    def one_run(r, carry):
        whole = run_ref[slot, group * nruns + r] > 0

        @pl.when(whole)
        def _one():
            bid = bt_ref[slot, group * pp + r * run] if start else 0
            for op, (src, buf) in enumerate(zip(hbm, bufs)):
                dst = buf.at[half] if nruns == 1 else \
                    buf.at[half, pl.ds(r * run, run)]
                copy = pltpu.make_async_copy(src.at[pl.ds(bid, run)], dst,
                                             sem.at[half, op])
                copy.start() if start else copy.wait()

        @pl.when(jnp.logical_not(whole))
        def _pages():
            _page_group_dma(start, hbm, bufs, sem, bt_ref, slot, total,
                            group, half, block=block, pp=pp, first=first,
                            span=None if nruns == 1 else (r * run, run))
        return carry

    if nruns == 1:
        one_run(0, 0)
    else:
        keys0 = group * pp * block
        live = jnp.clip(-(-(total - keys0) // (run * block)), 0, nruns)
        dead = 0 if isinstance(first, int) and first == 0 else jnp.clip(
            (first - keys0) // (run * block), 0, nruns)
        jax.lax.fori_loop(dead, live, one_run, 0)


def _walk_step(i, g, nwalk, meta_ref, bt_ref, run_ref, pool_hbm, buf, sem, *,
               block, pp, run):
    """The double-buffered fetch of a live grid step ``(walker i, group
    g)`` of ``nwalk`` walkers over a one-operand pool: cold start,
    prefetch of the next live step's group (of this walker or the next
    live one), wait for this step's.  Returns the buffer half that now
    holds the group."""
    live_groups = meta_ref[2, i]

    def fetch(w, group, half, start):
        _fetch_group(start, (pool_hbm,), (buf,), sem, bt_ref, run_ref,
                     meta_ref[5, w], meta_ref[1, w], group, half,
                     block=block, pp=pp, run=run)

    step = meta_ref[3, i] + g
    half = jax.lax.rem(step, 2)

    @pl.when(step == 0)
    def _cold_start():
        fetch(i, g, half, start=True)

    more = g + 1 < live_groups
    w1 = jnp.where(more, i, meta_ref[4, i])
    g1 = jnp.where(more, g + 1, 0)

    @pl.when(w1 < nwalk)
    def _prefetch_next():
        fetch(w1, g1, 1 - half, start=True)

    fetch(i, g, half, start=False)
    return half


def _unpack(x, kv_bits):
    """One pool slab ``[keys, W]`` → the matmul operand per q split:
    the slab itself, its int8 values, or its (low, high) nibbles."""
    if kv_bits == 0:
        return [x]
    xi = x.astype(jnp.int32)
    if kv_bits == 8:
        return [xi.astype(jnp.float32)]
    return [(((xi & 0xF) ^ 8) - 8).astype(jnp.float32),
            (xi >> 4).astype(jnp.float32)]


def _kernel(meta_ref, bt_ref, *refs, sm_scale, block, pp, run, part,
            kv_bits, width, heads, window_keys=None):
    """Online-softmax walk over one slot's live page groups, every head
    window inside the step.

    ``meta_ref [5, B]`` (scalar prefetch), per slot: (base, total, live
    groups, live steps before this slot, next live slot after this one
    or B).  Query row ``c`` sits at absolute position ``base +
    c``, sees keys ``<=`` its own position, and nothing at or past
    ``total`` is attended.  With ``window_keys`` (static) a row sees only
    the ``window_keys`` keys that end at its own position, and
    ``meta_ref`` has two more rows: the slot's first page GROUP (the
    grid's ``g`` counts from it) and its first attended position, the
    first row's window start.  With ``run`` (static; None: every page a
    DMA of its own) a third scalar-prefetch operand leads ``refs``,
    ``run_ref [B, groups * pp // run]``: 1 where a run of ``run`` pages is
    one DMA (:func:`_fetch_group`).  A group is contracted in PARTS of
    ``part`` pages, those that hold keys the slot attends and no others.
    Then ``coff_ref``, ``rhead_ref``, and q_ref ``[nwin, nsplit, R, W]``:
    per head window the block-diagonal queries of its ``heads`` kv heads
    (``R = heads * G * C`` rows; ``coff_ref``/``rhead_ref`` ``[R, 1]``
    give each row's chunk offset and head-within-window).  VMEM slabs kbuf/vbuf
    ``[2, pp, block, Hkv * De]`` in the pool dtype (+ ksbuf/vsbuf ``[2,
    pp, Hkv, 1, block]`` f32 when quantized); scratch m/l ``[nwin, R,
    1]``, acc ``[nwin, nsplit, R, W]`` f32; one DMA semaphore per
    (buffer half, operand)."""
    if run is not None:
        run_ref, *refs = refs
    coff_ref, rhead_ref, q_ref, *refs = refs
    nops = 2 if kv_bits == 0 else 4
    hbm = refs[:nops]
    o_ref = refs[nops]
    bufs = refs[nops + 1:nops + 1 + nops]
    m_scr, l_scr, acc_scr, sem = refs[nops + 1 + nops:]

    i, g = pl.program_id(0), pl.program_id(1)
    nslots, ng = pl.num_programs(0), pl.num_programs(1)
    nwin = q_ref.shape[0]
    keys, n = pp * block, part * block
    base, total, live_groups = meta_ref[0, i], meta_ref[1, i], meta_ref[2, i]

    def fetch(row, group, half, start):
        where = (row, meta_ref[1, row], group, half)
        kw = dict(block=block, pp=pp, first=(
            0 if window_keys is None else meta_ref[6, row]))
        if run is None:
            _page_group_dma(start, hbm, bufs, sem, bt_ref, *where, **kw)
        else:
            _fetch_group(start, hbm, bufs, sem, bt_ref, run_ref, *where,
                         run=run, **kw)

    # a step at or past its slot's length runs none of this
    @pl.when(g < live_groups)
    def _live():
        step = meta_ref[3, i] + g          # ordinal among the live steps
        half = jax.lax.rem(step, 2)
        # this step's page group: the grid's g, counted from the group a
        # window's walk starts at
        gi = g if window_keys is None else g + meta_ref[5, i]

        @pl.when(step == 0)
        def _cold_start():
            fetch(i, gi, half, start=True)

        # issue the NEXT LIVE step's fetch before waiting on ours: the
        # pipeline stays full across page groups, slots and dead steps
        more = g + 1 < live_groups
        row1 = jnp.where(more, i, meta_ref[4, i])
        if window_keys is None:
            g1 = jnp.where(more, g + 1, 0)
        else:
            # (a dead row1 is never fetched: the index is clamped for the
            # look-up of its first group alone)
            g1 = jnp.where(more, gi + 1,
                           meta_ref[5, jnp.minimum(row1, nslots - 1)])

        @pl.when(row1 < nslots)
        def _prefetch_next():
            fetch(row1, g1, 1 - half, start=True)

        fetch(i, gi, half, start=False)

        @pl.when(g == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        qpos = base + coff_ref[...]                            # [R, 1]

        def contract(p, carry):
            """Part ``p`` of the group — pages ``p * part ..`` of the
            buffer half, ``n`` keys — against every head window."""
            pages = pl.ds(p * part, part)
            key0 = gi * keys + p * n
            pos = key0 + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            in_range = pos < total                             # [1, n]
            visible = (pos <= qpos) & in_range                 # [R, n]
            # masked rows get probability ~0, but 0 * NaN = NaN: zero the
            # v rows (and scales) past the valid length so a recycled pool
            # block holding a quarantined request's non-finite KV cannot
            # re-poison its next owner — unfetched pages also leave stale
            # garbage in the buffer
            vpos = key0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
            v_valid = vpos < total                             # [n, 1]
            if window_keys is not None:
                # a row sees the `window_keys` keys ending at its own
                # position; a late row of a chunk meets whole parts it
                # does not see BEFORE the ones it does: what they add at
                # weight exp(MASK - MASK) is finite (the pages before the
                # walk's first position, never fetched, are zeroed like
                # the tail) and is wiped by alpha = exp(MASK - m) = 0 at
                # its first visible key
                visible = visible & (pos > qpos - window_keys)
                v_valid = v_valid & (vpos >= meta_ref[6, i])

            def window(w, carry):
                lanes = pl.ds(pl.multiple_of(w * width, width), width)

                def row_scale(sbuf):
                    # the window's per-head [1, block] scale rows of the
                    # part's pages -> [R, n], each query row taking its
                    # own head's
                    def of_head(t):
                        return jnp.concatenate(
                            [sbuf[half, p * part + j, w * heads + t]
                             for j in range(part)], axis=1)
                    s = of_head(0)
                    for t in range(1, heads):
                        s = jnp.where(rhead_ref[...] == t, of_head(t), s)
                    return s

                k = bufs[0][half, pages, :, lanes].reshape(n, width)
                s = sum(jax.lax.dot_general(
                    q_ref[w, c] if kv_bits == 0
                    else q_ref[w, c].astype(jnp.float32), kk,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for c, kk in enumerate(_unpack(k, kv_bits)))
                if kv_bits:
                    s = s * row_scale(bufs[2])
                s = jnp.where(visible, s * sm_scale, MASK_VALUE)   # [R, n]
                m_prev = m_scr[w]                                  # [R, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p_ = jnp.exp(s - m_new)
                l_scr[w] = alpha * l_scr[w] + jnp.sum(p_, axis=1,
                                                      keepdims=True)
                m_scr[w] = m_new
                v = bufs[1][half, pages, :, lanes].reshape(n, width)
                v = jnp.where(v_valid, v, jnp.zeros_like(v))
                if kv_bits:
                    p_ = p_ * jnp.where(in_range, row_scale(bufs[3]), 0.0)
                for c, vv in enumerate(_unpack(v, kv_bits)):
                    acc_scr[w, c] = alpha * acc_scr[w, c] + \
                        jax.lax.dot_general(
                            p_.astype(vv.dtype), vv,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [R, W]
                return carry

            # the windows are a loop, traced once whatever the number of
            # heads, and unrolled when it is lowered: their chains QK ->
            # max -> exp -> PV are independent, and side by side in one
            # basic block they overlap (Mosaic lowers a loop whole or
            # not at all)
            if nwin == 1:
                window(0, 0)
            else:
                jax.lax.fori_loop(0, nwin, window, 0, unroll=True)
            return carry

        # a slot's last group is as full as its length leaves it (a
        # window's first as empty as its start), and every key contracted
        # costs the same, fetched or not: the parts that hold attended
        # keys are contracted, under a loop whose trip count follows the
        # length — one copy of the body whatever the number of parts
        nparts = pp // part
        if nparts == 1:
            contract(0, 0)
        else:
            key0 = gi * keys
            last = jnp.clip(-(-(total - key0) // n), 0, nparts)
            first = 0 if window_keys is None else jnp.clip(
                (meta_ref[6, i] - key0) // n, 0, nparts)
            jax.lax.fori_loop(first, last, contract, 0)

    @pl.when(g == ng - 1)
    def _out():
        # a slot that never ran a group (length 0, idle prefill lane)
        # left the accumulators to its neighbours: zero rows
        alive = live_groups > 0
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)             # [nwin,R,1]
        o_ref[...] = jnp.where(alive, inv[:, None] * acc_scr[...],
                               0.0).astype(o_ref.dtype)


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
                     pages_per_program, what, window=None, block_rows=None):
    """q [B, C, H, D] — C query rows per slot at absolute positions
    ``base[b] .. base[b] + C - 1``; ``total[b]`` bounds the attended
    prefix; block_tables [B, pages].  ``window`` (static; None = every
    earlier key): a row attends the ``window`` keys that end at its own
    position, and the slot's walk starts at the page that holds position
    ``max(0, base[b] - window + 1)`` — table entries before it are never
    read.  ``block_rows`` (static; must divide C, ``base`` a multiple of
    it): row ``c`` sees the keys up to the END of the block of that many
    rows it stands in instead of up to itself.  Returns [B, C, H, D]."""
    b, c, h, d = q.shape
    if block_rows and (c % block_rows or window is not None):
        raise ValueError(
            f"{what}: block_rows {block_rows} must divide the {c} query "
            f"rows a slot, and takes no window")
    hkv, d_eff = _check_args(h, d, pool_k, pool_v, k_scale, v_scale,
                             kv_bits, what)
    block, lanes = pool_k.shape[1:]
    nsplit = d // d_eff                   # 2 for packed int4, else 1
    groups = h // hkv
    heads = _window_heads(hkv, _head_pack(hkv, d_eff), groups * c)
    width = heads * d_eff
    nwin = hkv // heads
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
    rows = heads * groups * c
    pp = _pages_per_program(pool_k, hkv, kv_bits, rows, width, npages,
                            pages_per_program)
    # runs where a page is small enough for its descriptor to cost more
    # than its bytes; parts of whole runs either way
    run = math.gcd(pp, PAGE_RUN)
    part = _part_pages(pp, run, block)
    if block * lanes * pool_k.dtype.itemsize >= _RUN_PAGE_BYTES:
        run = None
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    # what a step needs to know of the walk, so that a dead step tests
    # one number and a live one finds the next live step without
    # looking at its neighbours' pages
    base = jnp.asarray(base, jnp.int32).reshape(b)
    total = jnp.asarray(total, jnp.int32).reshape(b)
    first = None
    if window is not None:
        if kv_bits:
            raise NotImplementedError(
                f"{what}: a window over a quantized pool (the scale rows' "
                f"DMAs take no first page)")
        first = jnp.maximum(base - (window - 1), 0)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    tables = (block_tables,) if run is None else _grouped_tables(
        block_tables, total, pp, block, run, first)
    ngroups = -(-npages // pp)
    live = jnp.clip(-(-total // (pp * block)), 0, ngroups)
    if window is not None:
        group0 = jnp.where(total > 0, first // (pp * block), 0)
        live = live - jnp.minimum(group0, live)
    slot = jnp.where(live > 0, jnp.arange(b, dtype=jnp.int32), b)
    later = jnp.append(jax.lax.cummin(slot, reverse=True)[1:], b)
    meta = jnp.stack([base, total, live, jnp.cumsum(live) - live, later]
                     + ([] if window is None else [group0, first]))
    if kv_bits == 0:
        q = q.astype(pool_k.dtype)
    # [B, C, H, D] -> [B, window, split, (head-in-window, group, c), W]
    # with head j's rows non-zero only in lane window j (block-diagonal).
    # Query head (win * heads + j) * G + g reads kv head win * heads + j.
    qg = q.reshape(b, c, nwin, heads, groups, nsplit, d_eff)
    qg = qg.transpose(0, 2, 5, 3, 4, 1, 6)              # b w s j g c e
    eye = jnp.eye(heads, dtype=q.dtype)
    qg = jnp.einsum("bwsjgce,jk->bwsjgcke", qg, eye)
    qg = qg.reshape(b, nwin, nsplit, rows, width)
    coff = jnp.arange(c, dtype=jnp.int32)
    if block_rows:
        coff = coff // block_rows * block_rows + (block_rows - 1)
    coff = jnp.tile(coff, heads * groups).reshape(rows, 1)
    rhead = jnp.repeat(jnp.arange(heads, dtype=jnp.int32),
                       groups * c).reshape(rows, 1)

    nops = 2 if kv_bits == 0 else 4
    operands = [coff, rhead, qg, pool_k, pool_v]
    scratch = [pltpu.VMEM((2, pp, block, lanes), pool_k.dtype),
               pltpu.VMEM((2, pp, block, lanes), pool_v.dtype)]
    if kv_bits:
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
        scratch += [pltpu.VMEM((2, pp, hkv, 1, block), jnp.float32),
                    pltpu.VMEM((2, pp, hkv, 1, block), jnp.float32)]
    scratch += [pltpu.VMEM((nwin, rows, 1), jnp.float32),
                pltpu.VMEM((nwin, rows, 1), jnp.float32),
                pltpu.VMEM((nwin, nsplit, rows, width), jnp.float32),
                pltpu.SemaphoreType.DMA((2, nops))]
    qspec = pl.BlockSpec((None, nwin, nsplit, rows, width),
                         lambda i, g, *_: (i, 0, 0, 0, 0))
    rspec = pl.BlockSpec((rows, 1), lambda i, g, *_: (0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, block=block, pp=pp,
                          run=run, part=part, kv_bits=kv_bits, width=width,
                          heads=heads,
                          **({} if window is None
                             else {"window_keys": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(tables),
            grid=(b, ngroups),
            in_specs=[rspec, rspec, qspec]
            + [pl.BlockSpec(memory_space=pl.ANY)] * nops,
            out_specs=qspec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="paged_attention",
    )(meta, *tables, *operands)
    # keep each head's own lane window of its rows
    out = out.reshape(b, nwin, nsplit, heads, groups, c, heads, d_eff)
    out = jnp.einsum("bwsjgcke,jk->bwsjgce", out, eye)
    return out.transpose(0, 5, 1, 3, 4, 2, 6).reshape(b, c, h, d)


def paged_decode_attention(q: jnp.ndarray, pool_k: jnp.ndarray,
                           pool_v: jnp.ndarray, lengths: jnp.ndarray,
                           block_tables: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           kv_bits: int = 0,
                           pages_per_program: Optional[int] = None,
                           window: Optional[int] = None) -> jnp.ndarray:
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
    width.  ``window`` (static): attend only the newest ``window`` tokens
    — table entries of pages wholly before them are never read, so a
    window layer's table may hold anything there (the allocator's
    ``window`` kind hands those blocks on).
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
        pages_per_program=pages_per_program, what="paged_decode_attention",
        window=window)
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
                            pages_per_program: Optional[int] = None,
                            window: Optional[int] = None,
                            tile_rows: Optional[int] = None,
                            block_rows: Optional[int] = None) -> jnp.ndarray:
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
    ``tile_rows`` (static; must divide C) cuts the chunk into walkers of
    that many rows, each walking only as far as its own last row sees
    (and, with a ``window``, from where its first row's begins): what a
    chunk of many rows x grouped heads needs to fit VMEM.
    ``block_rows`` (static): BLOCK-causal instead — a row sees its whole
    block of that many rows (``base``, ``chunk_len`` and a tile are whole
    blocks).  Returns [C, H, D] in q's dtype.
    """
    if block_table.ndim != 1:
        raise ValueError(
            f"block_table must be [pages], got {block_table.shape}")
    base = jnp.asarray(base, jnp.int32)
    end = base + jnp.asarray(chunk_len, jnp.int32)
    c = q.shape[0]
    tiles = 1 if not tile_rows or tile_rows >= c else c // tile_rows
    if c % tiles:
        raise ValueError(f"tile_rows {tile_rows} must divide the chunk's "
                         f"{c} rows")
    if tiles > 1:
        base = base + (c // tiles) * jnp.arange(tiles, dtype=jnp.int32)
        end = jnp.where(base < end, jnp.minimum(end, base + c // tiles), 0)
    out = _paged_attention(
        q.reshape(tiles, c // tiles, *q.shape[1:]), pool_k, pool_v, base,
        end, jnp.broadcast_to(block_table, (tiles,) + block_table.shape),
        sm_scale=sm_scale, interpret=interpret,
        k_scale=k_scale, v_scale=v_scale, kv_bits=kv_bits,
        pages_per_program=pages_per_program,
        what="paged_prefill_attention", window=window,
        block_rows=block_rows)
    return out.reshape(q.shape).astype(q.dtype)


def paged_block_attention(q: jnp.ndarray, pool_k: jnp.ndarray,
                          pool_v: jnp.ndarray, base: jnp.ndarray,
                          active: jnp.ndarray, block_tables: jnp.ndarray,
                          sm_scale: Optional[float] = None,
                          interpret: Optional[bool] = None,
                          pages_per_program: Optional[int] = None
                          ) -> jnp.ndarray:
    """The block lane of generation by diffusion over blocks: q [B, C, H,
    D] — EVERY slot's current block of C rows at positions ``base[b] ..
    base[b] + C - 1`` (their k/v already in the pool), each row seeing the
    whole prefix and its whole block, ``base[b] + C`` keys; ``active``
    [B] (0: the slot rides no block, zero rows back).  ONE walk of a
    slot's pages serves its C rows of every head.  Returns [B, C, H, D]
    in q's dtype."""
    c = q.shape[1]
    base = jnp.asarray(base, jnp.int32).reshape(q.shape[0])
    total = jnp.where(jnp.asarray(active).reshape(q.shape[0]) > 0,
                      base + c, 0)
    return _paged_attention(
        q, pool_k, pool_v, base, total, block_tables, sm_scale=sm_scale,
        interpret=interpret, k_scale=None, v_scale=None, kv_bits=0,
        pages_per_program=pages_per_program, what="paged_block_attention",
        block_rows=c).astype(q.dtype)


# ---------------------------------------------------------------------------
# the latent case (multi-head latent attention, absorbed form)
# ---------------------------------------------------------------------------
#: query rows one latent grid step contracts: whole positions of every
#: head (a decode slot is one position; a chunk walks tiles of this many
#: rows, each tile only as far as its own last position sees)
_MLA_TILE_ROWS = 1024
#: parts of a page group a latent grid step may stop after: the step
#: contracts the parts that hold live keys, not the whole group
_MLA_PARTS = 4


def latent_pool_lanes(latent: int, rope: int) -> int:
    """Lanes of one latent pool row ``[c | k_rope | 0 ..]``: the latent and
    the rotary key side by side, padded to whole 128-lane tiles: ONE
    operand, so that a page — 20 KB at 16 tokens, where a descriptor costs
    more than the bytes — is one DMA and not two, and a run of
    ``PAGE_RUN`` consecutive pool blocks, which the cache manager makes the
    rule, is one DMA of 160 KB (``_fetch_group``)."""
    return -(-(latent + rope) // LANES) * LANES


def _mla_kernel(meta_ref, bt_ref, run_ref, coff_ref, q_ref, pool_hbm, o_ref,
                buf, m_scr, l_scr, acc_scr, sem, *, sm_scale, block, pp, run,
                parts, lat):
    """The page walk of :func:`_kernel` for one WALKER — a tile of query
    rows of one slot — over a latent pool: a page is ``[block, W]`` rows
    ``[c | k_rope | 0]``, key AND value of every head; all heads' rows of
    the tile are plain rows of one contraction, ``score = [q_lat | q_rope]
    . row``, ``o_lat = sum p c`` over the row's first ``lat`` lanes: one
    page fetch serves both.  A page group comes in RUNS of ``run`` pages
    (:func:`_fetch_group`): one DMA where ``run_ref [slots, runs]`` says
    the run's pool blocks are consecutive and live, one a page elsewhere.
    A group is contracted in as many of its ``parts`` equal parts as hold
    live keys.

    ``meta_ref [6, W]``: (base, total, live groups, live steps before,
    next live walker or W, the slot whose table this walker reads)."""
    i, g = pl.program_id(0), pl.program_id(1)
    nwalk, ng = pl.num_programs(0), pl.num_programs(1)
    keys = pp * block
    base, total, live_groups = meta_ref[0, i], meta_ref[1, i], meta_ref[2, i]

    @pl.when(g < live_groups)
    def _live():
        half = _walk_step(i, g, nwalk, meta_ref, bt_ref, run_ref, pool_hbm,
                          buf, sem, block=block, pp=pp, run=run)

        @pl.when(g == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        qpos = base + coff_ref[...]                            # [R, 1]

        def contract(npages):
            """The group's first ``npages`` pages against the tile."""
            n = npages * block
            pos = g * keys + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
            visible = (pos <= qpos) & (pos < total)            # [R, n]
            v_valid = g * keys + jax.lax.broadcasted_iota(
                jnp.int32, (n, 1), 0) < total                  # [n, 1]
            rows = buf[half, :npages].reshape(n, buf.shape[-1])
            s = jax.lax.dot_general(q_ref[...], rows,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(visible, s * sm_scale, MASK_VALUE)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1,
                                                      keepdims=True)
            m_scr[...] = m_new
            # stale or recycled rows past the length: zeroed, not
            # down-weighted
            c = rows[:, :lat]
            c = jnp.where(v_valid, c, jnp.zeros_like(c))
            acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        # a walker's last group is as full as its length leaves it, and
        # every key contracted costs the same, fetched or not: contract
        # the live quarters of the group only (one copy of the body a
        # part; keys past the length inside the last live part are
        # masked and zeroed as before)
        part = pp // parts
        live_parts = -(-jnp.minimum(total - g * keys, keys) // (part * block))
        if parts == 1:
            contract(pp)
        else:
            for k in range(1, parts + 1):
                pl.when(live_parts == k)(
                    functools.partial(contract, k * part))

    @pl.when(g == ng - 1)
    def _out():
        inv = 1.0 / jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = jnp.where(live_groups > 0, inv * acc_scr[...],
                               0.0).astype(o_ref.dtype)


def _mla_pages_per_program(block: int, lanes: int, lat: int, itemsize: int,
                           rows: int, npages: int,
                           override: Optional[int]) -> int:
    """Pages to a grid step, from the same budget as the k/v kernel: a
    page in both buffer halves, and per key the f32 score and probability
    rows plus the zeroed copy of the latent part."""
    if override is not None:
        if override < 1:
            raise ValueError(
                f"pages_per_program must be >= 1, got {override}")
        return min(override, npages)
    per_key = (2 * lanes + lat) * itemsize + 2 * 4 * rows
    pp = max(1, _VMEM_GROUP_BYTES // (block * per_key))
    return min(1 << (pp.bit_length() - 1), npages)


def _walkers(base, total, ntile: int, tp: int, keys: int, ngroups: int):
    """The walk of ``b * ntile`` walkers — tile ``j`` of slot ``i`` holds
    positions ``base[i] + j * tp ..`` and sees keys below ``min(total[i],
    its last position + 1)`` — as a latent kernel's ``meta [6, W]``:
    (first position, keys seen, live groups of ``keys`` keys, live steps
    before, next live walker or W, the slot whose table the walker
    reads)."""
    b = base.shape[0]
    nwalk = b * ntile
    first = (base[:, None]
             + tp * jnp.arange(ntile, dtype=jnp.int32)[None, :]).reshape(-1)
    upto = jnp.repeat(total, ntile)
    upto = jnp.where(first < upto, jnp.minimum(upto, first + tp), 0)
    live = jnp.clip(-(-upto // keys), 0, ngroups)
    walker = jnp.where(live > 0, jnp.arange(nwalk, dtype=jnp.int32), nwalk)
    later = jnp.append(jax.lax.cummin(walker, reverse=True)[1:], nwalk)
    slot = jnp.repeat(jnp.arange(b, dtype=jnp.int32), ntile)
    return jnp.stack([first, upto, live, jnp.cumsum(live) - live, later,
                      slot])


def _mla_paged_attention(q_lat, q_rope, pool, base, total, block_tables, *,
                         sm_scale, interpret, pages_per_program, what):
    """q_lat [B, C, H, R], q_rope [B, C, H, Dr] — C query positions a
    slot at absolute positions ``base[b] ..``; ``pool [num_blocks, block,
    W]`` with ``W >= R + Dr`` lanes a row, ``[c | k_rope | 0]``.  Returns
    o_lat [B, C, H, R].  Whether a run of ``PAGE_RUN`` pages is fetched
    with one DMA or page by page is read from the table and the lengths
    (``_grouped_tables``); the result is the same either way."""
    b, c, h, lat = q_lat.shape
    rope = q_rope.shape[-1]
    if pool.ndim != 3 or pool.shape[2] < lat + rope:
        raise ValueError(
            f"{what}: the latent pool must be [num_blocks, block, >= "
            f"{lat} + {rope}], got {pool.shape}")
    block, lanes = pool.shape[1:]
    interpret = resolve_interpret(interpret)
    if not interpret and (lat % LANES or lanes % LANES):
        raise ValueError(
            f"{what}: compiled for the TPU, the latent part ({lat}) and "
            f"the whole pool row ({lanes}) must be whole {LANES}-lane "
            f"tiles")
    # walkers: tiles of whole positions, rows position-major
    tp = max(1, min(c, _MLA_TILE_ROWS // h))
    while c % tp:
        tp -= 1
    ntile, rows = c // tp, tp * h
    nwalk = b * ntile
    npages = block_tables.shape[1]
    pp = _mla_pages_per_program(block, lanes, lat, pool.dtype.itemsize, rows,
                                npages, pages_per_program)
    run = math.gcd(pp, PAGE_RUN)
    base = jnp.asarray(base, jnp.int32).reshape(b)
    total = jnp.asarray(total, jnp.int32).reshape(b)
    tables, runs = _grouped_tables(block_tables, total, pp, block, run)
    ngroups = tables.shape[1] // pp
    meta = _walkers(base, total, ntile, tp, pp * block, ngroups)
    dtype = pool.dtype
    # the query laid out like a pool row: [q_lat | q_rope | 0]
    q = jnp.concatenate(
        [q_lat.astype(dtype), q_rope.astype(dtype),
         jnp.zeros((b, c, h, lanes - lat - rope), dtype)], axis=-1
    ).reshape(nwalk, rows, lanes)
    coff = (jnp.arange(rows, dtype=jnp.int32) // h).reshape(rows, 1)

    def qspec(width):
        return pl.BlockSpec((None, rows, width), lambda i, g, *_: (i, 0, 0))

    out = pl.pallas_call(
        functools.partial(_mla_kernel, sm_scale=sm_scale, block=block,
                          pp=pp, run=run, parts=math.gcd(pp, _MLA_PARTS),
                          lat=lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nwalk, ngroups),
            in_specs=[pl.BlockSpec((rows, 1), lambda i, g, *_: (0, 0)),
                      qspec(lanes), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec(lat),
            scratch_shapes=[
                pltpu.VMEM((2, pp, block, lanes), dtype),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, lat), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 1))],
        ),
        out_shape=jax.ShapeDtypeStruct((nwalk, rows, lat), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="mla_paged_attention",
    )(meta, tables, runs, coff, q, pool)
    return out.reshape(b, c, h, lat)


def mla_paged_decode_attention(q_lat, q_rope, pool, lengths, block_tables,
                               sm_scale: float,
                               interpret: Optional[bool] = None,
                               pages_per_program: Optional[int] = None):
    """Latent decode: q_lat [B, H, R] (the no-rope query already through
    ``W_UK``), q_rope [B, H, Dr]; one token per slot at position
    ``lengths - 1``; 0 = inactive (zero rows back).  Returns the latent
    output [B, H, R] — the caller applies ``W_UV``."""
    lengths = jnp.asarray(lengths, jnp.int32).reshape(q_lat.shape[0])
    return _mla_paged_attention(
        q_lat[:, None], q_rope[:, None], pool, lengths - 1, lengths,
        block_tables, sm_scale=sm_scale, interpret=interpret,
        pages_per_program=pages_per_program,
        what="mla_paged_decode_attention")[:, 0].astype(q_lat.dtype)


def mla_paged_prefill_attention(q_lat, q_rope, pool, base, chunk_len,
                                block_table, sm_scale: float,
                                interpret: Optional[bool] = None,
                                pages_per_program: Optional[int] = None):
    """Latent causal chunk of ONE slot: q_lat [C, H, R], q_rope
    [C, H, Dr] at positions ``base ..``; the chunk's own rows are already
    in the pool.  Rows at or past ``chunk_len`` come back as finite
    garbage or zeros; callers ignore them."""
    base = jnp.asarray(base, jnp.int32)
    return _mla_paged_attention(
        q_lat[None], q_rope[None], pool, base,
        base + jnp.asarray(chunk_len, jnp.int32), block_table[None],
        sm_scale=sm_scale, interpret=interpret,
        pages_per_program=pages_per_program,
        what="mla_paged_prefill_attention")[0].astype(q_lat.dtype)


def mla_paged_reference(q_lat, q_rope, pool, base, total, block_tables,
                        sm_scale: float):
    """float32 jnp reference of the latent kernel: q_lat [B, C, H, R],
    q_rope [B, C, H, Dr]; gathers each slot's pages and runs masked dense
    attention in the absorbed form.  Dead slots give zero rows."""
    b, c, h, lat = q_lat.shape
    dr = q_rope.shape[-1]
    block, npages = pool.shape[1], block_tables.shape[1]

    def one(ql, qr, table, bs, tot):
        rows = pool[table].reshape(npages * block, -1).astype(jnp.float32)
        cc, rr = rows[:, :lat], rows[:, lat:lat + dr]
        s = (jnp.einsum("chr,sr->chs", ql.astype(jnp.float32), cc)
             + jnp.einsum("chd,sd->chs", qr.astype(jnp.float32), rr)
             ) * sm_scale
        pos = jnp.arange(npages * block)
        qpos = bs + jnp.arange(c)[:, None, None]
        s = jnp.where((pos <= qpos) & (pos < tot), s, -1e30)
        cc = jnp.where((pos < tot)[:, None], cc, 0.0)
        o = jnp.einsum("chs,sr->chr", jax.nn.softmax(s, axis=-1), cc)
        return jnp.where(tot > 0, o, 0.0)

    return jax.vmap(one)(q_lat, q_rope, block_tables,
                         jnp.asarray(base, jnp.int32),
                         jnp.asarray(total, jnp.int32))


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
               v_scale, kv_bits, window=None, block_rows=None):
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
        coff = jnp.arange(c)
        if block_rows:
            coff = coff // block_rows * block_rows + (block_rows - 1)
        qpos = bs + coff[:, None, None]
        seen = (pos <= qpos) & (pos < tot)
        live = pos < tot
        if window is not None:
            seen = seen & (pos > qpos - window)
            live = live & (pos > bs - window)
        s = jnp.where(seen, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        v = jnp.where(live[:, None, None], v, 0.0)  # NaN-safe
        return jnp.einsum("chs,shd->chd", p, v.astype(jnp.float32))

    return jax.vmap(one)(q, block_tables, base, total)


def paged_prefill_reference(q, pool_k, pool_v, base, chunk_len,
                            block_table, k_scale=None, v_scale=None,
                            kv_bits=0, window=None, block_rows=None):
    """jnp reference for :func:`paged_prefill_attention`.  Padding
    queries (index >= chunk_len) are returned as zeros."""
    base = jnp.asarray(base, jnp.int32)
    out = _reference(q[None], pool_k, pool_v, base[None],
                     (base + chunk_len)[None], block_table[None], k_scale,
                     v_scale, kv_bits, window, block_rows)[0]
    valid = (jnp.arange(q.shape[0]) < chunk_len)[:, None, None]
    return jnp.where(valid, out, 0.0).astype(q.dtype)


def paged_attention_reference(q, pool_k, pool_v, lengths, block_tables,
                              k_scale=None, v_scale=None, kv_bits=0,
                              window=None):
    """jnp reference for :func:`paged_decode_attention`.
    O(B·pages·block) gather — test-scale only."""
    lengths = jnp.asarray(lengths, jnp.int32)
    out = _reference(q[:, None], pool_k, pool_v, lengths - 1, lengths,
                     block_tables, k_scale, v_scale, kv_bits, window)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


def supports(head_dim: int) -> bool:
    """Sublane-aligned head dim; lengths and batch are unbounded (KV
    pages stream through VMEM).  The lane-tiling conditions depend on
    the kv head count and ``kv_bits`` as well and are checked — with a
    message — when the kernel is built for the TPU."""
    return head_dim % 8 == 0
