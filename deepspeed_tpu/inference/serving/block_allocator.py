"""Paged KV-cache block allocator (host side) with prefix caching.

The bookkeeping half of PagedAttention (Kwon et al., SOSP '23): device
HBM holds one preallocated pool of fixed-size KV blocks
(``models/transformer.py init_paged_cache``); this allocator hands
block ids to sequences and keeps the pool leak-free.  Everything here is
pure Python over integers — no jax, so the policy is unit-testable at
property-test speed and the scheduler can ask "does this admission fit"
without touching the device.

Prefix caching (RadixAttention-style, SGLang / vLLM automatic prefix
caching): FULL blocks are content-addressed by a hash chained over the
block's token ids and its prefix's hash, so two sequences that share a
prefix (system prompts, few-shot templates, a preempted request
resubmitting its own history) resolve to the SAME pool blocks and skip
prefill for everything but their uncached tail.  A freed block whose
content is registered does not return to the raw free list — it parks
in an LRU of refcount-0 *cached* blocks that still serve hits until
capacity pressure evicts them (oldest first).  The chain property means
a hit walk stops at the first miss, so a stale child entry whose parent
was evicted is unreachable, never wrong.

Invariants (``assert_consistent`` checks them, tests fuzz them):

  * block 0 is RESERVED (the null block): padded block-table entries and
    inactive decode slots point at it so the kernel's index_map always
    lands on valid memory; it is never handed out and never freed.
  * every other block is, at all times, exactly one of: among the free
    blocks, parked in the cached-LRU (refcount 0, hash-registered), or
    referenced by >= 1 sequences (refcount > 1 through :meth:`fork`'s
    tail sharing or prefix-cache hits).
  * ``free``/``allocate`` raise :class:`BlockPoolError` on double-free,
    unknown sequence ids, and exhaustion — a serving scheduler bug
    surfaces as a loud error, not a silently corrupted cache.

Runs (docs/serving.md "Runs"): a kernel that walks a narrow pool pays a
descriptor a page, so blocks are handed out such that entries ``j *
PAGE_RUN .. (j + 1) * PAGE_RUN - 1`` of a sequence's table are
CONSECUTIVE pool blocks wherever the pool allows, and the kernel fetches
such a run with one DMA.  The pool, block 0 aside, is groups of
``PAGE_RUN`` consecutive ids; :meth:`PagedBlockAllocator._take_block`
prefers the id after a table's last block, then a block of an idle group
(none of whose blocks is referenced), then what it always did
(:meth:`PagedBlockAllocator._pop_block`).  Capacity reads as before: a
group's unused tail is free capacity that the fallback hands to anyone,
and a registered block is still evicted only when no unregistered one is
free.

Layer KINDS (docs/serving.md "The cache manager's kinds of state"): a
model whose layers do not all keep the same thing a token gives the
allocator more than one kind to hand out, all under one sequence id and
released by the one :meth:`free`:

  * ``full`` — the pool above: a block a ``block_size`` tokens for as long
    as the sequence lives.  Every model has it; the prefix cache, the
    host tier and the fork are its alone.
  * ``window`` (:meth:`add_window_kind`) — a pool of its own (its own
    block ids, its own null block 0) for layers that attend the newest
    ``window`` tokens only: :meth:`window_reserve` gives a sequence the
    pages that cover the positions a dispatch reads and writes and hands
    the pages wholly before them back, so a sequence holds a bounded
    number of them however long it grows; a page it gave back reads
    NULL in its table (the kernel never looks there).  The pool is
    handed out in GROUPS of ``PAGE_RUN`` consecutive ids, group ``g`` =
    ``1 + PAGE_RUN * g .. PAGE_RUN * (g + 1)``: a sequence takes a free
    group when its table reaches a page ``p`` with ``p % PAGE_RUN == 0``,
    page ``p`` is the group's block ``p % PAGE_RUN`` (the table is indexed
    by the sequence's absolute page, so every run of the table is
    consecutive blocks by construction), and the group goes back when
    its LAST page is handed back, or at :meth:`free`.  What the kind
    counts (``window_held_max``, ``num_used_by_kind``) is PAGES a window
    still reaches; a group's other places are the pool's slack, which
    :func:`window_pool_blocks` sizes in.
  * ``state`` (:meth:`attach_state`) — not pages at all: what a layer
    keeps per SEQUENCE (a recurrent state), indexed by the decode slot
    the scheduler seated it in.  The allocator only records who holds
    which slot's state, so that a drained server can show it holds none.

Tiered host cache (docs/serving.md &sect;Tiered prefix cache): with
:meth:`PagedBlockAllocator.attach_host_tier` wired, eviction becomes
*demotion* — the LRU walk in :meth:`_pop_block` hands the dying
block's bytes to the engine's spill callback (keyed by the same chain
digest) before unregistering it, and the :meth:`allocate` hit walk
extends past the device index into the host tier: a host hit claims a
pool block immediately, registers the digest, and queues a *promotion
job* (the encoded payload, engine-drained asynchronously during the
admission/prefill window).  Until the payload lands the block is
*pending*: refcounted and registered like any hit, but its pool bytes
are garbage — the scheduler must not prefill past it
(:meth:`seq_has_pending`), and a cancel (free/preempt before landing)
returns the bytes to the host tier, never the block to the cached LRU.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

from ...ops.transformer.paged_decode_attention import PAGE_RUN
from ...runtime.resilience.errors import ServingError
from ...runtime.resilience.fault_injection import get_fault_injector

NULL_BLOCK = 0

#: chain root: the "hash" of the empty prefix
ROOT_HASH = b""

#: bytes of each per-row per-head dequant scale (f32) stored alongside
#: a quantized pool block
_SCALE_BYTES = 4


def kv_block_bytes(block_size: int, kv_heads: int, head_dim: int,
                   kv_bits: int = 0, cache_itemsize: int = 2,
                   model_shards: int = 1) -> int:
    """PER-CHIP device HBM bytes one pool block costs across k AND v,
    including the per-row per-head f32 scales a quantized pool stores
    alongside (``serving.kv_cache_bits``).  ``cache_itemsize`` is the
    unquantized pool's dtype width (2 = bf16).  ``model_shards`` is the
    serving mesh's model-axis size: each chip then holds
    ``kv_heads / model_shards`` of every block (scale planes included),
    so the per-block cost divides by it — the data axis replicates the
    pool and changes nothing here.  Pure ints — the capacity-planning
    mirror of ``models/transformer.py init_paged_cache``, pinned
    against it by test."""
    if kv_bits not in (0, 4, 8):
        raise ValueError(f"kv_bits must be 0, 4 or 8, got {kv_bits}")
    if model_shards < 1 or kv_heads % model_shards:
        raise ValueError(
            f"model_shards ({model_shards}) must be >= 1 and divide "
            f"kv_heads ({kv_heads})")
    kv_heads //= model_shards
    if kv_bits == 0:
        per_row = kv_heads * head_dim * cache_itemsize
    else:
        values = kv_heads * ((head_dim * kv_bits + 7) // 8)
        per_row = values + kv_heads * _SCALE_BYTES
    return 2 * block_size * per_row          # k + v


def latent_block_bytes(block_size: int, latent_width: int,
                       rope_width: int, cache_itemsize: int = 2,
                       layers: int = 1, index_width: int = 0,
                       index_layers: int = 0) -> int:
    """Device HBM bytes one pool block costs in ``layers`` attention
    sublayers of a LATENT pool (``models/latent_moe.py init_paged_cache``;
    ONE by default): a token's row is its latent (``latent_width`` values:
    key and value of every head) and its rotary key side by side, padded
    to whole 128-lane tiles.  A model holds ``ATTN_SUBLAYERS * num_layers``
    sublayers (2 a layer in the shortcut block, 1 in the sandwich block).
    A model with a learned sparse selection holds a second kind of row
    under the same table (``models/sparse_latent_moe.py``): an indexer key
    of ``index_width`` values a token in each of its ``index_layers``
    layers that compute a selection.  Pure ints, pinned against the model
    by test, like :func:`kv_block_bytes`."""
    lanes = -(-(latent_width + rope_width) // 128) * 128
    return block_size * cache_itemsize * (layers * lanes
                                          + index_layers * index_width)


def blocks_for_budget(budget_bytes: int, block_size: int, kv_heads: int,
                      head_dim: int, kv_bits: int = 0,
                      cache_itemsize: int = 2,
                      model_shards: int = 1) -> int:
    """Pool blocks (INCLUDING the reserved null block 0) a PER-CHIP
    device HBM budget admits at the given KV width — the
    ``kv_cache_bits`` sizing rule: the same budget holds ~2x the blocks
    at 8-bit and ~3.8x at packed 4-bit, which is the concurrency the
    scheduler can actually admit.  With ``model_shards`` > 1 the same
    per-chip budget holds ``model_shards`` x the blocks, because each
    chip carries only its ``kv_heads / model_shards`` slice."""
    return budget_bytes // kv_block_bytes(block_size, kv_heads, head_dim,
                                          kv_bits, cache_itemsize,
                                          model_shards)


class BlockPoolError(ServingError):
    """Allocator invariant violation (double free, exhaustion, unknown
    sequence) — scheduler bugs, never user input.  Part of the
    resilience layer's :class:`ServingError` branch."""


class PromoteJob:
    """One queued host->device block promotion: the claimed pool block,
    the chain digest that keyed the host hit, and the encoded payload
    the engine must decode + scatter into the pool."""

    __slots__ = ("digest", "block", "payload")

    def __init__(self, digest: bytes, block: int, payload):
        self.digest = digest
        self.block = block
        self.payload = payload


def _chain_hash(prev: bytes, token_ids: Tuple[int, ...]) -> bytes:
    """Content hash of one full block, chained on its prefix's hash —
    equal prefixes produce equal chains, the radix-tree property
    flattened into a dict.  blake2b (not Python's builtin ``hash``)
    because a hit is trusted WITHOUT comparing tokens: the builtin
    tuple hash is 64-bit and its collisions are offline-constructible,
    which would let one request's chain resolve to another prompt's KV
    blocks — served-wrong-tokens corruption, not a missed reuse.  A
    128-bit keyed-construction digest makes that a non-event."""
    h = hashlib.blake2b(prev, digest_size=16)
    for t in token_ids:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.digest()


def window_groups(pages: int) -> int:
    """Groups of ``PAGE_RUN`` that ``pages`` consecutive pages of a table
    touch at most, wherever the first of them falls in its group."""
    return -(-(pages - 1) // PAGE_RUN) + 1


def window_pool_blocks(num_slots: int, held_decoding: int,
                       held_chunk: int) -> int:
    """Blocks of a ``window`` pool that holds every slot's bound at once:
    all but one slot decoding (``held_decoding`` pages), one with a chunk
    in flight (``held_chunk``), each in whole groups, and the null
    block."""
    return 1 + PAGE_RUN * ((num_slots - 1) * window_groups(held_decoding)
                           + window_groups(held_chunk))


class PagedBlockAllocator:
    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_cache: bool = True):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved null "
                f"block), got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.enable_prefix_cache = enable_prefix_cache
        # the raw free blocks, an ordered set (a dict's keys): LIFO at its
        # end — recently-freed blocks are re-handed first — and removal
        # by id in O(1), which taking the NEXT id of a run needs
        self._free: Dict[int, None] = dict.fromkeys(
            range(num_blocks - 1, 0, -1))
        self._ref = [0] * num_blocks
        # runs (module docstring): referenced blocks a group, and the
        # whole groups that have none — those without a registered block
        # at the front (a fresh pool's in ascending order), those that
        # hold cached content behind them, least recently idled first
        self._group_live = [0] * (-(-(num_blocks - 1) // PAGE_RUN))
        self._whole_groups = (num_blocks - 1) // PAGE_RUN
        self._idle: "OrderedDict[int, None]" = OrderedDict.fromkeys(
            range(self._whole_groups))
        self._tables: Dict[str, List[int]] = {}
        # prefix cache: chained content hash -> block id, and the reverse
        # map used to unregister on eviction/recycle
        self._hash_to_block: Dict[bytes, int] = {}
        self._block_hash: List[Optional[bytes]] = [None] * num_blocks
        # per-sequence chain hashes of its full blocks, in order —
        # extended incrementally by allocate()'s hit walk and
        # commit_cached(), so neither ever rehashes from the root
        # (an O(len²) trap: the engine commits at EVERY block boundary)
        self._chain: Dict[str, List[bytes]] = {}
        # refcount-0 blocks whose content is still registered: insertion
        # order == least-recently-used first (move_to_end on every hit)
        self._cached_lru: "OrderedDict[int, None]" = OrderedDict()
        # tiered host cache (attach_host_tier): spilled-block store,
        # the engine's spill callback, and promotion bookkeeping —
        # blocks claimed by a host hit whose payload has not landed yet
        self._host = None
        self._spill_fn = None
        # prefill-class engines publish chains to the fabric but never
        # claim from it (claiming would steal entries the decode class
        # is about to promote); the engine flips this per role
        self.allow_claims = True
        self._pending_blocks: Dict[int, bytes] = {}
        self._promote_jobs: "OrderedDict[bytes, PromoteJob]" = OrderedDict()
        # cumulative stats the serving engine polls into the metrics
        # registry (counters there, plain ints here — no jax/obs import)
        self.hit_tokens_total = 0
        self.evictions_total = 0
        self.host_hit_tokens_total = 0
        # the other layer kinds (module docstring): the window kind's
        # pool and per-sequence sparse tables, and who holds which
        # slot's per-sequence state
        self.window_tokens = 0
        # free GROUPS, each by its first block; a sequence's table by its
        # absolute page, the groups it holds by page // PAGE_RUN (NULL
        # once given back), and how many leading pages it has given back
        self._wfree: List[int] = []
        self._wtables: Dict[str, List[int]] = {}
        self._wgroups: Dict[str, List[int]] = {}
        self._wdead: Dict[str, int] = {}
        self.window_blocks = 0
        self.state_slots = 0
        self._state_slots: Dict[str, int] = {}
        #: the most window pages one sequence ever held at a reserve, by
        #: what rode for it, and the pages handed back so far
        self.window_held_max = {"decode": 0, "chunk": 0}
        self.window_freed_total = 0

    # -- capacity ----------------------------------------------------------
    @property
    def usable_blocks(self) -> int:
        """Pool capacity available to sequences (excludes the null block)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        """Blocks allocatable right now: the raw free list plus the
        refcount-0 cached blocks (a cached block is capacity first,
        cache second — allocation evicts it)."""
        return len(self._free) + len(self._cached_lru)

    @property
    def num_cached(self) -> int:
        """Refcount-0 blocks currently parked in the prefix-cache LRU."""
        return len(self._cached_lru)

    @property
    def num_used(self) -> int:
        """Blocks referenced by live sequences (cached-LRU blocks are
        reclaimable, so they do not count as used)."""
        return self.usable_blocks - self.num_free

    def blocks_for_tokens(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` cache rows (>= 1)."""
        return max(1, -(-tokens // self.block_size))

    def can_allocate(self, n_blocks: int) -> bool:
        return self.num_free >= n_blocks

    # -- host tier ---------------------------------------------------------
    def attach_host_tier(self, host_cache, spill_fn) -> None:
        """Wire the tiered host cache in (engine-owned: pools must
        exist before the spill/promote data paths do, so this is a
        post-construction attach).  ``spill_fn(block, digest)`` is
        called for every registered block the LRU evicts, BEFORE its
        registration drops; it must never raise — a failed spill
        degrades to a plain eviction inside the engine."""
        self._host = host_cache
        self._spill_fn = spill_fn

    def _claim_host_hit(self, h: bytes, table: List[int]) -> Optional[int]:
        """Extend the hit walk into the host tier: claim the encoded
        payload out of the host cache, claim a pool block for it (the
        next of ``table``), and queue the promotion.  Returns the
        (pending) block id, or None on a genuine miss / no pool capacity
        (the entry then stays host-resident and warm — a miss, never an
        error)."""
        if not self.allow_claims:
            return None
        if self._host is None or not self._host.contains(h):
            return None
        if not (self._free or self._cached_lru):
            return None
        payload = self._host.claim(h)
        if payload is None:
            return None
        b = self._take_block(table)
        self._ref[b] = 1
        self._block_hash[b] = h
        self._hash_to_block[h] = b
        self._pending_blocks[b] = h
        self._promote_jobs[h] = PromoteJob(h, b, payload)
        return b

    def _drop_host_duplicate(self, h: bytes) -> None:
        """A device hit on a digest the host tier also holds: the host
        copy is redundant (a prefill publisher may have republished
        content this replica never evicted) — drop it eagerly so the
        cross-tier disjointness self-heals instead of waiting for an
        orphan sweep."""
        if self._host is not None:
            self._host.discard(h)

    def seq_chain(self, seq_id: str) -> List[bytes]:
        """The chained content digests of ``seq_id``'s committed full
        blocks, in block order — the transport keys a prefill worker
        publishes to the KV fabric (digest i keys ``table[i]``)."""
        return list(self._chain.get(seq_id, ()))

    def pending_jobs(self) -> List[PromoteJob]:
        """Queued promotions, oldest first (the engine drains up to
        ``promote_parallelism`` per step)."""
        return list(self._promote_jobs.values())

    @property
    def num_pending(self) -> int:
        return len(self._promote_jobs)

    def seq_has_pending(self, seq_id: str) -> bool:
        """True while any block in ``seq_id``'s table awaits its
        promotion payload — the scheduler's PROMOTING predicate: the
        request must not prefill (its compiled gather would read
        garbage rows) until this turns False."""
        table = self._tables.get(seq_id)
        if table is None:
            return False
        return any(b in self._pending_blocks for b in table)

    def promotion_landed(self, digest: bytes) -> None:
        """The engine scattered the payload into the pool: the block
        graduates to a normal registered, refcounted block."""
        job = self._promote_jobs.pop(digest, None)
        if job is not None:
            self._pending_blocks.pop(job.block, None)

    def promotion_failed(self, digest: bytes) -> List[Tuple[str, int]]:
        """The payload could not be landed (fatal fault / exhausted
        retries): drop the job AND the registration — the block's pool
        bytes are garbage, so it must never serve a future hit — and
        report every ``(seq_id, block_index)`` holding it so the
        engine can roll those requests back to recompute.  The host
        entry stays dropped (it was claimed): never a wrong block,
        recompute rewrites identical content."""
        job = self._promote_jobs.pop(digest, None)
        if job is None:
            return []
        self._pending_blocks.pop(job.block, None)
        self._unregister(job.block)
        affected: List[Tuple[str, int]] = []
        for seq, table in self._tables.items():
            for i, b in enumerate(table):
                if b == job.block:
                    affected.append((seq, i))
        return affected

    def _cancel_pending(self, block: int) -> None:
        """A pending block's last reference dropped before its payload
        landed: unregister it, give the payload back to the host tier
        (the prefix stays warm), and return the block to the RAW free
        list — un-landed pool bytes must never park in the cached LRU
        where they could be spilled or hit."""
        h = self._pending_blocks.pop(block)
        job = self._promote_jobs.pop(h, None)
        self._unregister(block)
        if job is not None and self._host is not None:
            self._host.release_claim(h, job.payload)
        self._free[block] = None

    # -- internal: free-list / LRU plumbing --------------------------------
    def _pop_block(self) -> int:
        """Claim one block, always unregistered, wherever it lies: the
        raw free blocks first (never registered — `_release_block` parks
        those in the LRU), else evict the least-recently-used cached
        block.  What :meth:`_take_block` falls back to."""
        if self._free:
            return self._free.popitem()[0]
        if self._cached_lru:
            return self._evict(next(iter(self._cached_lru)))   # LRU end
        raise BlockPoolError("pool exhausted")

    def _evict(self, b: int) -> int:
        """Take cached block ``b`` out of the LRU and drop its
        registration — the pool page is about to be overwritten."""
        del self._cached_lru[b]
        h = self._block_hash[b]
        if h is not None and self._spill_fn is not None:
            # demotion instead of amnesia: hand the block's bytes
            # to the engine's spill path (device gather -> wire
            # codec -> host tier) while the pool content is still
            # valid.  The callback handles its own faults — by
            # contract it never raises, so a failed spill degrades
            # to the plain eviction below.
            self._spill_fn(b, h)
        self._unregister(b)
        self.evictions_total += 1
        return b

    # -- runs --------------------------------------------------------------
    def _take_block(self, table: Sequence[int]) -> int:
        """Claim the block that becomes entry ``k = len(table)`` of a
        sequence's table, so that entries ``j * PAGE_RUN .. (j + 1) *
        PAGE_RUN - 1`` are consecutive pool blocks wherever the pool
        allows (the latent kernel fetches such a run with one DMA).  In
        this order: the id after ``table[-1]`` when ``k`` is inside a
        run and that id lies in the same group and has no reference;
        else offset ``k % PAGE_RUN`` of the first idle group (one with no
        registered block before one that holds cached content, least
        recently idled first); else :meth:`_pop_block`.  A registered
        block is taken (evicted) only when no unregistered one is free,
        as :meth:`_pop_block` has it."""
        k = len(table) % PAGE_RUN
        after = table[-1] + 1 if k else NULL_BLOCK
        idle = 1 + next(iter(self._idle)) * PAGE_RUN + k if self._idle \
            else NULL_BLOCK
        if after and (after - 1) % PAGE_RUN and self._takes(after):
            b = after
        elif idle and self._takes(idle):
            b = idle
        else:
            b = self._pop_block()
        self._enter(b)
        return b

    def _takes(self, b: int) -> bool:
        """Take unreferenced block ``b`` out of where it waits, if that
        evicts nothing while an unregistered block is free."""
        if b in self._free:
            del self._free[b]
            return True
        if not self._free and b in self._cached_lru:
            self._evict(b)
            return True
        return False

    def _enter(self, b: int) -> None:
        """Block ``b`` got its first reference: its group is not idle."""
        g = (b - 1) // PAGE_RUN
        if self._group_live[g] == 0:
            self._idle.pop(g, None)
        self._group_live[g] += 1

    def _leave(self, b: int) -> None:
        """Block ``b`` lost its last reference (and is back among the
        free or the cached): a whole group with none left is idle."""
        g = (b - 1) // PAGE_RUN
        self._group_live[g] -= 1
        if self._group_live[g] == 0 and g < self._whole_groups:
            first = 1 + g * PAGE_RUN
            self._idle[g] = None
            if not any(self._block_hash[first:first + PAGE_RUN]):
                self._idle.move_to_end(g, last=False)

    def _unregister(self, block: int) -> None:
        h = self._block_hash[block]
        if h is not None:
            if self._hash_to_block.get(h) == block:
                del self._hash_to_block[h]
            self._block_hash[block] = None

    def _release_block(self, block: int) -> None:
        """Refcount hit zero: registered content parks in the cached
        LRU (most-recently-used end); unregistered blocks go straight
        back to the free list."""
        if self._block_hash[block] is not None:
            # fresh insertion lands at the MRU end (the block cannot
            # already be parked: it was refcounted until this call)
            self._cached_lru[block] = None
        else:
            self._free[block] = None

    def _claim_cached(self, block: int) -> None:
        """A cache hit revives a parked block: out of the LRU, refcount
        1, registration kept (it can be hit again while shared)."""
        del self._cached_lru[block]
        self._ref[block] = 1
        self._enter(block)

    # -- the other layer kinds ---------------------------------------------
    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kinds of state this allocator hands out."""
        return ("full",) + (("window",) if self.window_tokens else ()) + (
            ("state",) if self.state_slots else ())

    def add_window_kind(self, num_blocks: int, window_tokens: int) -> None:
        """Give the allocator a ``window`` kind: a pool of ``num_blocks``
        blocks (block 0 its null block, then whole groups of ``PAGE_RUN``)
        for layers that attend the newest ``window_tokens`` tokens."""
        if self.window_tokens:
            raise BlockPoolError("the window kind is already there")
        if num_blocks <= PAGE_RUN or window_tokens < 1:
            raise ValueError(
                f"a window kind needs > {PAGE_RUN} blocks (a group beside "
                f"the null block) and a window >= 1, got {num_blocks} and "
                f"{window_tokens}")
        self.window_tokens = window_tokens
        self.window_blocks = num_blocks
        groups = (num_blocks - 1) // PAGE_RUN
        self._wfree = [1 + PAGE_RUN * g for g in reversed(range(groups))]

    def add_state_kind(self, num_slots: int) -> None:
        """Give the allocator a ``state`` kind: ``num_slots`` per-sequence
        states, one a decode slot."""
        self.state_slots = num_slots

    def window_reserve(self, seq_id: str, first_row: int, end_row: int,
                       lane: str = "decode") -> int:
        """Before a dispatch that writes ``seq_id``'s rows ``first_row ..
        end_row - 1`` into the window layers: the sequence gets the pages
        up to ``end_row`` and gives back every page wholly before the
        first position the dispatch's rows attend, ``first_row -
        (window - 1)``.  Returns the pages it gave back.  A pool too small
        raises :class:`BlockPoolError`: the engine sizes the pool so that
        every slot's bound fits at once."""
        if seq_id not in self._tables:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        table = self._wtables.setdefault(seq_id, [])
        groups = self._wgroups.setdefault(seq_id, [])
        need = -(-end_row // self.block_size)
        while len(table) < need:
            at = len(table) % PAGE_RUN
            if at == 0:
                if not self._wfree:
                    raise BlockPoolError(
                        f"window pool exhausted: {seq_id!r} needs page "
                        f"{len(table)}, 0 groups free of "
                        f"{(self.window_blocks - 1) // PAGE_RUN}")
                groups.append(self._wfree.pop())
            table.append(groups[-1] + at)
        freed = self.window_trim(seq_id, first_row)
        held = len(table) - self._wdead.get(seq_id, 0)
        if held > self.window_held_max[lane]:
            self.window_held_max[lane] = held
        return freed

    def window_trim(self, seq_id: str, next_row: int) -> int:
        """Hand back ``seq_id``'s pages wholly before the window of
        ``next_row``, the first row a later dispatch will write."""
        table = self._wtables.get(seq_id)
        if not table:
            return 0
        # the pages given back are a prefix of the table: _wdead its length
        was = self._wdead.get(seq_id, 0)
        dead = min(max(0, next_row - (self.window_tokens - 1))
                   // self.block_size, len(table))
        groups = self._wgroups[seq_id]
        for page in range(was, dead):
            table[page] = NULL_BLOCK
            if page % PAGE_RUN == PAGE_RUN - 1:     # its group's last
                self._wfree.append(groups[page // PAGE_RUN])
                groups[page // PAGE_RUN] = NULL_BLOCK
        if dead > was:
            self._wdead[seq_id] = dead
            self.window_freed_total += dead - was
        return max(0, dead - was)

    def window_pages_held(self, seq_id: str) -> Tuple[int, List[int]]:
        """``(the first page still held, its and the later pages'
        blocks)``: the window kind's table without the pages given back
        (what a dispatch's operand needs: at most the chunk bound)."""
        dead = self._wdead.get(seq_id, 0)
        return dead, self._wtables.get(seq_id, [])[dead:]

    def attach_state(self, seq_id: str, slot: int) -> None:
        """``seq_id`` holds decode slot ``slot``'s per-sequence state
        until :meth:`free`."""
        if seq_id not in self._tables:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        if not 0 <= slot < self.state_slots or \
                slot in self._state_slots.values():
            raise BlockPoolError(
                f"slot {slot}'s state is not there to hold, or is held")
        self._state_slots[seq_id] = slot

    def num_used_by_kind(self) -> Dict[str, int]:
        """Blocks (``state``: slots) live sequences hold, by kind."""
        return {"full": self.num_used,
                "window": sum(len(t) - self._wdead.get(seq, 0)
                              for seq, t in self._wtables.items()),
                "state": len(self._state_slots)}

    # -- alloc / grow / free ----------------------------------------------
    def allocate(self, seq_id: str, tokens: int,
                 token_ids: Optional[Sequence[int]] = None
                 ) -> Tuple[List[int], int]:
        """Claim blocks for ``tokens`` cache rows; returns
        ``(block_table, cached_tokens)``.

        With ``token_ids`` (the request's prefix) and prefix caching
        enabled, leading FULL blocks whose chained content hash is
        registered are shared by reference instead of allocated fresh —
        ``cached_tokens`` is the number of leading rows whose KV already
        sits in the pool, and the caller prefills only the tail.  At
        least one prefix token is always left to compute (the engine
        needs the last position's logits to sample), so
        ``cached_tokens < len(token_ids)`` whenever token_ids is given.
        """
        if seq_id in self._tables:
            raise BlockPoolError(f"sequence {seq_id!r} already has blocks")
        # injection site BEFORE any state mutation: a fault here leaves
        # the pool exactly as it was (the chaos suite asserts that)
        get_fault_injector().check("serving.allocate")
        need = self.blocks_for_tokens(tokens)
        # feasibility discounts hits on LIVE blocks (pure refcount
        # sharing, no free capacity consumed) — without this a shared
        # prefix larger than the free pool could never be re-allocated
        # even though allocation would barely touch the pool.  The
        # probe's hash walk only runs when the full demand does NOT
        # already fit (the unpressured common case skips it).
        fresh = need if self.can_allocate(need) else \
            self.probe_fresh_need(tokens, token_ids)
        if not self.can_allocate(fresh):
            raise BlockPoolError(
                f"pool exhausted: {seq_id!r} needs {need} blocks "
                f"({fresh} from free capacity), "
                f"{self.num_free} free of {self.usable_blocks}")
        blocks: List[int] = []
        cached_tokens = 0
        chain: List[bytes] = []
        if token_ids is not None and self.enable_prefix_cache:
            bs = self.block_size
            # only full blocks are content-addressed, and the LAST full
            # block is never taken from cache: its logits (or at least
            # one tail token's) must be computed
            max_hit_blocks = max(0, (len(token_ids) - 1) // bs)
            max_hit_blocks = min(max_hit_blocks, need)
            h = ROOT_HASH
            host_tokens = 0
            for i in range(max_hit_blocks):
                h = _chain_hash(h, tuple(token_ids[i * bs:(i + 1) * bs]))
                b = self._hash_to_block.get(h)
                if b is None:
                    # past the device index: the digest may live in the
                    # host tier — a hit there claims a pool block now
                    # and lands the bytes asynchronously (PromoteJob)
                    b = self._claim_host_hit(h, blocks)
                    if b is None:
                        break
                    host_tokens += bs
                elif self._ref[b] == 0:
                    self._claim_cached(b)
                    self._drop_host_duplicate(h)
                else:
                    self._ref[b] += 1
                    self._drop_host_duplicate(h)
                blocks.append(b)
                chain.append(h)
                cached_tokens += bs
            self.hit_tokens_total += cached_tokens - host_tokens
            self.host_hit_tokens_total += host_tokens
        while len(blocks) < need:
            b = self._take_block(blocks)
            self._ref[b] = 1
            blocks.append(b)
        self._tables[seq_id] = blocks
        self._chain[seq_id] = chain
        return list(blocks), cached_tokens

    def probe_fresh_need(self, tokens: int,
                         token_ids: Optional[Sequence[int]] = None) -> int:
        """Free-capacity blocks :meth:`allocate` would actually consume
        for ``tokens`` rows — the admission-feasibility number.  Hits on
        LIVE blocks (refcount > 0) are pure sharing and consume nothing;
        hits on parked LRU blocks supply themselves (one unit of
        ``num_free`` each, same as a fresh block).  Without this the
        scheduler would demand free capacity for a whole shared prefix
        that allocation never takes from the pool, serializing admission
        in exactly the shared-prefix workload prefix caching targets."""
        need = self.blocks_for_tokens(tokens)
        if token_ids is None or not self.enable_prefix_cache:
            return need
        bs = self.block_size
        max_hit_blocks = min(max(0, (len(token_ids) - 1) // bs), need)
        h, live_hits = ROOT_HASH, 0
        for i in range(max_hit_blocks):
            h = _chain_hash(h, tuple(token_ids[i * bs:(i + 1) * bs]))
            b = self._hash_to_block.get(h)
            if b is None:
                break
            if self._ref[b] > 0:
                live_hits += 1
        return need - live_hits

    def probe_prefix_coverage(self, token_ids: Sequence[int],
                              split: bool = False):
        """READ-ONLY affinity probe for the fleet router: how many
        leading tokens of ``token_ids`` this pool (device radix index
        OR attached host tier) already covers, walking the same chained
        content digests :meth:`allocate`'s hit walk uses and stopping at
        the first miss.  Mutates nothing — no claims, no LRU touches,
        no promotions — so the router may probe every replica per
        placement decision (docs/serving.md "Fleet serving &
        failover").

        With ``split=True`` returns ``(device_tokens, host_tokens)``
        instead of their sum, so the router can discount host-resident
        coverage by the promote cost: a block in the host tier saves
        the recompute but still pays a claim + host->device landing.
        Host residency only counts when this allocator may actually
        claim it (``allow_claims``)."""
        if not self.enable_prefix_cache or not token_ids:
            return (0, 0) if split else 0
        bs = self.block_size
        max_hit_blocks = max(0, (len(token_ids) - 1) // bs)
        h = ROOT_HASH
        dev_blocks = host_blocks = 0
        for i in range(max_hit_blocks):
            h = _chain_hash(h, tuple(token_ids[i * bs:(i + 1) * bs]))
            if h in self._hash_to_block:
                dev_blocks += 1
            elif (self.allow_claims and self._host is not None
                    and self._host.contains(h)):
                host_blocks += 1
            else:
                break
        if split:
            return dev_blocks * bs, host_blocks * bs
        return (dev_blocks + host_blocks) * bs

    def append_block(self, seq_id: str) -> int:
        """Grow a sequence by one block (decode crossed a block
        boundary); raises on exhaustion — the scheduler preempts and
        retries."""
        table = self._tables.get(seq_id)
        if table is None:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        get_fault_injector().check("serving.append_block")
        if not self.can_allocate(1):
            raise BlockPoolError(
                f"pool exhausted growing {seq_id!r} "
                f"({len(table)} blocks held)")
        b = self._take_block(table)
        self._ref[b] = 1
        table.append(b)
        return b

    def block_table(self, seq_id: str) -> List[int]:
        table = self._tables.get(seq_id)
        if table is None:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        return list(table)

    def blocks_held(self, seq_id: str) -> int:
        """``len(block_table(seq_id))`` without the copy."""
        table = self._tables.get(seq_id)
        if table is None:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        return len(table)

    def free(self, seq_id: str, discard: bool = False) -> None:
        """Release a sequence's blocks (finish or preemption). Shared
        blocks (fork / prefix hits) only leave the tables when the last
        reference drops; registered blocks park in the cached LRU
        instead of the free list so the prefix they hold stays hittable
        until capacity pressure evicts it.

        ``discard=True`` is the quarantine path: the sequence's KV
        content is SUSPECT (non-finite activations were detected), so
        every block it touched is unregistered from the prefix-cache
        index before release — refcount-0 blocks go straight to the raw
        free list, never to the cached LRU, and a live shared block
        (still refcounted by a sibling) keeps serving that sibling but
        can never be hit again."""
        table = self._tables.pop(seq_id, None)
        if table is None:
            raise BlockPoolError(
                f"free of unknown (or already-freed) sequence {seq_id!r}")
        self._chain.pop(seq_id, None)
        self._state_slots.pop(seq_id, None)
        self._wdead.pop(seq_id, None)
        self._wtables.pop(seq_id, None)
        self._wfree.extend(g for g in self._wgroups.pop(seq_id, ())
                           if g != NULL_BLOCK)
        for b in table:
            if self._ref[b] <= 0:
                raise BlockPoolError(
                    f"double free of block {b} (sequence {seq_id!r})")
            if discard:
                self._unregister(b)
            self._ref[b] -= 1
            if self._ref[b] == 0:
                if b in self._pending_blocks:
                    self._cancel_pending(b)
                else:
                    self._release_block(b)
                self._leave(b)

    def commit_cached(self, seq_id: str, token_ids: Sequence[int],
                      upto_tokens: int) -> int:
        """Register the content of ``seq_id``'s FULL blocks whose rows
        are entirely below ``upto_tokens`` (rows the engine has actually
        written KV for).  ``token_ids`` are the tokens backing rows
        0..upto_tokens-1 (prompt + generated so far).  Idempotent; a
        hash already registered to another block keeps its first owner
        (byte-identical content, either block serves).  Returns the
        number of blocks newly registered."""
        if not self.enable_prefix_cache:
            return 0
        table = self._tables.get(seq_id)
        if table is None:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        bs = self.block_size
        n_full = min(upto_tokens, len(token_ids)) // bs
        n_full = min(n_full, len(table))
        # resume from the sequence's recorded chain: blocks below
        # len(chain) were hashed by an earlier commit (or came in as
        # hits), so each commit call hashes only the NEWLY completed
        # blocks — O(tokens) per sequence overall, not O(tokens²)
        chain = self._chain.setdefault(seq_id, [])
        new = 0
        for i in range(len(chain), n_full):
            h = _chain_hash(chain[-1] if chain else ROOT_HASH,
                            tuple(token_ids[i * bs:(i + 1) * bs]))
            chain.append(h)
            b = table[i]
            if self._block_hash[b] == h:
                continue                       # already committed
            if h in self._hash_to_block:
                continue                       # duplicate content: first wins
            self._unregister(b)                # drop any stale hash
            self._block_hash[b] = h
            self._hash_to_block[h] = b
            if self._host is not None:
                # the digest just (re-)entered the device index — drop
                # any host copy so a digest is resident in exactly one
                # place in the whole hierarchy (same bytes either way:
                # content-addressed)
                self._host.discard(h)
            new += 1
        return new

    def is_cache_resident(self, seq_id: str, tokens: int) -> bool:
        """True when every FULL block of ``seq_id``'s first ``tokens``
        rows has its chain hash registered SOMEWHERE in the index —
        preempting this sequence costs only its tail recompute, because
        its prefix stays hittable (the scheduler's preferred-victim
        predicate).  Membership is by content, not by block: a sequence
        whose blocks duplicate an earlier owner's (first-owner-wins in
        :meth:`commit_cached`) is just as cheap to evict — its
        re-admission hits the owner's copy."""
        table = self._tables.get(seq_id)
        if table is None:
            raise BlockPoolError(f"unknown sequence {seq_id!r}")
        n_full = min(tokens // self.block_size, len(table))
        chain = self._chain.get(seq_id, [])
        if len(chain) < n_full:
            return False                       # uncommitted full blocks
        return all(chain[i] in self._hash_to_block for i in range(n_full))

    def fork(self, src_id: str, dst_id: str,
             src_tokens: int) -> Optional[int]:
        """Copy-on-write fork (beam/parallel sampling): ``dst`` shares
        ``src``'s FULL blocks by reference and gets a private copy of
        the partially-filled tail block (both branches keep appending
        there).  Returns the fresh tail block id the caller must copy
        device-side (``None`` when src's tail landed exactly on a block
        boundary, i.e. nothing to copy)."""
        src = self._tables.get(src_id)
        if src is None:
            raise BlockPoolError(f"unknown fork source {src_id!r}")
        if dst_id in self._tables:
            raise BlockPoolError(f"fork target {dst_id!r} already exists")
        tail_rows = src_tokens % self.block_size
        shared = src if tail_rows == 0 else src[:-1]
        fresh: Optional[int] = None
        if tail_rows:
            if not self.can_allocate(1):
                raise BlockPoolError(
                    f"pool exhausted forking {src_id!r} -> {dst_id!r}")
            fresh = self._take_block(shared)
            self._ref[fresh] = 1
        for b in shared:
            self._ref[b] += 1
        self._tables[dst_id] = list(shared) + ([fresh] if fresh is not None
                                               else [])
        # the fork shares the prefix content, so it inherits the chain
        # record over the shared full blocks (its private tail is
        # unhashed by definition)
        self._chain[dst_id] = list(self._chain.get(src_id, []))[:len(shared)]
        return fresh

    # -- leak check --------------------------------------------------------
    def assert_consistent(self) -> None:
        """Every usable block is exactly one of: free, cached-LRU-parked
        (refcount 0 + hash registered), or referenced; the null block is
        none of them; the hash index and its reverse map agree.  Raises
        BlockPoolError with the exact discrepancy — the tests' (and a
        draining server's) leak check."""
        free_set = set(self._free)
        if NULL_BLOCK in free_set:
            raise BlockPoolError("null block 0 leaked onto the free list")
        cached_set = set(self._cached_lru)
        if NULL_BLOCK in cached_set:
            raise BlockPoolError("null block 0 parked in the cached LRU")
        if free_set & cached_set:
            raise BlockPoolError(
                f"blocks {sorted(free_set & cached_set)} both free and "
                f"cached")
        held: Dict[int, int] = {}
        for seq, table in self._tables.items():
            for b in table:
                if b == NULL_BLOCK:
                    raise BlockPoolError(
                        f"null block 0 inside {seq!r}'s table")
                held[b] = held.get(b, 0) + 1
        for b in range(1, self.num_blocks):
            refs = self._ref[b]
            in_free = b in free_set
            in_cache = b in cached_set
            if (in_free or in_cache) and (refs or b in held):
                raise BlockPoolError(f"block {b} both free and referenced")
            if in_cache and self._block_hash[b] is None:
                raise BlockPoolError(
                    f"block {b} in the cached LRU without a hash")
            if not (in_free or in_cache) and refs != held.get(b, 0):
                raise BlockPoolError(
                    f"block {b} refcount {refs} != {held.get(b, 0)} "
                    f"table references")
            if not (in_free or in_cache) and refs == 0:
                raise BlockPoolError(f"block {b} leaked (no refs, not free)")
        # runs: a group's count is its referenced blocks, and the idle
        # groups are the whole groups that have none
        live = [0] * len(self._group_live)
        for b in held:
            live[(b - 1) // PAGE_RUN] += 1
        if live != self._group_live:
            raise BlockPoolError(
                "a group's live count disagrees with the tables")
        if set(self._idle) != {g for g in range(self._whole_groups)
                               if not live[g]}:
            raise BlockPoolError(
                "the idle groups are not the whole groups without a "
                "referenced block")
        # the window kind: groups held and groups free are all the groups,
        # a sequence holds the groups its pages from the first live one to
        # the table's end fall in, and live page p is its group's block
        # p % PAGE_RUN
        wheld = [g for gs in self._wgroups.values() for g in gs
                 if g != NULL_BLOCK]
        if sorted(wheld + self._wfree) != list(
                range(1, self.window_blocks - PAGE_RUN + 1, PAGE_RUN)):
            raise BlockPoolError(
                "window groups leaked or held twice: free list and tables "
                "do not partition the window pool")
        for seq, table in self._wtables.items():
            dead, groups = self._wdead.get(seq, 0), self._wgroups[seq]
            pages = [NULL_BLOCK if p < dead else
                     groups[p // PAGE_RUN] + p % PAGE_RUN
                     for p in range(len(table))]
            kept = [j >= dead // PAGE_RUN
                    for j in range(-(-len(table) // PAGE_RUN))]
            if table != pages or [g != NULL_BLOCK for g in groups] != kept:
                raise BlockPoolError(
                    f"{seq!r}'s window table is not its groups' blocks "
                    f"from page {dead} on")
        for seq in list(self._wtables) + list(self._state_slots):
            if seq not in self._tables:
                raise BlockPoolError(
                    f"{seq!r} holds window pages or a state without a "
                    f"table of the full kind")
        for h, b in self._hash_to_block.items():
            if self._block_hash[b] != h:
                raise BlockPoolError(
                    f"hash index points at block {b} whose reverse entry "
                    f"disagrees")
            if b in free_set:
                raise BlockPoolError(
                    f"registered block {b} sits on the raw free list")
        # promotion bookkeeping: jobs and pending blocks are a
        # bijection; a pending block is always live (refcounted, never
        # free/cached — its pool bytes are garbage until landing) and,
        # when registered at all, registered to its own digest
        if len(self._promote_jobs) != len(self._pending_blocks):
            raise BlockPoolError(
                f"{len(self._promote_jobs)} promote jobs != "
                f"{len(self._pending_blocks)} pending blocks")
        for b, h in self._pending_blocks.items():
            job = self._promote_jobs.get(h)
            if job is None or job.block != b:
                raise BlockPoolError(
                    f"pending block {b} has no matching promote job")
            if self._ref[b] <= 0:
                raise BlockPoolError(f"pending block {b} unreferenced")
            if b in free_set or b in cached_set:
                raise BlockPoolError(
                    f"pending block {b} parked free/cached before its "
                    f"payload landed")
            if self._block_hash[b] not in (h, None):
                raise BlockPoolError(
                    f"pending block {b} registered under a foreign digest")
        # cross-tier disjointness: a digest lives in exactly one place —
        # the device radix index (landed or pending) xor one host tier
        if self._host is not None:
            in_flight = set(self._pending_blocks.values())
            try:
                self._host.assert_consistent(
                    set(self._hash_to_block) | in_flight)
            except AssertionError as e:
                raise BlockPoolError(f"host tier inconsistent: {e}")
