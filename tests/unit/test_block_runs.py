"""The cache manager keeps runs (docs/serving.md "Runs"): a sequence's
table entries ``j * PAGE_RUN .. (j + 1) * PAGE_RUN - 1`` are consecutive
pool blocks wherever the pool allows, and nothing a scheduler can observe
— ``num_free``, ``can_allocate``, who is admitted and who preempted —
reads differently from the allocator that popped a LIFO list.  Host only:
no device, no engine."""
import random

import numpy as np
import pytest

from deepspeed_tpu.inference.serving import block_allocator
from deepspeed_tpu.inference.serving.block_allocator import (
    PAGE_RUN, BlockPoolError, PagedBlockAllocator)
from deepspeed_tpu.inference.serving.host_cache import HostTierCache
from deepspeed_tpu.ops.transformer import paged_decode_attention

BLOCK = 4


class HeadAllocator(PagedBlockAllocator):
    """The allocator as it was before it kept runs: every block is the
    LIFO list's last, else the least recently used cached one."""

    def _take_block(self, table):
        b = self._pop_block()
        self._enter(b)
        return b


def _tiered(cls, num_blocks):
    a = cls(num_blocks=num_blocks, block_size=BLOCK)
    hc = HostTierCache(64, dram_slots=64)
    a.attach_host_tier(hc, lambda b, h: hc.put(
        h, np.full((64,), h[0], np.uint8)))
    return a


#: what a churn's requests share: nothing (every prompt its own), a few
#: system prompts (prefix hits on live and on parked blocks), or those
#: under a host tier (evictions spill, re-hits claim a block and promote)
MODES = ("distinct", "shared", "host_tier")


def churn(alloc, seed: int, steps: int, mode: str, watch=None) -> list:
    """A seeded script of admissions, growth, forks, frees (some with
    ``discard``) against ``alloc``, preempting the newest sequence when a
    block is not to be had.  Returns what a scheduler would have seen:
    one entry an operation."""
    rng = random.Random(seed)
    systems = [[rng.randrange(1000) for _ in range(BLOCK * rng.randint(2, 9))]
               for _ in range(4)]
    live, order, seen, n = {}, [], [], 0

    def drop(sid, **kw):
        alloc.free(sid, **kw)
        del live[sid]
        order.remove(sid)

    for _ in range(steps):
        op = rng.random()
        if op < 0.35 or not live:
            tail = [rng.randrange(1000) for _ in range(rng.randint(1, 40))]
            ids = tail if mode == "distinct" else rng.choice(systems) + tail
            need = alloc.blocks_for_tokens(len(ids) + 1)
            if not alloc.can_allocate(need):
                need = alloc.probe_fresh_need(len(ids) + 1, ids)
            if alloc.can_allocate(need):
                sid, n = f"s{n}", n + 1
                table, cached = alloc.allocate(sid, len(ids) + 1,
                                               token_ids=ids)
                for job in alloc.pending_jobs():
                    alloc.promotion_landed(job.digest)
                live[sid] = ids
                order.append(sid)
                alloc.commit_cached(sid, ids, len(ids))
                seen.append(("admit", sid, len(table)))
            elif order:
                seen.append(("preempt", order[-1]))
                drop(order[-1])
        elif op < 0.7:
            sid = rng.choice(order)
            live[sid] = live[sid] + [rng.randrange(1000)
                                     for _ in range(rng.randint(1, 12))]
            while (alloc.blocks_held(sid)
                   < alloc.blocks_for_tokens(len(live[sid]) + 1)):
                if alloc.can_allocate(1):
                    alloc.append_block(sid)
                else:
                    victim = order[-1]
                    seen.append(("preempt", victim))
                    drop(victim)
                    if victim == sid:
                        break
            if sid in live:
                alloc.commit_cached(sid, live[sid], len(live[sid]))
        elif op < 0.78:
            src = rng.choice(order)
            if alloc.can_allocate(1):
                sid, n = f"f{n}", n + 1
                alloc.fork(src, sid, len(live[src]))
                live[sid] = list(live[src])
                order.append(sid)
                seen.append(("fork", src, sid))
        else:
            sid = rng.choice(order)
            seen.append(("free", sid))
            drop(sid, discard=rng.random() < 0.15)
        seen.append((alloc.num_free, alloc.num_used,
                     alloc.can_allocate(PAGE_RUN)))
        if watch is not None:
            watch(alloc)
    return seen


def make(cls, mode, num_blocks):
    return _tiered(cls, num_blocks) if mode == "host_tier" else cls(
        num_blocks=num_blocks, block_size=BLOCK)


def test_the_run_is_one_constant_of_the_allocator_and_the_kernel():
    assert block_allocator.PAGE_RUN is paged_decode_attention.PAGE_RUN
    assert PAGE_RUN == 8


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_churn_keeps_the_pool_consistent(seed, mode):
    """Under pressure (a pool of 12 groups and a tail): every operation
    leaves the free blocks, the cached ones, the tables, the groups' live
    counts and the idle groups in agreement, and a drained pool holds
    nothing."""
    alloc = make(PagedBlockAllocator, mode, 1 + 12 * PAGE_RUN + 3)
    churn(alloc, seed, 500, mode, watch=lambda a: a.assert_consistent())
    for sid in list(alloc._tables):
        alloc.free(sid)
    alloc.assert_consistent()
    assert alloc.num_used == 0 and alloc.num_free == alloc.usable_blocks
    assert len(alloc._idle) == alloc._whole_groups


@pytest.mark.parametrize("mode, num_blocks", [
    ("distinct", 1 + 12 * PAGE_RUN + 3),      # preemptions, evictions
    ("shared", 1 + 300 * PAGE_RUN),           # hits; nothing is evicted
    ("host_tier", 1 + 300 * PAGE_RUN)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_capacity_reads_as_it_did_before_runs(seed, mode, num_blocks):
    """``num_free``, ``num_used``, ``can_allocate``, every admission's
    table length, every preemption and its victim: the same from the
    same script as on the allocator that popped a LIFO list.  (Where
    hits meet evictions the two may park different content, so the
    cases with hits run in a pool that evicts nothing.)"""
    new = churn(make(PagedBlockAllocator, mode, num_blocks), seed, 600, mode)
    head = churn(make(HeadAllocator, mode, num_blocks), seed, 600, mode)
    assert new == head
    assert any(e[0] == "preempt" for e in new) == (mode == "distinct")


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 1])
def test_no_registered_block_is_evicted_while_an_unregistered_one_is_free(
        seed, mode):
    alloc = make(PagedBlockAllocator, mode, 1 + 12 * PAGE_RUN + 3)
    evict, evicted = alloc._evict, []

    def watched(b):
        assert not alloc._free, (b, list(alloc._free))
        evicted.append(b)
        return evict(b)
    alloc._evict = watched
    churn(alloc, seed, 600, mode)
    assert evicted, "the pool was never under pressure"


def pages_in_runs(alloc) -> tuple:
    """``(pages of live tables that lie in whole aligned runs of
    consecutive blocks, all their pages)``."""
    runs = pages = 0
    for table in alloc._tables.values():
        pages += len(table)
        for j in range(0, len(table) - PAGE_RUN + 1, PAGE_RUN):
            run = table[j:j + PAGE_RUN]
            runs += PAGE_RUN * all(b == run[0] + i
                                   for i, b in enumerate(run))
    return runs, pages


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["unregistered", "registered"])
def test_the_reasoning_mix_keeps_its_pages_in_runs(prefix_cache):
    """``openpangu-ultra-moe.serve-reason-sat``'s pool and traffic
    (17,408 blocks of 16, 128 slots, prompts 256-2,048, outputs
    512-2,048, closed loop) for twelve hundred requests: from the first
    turnover of the slots on, at least 85 % of live pages lie in whole
    aligned runs — with nothing registered (every freed block is free)
    and with every full block registered as the engine registers them
    (every freed block parks in the LRU but a sequence's last)."""
    rng = random.Random(5)
    alloc = PagedBlockAllocator(17408, 16, enable_prefix_cache=prefix_cache)
    slots, done, started, shares, preempted = {}, 0, 0, [], 0
    while done < 1200:
        # a step is sixteen iterations: a few prompts' chunks fit in them
        for _ in range(4):
            prompt = rng.choice([256, 512, 1024, 2048])
            if len(slots) == 128 or not alloc.can_allocate(
                    alloc.blocks_for_tokens(prompt + 1)):
                break
            sid, started = f"r{started}", started + 1
            alloc.allocate(sid, prompt + 1)
            slots[sid] = [prompt + rng.choice([512, 1024, 1536, 2048]),
                          list(range(started * 4096, started * 4096 + prompt))]
        # every slot decodes on to its next page boundary
        for sid in list(slots):
            if sid not in slots:
                continue
            total, ids = slots[sid]
            ids.extend(range(ids[-1] + 1,
                             ids[-1] + 1 + min(16, total - len(ids))))
            while (alloc.blocks_held(sid)
                   < alloc.blocks_for_tokens(len(ids) + 1)):
                if not alloc.can_allocate(1):      # the newest gives way
                    alloc.free(list(slots)[-1])
                    del slots[list(slots)[-1]]
                    preempted += 1
                    continue
                alloc.append_block(sid)
            alloc.commit_cached(sid, ids, len(ids))
            if len(ids) == total:
                alloc.free(sid)
                del slots[sid]
                done += 1
        if done >= 128:
            runs, pages = pages_in_runs(alloc)
            shares.append(runs / pages)
    alloc.assert_consistent()
    assert min(shares) >= 0.85, (min(shares), sum(shares) / len(shares))
    assert preempted < 12 and alloc.num_used / alloc.usable_blocks > 0.5


@pytest.mark.parametrize("long_blocks", [3, PAGE_RUN, 5 * PAGE_RUN + 2])
def test_a_pool_with_no_idle_group_allocates_wherever_there_is_room(
        long_blocks):
    """One live block in every group: no run is to be had, and a
    sequence still gets every block ``can_allocate`` promised."""
    groups = 12
    alloc = PagedBlockAllocator(1 + groups * PAGE_RUN, BLOCK)
    for g in range(groups):
        table, _ = alloc.allocate(f"one{g}", 1)
        assert table == [1 + g * PAGE_RUN]       # offset 0 of a fresh group
    assert not alloc._idle
    assert alloc.can_allocate(long_blocks)
    table, _ = alloc.allocate("long", long_blocks * BLOCK)
    assert len(table) == long_blocks
    alloc.assert_consistent()
    with pytest.raises(BlockPoolError, match="exhausted"):
        alloc.allocate("more", (alloc.num_free + 1) * BLOCK)
    for _ in range(alloc.num_free):
        alloc.append_block("long")
    assert alloc.num_free == 0 and not alloc.can_allocate(1)
    alloc.assert_consistent()


@pytest.mark.parametrize("tokens", [1, BLOCK * PAGE_RUN, BLOCK * 37 + 1])
def test_a_fresh_pool_hands_out_ascending_consecutive_blocks(tokens):
    """A document prefilled into a fresh pool lies in consecutive blocks
    from the start of a group, whatever its length (the indexer's walk
    takes a whole page group of them as one DMA), and the next one starts
    the next group."""
    alloc = PagedBlockAllocator(1 + 40 * PAGE_RUN, BLOCK)
    first, _ = alloc.allocate("a", tokens)
    assert first == list(range(1, 1 + len(first)))
    second, _ = alloc.allocate("b", tokens)
    start = 1 + -(-len(first) // PAGE_RUN) * PAGE_RUN
    assert second == list(range(start, start + len(second)))
    # growth continues the open run, then opens the next idle group
    grown = [alloc.append_block("a") for _ in range(PAGE_RUN)]
    table = alloc.block_table("a")
    assert table[-PAGE_RUN:] == grown
    runs, pages = pages_in_runs(alloc)
    assert runs == sum(len(t) // PAGE_RUN * PAGE_RUN
                       for t in (table, second))
