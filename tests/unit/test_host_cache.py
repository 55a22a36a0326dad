"""Tiered host prefix cache (docs/serving.md "Tiered prefix cache").

Covers the spill/promote hierarchy bottom-up:

  * capacity math — ``host_block_bytes`` / ``tiered_blocks_for_budget``
    pinned against hand-computed byte counts AND against what
    :class:`BlockCodec` actually emits (planning and encoding must never
    drift apart);
  * the wire codec — quantized pools round-trip BYTE-EXACT (int8 and
    packed int4 values + f32 scale planes verbatim), raw pools encode
    at ``wire_bits`` within the quantizer's error envelope, and
    ``wire_bits=0`` is a lossless raw-bytes path;
  * :class:`HostTierCache` — LRU demotion DRAM->NVMe, aging out of the
    last tier, the claim/release ownership protocol, and the
    cross-tier disjointness invariants;
  * the allocator integration — eviction-as-demotion, host hits
    claiming pending blocks, promotion land/fail/cancel bookkeeping;
  * the serving engine end-to-end — greedy streams token-identical to
    sequential ``generate()`` across a forced spill/promote cycle at
    int8 at-rest, through the NVMe tier, and under injected
    ``serving.spill`` / ``serving.promote`` faults (transient faults
    retry; fatal faults degrade to eviction / recompute — never a
    wrong token), with ``decode_builds == 2`` throughout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import (BlockCodec, BlockPoolError,
                                             HostTierCache,
                                             PagedBlockAllocator,
                                             blocks_for_budget,
                                             host_block_bytes,
                                             kv_block_bytes,
                                             tiered_blocks_for_budget)
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.runtime.resilience import (FaultInjector,
                                              install_fault_injector)

pytestmark = [pytest.mark.inference, pytest.mark.host_cache]


@pytest.fixture
def injector():
    """A fresh process-global FaultInjector for the test, restored to an
    empty one afterwards (so plans never leak across tests)."""
    fi = install_fault_injector(FaultInjector())
    yield fi
    install_fault_injector(FaultInjector())


# ---------------------------------------------------------------------------
# capacity math
# ---------------------------------------------------------------------------
class TestCapacityMath:
    def test_host_block_bytes_hand_computed(self):
        # int8 at rest: 4 layers x 8 tokens x 4 heads, head_dim 32
        # per row: 32 int8 bytes + 4 scale bytes; k AND v
        assert host_block_bytes(4, 8, 4, 32, kv_bits=8) == \
            4 * 2 * 8 * 4 * (32 + 4)
        # packed int4: 16 value bytes + 4 scale bytes per row
        assert host_block_bytes(4, 8, 4, 32, kv_bits=4) == \
            4 * 2 * 8 * 4 * (16 + 4)
        # raw pool at wire_bits=0: plain dtype bytes, no scales
        assert host_block_bytes(4, 8, 4, 32, kv_bits=0, wire_bits=0,
                                cache_itemsize=2) == 4 * 2 * 8 * 4 * 32 * 2
        # raw pool at wire 8: same at-rest cost as an int8 pool
        assert host_block_bytes(4, 8, 4, 32, kv_bits=0, wire_bits=8) == \
            host_block_bytes(4, 8, 4, 32, kv_bits=8)

    @pytest.mark.parametrize("kv_bits,wire_bits",
                             [(0, 0), (0, 8), (0, 4), (8, 8), (4, 4)])
    def test_planning_matches_codec(self, kv_bits, wire_bits):
        """The sizing rule and the encoder must agree EXACTLY — a slot
        sized by ``host_block_bytes`` holds one ``BlockCodec`` payload."""
        codec = BlockCodec(4, 8, 4, 32, kv_bits=kv_bits,
                           wire_bits=wire_bits, dtype=np.float16)
        assert codec.nbytes == host_block_bytes(4, 8, 4, 32, kv_bits,
                                                wire_bits)

    def test_tiered_blocks_for_budget(self):
        hbm, dram, nvme = tiered_blocks_for_budget(
            10**6, 10**7, 10**8, num_layers=2, block_size=4, kv_heads=2,
            head_dim=8, kv_bits=0, wire_bits=8)
        assert hbm == blocks_for_budget(10**6, 4, 2, 8, 0)
        entry = host_block_bytes(2, 4, 2, 8, 0, 8)
        assert (dram, nvme) == (10**7 // entry, 10**8 // entry)

    def test_host_entry_is_unsharded(self):
        """A model-sharded pool still spills the GLOBAL block: the host
        entry size must not shrink with model_shards (only the per-chip
        HBM block count sees the shard divisor)."""
        full = tiered_blocks_for_budget(10**6, 10**7, 0, 2, 4, 8, 16,
                                        model_shards=1)
        half = tiered_blocks_for_budget(10**6, 10**7, 0, 2, 4, 8, 16,
                                        model_shards=2)
        assert half[0] > full[0]          # per-chip HBM blocks grow
        assert half[1] == full[1]         # host entries do not


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------
class TestBlockCodec:
    def _pool_block(self, rng, codec, quantized):
        if quantized:
            # the POOL representation: packed values at d_eff + scales
            k = rng.integers(-128, 128, codec._vshape()).astype(np.int8)
            v = rng.integers(-128, 128, codec._vshape()).astype(np.int8)
            ks = rng.random(codec._sshape()).astype(np.float32) + 1e-3
            vs = rng.random(codec._sshape()).astype(np.float32) + 1e-3
            return k, v, ks, vs
        # a RAW pool block always carries the full head_dim; the codec
        # compresses on the way out
        shape = (codec.num_layers, codec.block_size, codec.kv_heads,
                 codec.head_dim)
        k = rng.standard_normal(shape).astype(codec.dtype)
        v = rng.standard_normal(shape).astype(codec.dtype)
        return k, v, None, None

    @pytest.mark.parametrize("kv_bits", [8, 4])
    def test_quantized_pool_roundtrip_byte_exact(self, kv_bits):
        """The token-exactness enabler: a quantized pool's bytes spill
        and promote VERBATIM — zero requantization error."""
        rng = np.random.default_rng(0)
        codec = BlockCodec(3, 8, 4, 32, kv_bits=kv_bits)
        k, v, ks, vs = self._pool_block(rng, codec, True)
        payload = codec.encode(k, v, ks, vs)
        assert payload.dtype == np.uint8 and payload.nbytes == codec.nbytes
        k2, v2, ks2, vs2 = codec.decode(payload)
        np.testing.assert_array_equal(k2, k)
        np.testing.assert_array_equal(v2, v)
        np.testing.assert_array_equal(ks2, ks)
        np.testing.assert_array_equal(vs2, vs)

    def test_raw_pool_wire0_lossless(self):
        rng = np.random.default_rng(1)
        codec = BlockCodec(3, 8, 4, 32, wire_bits=0, dtype=np.float16)
        k, v, _, _ = self._pool_block(rng, codec, False)
        k2, v2, ks2, vs2 = codec.decode(codec.encode(k, v))
        np.testing.assert_array_equal(k2, k)
        np.testing.assert_array_equal(v2, v)
        assert ks2 is None and vs2 is None

    @pytest.mark.parametrize("wire_bits,tol", [(8, 0.02), (4, 0.3)])
    def test_raw_pool_wire_quantization_envelope(self, wire_bits, tol):
        """bf16/f32 pools compress through the SAME per-row symmetric
        quantizer the device pool uses; the reconstruction error must
        sit inside that quantizer's envelope (~scale/2 per element)."""
        rng = np.random.default_rng(2)
        codec = BlockCodec(2, 8, 4, 32, wire_bits=wire_bits,
                           dtype=np.float32)
        k, v, _, _ = self._pool_block(rng, codec, False)
        k2, v2, _, _ = codec.decode(codec.encode(k, v))
        assert k2.dtype == np.float32
        assert float(np.max(np.abs(k2 - k))) < tol
        assert float(np.max(np.abs(v2 - v))) < tol

    def test_validation(self):
        with pytest.raises(ValueError, match="even head_dim"):
            BlockCodec(2, 8, 4, 33, kv_bits=4)
        with pytest.raises(ValueError, match="wire_bits"):
            BlockCodec(2, 8, 4, 32, wire_bits=3)
        codec = BlockCodec(2, 8, 4, 32, kv_bits=8)
        with pytest.raises(ValueError, match="scale planes"):
            codec.encode(np.zeros(codec._vshape(), np.int8),
                         np.zeros(codec._vshape(), np.int8))
        with pytest.raises(ValueError, match="codec expects"):
            codec.decode(np.zeros(3, np.uint8))


# ---------------------------------------------------------------------------
# the tiered store
# ---------------------------------------------------------------------------
def _payload(i, nbytes=64):
    return np.full(nbytes, i % 251, np.uint8)


class TestHostTierCache:
    def test_put_claim_roundtrip(self):
        hc = HostTierCache(64, dram_slots=4)
        hc.put(b"a" * 16, _payload(1))
        assert hc.contains(b"a" * 16) and hc.spills_total == 1
        got = hc.claim(b"a" * 16)
        np.testing.assert_array_equal(got, _payload(1))
        # claim REMOVES: in flight toward the pool, resident nowhere
        assert not hc.contains(b"a" * 16)
        assert hc.hits_total == {"dram": 1}
        assert hc.claim(b"a" * 16) is None
        hc.assert_consistent(set())

    def test_reput_refreshes_lru_not_spill_count(self):
        hc = HostTierCache(64, dram_slots=2)
        hc.put(b"a" * 16, _payload(1))
        hc.put(b"b" * 16, _payload(2))
        hc.put(b"a" * 16, _payload(1))       # refresh, not a new spill
        assert hc.spills_total == 2
        hc.put(b"c" * 16, _payload(3))       # evicts b (now the oldest)
        assert hc.contains(b"a" * 16) and not hc.contains(b"b" * 16)

    def test_dram_overflow_demotes_to_nvme_then_ages_out(self, tmp_path):
        hc = HostTierCache(64, dram_slots=2, nvme_slots=2,
                           nvme_path=str(tmp_path))
        for i in range(4):
            hc.put(bytes([i]) * 16, _payload(i))
        # 0 and 1 rippled into nvme; 2 and 3 hold dram
        assert hc.demotions_total == 2 and hc.evictions_total == 0
        assert hc.resident_entries("dram") == 2
        assert hc.resident_entries("nvme") == 2
        hc.put(bytes([4]) * 16, _payload(4))
        # dram's oldest (2) demoted; nvme's oldest (0) aged out
        assert hc.demotions_total == 3 and hc.evictions_total == 1
        assert not hc.contains(bytes([0]) * 16)
        # a claim through the nvme tier returns the demoted bytes intact
        np.testing.assert_array_equal(hc.claim(bytes([1]) * 16),
                                      _payload(1))
        assert hc.hits_total["nvme"] == 1
        hc.assert_consistent(set())
        hc.close()

    def test_dram_only_overflow_drops(self):
        hc = HostTierCache(64, dram_slots=2)
        for i in range(3):
            hc.put(bytes([i]) * 16, _payload(i))
        assert hc.evictions_total == 1 and hc.demotions_total == 0
        assert hc.resident_entries("dram") == 2

    def test_release_claim_and_discard(self):
        hc = HostTierCache(64, dram_slots=2)
        hc.put(b"a" * 16, _payload(1))
        p = hc.claim(b"a" * 16)
        hc.release_claim(b"a" * 16, p)       # cancelled promotion
        assert hc.contains(b"a" * 16) and hc.spills_total == 1
        assert hc.discard(b"a" * 16) and not hc.contains(b"a" * 16)
        assert not hc.discard(b"a" * 16)

    def test_assert_consistent_flags_device_overlap(self):
        hc = HostTierCache(64, dram_slots=2)
        hc.put(b"a" * 16, _payload(1))
        hc.assert_consistent({b"b" * 16})
        with pytest.raises(AssertionError, match="both host-side"):
            hc.assert_consistent({b"a" * 16})

    def test_needs_a_tier(self):
        with pytest.raises(ValueError, match="at least one tier"):
            HostTierCache(64, dram_slots=0, nvme_slots=0)


# ---------------------------------------------------------------------------
# allocator integration: eviction-as-demotion, host hits, promotion
# ---------------------------------------------------------------------------
def mk_tiered_alloc(num_blocks=8, block_size=4, dram_slots=8):
    a = PagedBlockAllocator(num_blocks=num_blocks, block_size=block_size)
    hc = HostTierCache(64, dram_slots=dram_slots)
    # payload keyed by digest so a later claim (into a DIFFERENT pool
    # block) can still be content-checked
    a.attach_host_tier(hc, lambda b, h: hc.put(h, _payload(h[0])))
    return a, hc


class TestAllocatorHostTier:
    def test_eviction_spills_then_rehit_promotes(self):
        a, hc = mk_tiered_alloc()
        ids = list(range(12))                      # 3 FULL blocks
        a.allocate("s1", 13, token_ids=ids)
        a.commit_cached("s1", ids, 12)
        a.free("s1")
        assert a.num_cached == 3
        # flood the 7-usable-block pool: the cached chain is evicted
        # THROUGH the spill callback into the host tier
        a.allocate("big", 7 * 4)
        assert hc.spills_total == 3 and a.num_cached == 0
        a.free("big")
        # re-hit: the chain digests resolve host-side, blocks come back
        # as PENDING claims gated out of prefill until they land (the
        # hit walk stops one full block short of the prompt end — the
        # engine must compute the last position's logits)
        _, cached = a.allocate("s2", 13, token_ids=ids)
        assert cached == 8 and a.host_hit_tokens_total == 8
        assert a.hit_tokens_total == 0             # host hits counted apart
        assert a.num_pending == 2 and a.seq_has_pending("s2")
        assert len(hc.digests()) == 1, \
            "claimed digests must leave the host tier (1 of 3 unclaimed)"
        for job in a.pending_jobs():
            np.testing.assert_array_equal(job.payload,
                                          _payload(job.digest[0]))
            a.promotion_landed(job.digest)
        assert a.num_pending == 0 and not a.seq_has_pending("s2")
        a.assert_consistent()
        a.free("s2")
        a.assert_consistent()

    def test_free_cancels_pending_and_restores_host_entry(self):
        a, hc = mk_tiered_alloc()
        ids = list(range(5))                       # 1 cacheable FULL block
        a.allocate("s1", 6, token_ids=ids)
        a.commit_cached("s1", ids, 5)
        a.free("s1")
        a.allocate("big", 7 * 4)                   # evict -> spill
        a.free("big")
        a.allocate("s2", 6, token_ids=ids)
        assert a.num_pending == 1
        free_before = a.num_free
        a.free("s2")                               # cancel mid-promotion
        # the un-landed block went back to the RAW free list (it never
        # held real KV — it must not be LRU-hittable), and the payload
        # went back to the host tier so the prefix stays warm
        assert a.num_pending == 0 and a.num_cached == 0
        assert a.num_free == free_before + 2       # pending + tail block
        assert len(hc.digests()) == 1
        a.assert_consistent()

    def test_promotion_failed_unregisters_and_reports_holders(self):
        a, hc = mk_tiered_alloc()
        ids = list(range(5))
        a.allocate("s1", 6, token_ids=ids)
        a.commit_cached("s1", ids, 5)
        a.free("s1")
        a.allocate("big", 7 * 4)
        a.free("big")
        a.allocate("s2", 6, token_ids=ids)
        [job] = a.pending_jobs()
        affected = a.promotion_failed(job.digest)
        assert affected == [("s2", 0)]
        assert a.num_pending == 0
        # the block stays in s2's table (prefill recomputes into it) but
        # is no longer hash-registered, and the host entry is gone
        assert not hc.contains(job.digest)
        a.assert_consistent()
        a.free("s2")
        a.assert_consistent()

    def test_commit_discards_redundant_host_entry(self):
        """A sibling recomputing a spilled prefix re-registers the
        digest device-side; the host copy must drop to keep residency
        disjoint."""
        a, hc = mk_tiered_alloc()
        ids = list(range(5))
        a.allocate("s1", 6, token_ids=ids)
        a.commit_cached("s1", ids, 5)
        a.free("s1")
        a.allocate("big", 7 * 4)                   # evict -> spill
        a.free("big")
        assert len(hc.digests()) == 1
        a.allocate("s3", 6)                        # no token_ids: a fresh
        a.assert_consistent()                      # prefill, no host walk
        a.free("s3")
        a.allocate("s4", 6, token_ids=ids)
        for job in a.pending_jobs():               # promote normally...
            a.promotion_landed(job.digest)
        a.free("s4")
        a.allocate("big", 7 * 4)                   # ...spill again
        a.free("big")
        a.allocate("s5", 6)
        a.commit_cached("s5", ids, 5)              # recomputed same content
        assert len(hc.digests()) == 0, \
            "re-registration must discard the host duplicate"
        a.assert_consistent()
        a.free("s5")

    def test_no_capacity_no_claim(self):
        """A host hit needs a free or reclaimable device block; when the
        pool is fully referenced the walk stops instead of claiming."""
        a, hc = mk_tiered_alloc()
        ids = list(range(5))
        a.allocate("s1", 6, token_ids=ids)
        a.commit_cached("s1", ids, 5)
        a.free("s1")
        a.allocate("big", 7 * 4)                   # pool fully referenced
        with pytest.raises(BlockPoolError):
            a.allocate("s2", 6, token_ids=ids)
        assert a.num_pending == 0
        assert len(hc.digests()) == 1, "failed admission must not claim"
        a.assert_consistent()


# ---------------------------------------------------------------------------
# serving engine end-to-end
# ---------------------------------------------------------------------------
def tiny_cfg(**kw):
    return gpt2_config("125m", num_layers=4, d_model=32, num_heads=4,
                       vocab_size=64, max_seq_len=64, dtype=jnp.float32,
                       **kw)


def serving_engine(serving=None, **cfg):
    eng = ds.init_inference(
        TransformerLM(tiny_cfg()),
        config={"dtype": "float32", "max_out_tokens": 64,
                "temperature": 0.0, "replace_with_kernel_inject": False,
                "serving": {"enabled": True, "kv_block_size": 8,
                            "num_kv_blocks": 12, "max_batch_slots": 8,
                            "prefill_chunk_tokens": 16,
                            **(serving or {})},
                **cfg})
    return eng, eng.serving_engine()


HOST_DRAM = {"enabled": True, "dram_budget_bytes": 1 << 20}


def run_spill_promote_cycle(eng, srv, seed=0):
    """Shared scenario: serve a prompt, flood the 12-block pool until
    its cached chain spills, re-serve the prompt (host hit -> promote),
    and require the post-promote stream token-identical to sequential
    ``generate()``.  Returns the re-served request."""
    rs = np.random.RandomState(seed)
    prompt = rs.randint(0, 64, (28,)).tolist()     # 3 FULL blocks + tail
    r1 = srv.submit(prompt, max_new_tokens=6)
    srv.run()
    want = np.asarray(eng.generate(np.asarray(prompt, np.int32)[None],
                                   max_new_tokens=6, temperature=0.0))[0]
    np.testing.assert_array_equal(np.asarray(r1.output), want)
    for _ in range(6):                             # force LRU eviction
        srv.submit(rs.randint(0, 64, (30,)).tolist(), max_new_tokens=4)
    srv.run()
    assert srv.host_cache.spills_total > 0, "pool never spilled"
    r2 = srv.submit(prompt, max_new_tokens=6)
    srv.run()
    np.testing.assert_array_equal(np.asarray(r2.output), want)
    srv.allocator.assert_consistent()
    assert srv.decode_builds == 2, \
        f"tiering must not retrace: {srv.decode_builds} builds"
    return r2


class TestServingEngineHostCache:
    @pytest.mark.slow
    def test_int8_spill_promote_token_exact(self):
        """THE acceptance pin: int8 at-rest spills round-trip byte-exact,
        so the greedy stream after a forced eviction + host promote is
        token-identical to generate() — and still one compiled step."""
        eng, srv = serving_engine(serving={"kv_cache_bits": 8,
                                           "host_cache": HOST_DRAM})
        run_spill_promote_cycle(eng, srv)
        assert srv.host_counts["promoted_blocks"] >= 3
        assert srv.allocator.host_hit_tokens_total >= 24
        assert srv.host_cache.hits_total["dram"] >= 3
        assert srv.host_counts["promote_failures"] == 0
        assert srv.host_counts["spill_failures"] == 0

    @pytest.mark.slow
    def test_raw_pool_wire0_spill_promote_token_exact(self):
        """An unquantized pool with wire_bits=0 (raw dtype bytes at
        rest) is equally lossless end-to-end."""
        eng, srv = serving_engine(serving={
            "host_cache": dict(HOST_DRAM, wire_bits=0)})
        run_spill_promote_cycle(eng, srv)
        assert srv.host_counts["promoted_blocks"] >= 3

    @pytest.mark.slow
    def test_nvme_tier_spill_promote_token_exact(self, tmp_path):
        """Size DRAM to a single entry so spills ripple into the NVMe
        slot file; the promote path reads back through the aio store."""
        entry = host_block_bytes(4, 8, 4, 8, kv_bits=8)
        eng, srv = serving_engine(serving={
            "kv_cache_bits": 8,
            "host_cache": {"enabled": True, "dram_budget_bytes": entry,
                           "nvme_budget_bytes": 64 * entry,
                           "nvme_path": str(tmp_path)}})
        assert srv.host_cache.tier_names == ["dram", "nvme"]
        run_spill_promote_cycle(eng, srv)
        assert srv.host_cache.demotions_total > 0, "nvme tier never used"
        assert srv.host_cache.hits_total["nvme"] > 0, \
            "promote never read through nvme"

    @pytest.mark.slow
    def test_transient_faults_retry_in_place(self, injector):
        """`fail` plans on both new sites: the resilience backoff
        absorbs them inside the call and the streams stay exact."""
        injector.add_plan("serving.spill", "fail", at=1, count=2)
        injector.add_plan("serving.promote", "fail", at=1, count=2)
        eng, srv = serving_engine(serving={"kv_cache_bits": 8,
                                           "host_cache": HOST_DRAM})
        run_spill_promote_cycle(eng, srv)
        assert injector.fire_count("serving.spill") == 2
        assert injector.fire_count("serving.promote") == 2
        # retried THROUGH, not degraded
        assert srv.host_counts["spill_failures"] == 0
        assert srv.host_counts["promote_failures"] == 0
        assert srv.host_counts["promoted_blocks"] >= 3

    @pytest.mark.slow
    def test_fatal_spill_degrades_to_eviction(self, injector):
        """A fatal spill loses warmth, never correctness: the block is
        simply evicted and the re-served prompt recomputes exactly."""
        injector.add_plan("serving.spill", "fatal", at=1, count=1)
        eng, srv = serving_engine(serving={"kv_cache_bits": 8,
                                           "host_cache": HOST_DRAM})
        run_spill_promote_cycle(eng, srv)
        assert srv.host_counts["spill_failures"] == 1

    @pytest.mark.slow
    def test_fatal_promote_falls_back_to_recompute(self, injector):
        """A fatal promote drops the host entry and rolls the holder
        back to recompute — the stream must still be token-identical
        (the recomputed block holds the same content by construction)."""
        injector.add_plan("serving.promote", "fatal", at=1, count=1)
        eng, srv = serving_engine(serving={"kv_cache_bits": 8,
                                           "host_cache": HOST_DRAM})
        run_spill_promote_cycle(eng, srv)
        assert srv.host_counts["promote_failures"] == 1

    def test_requires_prefix_cache(self):
        with pytest.raises(ValueError, match="prefix_cache"):
            serving_engine(serving={"prefix_cache": False,
                                    "host_cache": HOST_DRAM})

    def test_budget_must_admit_an_entry(self):
        with pytest.raises(ValueError, match="zero entries"):
            serving_engine(serving={"host_cache": {
                "enabled": True, "dram_budget_bytes": 16}})

    def test_gauges_polled(self):
        """The engine's polled-delta bridge must surface the host-tier
        counters without the host modules importing observability.
        (Registry metrics are process-global: assert DELTAS, not
        absolutes.)"""
        eng, srv = serving_engine(serving={"kv_cache_bits": 8,
                                           "host_cache": HOST_DRAM})
        before = srv._m_host_spills.value
        srv.host_cache.put(b"x" * 16, np.zeros(
            srv.host_cache.entry_nbytes, np.uint8))
        srv._update_gauges()
        assert srv._m_host_spills.value == before + 1
        assert srv._m_host_dram_bytes.value == srv.host_cache.entry_nbytes
        assert srv._m_promote_depth.value == 0


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------
class TestHostCacheConfig:
    def mk(self, **hc):
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
        return DeepSpeedInferenceConfig(
            serving={"enabled": True, "host_cache": hc})

    def test_defaults_off(self):
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
        cfg = DeepSpeedInferenceConfig(serving={"enabled": True})
        assert not cfg.serving.host_cache.enabled

    def test_enabled_needs_a_budget(self):
        with pytest.raises(ValueError, match="budget"):
            self.mk(enabled=True)

    def test_nvme_budget_needs_a_path(self):
        with pytest.raises(ValueError, match="nvme_path"):
            self.mk(enabled=True, nvme_budget_bytes=1 << 20)

    def test_wire_bits_domain(self):
        with pytest.raises(ValueError, match="wire_bits"):
            self.mk(enabled=True, dram_budget_bytes=1 << 20, wire_bits=3)

    def test_valid_roundtrip(self):
        cfg = self.mk(enabled=True, dram_budget_bytes=1 << 30,
                      nvme_budget_bytes=1 << 32, nvme_path="/tmp/kv",
                      promote_parallelism=8, wire_bits=4)
        hc = cfg.serving.host_cache
        assert (hc.dram_budget_bytes, hc.nvme_budget_bytes) == \
            (1 << 30, 1 << 32)
        assert hc.promote_parallelism == 8 and hc.wire_bits == 4
