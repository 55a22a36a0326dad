"""Tensor-parallel paged serving over the (data, model) mesh.

The PR-10 acceptance suite (docs/serving.md "Tensor-parallel serving"),
on the conftest 8-device virtual CPU mesh:

  * the hard pin: on a (data=2, model=2) mesh, greedy serving streams
    are TOKEN-IDENTICAL to single-device ``generate()`` — bf16 AND int8
    KV — while the mixed decode+prefill step still compiles its two
    shapes once each (``decode_builds == 2``) and the measured per-chip
    KV pool bytes are 1/model of the unsharded pool, pinned against
    ``kv_block_bytes(model_shards=...)``;
  * the mesh-shape matrix: model ∈ {1, 2, 4} x kv_cache_bits ∈ {0, 8},
    every shape streaming exact with one trace, including warm
    prefix-cache hits;
  * forced preemption on a sharded mesh (pool too small for the load):
    recompute preemption + data-sharded slots still stream exact;
  * int8 WEIGHTS x TP: the engine flips to per-output-channel scales
    when serving.mesh.model > 1 and the sharded dequant stays exact;
  * allocator fuzz re-run at the pool size a per-chip HBM budget admits
    under model_shards=2 (the allocator itself is shard-agnostic — the
    invariants must hold at the sharded pool's size);
  * config/validation and the mesh-shape gauges.

Everything here runs the REAL collectives: shard_map over 'data' and
'model' via parallel/shard_map_compat (psum on block outputs, the
vocab-sharded embed/head, the data-axis decode-row all_gather).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import (PagedBlockAllocator,
                                             blocks_for_budget,
                                             kv_block_bytes)
from deepspeed_tpu.models.transformer import TransformerLM, gpt2_config

pytestmark = pytest.mark.inference


def tiny_cfg(**kw):
    return gpt2_config("125m", num_layers=4, d_model=32, num_heads=4,
                       vocab_size=64, max_seq_len=64, dtype=jnp.float32,
                       **kw)


# one param set + one reference-stream table shared by every mesh case:
# the reference engine (no serving mesh) runs single-device generate()
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = TransformerLM(tiny_cfg()).init(jax.random.PRNGKey(0))
    return _PARAMS


def build_engine(mesh=None, serving=None, **cfg):
    srv_cfg = {"enabled": True, "kv_block_size": 8, "num_kv_blocks": 48,
               "max_batch_slots": 8, "prefill_chunk_tokens": 16,
               **(serving or {})}
    if mesh is not None:
        srv_cfg["mesh"] = mesh
    return ds.init_inference(
        TransformerLM(tiny_cfg()), params=_params(),
        config={"dtype": "float32", "max_out_tokens": 64,
                "temperature": 0.0, "replace_with_kernel_inject": False,
                "serving": srv_cfg, **cfg})


_REF_CACHE = {}


def ref_streams(prompts, max_new=8, **cfg):
    # the single-device reference is identical across the mesh/kv_bits
    # matrix — compute each (prompts, max_new, cfg) point once
    key = (tuple(map(tuple, prompts)), max_new, repr(sorted(cfg.items())))
    if key not in _REF_CACHE:
        eng = build_engine(**cfg)
        _REF_CACHE[key] = [
            np.asarray(eng.generate(np.asarray(p, np.int32)[None],
                                    max_new_tokens=max_new,
                                    temperature=0.0))[0].tolist()
            for p in prompts]
    return _REF_CACHE[key]


def _run_parity(mesh, kv_bits, prompts=None, max_new=8,
                serving_override=None, **cfg):
    """Serve ``prompts`` on ``mesh``; assert every stream matches
    single-device generate(), one trace, leak-free pool.  Returns the
    ServingEngine for extra assertions."""
    rs = np.random.RandomState(11)
    if prompts is None:
        prompts = [rs.randint(0, 64, (n,)).tolist()
                   for n in (5, 9, 12, 16, 3, 7)]
    want = ref_streams(prompts, max_new, **cfg)
    eng = build_engine(mesh=mesh,
                       serving={"kv_cache_bits": kv_bits,
                                **(serving_override or {})},
                       **cfg)
    srv = eng.serving_engine()
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts[:3]]
    srv.step()                              # staggered arrivals
    reqs += [srv.submit(p, max_new_tokens=max_new) for p in prompts[3:]]
    srv.run(max_steps=400)
    for p, r, w in zip(prompts, reqs, want):
        np.testing.assert_array_equal(np.asarray(r.output), w,
                                      err_msg=f"mesh={mesh} prompt={p}")
    assert srv.decode_builds == 2, \
        f"mesh {mesh} retraced the step ({srv.decode_builds} builds)"
    srv.allocator.assert_consistent()
    assert srv.allocator.num_used == 0
    return srv


class TestTpAcceptance:
    """The (data=2, model=2) hard pins — kept OUT of `slow` so tier-1
    always runs them."""

    @pytest.mark.parametrize("kv_bits", [0, 8])
    @pytest.mark.slow
    def test_dp2_mp2_streams_exact_one_trace(self, kv_bits):
        srv = _run_parity({"data": 2, "model": 2}, kv_bits)
        # per-chip KV pool bytes: measured (sharded device arrays /
        # model_size) must equal the capacity-planning ints at
        # model_shards=2 — f32 pools in this suite, so itemsize 4
        cfg = tiny_cfg()
        per_block = kv_block_bytes(8, cfg.kv_heads, cfg.hdim, kv_bits,
                                   cache_itemsize=4, model_shards=2)
        assert srv.kv_pool_bytes == per_block * 48 * cfg.num_layers
        # and it is HALF the unsharded pool
        full = kv_block_bytes(8, cfg.kv_heads, cfg.hdim, kv_bits,
                              cache_itemsize=4)
        assert 2 * srv.kv_pool_bytes == full * 48 * cfg.num_layers

    def test_two_shapes_on_the_mesh_built_by_a_chunkless_first_dispatch(
            self):
        """(data=2, model=2): the first dispatch has no chunk, so it
        builds the decode-only shape by running and the mixed one by an
        idle run over the sharded pools; then prompts of 1, chunk,
        chunk + 1 and 3 x chunk tokens arrive while the others decode —
        tokens alternately from the two programs, exact against the
        single-device ``generate()``, and nothing is built again."""
        rs = np.random.RandomState(17)
        prompts = [rs.randint(0, 64, (n,)).tolist() for n in (1, 16, 17, 48)]
        want = ref_streams(prompts, 6)
        srv = build_engine(mesh={"data": 2, "model": 2}).serving_engine()
        assert srv._dispatch([], None) == 0
        assert srv.decode_builds == 2
        reqs = []
        for p in prompts:
            reqs.append(srv.submit(p, max_new_tokens=6))
            srv.step()
            srv.step()
        srv.run(max_steps=200)
        for p, r, w in zip(prompts, reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output), w,
                                          err_msg=f"prompt of {len(p)}")
        assert srv.decode_builds == 2
        srv.allocator.assert_consistent()
        assert srv.allocator.num_used == 0

    def test_mesh_gauges_and_psum_accounting(self):
        from deepspeed_tpu.observability import get_registry
        eng = build_engine(mesh={"data": 2, "model": 2})
        srv = eng.serving_engine()
        reg = get_registry()
        assert reg.gauge("dstpu_mesh_data_size").value == 2
        assert reg.gauge("dstpu_mesh_model_size").value == 2
        assert reg.gauge("dstpu_serving_kv_pool_bytes").value \
            == srv.kv_pool_bytes
        # GPT-2 blocks are serial residual: 2 psums/layer of d_model f32
        assert srv.tp_psum_bytes_per_token_layer == 2 * 32 * 4
        # no-mesh engine: zero collective volume, gauges read 1x1
        srv1 = build_engine().serving_engine()
        assert srv1.tp_psum_bytes_per_token_layer == 0
        assert reg.gauge("dstpu_mesh_model_size").value == 1


class TestTpMeshMatrix:
    """model ∈ {1, 2, 4} x kv_bits ∈ {0, 8}, data sized to keep 8 chips
    busy.  Each case compiles its own shard_map program — marked slow;
    run_tests.sh's multichip-serving stage (and plain pytest) run them."""

    @pytest.mark.slow
    @pytest.mark.parametrize("model_size", [1, 2, 4])
    @pytest.mark.parametrize("kv_bits", [0, 8])
    def test_streams_exact_across_mesh_shapes(self, model_size, kv_bits):
        mesh = {"data": 8 // model_size, "model": model_size}
        srv = _run_parity(mesh, kv_bits)
        # per-chip pool honesty across every model size
        cfg = tiny_cfg()
        per_block = kv_block_bytes(8, cfg.kv_heads, cfg.hdim, kv_bits,
                                   cache_itemsize=4,
                                   model_shards=model_size)
        assert srv.kv_pool_bytes == per_block * 48 * cfg.num_layers

    @pytest.mark.slow
    def test_warm_prefix_hits_stream_exact_on_tp_mesh(self):
        """RadixAttention reuse against a SHARDED pool: the resubmitted
        shared prefix hits committed (model-sharded) blocks and the
        stream is still exact — block ids and digests are host-side and
        shard-agnostic, so the hit machinery must not notice the mesh."""
        rs = np.random.RandomState(23)
        shared = rs.randint(0, 64, (24,)).tolist()     # 3 full blocks
        want = ref_streams([shared], 5)[0]
        eng = build_engine(mesh={"data": 2, "model": 2})
        srv = eng.serving_engine()
        r1 = srv.submit(shared, max_new_tokens=5)
        srv.run(max_steps=100)
        assert r1.cache_hit_tokens == 0                # cold
        r2 = srv.submit(shared, max_new_tokens=5)
        srv.run(max_steps=100)
        assert r2.cache_hit_tokens == 16               # warm: 2 blocks
        np.testing.assert_array_equal(np.asarray(r1.output), want)
        np.testing.assert_array_equal(np.asarray(r2.output), want)
        assert srv.decode_builds == 2

    @pytest.mark.slow
    def test_forced_preemption_streams_exact_on_tp_mesh(self):
        """A pool too small for the offered load forces recompute
        preemption while slots are data-sharded; streams still match
        sequential generate and the step still traces once a shape."""
        # 8 usable blocks x 8 tokens; four requests admit at 7 prompt
        # blocks but need 13 once grown to prompt+12 tokens -> growth
        # must evict and recompute mid-decode
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 64, (n,)).tolist()
                   for n in (9, 13, 11, 7)]
        srv = _run_parity({"data": 2, "model": 2}, 0, prompts=prompts,
                          max_new=12,
                          serving_override={"num_kv_blocks": 9})
        assert srv.scheduler.preemption_count > 0

    @pytest.mark.slow
    def test_int8_weights_channel_scales_exact_on_tp_mesh(self):
        """Weight quantization x TP: serving.mesh.model > 1 flips the
        quantizer to per-output-channel scales at init_inference time
        (grouped scales cross shard boundaries); the permuted qkv scale
        vector dequantizes shard-locally and streams stay exact against
        the SAME engine's single-device generate()."""
        eng = build_engine(mesh={"data": 2, "model": 2},
                           quant={"enabled": True, "bits": 8})
        assert eng._qmode == "channel"
        rs = np.random.RandomState(3)
        prompts = [rs.randint(1, 64, (n,)).tolist() for n in (5, 11, 3)]
        # generate() on this engine runs the single-device path over
        # the same channel-quantized weights — the exact reference
        want = [np.asarray(eng.generate(np.asarray(p, np.int32)[None],
                                        max_new_tokens=8,
                                        temperature=0.0))[0].tolist()
                for p in prompts]
        srv = eng.serving_engine()
        reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.run(max_steps=200)
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(r.output), w)
        assert srv.decode_builds == 2


class TestShardedCapacityPlanning:
    def test_kv_block_bytes_model_shards(self):
        # per-chip cost divides exactly by the shard count (scale
        # planes included: they carry the same kv_heads axis)
        for bits in (0, 8, 4):
            full = kv_block_bytes(8, 4, 32, bits)
            for mp in (1, 2, 4):
                assert kv_block_bytes(8, 4, 32, bits,
                                      model_shards=mp) == full // mp
        with pytest.raises(ValueError, match="model_shards"):
            kv_block_bytes(8, 4, 32, model_shards=3)   # 3 !| 4 heads
        with pytest.raises(ValueError, match="model_shards"):
            kv_block_bytes(8, 4, 32, model_shards=0)

    def test_blocks_for_budget_model_shards(self):
        budget = 24 * kv_block_bytes(4, 4, 32)
        assert blocks_for_budget(budget, 4, 4, 32,
                                 model_shards=2) == 48

    def test_allocator_fuzz_at_sharded_pool_size(self):
        """The same per-chip HBM budget admits model_shards x the
        blocks; the allocator invariants must hold at THAT pool size —
        the allocator is host-side and shard-agnostic, so this is the
        whole contract the sharded pool asks of it."""
        rng = np.random.default_rng(1)
        budget = 24 * kv_block_bytes(4, 4, 32)         # 24 full blocks
        nb = blocks_for_budget(budget, 4, 4, 32, model_shards=2)
        assert nb == 48
        a = PagedBlockAllocator(num_blocks=nb, block_size=4)
        prompts = [list(rng.integers(0, 50, n)) for n in (8, 12, 20, 9)]
        live, counter = {}, 0
        max_tok = 30 * nb // 24
        for _ in range(600):
            op = rng.choice(["alloc", "alloc_cached", "grow", "free",
                             "commit"])
            try:
                if op == "alloc":
                    sid = f"s{counter}"
                    counter += 1
                    a.allocate(sid, int(rng.integers(1, max_tok)))
                    live[sid] = None
                elif op == "alloc_cached":
                    sid = f"s{counter}"
                    counter += 1
                    ids = prompts[int(rng.integers(len(prompts)))]
                    a.allocate(sid, len(ids) + 1, token_ids=ids)
                    live[sid] = list(ids)
                elif op == "grow" and live:
                    a.append_block(str(rng.choice(sorted(live))))
                elif op == "free" and live:
                    sid = str(rng.choice(sorted(live)))
                    a.free(sid)
                    del live[sid]
                elif op == "commit" and live:
                    sid = str(rng.choice(sorted(live)))
                    ids = live[sid]
                    if ids:
                        a.commit_cached(sid, ids, len(ids))
            except Exception as e:
                if "BlockPool" not in type(e).__name__:
                    raise
            a.assert_consistent()
        for sid in list(live):
            a.free(sid)
        a.assert_consistent()
        assert a.num_used == 0


class TestTpValidation:
    def test_mesh_data_must_divide_slots(self):
        with pytest.raises(Exception, match="mesh.data"):
            build_engine(mesh={"data": 3, "model": 1})

    def test_mesh_model_must_divide_heads(self):
        eng = build_engine(mesh={"data": 1, "model": 8})  # 8 !| 4 heads
        with pytest.raises(ValueError, match="model"):
            eng.serving_engine()

    def test_mesh_needs_enough_devices(self):
        cfg = {"data": 4, "model": 4}                  # 16 > 8 devices
        eng = build_engine(mesh=cfg,
                           serving={"max_batch_slots": 8})
        with pytest.raises(ValueError, match="devices"):
            eng.serving_engine()

    def test_generate_unaffected_by_serving_mesh(self):
        """generate() on a mesh-configured engine keeps its
        single-device program — the TP view only arms inside the
        serving step."""
        rs = np.random.RandomState(2)
        p = rs.randint(0, 64, (7,)).tolist()
        want = ref_streams([p], 6)[0]
        eng = build_engine(mesh={"data": 2, "model": 2})
        got = np.asarray(eng.generate(np.asarray(p, np.int32)[None],
                                      max_new_tokens=6,
                                      temperature=0.0))[0].tolist()
        assert got == want
