"""ZeRO-Infinity tier: aio, slot stores, pipelined optimizer, streamed step.

Mirrors the reference test strategy for swap/offload
(`/root/reference/tests/unit/test_aio.py` read/write parity,
`test_zero.py` offload correctness): native IO roundtrips, host-optimizer
parity against the reference implementation in numpy, and end-to-end loss
trajectories of the streamed engine against the in-HBM engine.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              TransformerLM)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

TINY = dict(vocab_size=128, max_seq_len=32, num_layers=3, num_heads=2,
            d_model=32, loss_chunk=0, param_dtype=jnp.float32,
            dtype=jnp.bfloat16)


def tiny_model():
    return TransformerLM(TransformerConfig(**TINY))


def single_mesh():
    """Infinity is the single-chip beyond-HBM path; carve one device out
    of the 8-device CPU test mesh (all six named axes, each size 1, so the
    model's TP partition specs still resolve)."""
    from jax.sharding import Mesh
    from deepspeed_tpu.parallel import topology as topo
    axes = (topo.DCN_DATA_AXIS, topo.PIPE_AXIS, topo.DATA_AXIS,
            topo.EXPERT_AXIS, topo.SEQUENCE_AXIS, topo.MODEL_AXIS)
    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * 6), axes)


def ids_batch(n=4, t=32, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n, t), 0, 128))


def engine_cfg(gas=1, clip=0.0, zero=None, batch=4):
    cfg = {"train_batch_size": batch,
           "train_micro_batch_size_per_gpu": batch // gas,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True},
           "gradient_clipping": clip,
           "mesh": {"data": 1}}
    if zero:
        cfg["zero_optimization"] = zero
    return cfg


def infinity_zero(param_dev="cpu", opt_dev="cpu", nvme=None):
    return {"stage": 3,
            "offload_param": {"device": param_dev, "nvme_path": nvme},
            "offload_optimizer": {"device": opt_dev, "nvme_path": nvme}}


# ---------------------------------------------------------------------------
# aio
# ---------------------------------------------------------------------------
class TestAio:
    def test_roundtrip_and_async(self, tmp_path):
        from deepspeed_tpu.ops.aio import AsyncIOHandle, PinnedBuffer
        h = AsyncIOHandle(num_threads=2)
        buf = PinnedBuffer(1 << 20)
        w = buf.view(np.float32, (1 << 18,))
        w[:] = np.random.default_rng(0).standard_normal(1 << 18)
        p = str(tmp_path / "x.bin")
        h.sync_pwrite(w, p)
        r = PinnedBuffer(1 << 20)
        rv = r.view(np.float32, (1 << 18,))
        h.sync_pread(rv, p)
        np.testing.assert_array_equal(w, rv)
        # several ops in flight, wait-all
        for k in range(4):
            h.pwrite(w, str(tmp_path / f"y{k}.bin"))
        h.wait()
        assert os.path.getsize(tmp_path / "y3.bin") == w.nbytes
        h.close()

    def test_offset_io(self, tmp_path):
        from deepspeed_tpu.ops.aio import ALIGN, AsyncIOHandle, PinnedBuffer
        h = AsyncIOHandle(num_threads=1)
        buf = PinnedBuffer(ALIGN)
        v = buf.view(np.uint8, (ALIGN,))
        v[:] = 7
        p = str(tmp_path / "o.bin")
        h.sync_pwrite(v, p, ALIGN * 3)          # hole before the write
        v[:] = 9
        h.sync_pwrite(v, p, 0)
        rbuf = PinnedBuffer(ALIGN)              # keep the owner alive:
        rv = rbuf.view(np.uint8, (ALIGN,))      # views die with the buffer
        h.sync_pread(rv, p, ALIGN * 3)
        assert (rv == 7).all()
        h.sync_pread(rv, p, 0)
        assert (rv == 9).all()
        h.close()

    def test_errors_surface(self, tmp_path):
        from deepspeed_tpu.ops.aio import AsyncIOHandle, PinnedBuffer
        h = AsyncIOHandle(num_threads=1)
        rbuf = PinnedBuffer(4096)
        rv = rbuf.view(np.uint8, (4096,))
        with pytest.raises(OSError):
            h.sync_pread(rv, str(tmp_path / "missing.bin"))
        h.close()


# ---------------------------------------------------------------------------
# slot stores
# ---------------------------------------------------------------------------
class TestSlotStore:
    @pytest.mark.parametrize("device", ["cpu", "nvme"])
    def test_roundtrip(self, tmp_path, device):
        from deepspeed_tpu.runtime.swap_tensor import make_slot_store
        st = make_slot_store(device, 6, 1000, nvme_path=str(tmp_path),
                             buffer_count=3, name="t")
        rng = np.random.default_rng(0)
        rows = [rng.integers(0, 255, 1000).astype(np.uint8)
                for _ in range(6)]
        for i, r in enumerate(rows):
            st.write_slot(i, r)
        st.flush()
        # sequential walk with prefetch (forward order)
        for i in range(6):
            if i + 1 < 6:
                st.prefetch(i + 1)
            got = st.acquire(i)
            np.testing.assert_array_equal(got[:1000], rows[i])
            st.release(i, dirty=False)
        # reverse walk with mutation
        for i in reversed(range(6)):
            buf = st.acquire(i)
            buf[:1000] = (rows[i] + 1) % 255
            st.release(i, dirty=True)
        st.flush()
        for i in range(6):
            got = st.read_slot(i, 1000)
            np.testing.assert_array_equal(got, (rows[i] + 1) % 255)
        st.close()

    def test_nvme_pinning_guard(self, tmp_path):
        from deepspeed_tpu.runtime.swap_tensor import NvmeSlotStore
        st = NvmeSlotStore(5, 100, str(tmp_path / "p.swp"), buffer_count=2)
        st.PIN_WAIT_TIMEOUT = 0.3
        st.acquire(0)
        st.acquire(1)
        with pytest.raises(RuntimeError):
            st.acquire(2)   # both buffers pinned, nobody will release
        st.release(0)
        st.acquire(2)       # now fine
        # a pinned-out store WAITS for a concurrent release instead of
        # aborting the step (ADVICE r3: stream-mode transfer lag)
        st.PIN_WAIT_TIMEOUT = 10.0
        import threading as _t
        _t.Timer(0.1, lambda: st.release(1)).start()
        st.acquire(3)       # blocks until the timer releases slot 1
        st.close()


# ---------------------------------------------------------------------------
# slot optimizer
# ---------------------------------------------------------------------------
class TestSlotOptimizer:
    @pytest.mark.parametrize("device", ["cpu", "nvme"])
    @pytest.mark.parametrize("g16", [False, True])
    def test_matches_cpu_adam(self, tmp_path, device, g16):
        import ml_dtypes
        from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam
        from deepspeed_tpu.runtime.swap_tensor import SlotOptimizer
        rng = np.random.default_rng(0)
        n, slots = 1024, 3
        masters = [rng.standard_normal(n).astype(np.float32)
                   for _ in range(slots)]
        ref = DeepSpeedCPUAdam([m.copy() for m in masters], lr=1e-2,
                               weight_decay=0.01)
        opt = SlotOptimizer(slots, n, device=device,
                            nvme_path=str(tmp_path), lr=1e-2,
                            weight_decay=0.01)
        for i, m in enumerate(masters):
            opt.init_slot(i, m)
        for step in range(3):
            grads = [rng.standard_normal(n).astype(np.float32)
                     for _ in range(slots)]
            if g16:
                grads = [g.astype(ml_dtypes.bfloat16) for g in grads]
            ref.step([np.asarray(g, np.float32) for g in grads], lr=1e-2)
            opt.begin_step()
            out16 = np.empty(n, np.uint16)
            for i, g in enumerate(grads):
                gi = g.view(np.uint16) if g16 else g
                opt.step_slot(i, gi, lr=1e-2, out_bf16=out16)
        for i in range(slots):
            p, m, v = opt.state(i)
            np.testing.assert_allclose(p, ref.master[i], rtol=2e-6,
                                       atol=1e-7)
            np.testing.assert_allclose(m, ref.m[i], rtol=2e-6, atol=1e-7)
        # bf16 emit matches master cast
        np.testing.assert_array_equal(
            out16, ref.master[-1].astype(ml_dtypes.bfloat16).view(np.uint16))
        opt.close()


# ---------------------------------------------------------------------------
# gradient-wire codec
# ---------------------------------------------------------------------------
class TestWireCodec:
    """Unbiased stochastic-rounding D2H compression (wire_codec.py) — the
    role the reference's 1-bit error-feedback collective plays on the
    network wire (`runtime/comm/nccl.py:52`), re-derived for the offload
    wire (no persistent device error state)."""

    @pytest.mark.parametrize("bits", [8, 4, 1])
    def test_nonfinite_grads_poison_the_decode(self, bits):
        """A diverged (NaN) gradient must come OUT of the wire as NaN —
        quantizing it into finite garbage would hide the divergence the
        uncompressed path surfaces (advisor r5)."""
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        n = 2 * wc.CHUNK
        g = np.zeros(n, np.float32)
        g[1] = np.nan          # chunk 0 diverged; chunk 1 clean
        g[wc.CHUNK + 5] = 3.0
        payload, scales = jax.jit(wc.encode, static_argnums=1)(
            jnp.asarray(g), bits, jax.random.PRNGKey(1))
        out = np.empty(n, np.float32)
        wc.decode_into(out, np.asarray(payload), np.asarray(scales), bits)
        assert not np.all(np.isfinite(out[:wc.CHUNK]))
        assert np.all(np.isfinite(out[wc.CHUNK:]))

    @pytest.mark.parametrize("bits", [8, 4, 1])
    def test_roundtrip_error_bounded(self, bits):
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        n = 4 * wc.CHUNK
        g = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n,)),
                       np.float32)
        payload, scales = jax.jit(wc.encode, static_argnums=1)(
            g, bits, jax.random.PRNGKey(1))
        out = np.empty(n, np.float32)
        wc.decode_into(out, np.asarray(payload), np.asarray(scales), bits)
        # error bounded by one quantization step per element
        step = np.repeat(np.asarray(scales), wc.CHUNK)
        if bits == 1:
            assert np.all(np.abs(out - g) <= 2 * step + 1e-6)
        else:
            assert np.all(np.abs(out - g) <= step + 1e-6)
        # wire volume is what the format promises
        assert payload.nbytes == {8: n, 4: n // 2, 1: n // 8}[bits]

    @pytest.mark.parametrize("bits", [8, 4, 1])
    def test_unbiased(self, bits):
        """E[decode(encode(g))] = g — the property that replaces error
        feedback. Average over many independent keys."""
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        n = wc.CHUNK
        g = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n,)),
                       np.float32) * 0.1
        reps = 300 if bits == 1 else 100
        enc = jax.jit(wc.encode, static_argnums=1)
        acc = np.zeros(n, np.float64)
        out = np.empty(n, np.float32)
        for r in range(reps):
            payload, scales = enc(g, bits, jax.random.PRNGKey(100 + r))
            wc.decode_into(out, np.asarray(payload), np.asarray(scales),
                           bits)
            acc += out
        mean = acc / reps
        # 5-sigma tolerance on the SR noise of the mean
        sig = {8: np.max(np.abs(g)) / 127, 4: np.max(np.abs(g)) / 7,
               1: np.max(np.abs(g))}[bits] / np.sqrt(reps)
        assert np.max(np.abs(mean - g)) < 5 * max(sig, 1e-8)

    def test_zero_chunks_decode_to_zero(self):
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        g = np.zeros(2 * wc.CHUNK, np.float32)
        for bits in (8, 4, 1):
            payload, scales = jax.jit(wc.encode, static_argnums=1)(
                g, bits, jax.random.PRNGKey(0))
            out = np.ones_like(g)
            wc.decode_into(out, np.asarray(payload), np.asarray(scales),
                           bits)
            np.testing.assert_array_equal(out, 0.0)

    @pytest.mark.parametrize("bits", [8, 1])
    @pytest.mark.slow
    def test_compressed_training_converges(self, bits):
        """Verdict r3 #3 'Done' condition: convergence parity vs the
        uncompressed wire on a small model."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        zero = dict(infinity_zero(), offload_wire_bits=bits)
        eng = DeepSpeedEngine(tiny_model(), config=engine_cfg(zero=zero),
                              rng=rng, mesh=single_mesh())
        ref = DeepSpeedEngine(tiny_model(),
                              config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        l0 = eng.eval_loss({"input_ids": ids})
        for _ in range(8):
            eng.train_step({"input_ids": ids})
            ref.train_step({"input_ids": ids})
        l1 = eng.eval_loss({"input_ids": ids})
        lr = ref.eval_loss({"input_ids": ids})
        assert float(l1) < float(l0) - 0.3       # memorizes the batch
        # trajectory parity: compressed end-loss within a band of exact
        band = 0.15 if bits == 8 else 0.5
        assert abs(float(l1) - float(lr)) < band

    def test_wire_with_gas_and_clip(self):
        zero = dict(infinity_zero(), offload_wire_bits=8)
        eng = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(gas=2, clip=0.5, batch=8, zero=zero),
            rng=jax.random.PRNGKey(0), mesh=single_mesh())
        ids = ids_batch(n=8)
        losses = [eng.train_step({"input_ids": ids})["loss"]
                  for _ in range(6)]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]


class TestParamWireCodec:
    """H2D parameter wire (encode_params_host / decode_params): the upload
    direction of the offload wire. Deterministic round-to-nearest — params
    are values, not averaged quantities, so SR's unbiasedness buys nothing
    and would make repeated uploads of unchanged masters disagree."""

    @pytest.mark.parametrize("bits", [8, 4])
    def test_roundtrip_error_bounded_and_deterministic(self, bits):
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        import ml_dtypes
        n = 4 * wc.CHUNK
        w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (n,)),
                       np.float32).astype(ml_dtypes.bfloat16)
        p1, s1 = wc.encode_params_host(w, bits)
        p2, s2 = wc.encode_params_host(w, bits)
        np.testing.assert_array_equal(p1, p2)   # RTN: bit-stable re-encode
        np.testing.assert_array_equal(s1, s2)
        dec = np.asarray(wc.decode_params(jnp.asarray(p1), jnp.asarray(s1),
                                          bits), np.float32)
        # RTN error is at most half a quantization step per element, plus
        # one bf16 ULP of the decoded value (decode emits bf16)
        step = np.repeat(s1, wc.CHUNK)
        wf = w.astype(np.float32)
        assert np.all(np.abs(dec - wf)
                      <= 0.5 * step + np.abs(wf) * 2**-7 + 1e-6)
        assert p1.nbytes == {8: n, 4: n // 2}[bits]

    def test_nonfinite_masters_poison_the_upload(self):
        from deepspeed_tpu.runtime.zero import wire_codec as wc
        n = 2 * wc.CHUNK
        w = np.zeros(n, np.float32)
        w[3] = np.inf
        w[wc.CHUNK + 1] = 1.0
        p, s = wc.encode_params_host(w, 8)
        dec = np.asarray(wc.decode_params(jnp.asarray(p), jnp.asarray(s), 8),
                         np.float32)
        assert not np.all(np.isfinite(dec[:wc.CHUNK]))
        assert np.all(np.isfinite(dec[wc.CHUNK:]))

    # the 4-bit arm re-runs the same ~13s convergence loop at a coarser
    # codec; the 8-bit arm stays the tier-1 representative
    @pytest.mark.parametrize("bits", [
        8, pytest.param(4, marks=pytest.mark.slow)])
    def test_param_wire_training_converges(self, bits):
        """Streamed training with quantized param uploads still memorizes
        the batch; 8-bit stays in a band of the exact-upload trajectory."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        zero = dict(infinity_zero(), offload_param_bits=bits)
        eng = DeepSpeedEngine(tiny_model(), config=engine_cfg(zero=zero),
                              rng=rng, mesh=single_mesh())
        ref = DeepSpeedEngine(tiny_model(),
                              config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        l0 = eng.eval_loss({"input_ids": ids})
        for _ in range(8):
            eng.train_step({"input_ids": ids})
            ref.train_step({"input_ids": ids})
        l1 = eng.eval_loss({"input_ids": ids})
        lr = ref.eval_loss({"input_ids": ids})
        assert float(l1) < float(l0) - 0.3
        band = 0.15 if bits == 8 else 0.6
        assert abs(float(l1) - float(lr)) < band

    def test_param_wire_composes_with_grad_wire_gas_clip(self):
        """Both wire directions compressed at once, under gradient
        accumulation and clipping — the 6.7B bench configuration."""
        zero = dict(infinity_zero(), offload_param_bits=8,
                    offload_wire_bits=1)
        eng = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(gas=2, clip=0.5, batch=8, zero=zero),
            rng=jax.random.PRNGKey(0), mesh=single_mesh())
        ids = ids_batch(n=8)
        losses = [eng.train_step({"input_ids": ids})["loss"]
                  for _ in range(6)]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]

    def test_quantized_cache_holds_more_layers(self):
        """The device layer cache accounts bytes, not params: at 8-bit the
        same max_live_parameters budget holds 2x the layers (all through
        the real config knob)."""
        rng = jax.random.PRNGKey(0)
        probe = DeepSpeedEngine(
            tiny_model(), config=engine_cfg(zero=infinity_zero()),
            rng=rng, mesh=single_mesh())
        n = probe._infinity.n_elems
        lives = {}
        for bits in (0, 8):
            zero = dict(infinity_zero(), offload_param_bits=bits,
                        max_live_parameters=2 * n)   # 2 bf16 layers' bytes
            eng = DeepSpeedEngine(
                tiny_model(), config=engine_cfg(zero=zero), rng=rng,
                mesh=single_mesh())
            lives[bits] = eng._infinity.max_live_layers
        assert lives[0] == 2
        assert lives[8] == 3     # doubled, clipped to the model's L=3

    def test_checkpoint_roundtrip_with_param_wire(self, tmp_path):
        """Masters stay exact under the quantized upload: a checkpoint
        written from a param-wire engine restores into a NON-quantized
        engine and the loss matches the donor's own eval."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        zero = dict(infinity_zero(), offload_param_bits=8)
        a = DeepSpeedEngine(tiny_model(), config=engine_cfg(zero=zero),
                            rng=rng, mesh=single_mesh())
        for _ in range(3):
            a.train_step({"input_ids": ids})
        a._infinity.save_to_dir(str(tmp_path / "ck"))
        b = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=jax.random.PRNGKey(7), mesh=single_mesh())
        b._infinity.load_from_dir(str(tmp_path / "ck"))
        # donor evaluates THROUGH its quantized upload; the restored engine
        # uploads exact bf16 — compare against the quantization band
        la = float(a.eval_loss({"input_ids": ids}))
        lb = float(b.eval_loss({"input_ids": ids}))
        assert abs(la - lb) < 0.05


# ---------------------------------------------------------------------------
# streamed engine
# ---------------------------------------------------------------------------
class TestInfinityEngine:
    def test_init_matches_model_init(self):
        rng = jax.random.PRNGKey(0)
        e = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=rng, mesh=single_mesh())
        ref = jax.device_get(jax.jit(tiny_model().init)(rng))
        got = e._infinity.gather_params()
        flat_ref = jax.tree_util.tree_leaves(ref)
        flat_got = jax.tree_util.tree_leaves(got)
        assert len(flat_ref) == len(flat_got)
        for a, b in zip(flat_ref, flat_got):
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=1e-6, atol=1e-7)

    @pytest.mark.slow
    def test_parity_with_base_engine(self):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        base = DeepSpeedEngine(tiny_model(), config=engine_cfg(), rng=rng, mesh=single_mesh())
        inf = DeepSpeedEngine(tiny_model(),
                              config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        for _ in range(4):
            r1 = base.train_step({"input_ids": ids})
            r2 = inf.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3
            assert abs(float(r1["grad_norm"]) - float(r2["grad_norm"])) \
                < 5e-2 * max(1.0, float(r1["grad_norm"]))

    def test_param_wire_encode_cache_and_invalidation(self):
        """The H2D quantize pass (encode_params_host) no longer runs on
        the streaming thread per upload: payloads are cached while a
        layer's masters are unchanged (repeated forwards re-use the
        SAME encoded arrays), the host Adam sweep invalidates per
        layer, and training still converges through the cached path."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        zero = dict(infinity_zero(), offload_param_bits=8)
        e = DeepSpeedEngine(tiny_model(), config=engine_cfg(zero=zero),
                            rng=rng, mesh=single_mesh())
        st = e._infinity
        assert st._enc_async          # DRAM param store: offload enabled
        l0 = e.eval_loss({"input_ids": ids})
        assert set(st._enc_cache) == set(range(st.L))
        before = {i: id(st._enc_cache[i][0]) for i in st._enc_cache}
        e.eval_loss({"input_ids": ids})   # unchanged masters: pure hits
        assert {i: id(st._enc_cache[i][0])
                for i in st._enc_cache} == before
        versions = list(st._enc_version)
        e.train_step({"input_ids": ids})  # sweep rewrites every layer
        assert all(v2 > v1 for v1, v2 in zip(versions, st._enc_version))
        for _ in range(5):
            m = e.train_step({"input_ids": ids})
            assert np.isfinite(m["loss"])
        assert float(e.eval_loss({"input_ids": ids})) < float(l0) - 0.2

    def test_nvme_bitwise_matches_dram(self, tmp_path):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        dram = DeepSpeedEngine(tiny_model(),
                               config=engine_cfg(zero=infinity_zero()),
                               rng=rng, mesh=single_mesh())
        nvme = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(zero=infinity_zero("nvme", "nvme",
                                                 str(tmp_path))),
            rng=rng, mesh=single_mesh())
        for _ in range(3):
            r1 = dram.train_step({"input_ids": ids})
            r2 = nvme.train_step({"input_ids": ids})
            assert float(r1["loss"]) == float(r2["loss"])
        nvme._infinity.close()

    @pytest.mark.slow
    def test_streamed_gas_no_clip_vs_base(self):
        """gas>1 with clip==0 takes the streamed-finish path (per-layer
        Adam fires during the last microbatch's backward) — must match the
        in-HBM engine like collect mode does."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        base = DeepSpeedEngine(tiny_model(),
                               config=engine_cfg(gas=4, clip=0.0, batch=8),
                               rng=rng, mesh=single_mesh())
        inf = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(gas=4, clip=0.0, zero=infinity_zero(),
                              batch=8),
            rng=rng, mesh=single_mesh())
        for _ in range(3):
            r1 = base.train_step({"input_ids": ids})
            r2 = inf.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3
            assert abs(float(r1["grad_norm"]) - float(r2["grad_norm"])) \
                < 5e-2 * max(1.0, float(r1["grad_norm"]))

    @pytest.mark.slow
    def test_nvme_gas_clip_composition(self, tmp_path):
        """NVMe tiers x gradient accumulation x clipping — the round-3
        verdict's 'narrowest composition' gap: the flagship overlap path
        must run (and stay correct) for realistic large-model recipes."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        base = DeepSpeedEngine(tiny_model(),
                               config=engine_cfg(gas=2, clip=0.5, batch=8),
                               rng=rng, mesh=single_mesh())
        nvme = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(gas=2, clip=0.5, batch=8,
                              zero=infinity_zero("nvme", "nvme",
                                                 str(tmp_path))),
            rng=rng, mesh=single_mesh())
        for _ in range(3):
            r1 = base.train_step({"input_ids": ids})
            r2 = nvme.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3
        nvme._infinity.close()

    @pytest.mark.slow
    def test_gas_and_clipping_vs_base(self):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        base = DeepSpeedEngine(tiny_model(),
                               config=engine_cfg(gas=2, clip=0.5, batch=8),
                               rng=rng, mesh=single_mesh())
        inf = DeepSpeedEngine(
            tiny_model(),
            config=engine_cfg(gas=2, clip=0.5, zero=infinity_zero(),
                              batch=8),
            rng=rng, mesh=single_mesh())
        for _ in range(3):
            r1 = base.train_step({"input_ids": ids})
            r2 = inf.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3

    @pytest.mark.parametrize("variant", ["bloom_ln_embed", "bert_types"])
    @pytest.mark.slow
    def test_embed_variants_match_base(self, variant):
        """ADVICE r3 (medium): embed_layernorm (BLOOM) and token-type
        embeddings (BERT) must produce the SAME forward math under offload
        as the in-HBM engine — embed_fwd now delegates to the model's
        _embed_tokens instead of re-implementing a subset of it."""
        over = (dict(embed_layernorm=True) if variant == "bloom_ln_embed"
                else dict(token_type_vocab=2))
        mk = lambda: TransformerLM(TransformerConfig(**{**TINY, **over}))
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        base = DeepSpeedEngine(mk(), config=engine_cfg(), rng=rng,
                               mesh=single_mesh())
        inf = DeepSpeedEngine(mk(), config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        for _ in range(3):
            r1 = base.train_step({"input_ids": ids})
            r2 = inf.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3

    def test_token_type_ids_change_the_loss(self):
        """Explicit token_type_ids must reach the embedding under offload
        (not silently fall back to all-zero types)."""
        over = dict(token_type_vocab=2)
        mk = lambda: TransformerLM(TransformerConfig(**{**TINY, **over}))
        ids = ids_batch()
        tt = np.ones_like(ids)
        inf = DeepSpeedEngine(mk(), config=engine_cfg(zero=infinity_zero()),
                              rng=jax.random.PRNGKey(0), mesh=single_mesh())
        l0 = inf.eval_loss({"input_ids": ids})
        l1 = inf.eval_loss({"input_ids": ids, "token_type_ids": tt})
        assert abs(l0 - l1) > 1e-6
        # and the train path accepts the key
        m = inf.train_step({"input_ids": ids, "token_type_ids": tt})
        assert np.isfinite(m["loss"])

    def test_eval_loss_and_convergence(self):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        inf = DeepSpeedEngine(tiny_model(),
                              config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        l0 = inf.eval_loss({"input_ids": ids})
        for _ in range(8):
            inf.train_step({"input_ids": ids})
        l1 = inf.eval_loss({"input_ids": ids})
        assert float(l1) < float(l0) - 0.3   # memorizes the tiny batch

    def test_checkpoint_roundtrip_resumes(self, tmp_path):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        a = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=rng, mesh=single_mesh())
        for _ in range(2):
            a.train_step({"input_ids": ids})
        sd = a._infinity.state_dict()
        b = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=jax.random.PRNGKey(7),
                            mesh=single_mesh())   # different init
        b._infinity.load_state_dict(sd)
        b.state["step"] = a.state["step"]
        ra = a.train_step({"input_ids": ids})
        rb = b.train_step({"input_ids": ids})
        assert float(ra["loss"]) == float(rb["loss"])

    @pytest.mark.slow
    def test_engine_save_load_checkpoint(self, tmp_path):
        """The engine-level surface must carry the host stores (a save that
        silently drops them would resume from fresh weights)."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        a = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=rng, mesh=single_mesh())
        for _ in range(2):
            a.train_step({"input_ids": ids})
        a.save_checkpoint(str(tmp_path), tag="t2")
        b = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=jax.random.PRNGKey(7),
                            mesh=single_mesh())
        b.load_checkpoint(str(tmp_path))
        ra = a.train_step({"input_ids": ids})
        rb = b.train_step({"input_ids": ids})
        assert float(ra["loss"]) == float(rb["loss"])
        # module-only load: params restored, fresh moments -> different step
        c = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=jax.random.PRNGKey(9),
                            mesh=single_mesh())
        c.load_checkpoint(str(tmp_path), load_module_only=True)
        p_a = a._infinity.opt.master(0)   # stepped once more above
        p_c = c._infinity.opt.master(0)
        assert np.isfinite(p_c).all() and p_c.shape == p_a.shape

    def test_labels_and_mask_path(self):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        labels = np.roll(ids, -1, axis=1)
        mask = np.ones_like(ids, np.float32)
        mask[:, -4:] = 0.0
        inf = DeepSpeedEngine(tiny_model(),
                              config=engine_cfg(zero=infinity_zero()),
                              rng=rng, mesh=single_mesh())
        r = inf.train_step({"input_ids": ids, "labels": labels,
                            "loss_mask": mask})
        assert np.isfinite(r["loss"])

    def test_rejects_bad_configs(self):
        rng = jax.random.PRNGKey(0)
        # param offload without optimizer offload
        with pytest.raises(ValueError, match="offload_optimizer"):
            DeepSpeedEngine(
                tiny_model(),
                config=engine_cfg(zero={
                    "stage": 3, "offload_param": {"device": "cpu"}}),
                rng=rng, mesh=single_mesh())
        # fp16 loss scaling not wired
        cfg = engine_cfg(zero=infinity_zero())
        del cfg["bf16"]
        cfg["fp16"] = {"enabled": True}
        with pytest.raises(NotImplementedError, match="bf16"):
            DeepSpeedEngine(tiny_model(), config=cfg, rng=rng, mesh=single_mesh())

    def test_universal_export_from_infinity_checkpoint(self, tmp_path):
        """zero_to_fp32 + universal export work OFFLINE from the streamed
        checkpoint's flat slots (leaf layout in meta) and match the live
        gather."""
        from deepspeed_tpu.checkpoint.universal import (export_universal,
                                                        load_universal,
                                                        unflatten)
        from deepspeed_tpu.runtime.checkpoint_engine.engine import (
            get_fp32_state_dict_from_zero_checkpoint)
        rng = jax.random.PRNGKey(0)
        ids = ids_batch()
        a = DeepSpeedEngine(tiny_model(),
                            config=engine_cfg(zero=infinity_zero()),
                            rng=rng, mesh=single_mesh())
        a.train_step({"input_ids": ids})
        a.save_checkpoint(str(tmp_path / "ck"), tag="t")
        live = a._infinity.gather_params()
        offline = get_fp32_state_dict_from_zero_checkpoint(
            str(tmp_path / "ck"), "t")
        for (pa, la), (pb, lb) in zip(
                jax.tree_util.tree_flatten_with_path(live)[0],
                jax.tree_util.tree_flatten_with_path(offline)[0]):
            np.testing.assert_allclose(np.asarray(la), lb, atol=1e-7,
                                       err_msg=str(pa))
        out = export_universal(str(tmp_path / "ck"), str(tmp_path / "uni"),
                               tag="t")
        flat = load_universal(out)
        tree = unflatten(flat)
        np.testing.assert_allclose(
            tree["blocks"]["mlp"]["fc_in"]["kernel"],
            np.asarray(live["blocks"]["mlp"]["fc_in"]["kernel"]),
            atol=1e-7)


# ---------------------------------------------------------------------------
# multi-chip composition: ZeRO-3 dp sharding x Infinity offload
# (reference stage3.py:480 _configure_tensor_swapping — per-rank partition
# swap — re-expressed as a dp-sharded flat vector with GSPMD allgather on
# use and reduce-scatter on grads; tested on the virtual 8-device CPU mesh)
# ---------------------------------------------------------------------------
def dp_cfg(gas=1, clip=0.0, zero=None, batch=8, dp=8):
    micro = batch // gas
    assert micro % dp == 0 or dp == 1
    cfg = {"train_batch_size": batch,
           "train_micro_batch_size_per_gpu": micro // dp,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "bf16": {"enabled": True},
           "gradient_clipping": clip,
           "mesh": {"data": dp}}
    if zero:
        cfg["zero_optimization"] = zero
    return cfg


def dp8_mesh():
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.config import MeshConfig
    return build_mesh(MeshConfig(data=8))


class TestInfinityMultiChip:
    @pytest.mark.slow
    def test_dp8_parity_with_single_chip(self):
        """8-device dp-sharded Infinity walks the same loss trajectory as
        the single-chip streamed engine (VERDICT r3 'done' criterion)."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        one = DeepSpeedEngine(tiny_model(),
                              config=dp_cfg(zero=infinity_zero(), dp=1),
                              rng=rng, mesh=single_mesh())
        eight = DeepSpeedEngine(tiny_model(),
                             config=dp_cfg(zero=infinity_zero(), dp=8),
                             rng=rng, mesh=dp8_mesh())
        for _ in range(3):
            r1 = one.train_step({"input_ids": ids})
            r8 = eight.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r8["loss"])) < 5e-3
            assert abs(float(r1["grad_norm"]) - float(r8["grad_norm"])) \
                < 5e-2 * max(1.0, float(r1["grad_norm"]))
        # masters agree after 3 steps (bf16 wire + reduction-order slack)
        a = one._infinity.gather_params()
        b = eight._infinity.gather_params()
        ka = a["blocks"]["mlp"]["fc_in"]["kernel"]
        kb = b["blocks"]["mlp"]["fc_in"]["kernel"]
        np.testing.assert_allclose(ka, kb, atol=5e-3)

    def test_dp8_param_buffers_are_sharded(self):
        """Each chip's HBM holds 1/8 of the streamed layer vector — the
        memory claim of the composition."""
        rng = jax.random.PRNGKey(0)
        e = DeepSpeedEngine(tiny_model(),
                            config=dp_cfg(zero=infinity_zero(), dp=8),
                            rng=rng, mesh=dp8_mesh())
        st = e._infinity
        assert st.dp == 8 and st.n_pad % 8 == 0
        arr, = st._ensure_layer(0, {0})
        shard = arr.addressable_shards[0]
        assert shard.data.shape == (st.n_pad // 8,)
        assert len({s.device for s in arr.addressable_shards}) == 8
        st._sweep_uploads(block=True)

    def test_dp8_wire_compression(self):
        """Wire compression composes with the dp-sharded mesh: every chip
        encodes its own shard (payload/scales stay P(data)-sharded) and
        training still converges."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        zero = dict(infinity_zero(), offload_wire_bits=8)
        eng = DeepSpeedEngine(tiny_model(), config=dp_cfg(zero=zero, dp=8),
                              rng=rng, mesh=dp8_mesh())
        st = eng._infinity
        assert st.wire_bits == 8 and st.n_pad % (8 * 2048) == 0
        l0 = eng.eval_loss({"input_ids": ids})
        for _ in range(6):
            m = eng.train_step({"input_ids": ids})
            assert np.isfinite(m["loss"])
        l1 = eng.eval_loss({"input_ids": ids})
        assert float(l1) < float(l0) - 0.2

    def test_dp8_param_wire(self):
        """Quantized param uploads compose with the dp-sharded mesh: the
        payload and scales stay P(data)-sharded (each chip dequants its
        own span inside the layer program) and training converges."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        zero = dict(infinity_zero(), offload_param_bits=8,
                    offload_wire_bits=1)
        eng = DeepSpeedEngine(tiny_model(), config=dp_cfg(zero=zero, dp=8),
                              rng=rng, mesh=dp8_mesh())
        st = eng._infinity
        assert st.param_bits == 8 and st.n_pad % (8 * 2048) == 0
        payload, scales = st._ensure_layer(0, {0})
        assert payload.dtype == jnp.uint8
        assert payload.addressable_shards[0].data.shape == (st.n_pad // 8,)
        assert scales.shape == (st.n_pad // 2048,)
        assert len({s.device for s in payload.addressable_shards}) == 8
        st._sweep_uploads(block=True)
        l0 = eng.eval_loss({"input_ids": ids})
        for _ in range(6):
            m = eng.train_step({"input_ids": ids})
            assert np.isfinite(m["loss"])
        assert float(eng.eval_loss({"input_ids": ids})) < float(l0) - 0.2

    @pytest.mark.slow
    def test_dp8_gas_clip_and_convergence(self):
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=16)
        base = DeepSpeedEngine(tiny_model(),
                               config=dp_cfg(gas=2, clip=0.5, batch=16,
                                             dp=1),
                               rng=rng, mesh=single_mesh())
        inf = DeepSpeedEngine(tiny_model(),
                              config=dp_cfg(gas=2, clip=0.5, batch=16,
                                            zero=infinity_zero(), dp=8),
                              rng=rng, mesh=dp8_mesh())
        l0 = inf.eval_loss({"input_ids": ids})
        for _ in range(3):
            r1 = base.train_step({"input_ids": ids})
            r2 = inf.train_step({"input_ids": ids})
            assert abs(float(r1["loss"]) - float(r2["loss"])) < 5e-3
        for _ in range(5):
            inf.train_step({"input_ids": ids})
        assert float(inf.eval_loss({"input_ids": ids})) < float(l0) - 0.2

    def test_checkpoint_crosses_meshes(self, tmp_path):
        """A dp=1 Infinity checkpoint restores onto a dp=8 mesh (and the
        restored engine matches the donor's next step) — checkpoints are
        mesh-independent like the orbax reshard-on-read path."""
        rng = jax.random.PRNGKey(0)
        ids = ids_batch(n=8)
        a = DeepSpeedEngine(tiny_model(),
                            config=dp_cfg(zero=infinity_zero(), dp=1),
                            rng=rng, mesh=single_mesh())
        a.train_step({"input_ids": ids})
        a.save_checkpoint(str(tmp_path / "ck"), tag="x")
        b = DeepSpeedEngine(tiny_model(),
                            config=dp_cfg(zero=infinity_zero(), dp=8),
                            rng=jax.random.PRNGKey(7), mesh=dp8_mesh())
        b.load_checkpoint(str(tmp_path / "ck"), tag="x")
        ra = a.train_step({"input_ids": ids})
        rb = b.train_step({"input_ids": ids})
        assert abs(float(ra["loss"]) - float(rb["loss"])) < 5e-3

    def test_rejects_tp_under_offload(self):
        from deepspeed_tpu.parallel.topology import build_mesh
        from deepspeed_tpu.runtime.config import MeshConfig
        mesh = build_mesh(MeshConfig(data=4, model=2))
        cfg = dp_cfg(zero=infinity_zero(), dp=4)
        cfg["mesh"] = {"data": 4, "model": 2}
        with pytest.raises(NotImplementedError, match="data-like"):
            DeepSpeedEngine(tiny_model(), config=cfg,
                            rng=jax.random.PRNGKey(0), mesh=mesh)
