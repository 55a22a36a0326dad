"""MoE / expert-parallel tests (8-device CPU mesh).

Reference coverage model: `/root/reference/tests/unit/moe/test_moe.py`
(EP group construction, top-1/top-2 training steps) plus gating-math unit
checks against the reference's top1gating/top2gating semantics
(`deepspeed/moe/sharded_moe.py:177,278`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.moe import (MoEConfig, MoELayer, capacity, top1_gating,
                               top2_gating)
from deepspeed_tpu.models import TransformerLM, gpt2_config


def moe_model(layers=4, experts=4, **kw):
    cfg = gpt2_config("125m", num_layers=layers, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=16, dtype=jnp.float32,
                      moe_num_experts=experts, **kw)
    return TransformerLM(cfg)


def batch(n, seq=16, vocab=64, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, vocab, (n, seq), dtype=np.int32)}


class TestGating:
    def test_capacity_math(self):
        # reference _capacity: ceil(S/E * factor), floored at min_capacity
        assert capacity(64, 4, 1.0, 4) == 16
        assert capacity(64, 4, 1.5, 4) == 24
        assert capacity(8, 8, 1.0, 4) == 4  # min_capacity wins

    def test_top1_all_tokens_routed_when_capacity_ample(self):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (32, 4))
        out = top1_gating(logits, capacity_factor=4.0, min_capacity=1)
        # every token got exactly one slot
        assert float(jnp.sum(out.dispatch_mask)) == 32
        # combine weights per token sum to its top gate prob
        gates = jax.nn.softmax(logits, axis=-1)
        top = jnp.max(gates, axis=1)
        np.testing.assert_allclose(
            np.asarray(jnp.sum(out.combine_weights, axis=(1, 2))),
            np.asarray(top), rtol=1e-5)

    def test_top1_capacity_drop(self):
        # all tokens prefer expert 0 → only `capacity` survive
        logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (16, 1))
        out = top1_gating(logits, capacity_factor=0.25, min_capacity=1)
        # capacity = ceil(16/4 * 0.25) = 1
        assert float(jnp.sum(out.dispatch_mask)) == 1
        assert int(out.exp_counts[0]) == 16  # pre-drop routing counts

    def test_top1_aux_loss_uniform_vs_skewed(self):
        """Balanced routing minimizes l_aux (→1.0); skew pushes it up."""
        rng = jax.random.PRNGKey(1)
        uniform = 0.01 * jax.random.normal(rng, (256, 4))
        skewed = uniform.at[:, 0].add(8.0)
        l_uni = float(top1_gating(uniform, 4.0, 1).l_aux)
        l_skew = float(top1_gating(skewed, 4.0, 1).l_aux)
        assert abs(l_uni - 1.0) < 0.1
        assert l_skew > 3.0

    def test_top1_rts_respects_capacity(self):
        logits = jnp.tile(jnp.array([[10.0, 0.0, 0.0, 0.0]]), (16, 1))
        out = top1_gating(logits, capacity_factor=0.5, min_capacity=1,
                          rng=jax.random.PRNGKey(3), use_rts=True)
        assert float(jnp.sum(out.dispatch_mask)) == 2  # cap = 2
        # each surviving token occupies a distinct capacity slot
        slot_use = jnp.sum(out.dispatch_mask.astype(jnp.int32), axis=0)
        assert int(jnp.max(slot_use)) == 1

    def test_top2_two_experts_per_token(self):
        rng = jax.random.PRNGKey(2)
        logits = jax.random.normal(rng, (32, 4))
        out = top2_gating(logits, capacity_factor=4.0, min_capacity=1)
        # ample capacity: every token reaches 2 experts
        per_token = jnp.sum(out.dispatch_mask.astype(jnp.int32), axis=(1, 2))
        assert int(jnp.min(per_token)) == 2
        # combine weights normalized over the two experts
        np.testing.assert_allclose(
            np.asarray(jnp.sum(out.combine_weights, axis=(1, 2))),
            np.ones(32), rtol=1e-5)

    def test_top2_capacity_doubles(self):
        assert capacity(64, 4, 1.0 * 2, 4) == 32  # reference: factor*2

    def test_drop_tokens_false_rejected(self):
        with pytest.raises(ValueError):
            top1_gating(jnp.zeros((8, 2)), drop_tokens=False)


class TestMoELayer:
    def test_forward_shape_and_identity_combine(self):
        layer = MoELayer(16, MoEConfig(num_experts=4, k=1,
                                       capacity_factor=4.0, min_capacity=1))
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 6, 16))
        y, laux, counts = layer.apply(params, x)
        assert y.shape == x.shape
        assert np.isfinite(float(laux))
        assert int(jnp.sum(counts)) == 8 * 6

    def test_moe_matches_manual_expert_computation(self):
        """With 1 expert and ample capacity, MoE == plain FFN (gate prob 1)."""
        layer = MoELayer(16, MoEConfig(num_experts=1, k=1,
                                       capacity_factor=1.0, min_capacity=64))
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
        y, _, _ = layer.apply(params, x)
        single = jax.tree_util.tree_map(lambda p: p[0], params["experts"])
        ref = layer.expert_apply(single, x.reshape(-1, 16)).reshape(x.shape)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_residual_moe(self):
        layer = MoELayer(16, MoEConfig(num_experts=2, k=1, use_residual=True,
                                       capacity_factor=4.0, min_capacity=1))
        params = layer.init(jax.random.PRNGKey(0))
        assert "residual_mlp" in params and "coefficient" in params
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 16))
        y, laux, _ = layer.apply(params, x)
        assert y.shape == x.shape and np.isfinite(float(laux))

    def test_partition_specs_shard_experts(self):
        from jax.sharding import PartitionSpec as P
        layer = MoELayer(16, MoEConfig(num_experts=4))
        specs = layer.partition_specs()
        assert specs["experts"]["fc_in"]["kernel"][0] == "expert"
        assert specs["gate"]["kernel"] == P(None, None)


class TestMoETraining:
    def _train(self, mesh, experts=4, k=1, freq=2, steps=3, seed=0, **cfg_kw):
        model = moe_model(experts=experts, moe_k=k, moe_freq=freq)
        config = {
            "train_batch_size": 32,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": mesh,
            "steps_per_print": 0,
            **cfg_kw,
        }
        engine, _, _, _ = ds.initialize(model=model, config=config,
                                        rng=jax.random.PRNGKey(seed))
        return engine, [float(engine.train_step(
            batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(steps)]

    @pytest.mark.slow
    def test_ep_matches_dp(self):
        """Same model, same data: pure-DP mesh vs expert-parallel mesh must
        produce identical losses (EP is a layout, not a different program)."""
        _, dp = self._train({"data": 8})
        _, ep = self._train({"data": 2, "expert": 4})
        np.testing.assert_allclose(dp, ep, rtol=2e-4)

    @pytest.mark.slow
    def test_ep_with_tp(self):
        _, dp = self._train({"data": 8})
        _, ep_tp = self._train({"data": 2, "expert": 2, "model": 2})
        np.testing.assert_allclose(dp, ep_tp, rtol=2e-3)

    @pytest.mark.slow
    def test_top2_trains(self):
        _, losses = self._train({"data": 2, "expert": 4}, k=2)
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0] + 0.5

    def test_every_layer_moe(self):
        _, losses = self._train({"data": 2, "expert": 4}, freq=1)
        assert all(np.isfinite(losses))

    @pytest.mark.slow
    def test_moe_with_zero2(self):
        _, z0 = self._train({"data": 2, "expert": 4})
        _, z2 = self._train({"data": 2, "expert": 4},
                            zero_optimization={"stage": 2})
        np.testing.assert_allclose(z0, z2, rtol=2e-4)

    @pytest.mark.slow
    def test_expert_params_sharded(self):
        engine, _ = self._train({"data": 2, "expert": 4}, steps=1)
        specs = engine.zero_policy.param_specs
        blk = specs["blocks"]["moe_blk"]["moe"]["experts"]
        assert blk["fc_in"]["kernel"][1] == "expert"

    @pytest.mark.slow
    def test_rsample_rts_via_engine_rng(self):
        """batch['moe_rng'] reaches the gate through shard_batch + GAS scan:
        RSample/RTS configs train, and the key changes the routing."""
        model = moe_model(experts=4, moe_noisy_gate_policy="RSample",
                          moe_capacity_factor=0.5)
        config = {
            "train_batch_size": 32, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 2, "expert": 4}, "steps_per_print": 0,
        }
        engine, _, _, _ = ds.initialize(model=model, config=config,
                                        rng=jax.random.PRNGKey(0))
        b = batch(32)
        l1 = float(engine.train_step(
            {**b, "moe_rng": jax.random.PRNGKey(1)})["loss"])
        assert np.isfinite(l1)
        # missing rng with RSample fails loudly at trace time
        model2 = moe_model(experts=4, moe_noisy_gate_policy="RSample")
        engine2, _, _, _ = ds.initialize(model=model2, config=dict(config),
                                         rng=jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="rng"):
            engine2.train_step(batch(32))

    def test_pipeline_rejects_rsample(self):
        from deepspeed_tpu.parallel.topology import build_mesh
        from deepspeed_tpu.runtime.config import MeshConfig
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        mesh = build_mesh(MeshConfig(pipe=2, data=4))
        with pytest.raises(NotImplementedError):
            PipelineEngine(
                model=moe_model(moe_noisy_gate_policy="RSample"),
                config={"train_batch_size": 32,
                        "gradient_accumulation_steps": 2,
                        "mesh": {"pipe": 2, "data": 4},
                        "steps_per_print": 0},
                mesh=mesh, rng=jax.random.PRNGKey(0))

    @pytest.mark.slow
    def test_moe_under_pipeline(self):
        """PP(2) × EP(2) × DP(2) matches pure DP — the pipeline loop must
        accumulate MoE aux loss only on valid (non-bubble) ticks."""
        from deepspeed_tpu.parallel.topology import build_mesh
        from deepspeed_tpu.runtime.config import MeshConfig
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        _, dp = self._train({"data": 8})
        mesh_conf = {"pipe": 2, "data": 2, "expert": 2}
        mesh = build_mesh(MeshConfig(**mesh_conf))
        cfgd = {
            "train_batch_size": 32,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": mesh_conf,
            "steps_per_print": 0,
        }
        engine = PipelineEngine(model=moe_model(), config=cfgd, mesh=mesh,
                                rng=jax.random.PRNGKey(0))
        pp = [float(engine.train_step(
            batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(3)]
        np.testing.assert_allclose(dp, pp, rtol=2e-4)

    @pytest.mark.slow
    def test_moe_checkpoint_roundtrip(self, tmp_path):
        engine, losses = self._train({"data": 2, "expert": 4}, steps=2)
        engine.save_checkpoint(str(tmp_path), tag="m1")
        engine2, _ = self._train({"data": 2, "expert": 4}, steps=0, seed=1)
        engine2.load_checkpoint(str(tmp_path), tag="m1")
        l1 = float(engine.train_step(batch(engine.train_batch_size, seed=9))
                   ["loss"])
        l2 = float(engine2.train_step(batch(engine2.train_batch_size, seed=9))
                   ["loss"])
        assert abs(l1 - l2) < 1e-5


class TestMoEInference:
    """MoE serving (reference ops/transformer/inference/moe_inference.py):
    the compiled prefill+decode loop over an expert-parallel model."""

    def _moe_model(self):
        from deepspeed_tpu.models import TransformerLM, gpt2_config
        return TransformerLM(gpt2_config(
            "125m", num_layers=2, d_model=32, num_heads=4, vocab_size=64,
            max_seq_len=64, loss_chunk=0, dtype=jnp.float32,
            moe_num_experts=4, moe_freq=2, moe_k=1, moe_use_rts=False))

    @pytest.mark.slow
    def test_generate_runs_and_matches_forward_argmax(self):
        import deepspeed_tpu as ds
        model = self._moe_model()
        params = jax.device_get(model.init(jax.random.PRNGKey(0)))
        eng = ds.init_inference(self._moe_model(), params=params, config={
            "dtype": "float32", "max_out_tokens": 64, "prompt_bucket": 0,
            "moe": {"enabled": True, "ep_size": 2}})
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 64, (2, 8)).astype(np.int32)
        out = np.asarray(eng.generate(ids, max_new_tokens=4,
                                      temperature=0.0))
        assert out.shape == (2, 4)
        # greedy decode must agree with repeated full forwards (the cached
        # expert-dispatch path vs the scan path)
        cur = ids
        for t in range(4):
            logits = np.asarray(eng.forward(cur))
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            np.testing.assert_array_equal(out[:, t], nxt)
            cur = np.concatenate([cur, nxt[:, None]], axis=1)


# ---------------------------------------------------------------------------
# the dropless grouped product's backward (moe/dropless.py): dx is the
# same kernel over the weights' other axis, dw the kernel
# ``moe_grouped_matmul_dw`` — against a loop of einsums, in the interpreter
# ---------------------------------------------------------------------------
def grouped_case(counts, tile, k_dim=32, n=48, dead=3, seed=0):
    """A row buffer laid out as ``dropless._layout`` lays it: expert ``e``'s
    ``counts[e]`` rows from a tile boundary on, padding rows zero, ``dead``
    tiles after the last live one."""
    tiles_of = [-(-c // tile) for c in counts]
    live = sum(tiles_of)
    rows = (live + dead) * tile
    te, real = [], np.zeros(rows, bool)
    at = 0
    for e, (c, t) in enumerate(zip(counts, tiles_of)):
        te += [e] * t
        real[at * tile:at * tile + c] = True
        at += t
    te += [len(counts) - 1] * dead
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (rows, k_dim)) * real[:, None]
    w = jax.random.normal(kw, (len(counts), k_dim, n))
    g = jax.random.normal(kg, (rows, n)) * real[:, None]
    return x, w, g, jnp.asarray(te, jnp.int32), live, real


class TestGroupedProductBackward:
    @pytest.mark.parametrize("tile", [16, 32])
    @pytest.mark.parametrize("counts", [
        [20, 20, 20, 20], [0, 0, 70, 0], [17, 0, 33, 5]],
        ids=["balanced", "one_expert", "an_expert_without_rows"])
    def test_dx_and_dw_against_a_loop_of_einsums(self, counts, tile):
        from deepspeed_tpu.moe import dropless
        x, w, g, te, live, real = grouped_case(counts, tile)
        row_expert = np.repeat(np.asarray(te), tile)

        def through_kernel(x, w):
            y = dropless.grouped_matmul(x, w, te, live)
            return jnp.sum(jnp.where(real[:, None], y, 0.0) * g)

        def through_einsums(x, w):
            y = jnp.zeros((x.shape[0], w.shape[2]))
            for e in range(w.shape[0]):
                y = y + jnp.where((row_expert == e)[:, None],
                                  jnp.einsum("mk,kn->mn", x, w[e]), 0.0)
            return jnp.sum(jnp.where(real[:, None], y, 0.0) * g)
        dx, dw = jax.grad(through_kernel, (0, 1))(x, w)
        want_dx, want_dw = jax.grad(through_einsums, (0, 1))(x, w)
        live_rows = np.arange(x.shape[0]) < live * tile
        np.testing.assert_allclose(np.asarray(dx)[live_rows],
                                   np.asarray(want_dx)[live_rows],
                                   atol=2e-5)
        np.testing.assert_allclose(dw, want_dw, atol=5e-5)
        for e, c in enumerate(counts):
            if not c:                      # exactly zero, not small
                assert not np.asarray(dw[e]).any()
            else:
                assert np.asarray(dw[e]).any()

    def test_expert_share_differentiates_in_one_pass(self):
        """``pass_rows=None``: one pass over the whole buffer, whose
        gradients (rows, weights, pick weights) equal a dense loop's; the
        load-dependent loop refuses reverse differentiation."""
        from deepspeed_tpu.moe import dropless
        t, h, f, held = 96, 32, 48, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        u = jax.random.normal(ks[0], (t, h))
        experts = dropless.init_experts(ks[1], held, h, f, 0.2, 0.2,
                                        jnp.float32)
        index = jax.random.randint(ks[2], (t, 1), 0, 8)     # 4..7 absent
        weight = jax.random.uniform(ks[3], (t, 1)) + 0.5

        def ours(u, experts, weight, **kw):
            y, _ = dropless.expert_share(
                experts, u, dropless.Routing(index, weight), 8, (0, held),
                **kw)
            return jnp.sum(y * y)

        def dense(u, experts, weight):
            y = 0.0
            for e in range(held):
                out = (jax.nn.silu(u @ experts["w_gate"][e])
                       * (u @ experts["w_up"][e])) @ experts["w_down"][e]
                y = y + jnp.where(index == e, weight, 0.0) * out
            return jnp.sum(y * y)
        want = jax.grad(dense, (0, 1, 2))(u, experts, weight)
        for tile in (16, 32):
            got = jax.grad(ours, (0, 1, 2))(u, experts, weight,
                                            pass_rows=None, tile_rows=tile)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        with pytest.raises(ValueError, match="[Rr]everse"):
            jax.jit(jax.grad(lambda u: ours(u, experts, weight,
                                            pass_rows=32)))(u)

    def test_route_is_route_logits_of_one_product(self):
        from deepspeed_tpu.moe import dropless
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        u = jax.random.normal(ks[0], (40, 32))
        kernel = jax.random.normal(ks[1], (32, 12))
        bias = 0.1 * jax.random.normal(ks[2], (12,))
        for scoring, renorm in (("softmax", False), ("sigmoid", True)):
            a = dropless.route(u, kernel, bias, 3, 2.5, scoring, renorm)
            b = dropless.route_logits(
                jnp.einsum("th,he->te", u, kernel,
                           preferred_element_type=jnp.float32),
                bias, 3, 2.5, scoring, renorm)
            assert np.array_equal(a.index, b.index)
            assert np.array_equal(a.weight, b.weight)
