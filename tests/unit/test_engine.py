"""Engine + ZeRO tests on the 8-device virtual mesh.

The headline correctness property (the reference tests it per stage in
`/root/reference/tests/unit/runtime/zero/test_zero.py`): **ZeRO stages 0-3
produce the same training trajectory** — sharding is an execution detail,
not a numerics change.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, gpt2_config


def tiny_model(dtype=jnp.float32):
    cfg = gpt2_config("125m", num_layers=2, d_model=64, num_heads=4,
                      vocab_size=128, max_seq_len=32, dtype=dtype)
    return TransformerLM(cfg)


def base_config(**over):
    cfg = {
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "mesh": {"data": 8},
    }
    cfg.update(over)
    return cfg


def fixed_batch(n=16, seq=32, vocab=128, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, vocab, (n, seq), dtype=np.int32)}


def run_steps(config, n=3, model=None, seed=0):
    engine, _, _, _ = ds.initialize(
        model=model or tiny_model(), config=config,
        rng=jax.random.PRNGKey(42))
    losses = []
    for i in range(n):
        m = engine.train_step(fixed_batch(seed=seed + i))
        losses.append(float(m["loss"]))
    return engine, losses


class TestBasicTraining:
    def test_loss_decreases(self):
        _, losses = run_steps(base_config(), n=5)
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_gas_equivalence(self):
        """Same global batch, different gas split → same trajectory."""
        _, l1 = run_steps(base_config(train_micro_batch_size_per_gpu=2))
        _, l2 = run_steps(base_config(train_micro_batch_size_per_gpu=1))
        np.testing.assert_allclose(l1, l2, rtol=1e-4)

    def test_metrics_keys(self):
        engine, _, _, _ = ds.initialize(model=tiny_model(),
                                        config=base_config())
        m = engine.train_step(fixed_batch())
        for k in ("loss", "lr", "grad_norm", "overflow"):
            assert k in m

    @pytest.mark.slow
    def test_grad_clipping_applied(self):
        """The reported grad_norm is the PRE-clip global norm, and with a
        LINEAR optimizer (SGD — Adam's normalizer hides the scale) the
        applied update norm is exactly lr * clip when clip < gnorm."""
        def delta_norm(clip):
            cfg = base_config(gradient_clipping=clip,
                              optimizer={"type": "sgd",
                                         "params": {"lr": 1.0}})
            eng, _, _, _ = ds.initialize(model=tiny_model(), config=cfg,
                                         rng=jax.random.PRNGKey(0))
            p0 = jax.device_get(eng.state["params"])
            m = eng.train_step(fixed_batch())
            d2 = sum(float(jnp.sum((jnp.asarray(a) - jnp.asarray(b)) ** 2))
                     for a, b in zip(jax.tree_util.tree_leaves(p0),
                                     jax.tree_util.tree_leaves(
                                         jax.device_get(
                                             eng.state["params"]))))
            return np.sqrt(d2), float(m["grad_norm"])

        d1, g1 = delta_norm(0.01)
        d2, g2 = delta_norm(0.02)
        assert g1 > 0.02                       # pre-clip norm reported
        np.testing.assert_allclose(g1, g2, rtol=1e-5)
        np.testing.assert_allclose(d1, 0.01, rtol=1e-3)   # lr * clip
        np.testing.assert_allclose(d2 / d1, 2.0, rtol=1e-3)


class TestZeroParity:
    """Stages must agree step-for-step (fp32 exact-ish)."""

    @pytest.mark.parametrize("stage", [1, 2, 3])
    @pytest.mark.slow
    def test_stage_matches_stage0(self, stage):
        _, l0 = run_steps(base_config(), n=3)
        _, ls = run_steps(base_config(
            zero_optimization={"stage": stage}), n=3)
        np.testing.assert_allclose(l0, ls, rtol=2e-4)

    def test_stage1_opt_state_sharded(self):
        engine, _ = run_steps(base_config(zero_optimization={"stage": 1}), n=1)
        m = engine.state["opt"]["m"]["blocks"]["mlp"]["fc_in"]["kernel"]
        assert "data" in str(m.sharding.spec)
        # params stay replicated at stage 1... but master fp32 shards too
        p = engine.state["params"]["blocks"]["mlp"]["fc_in"]["kernel"]
        assert "data" in str(p.sharding.spec)

    def test_stage3_param_sharded_excluding_scan_axis(self):
        # persistence threshold 0: tiny test params must actually shard
        engine, _ = run_steps(base_config(zero_optimization={
            "stage": 3, "param_persistence_threshold": 0}), n=1)
        p = engine.state["params"]["blocks"]["mlp"]["fc_in"]["kernel"]
        spec = p.sharding.spec
        assert spec[0] is None          # scan/layer axis never sharded
        assert "data" in str(spec)

    @pytest.mark.slow
    def test_stage3_param_persistence_threshold(self):
        """Params below the threshold stay resident (replicated) — the
        reference's persisted-param set (stage3_param_persistence_threshold,
        zero/config.py)."""
        engine, losses = run_steps(base_config(zero_optimization={
            "stage": 3, "param_persistence_threshold": 10 ** 9}), n=2)
        p = engine.state["params"]["blocks"]["mlp"]["fc_in"]["kernel"]
        assert "data" not in str(p.sharding.spec)  # everything persisted
        assert all(np.isfinite(losses))
        _, ref = run_steps(base_config(zero_optimization={
            "stage": 3, "param_persistence_threshold": 0}), n=2)
        np.testing.assert_allclose(losses, ref, rtol=1e-4)

    @pytest.mark.slow
    def test_zero_with_tp_mesh(self):
        cfg = base_config(mesh={"data": 4, "model": 2},
                          zero_optimization={"stage": 2})
        _, l0 = run_steps(base_config(), n=2)
        _, ltp = run_steps(cfg, n=2)
        np.testing.assert_allclose(l0, ltp, rtol=2e-3)


class TestMixedPrecision:
    def test_bf16_trains(self):
        _, losses = run_steps(base_config(bf16={"enabled": True}),
                              model=tiny_model(jnp.bfloat16), n=5)
        assert losses[-1] < losses[0]

    def test_fp16_dynamic_scaler_present(self):
        engine, _ = run_steps(base_config(
            fp16={"enabled": True, "initial_scale_power": 8}),
            model=tiny_model(jnp.float16), n=2)
        assert engine.loss_scale == 2 ** 8  # no overflow in 2 tiny steps

    def test_fp16_overflow_skips_step(self):
        engine, _, _, _ = ds.initialize(
            model=tiny_model(jnp.float16),
            config=base_config(fp16={"enabled": True,
                                     "initial_scale_power": 4,
                                     "hysteresis": 1}))
        step_before = int(engine.state["step"])
        bad = {"input_ids": fixed_batch()["input_ids"]}
        # poison params to force inf grads
        engine.state["params"]["embed"]["embedding"] = \
            engine.state["params"]["embed"]["embedding"].at[0, 0].set(jnp.inf)
        engine.train_step(bad)
        assert int(engine.state["step"]) == step_before  # skipped
        assert engine.loss_scale == 2 ** 3  # halved


class TestCompatAPI:
    @pytest.mark.slow
    def test_forward_backward_step(self):
        engine, _, _, _ = ds.initialize(model=tiny_model(),
                                        config=base_config(),
                                        rng=jax.random.PRNGKey(42))
        ref_engine, ref_losses = run_steps(base_config(), n=1)  # same rng
        batch = fixed_batch()
        gas = engine.gradient_accumulation_steps
        micro = batch["input_ids"].reshape(
            gas, -1, batch["input_ids"].shape[-1])
        for g in range(gas):
            loss = engine.forward({"input_ids": micro[g]})
            engine.backward(loss)
        assert engine.is_gradient_accumulation_boundary()
        engine.step()
        assert int(engine.state["step"]) == 1
        # NUMERIC parity with the fused train_step: the first fused loss
        # must equal the mean of the compat micro losses, and the params
        # after one compat step must match the fused engine's params
        ref_p = jax.device_get(ref_engine.state["params"])
        got_p = jax.device_get(engine.state["params"])
        for a, b in zip(jax.tree_util.tree_leaves(ref_p),
                        jax.tree_util.tree_leaves(got_p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)

    def test_lr_and_introspection(self):
        engine, _ = run_steps(base_config(scheduler={
            "type": "WarmupLR",
            "params": {"warmup_num_steps": 10, "warmup_max_lr": 1e-3,
                       "warmup_type": "linear"}}), n=2)
        assert 0 < engine.get_lr() <= 1e-3
        assert engine.num_parameters() > 0


class TestBatchReconciliation:
    def test_infers_gas(self):
        engine, _, _, _ = ds.initialize(
            model=tiny_model(),
            config=base_config(train_batch_size=32,
                               train_micro_batch_size_per_gpu=2))
        assert engine.gradient_accumulation_steps == 2  # 32/(2*8)

    def test_rejects_mismatch(self):
        with pytest.raises(ValueError):
            ds.initialize(model=tiny_model(), config=base_config(
                train_batch_size=17))


class TestLossWithCounters:
    """A loss that returns ``(loss, counters)``: the fused step hands the
    counters back in ``train_step``'s result, summed over the
    microbatches; the trajectory is the scalar loss's."""

    @pytest.mark.parametrize("gas", [1, 2])
    def test_counters_ride_the_fused_step(self, gas):
        model = tiny_model()

        def counted(params, batch):
            ids = batch["input_ids"]
            return model.loss(params, batch), {
                "rows": jnp.asarray(ids.shape[0], jnp.int32),
                "even_ids": jnp.sum(ids % 2 == 0).astype(jnp.int32)}
        config = base_config(train_batch_size=16 * gas,
                             gradient_accumulation_steps=gas)
        batch = fixed_batch(n=16 * gas)
        plain, *_ = ds.initialize(model=model, config=config,
                                  rng=jax.random.PRNGKey(42))
        engine, *_ = ds.initialize(model=model, config=config,
                                   loss_fn=counted,
                                   rng=jax.random.PRNGKey(42))
        for _ in range(2):
            want, got = plain.train_step(batch), engine.train_step(batch)
            assert float(got["loss"]) == float(want["loss"])
            assert "rows" not in want
            assert int(got["rows"]) == 16 * gas
            assert int(got["even_ids"]) == int(
                (batch["input_ids"] % 2 == 0).sum())
        assert float(engine.eval_loss(batch)) == float(
            plain.eval_loss(batch))


class TestStage3GathersLayers:
    """Stage 3 over more than one data-parallel device puts the policy's
    ``gather_layer`` on the model's per-layer seam; every other engine
    leaves the model as it was built."""

    SHARD_ALL = {"stage": 3, "param_persistence_threshold": 0}

    @pytest.mark.parametrize("mesh", [{"data": 8}, {"data": 4, "model": 2}],
                             ids=["data8", "data4_model2"])
    def test_sharded_stage3_matches_stage0(self, mesh):
        """Every parameter sharded (threshold 0) and every layer gathered
        in its body: the trajectory is stage 0's, and the state leaves
        the step in ``state_shardings()``."""
        _, l0 = run_steps(base_config(), n=3)
        engine, l3 = run_steps(base_config(
            mesh=mesh, zero_optimization=self.SHARD_ALL), n=3)
        np.testing.assert_allclose(l0, l3, rtol=2e-3 if "model" in mesh
                                   else 2e-4)
        assert engine.zero_policy.gathers_layers
        want = jax.tree_util.tree_leaves(engine.state_shardings())
        got = jax.tree_util.tree_leaves(engine.state)
        assert len(want) == len(got)
        for leaf, sharding in zip(got, want):
            assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)

    @pytest.mark.parametrize("stage,mesh", [
        (0, {"data": 8}), (1, {"data": 8}), (2, {"data": 8}),
        (2, {"data": 4, "model": 2}), (3, {"data": 1}),
        (3, {"data": 1, "model": 8})])
    def test_other_engines_never_see_the_gather(self, monkeypatch, stage,
                                                mesh):
        """Stages 0-2, and stage 3 with nothing to gather over: the
        model's ``block_transform`` is the one it was built with, and
        tracing the step never reaches ``gather_layer`` — the step's
        jaxpr is what it was before the policy had the function."""
        from deepspeed_tpu.parallel.topology import build_mesh
        from deepspeed_tpu.runtime.config import MeshConfig
        from deepspeed_tpu.runtime.zero.sharding import ZeroShardingPolicy

        def never(self, layer):
            raise AssertionError("gather_layer reached")
        monkeypatch.setattr(ZeroShardingPolicy, "gather_layer", never)
        model = tiny_model()
        built_with = model.block_transform
        devices = jax.devices()[:int(np.prod(list(mesh.values())))]
        engine, *_ = ds.initialize(
            model=model, mesh=build_mesh(MeshConfig(**mesh), devices=devices),
            config=base_config(
                mesh=mesh, train_batch_size=2 * mesh["data"],
                zero_optimization={"stage": stage,
                                   "param_persistence_threshold": 0}))
        assert not engine.zero_policy.gathers_layers
        assert engine.model.block_transform is built_with
        batch = engine.shard_batch(fixed_batch(n=2 * mesh["data"]))
        jaxpr = engine._build_train_step().trace(engine.state, batch).jaxpr
        assert "sharding_constraint" in str(jaxpr)   # the engine's own

    def test_gathered_layer_keeps_its_model_axis(self):
        """On ``{model: 2, data: 4}`` the gathered layout is
        ``param_specs``': the ``model`` axis stays where the model
        declared it and only the data axis is gathered away."""
        from jax.sharding import PartitionSpec as P
        engine, _ = run_steps(base_config(
            mesh={"data": 4, "model": 2},
            zero_optimization=self.SHARD_ALL), n=0)
        blocks = engine.state["params"]["blocks"]
        stored = blocks["mlp"]["fc_in"]["kernel"].sharding.spec
        assert "data" in str(stored) and "model" in str(stored)
        with engine.mesh:
            layer = jax.jit(lambda b: engine.zero_policy.gather_layer(
                jax.tree_util.tree_map(lambda a: a[0], b)))(blocks)
        declared = engine.zero_policy.param_specs["blocks"]
        for leaf, spec in zip(jax.tree_util.tree_leaves(layer),
                              jax.tree_util.tree_leaves(
                                  declared,
                                  is_leaf=lambda x: isinstance(x, P))):
            assert "data" not in str(leaf.sharding.spec)
            want = jax.sharding.NamedSharding(engine.mesh, P(*spec[1:]))
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
        assert "model" in str(layer["mlp"]["fc_in"]["kernel"].sharding.spec)
        np.testing.assert_array_equal(
            np.asarray(layer["mlp"]["fc_in"]["kernel"]),
            np.asarray(blocks["mlp"]["fc_in"]["kernel"][0]))

    def test_composes_with_the_models_own_transform(self, monkeypatch):
        """A model built with a ``block_transform`` keeps it BEHIND the
        gather: the policy sees the stored slice its spec tree describes,
        the model's transform what the gather returned.  A later engine
        with nothing to gather puts the model's own back."""
        seen = []

        def own(layer):
            seen.append(layer)
            return layer
        cfg = tiny_model().config
        model = TransformerLM(cfg, block_transform=own)
        engine, *_ = ds.initialize(model=model, config=base_config(
            zero_optimization=self.SHARD_ALL))
        seam = engine.model.block_transform
        assert seam is not own and seam.model_transform is own
        stored, gathered = object(), object()
        monkeypatch.setattr(
            engine.zero_policy, "gather_layer",
            lambda layer: gathered if layer is stored else None)
        assert seam(stored) is gathered and seen == [gathered]
        monkeypatch.undo()
        seen.clear()
        _, losses = run_steps(base_config(zero_optimization=self.SHARD_ALL),
                              n=2, model=model)
        assert model.block_transform.model_transform is own   # not nested
        assert seen and all(np.isfinite(losses))
        _, l0 = run_steps(base_config(), n=2)
        np.testing.assert_allclose(l0, losses, rtol=2e-4)
        ds.initialize(model=model, config=base_config())       # stage 0
        assert model.block_transform is own
