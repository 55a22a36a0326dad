"""Training-perf suite: remat overrides, fused loss head, and the
autotuner feedback loop (docs/training_perf.md).

Pins the PR-11 acceptance contracts:
  * the ``training`` config block rebuilds the model per-engine and the
    step is numerically identical across remat policies;
  * the fused loss head (analytic custom-VJP cross-entropy) matches the
    autodiff path in value AND gradient for tied and untied heads;
  * a 2-point CPU smoke search emits a best-config JSON that the master
    ``DeepSpeedConfig`` parses round-trip and ``ds.initialize`` applies.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              TransformerLM)

pytestmark = pytest.mark.autotune

TINY = dict(vocab_size=64, max_seq_len=16, num_layers=2, num_heads=2,
            d_model=16)


def tiny_model(**kw):
    return TransformerLM(TransformerConfig(**{**TINY, **kw}))


def base_cfg(**extra):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 0},
           "steps_per_print": 0}
    cfg.update(extra)
    return cfg


def make_batch(bs, seq=16, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, TINY["vocab_size"], (bs, seq),
                                    dtype=np.int32)}


def first_leaf(tree):
    return np.asarray(jax.tree_util.tree_leaves(tree)[0],
                      dtype=np.float32)


class TestRematParity:
    @pytest.mark.slow
    def test_step_identical_across_policies(self):
        """remat changes WHAT is stored, never what is computed: one
        train step under none / dots_saveable / full must produce the
        same loss and the same updated params."""
        ref_loss, ref_leaf = None, None
        for remat in ("none", "dots_saveable", "full"):
            engine, _, _, _ = ds.initialize(
                model=tiny_model(), config=base_cfg(
                    training={"remat": remat}))
            # the engine — not the caller — rebuilt the model
            assert engine.model.config.remat == remat
            m = engine.train_step(make_batch(engine.train_batch_size))
            loss = float(m["loss"])
            leaf = first_leaf(engine.state["params"])
            if ref_loss is None:
                ref_loss, ref_leaf = loss, leaf
            else:
                np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
                np.testing.assert_allclose(leaf, ref_leaf, atol=1e-5)

    def test_bogus_policy_rejected_at_parse(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        with pytest.raises(ValueError, match="remat"):
            DeepSpeedConfig(base_cfg(training={"remat": "bogus"}))

    def test_override_is_validated_against_model(self):
        """An override the model config has no field for must fail loud,
        not silently tune nothing."""
        class NoConfig:
            def loss(self, params, batch, scale):   # pragma: no cover
                raise NotImplementedError

        with pytest.raises(ValueError, match="training"):
            ds.initialize(model=NoConfig(),
                          config=base_cfg(training={"remat": "full"}))


class TestFusedLossHead:
    def _loss_and_grads(self, model, batch, head_bias=False):
        params = model.init(jax.random.PRNGKey(0))
        if head_bias:    # init makes none; a GPT-J checkpoint brings one
            params["lm_head"]["bias"] = 0.5 * jax.random.normal(
                jax.random.PRNGKey(1), (TINY["vocab_size"],))
        val, grads = jax.value_and_grad(model.loss)(params, batch)
        return float(val), grads

    # the tied-head arm is the heaviest (~12s) of the parity pins; the
    # untied + chunked arms keep the contract in tier-1
    @pytest.mark.parametrize("kw", [
        pytest.param({}, marks=pytest.mark.slow),  # tied embedding head
        {"tie_embeddings": False},        # untied lm_head kernel
        {"loss_chunk": 8},                # chunked scan path
        # db, dw and the logsumexp the forward keeps, across two chunks
        {"tie_embeddings": False, "loss_chunk": 8, "head_bias": True},
        # a label no column matches, in a row the mask drops
        {"loss_chunk": 8, "bad_label": 10 ** 6},
        {"tie_embeddings": False, "bad_label": -100},
    ], ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()) or "tied")
    def test_matches_autodiff(self, kw):
        # f32 end to end: the contract is that the analytic VJP computes
        # the same MATH as autodiff. Under bf16 params the fused head is
        # a bf16 ulp apart (it accumulates dw in f32 where autodiff
        # rounds per-matmul), which is an improvement, not parity.
        import jax.numpy as jnp
        kw = {**kw, "dtype": jnp.float32, "param_dtype": jnp.float32}
        head_bias = kw.pop("head_bias", False)
        bad_label = kw.pop("bad_label", None)
        batch = dense_batch = make_batch(2)
        if bad_label is not None:
            ids = batch["input_ids"]
            mask = np.ones(ids.shape, np.float32)
            mask[:, -1] = mask[1, 3] = 0.0
            labels = np.roll(ids, -1, axis=1)
            batch = {"input_ids": ids, "labels": labels.copy(),
                     "loss_mask": mask}
            batch["labels"][1, 3] = bad_label
            # autodiff's arm sees the row dropped, its label in range
            dense_batch = {**batch, "labels": labels}
        v_fused, g_fused = self._loss_and_grads(
            tiny_model(fused_loss_head=True, **kw), batch, head_bias)
        v_dense, g_dense = self._loss_and_grads(
            tiny_model(fused_loss_head=False, **kw), dense_batch, head_bias)
        if bad_label is None:
            # with one, the VALUE is NaN on either path (take_along_axis
            # fills what is out of range, and NaN * 0 is NaN): the mask
            # makes the gradients right, not the label valid
            np.testing.assert_allclose(v_fused, v_dense, rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_fused),
                        jax.tree_util.tree_leaves(g_dense)):
            assert np.isfinite(np.asarray(a, dtype=np.float32)).all()
            np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                       np.asarray(b, dtype=np.float32),
                                       atol=2e-5)

    def test_engine_override_disables_it(self):
        engine, _, _, _ = ds.initialize(
            model=tiny_model(), config=base_cfg(
                training={"fused_loss_head": False, "loss_chunk": 4}))
        assert engine.model.config.fused_loss_head is False
        assert engine.model.config.loss_chunk == 4
        m = engine.train_step(make_batch(engine.train_batch_size))
        assert np.isfinite(float(m["loss"]))


class TestAutotuneSmoke:
    @pytest.mark.slow
    def test_two_point_search_emits_config_json(self, tmp_path):
        """The acceptance loop end-to-end on CPU: search remat over two
        points, export the winner per hardware profile, parse it back
        through DeepSpeedConfig, and initialize an engine from the file
        — the tuned settings must be live on the engine's model."""
        from deepspeed_tpu.autotuning.autotuner import (Autotuner,
                                                        hardware_profile)
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(2,),
                       zero_stages=(0,), remat_policies=("none", "full"),
                       steps_per_trial=1, tuner_type="grid")
        best = at.tune(lambda bs: make_batch(bs))
        assert len(at.results) == 2
        assert best["_model_overrides"]["remat"] in ("none", "full")

        cfg, path = Autotuner.export_best(best, path=str(tmp_path))
        prof = hardware_profile()
        assert os.path.basename(path) == f"autotune_best_{prof}.json"
        loaded = json.loads(open(path).read())
        assert loaded["autotune_profile"] == prof
        assert loaded["training"]["remat"] == \
            best["_model_overrides"]["remat"]
        assert "_model_overrides" not in loaded

        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        dc = DeepSpeedConfig(loaded)   # round-trip: parses as-is
        assert dc.training.remat == loaded["training"]["remat"]
        engine, _, _, _ = ds.initialize(model=tiny_model(),
                                        config=loaded)
        assert engine.model.config.remat == loaded["training"]["remat"]
        m = engine.train_step(make_batch(engine.train_batch_size))
        assert np.isfinite(float(m["loss"]))

    def test_offload_bits_only_on_offload_arm(self):
        from deepspeed_tpu.autotuning.autotuner import Autotuner
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(1,),
                       zero_stages=(0,), offload_options=(False, True),
                       offload_bits=(0, 8), tuner_type="grid")
        exps = at.generate_experiments()
        arms = {(e["key"][3], e["wire_bits"]) for e in exps}
        assert arms == {(False, 0), (True, 0), (True, 8)}
        for e in exps:
            z = e["cfg"]["zero_optimization"]
            if e["wire_bits"]:
                assert z["offload_wire_bits"] == e["wire_bits"]
                assert z["offload_optimizer"] == {"device": "cpu"}
            else:
                assert "offload_wire_bits" not in z

    def test_mesh_shapes_pruned_to_device_count(self):
        from deepspeed_tpu.autotuning.autotuner import Autotuner
        ndev = jax.device_count()
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(1,),
                       zero_stages=(0,),
                       mesh_shapes=((1, 1), (1, ndev * 2)),
                       tuner_type="grid")
        exps = at.generate_experiments()
        assert {e["mesh"] for e in exps} == {(1, 1)}
        assert all(e["cfg"]["mesh"] == {"data": 1, "model": 1}
                   for e in exps)

    def test_apply_best_compat(self):
        """tune()'s raw dict keeps working through apply_best — the
        pre-export consumer contract."""
        from deepspeed_tpu.autotuning.autotuner import Autotuner
        best = {**base_cfg(), "_model_overrides": {"remat": "full"}}
        model, cfg = Autotuner.apply_best(tiny_model(), best)
        assert model.config.remat == "full"
        assert "_model_overrides" not in cfg
