"""Pipeline parallelism tests (8-device CPU mesh).

Reference coverage model: `/root/reference/tests/unit/runtime/pipe/` —
schedule instruction generation and PP-vs-DP train parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine, PipelinedLM
from deepspeed_tpu.runtime.pipe import schedule as S
from deepspeed_tpu.runtime.pipe.module import (LayerSpec, PipelineModule,
                                               partition_layers)


def tiny_model(layers=4, **kw):
    cfg = gpt2_config("125m", num_layers=layers, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=16, dtype=jnp.float32, **kw)
    return TransformerLM(cfg)


def base_config(**over):
    cfg = {
        "train_batch_size": 64,
        "gradient_accumulation_steps": 4,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    cfg.update(over)
    return cfg


def fixed_batch(n, seq=16, vocab=64, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, vocab, (n, seq), dtype=np.int32)}


class TestSchedules:
    def test_train_schedule_covers_all_microbatches(self):
        sched = S.TrainSchedule(micro_batches=4, stages=2, stage_id=0)
        steps = list(sched.steps())
        fwd = [c.buffer_id for step in steps for c in step
               if isinstance(c, S.ForwardPass)]
        bwd = [c.buffer_id for step in steps for c in step
               if isinstance(c, S.BackwardPass)]
        assert len(fwd) == 4 and len(bwd) == 4
        assert any(isinstance(c, S.OptimizerStep)
                   for step in steps for c in step)

    def test_inference_schedule_step_count(self):
        sched = S.InferenceSchedule(micro_batches=3, stages=4, stage_id=1)
        assert len(list(sched.steps())) == 3 + 4 - 1

    def test_1f1b_interleaving(self):
        """Steady state on a middle stage alternates fwd/bwd."""
        sched = S.TrainSchedule(micro_batches=8, stages=4, stage_id=1)
        kinds = []
        for step in sched.steps():
            for c in step:
                if isinstance(c, (S.ForwardPass, S.BackwardPass)):
                    kinds.append("F" if isinstance(c, S.ForwardPass) else "B")
        s = "".join(kinds)
        assert "FBFB" in s  # alternation appears in steady state

    @pytest.mark.parametrize("m,stages", [(4, 2), (6, 4), (8, 3), (3, 3)])
    def test_compiled_loop_timing_matches_schedule(self, m, stages):
        """The compiled 1F1B loop's closed-form tick mapping (fwd at
        2m+s, bwd at 2m+2S-1-s) must reproduce the TrainSchedule
        instruction simulation exactly — the validation the schedule
        docstring promises."""
        for sid in range(stages):
            sched = S.TrainSchedule(micro_batches=m, stages=stages,
                                    stage_id=sid)
            sim = {}
            for t in range(2 * (m + stages - 1)):
                mb_id, fwd = sched._step_to_micro_batch(t)
                if sched._valid_micro_batch(mb_id):
                    sim[(t, "F" if fwd else "B")] = mb_id
            compiled = {}
            for t in range(2 * (m + stages - 1)):
                mf2 = t - sid
                if mf2 >= 0 and mf2 % 2 == 0 and mf2 // 2 < m:
                    compiled[(t, "F")] = mf2 // 2
                mb2 = t - (2 * stages - 1 - sid)
                if mb2 >= 0 and mb2 % 2 == 0 and mb2 // 2 < m:
                    compiled[(t, "B")] = mb2 // 2
            assert compiled == sim, (sid, compiled, sim)

    def test_ordering_invariants(self):
        """Backward of m at stage s must come after forward of m at s and
        after backward of m at stage s+1 (grad flow feasibility)."""
        for stages in (2, 3, 4):
            for m in range(6):
                for s in range(stages):
                    tf, tb = 2 * m + s, 2 * m + 2 * stages - 1 - s
                    assert tb > tf
                    if s + 1 < stages:
                        assert tb > 2 * m + 2 * stages - 1 - (s + 1)


class TestPartitioning:
    def test_uniform(self):
        assert partition_layers(
            [LayerSpec(lambda r: {}, lambda p, x: x)] * 8, 4,
            "uniform") == [0, 2, 4, 6, 8]

    def test_parameters_balanced(self):
        def mk(n):
            return LayerSpec(lambda r, n=n: {"w": jnp.zeros((n,))},
                             lambda p, x: x)
        # weights 4,4,1,1,1,1 over 2 stages → [4,4] vs rest
        bounds = partition_layers([mk(4), mk(4), mk(1), mk(1), mk(1), mk(1)],
                                  2, "parameters")
        assert bounds[0] == 0 and bounds[-1] == 6
        w = [4, 4, 1, 1, 1, 1]
        loads = [sum(w[bounds[i]:bounds[i+1]]) for i in range(2)]
        assert max(loads) <= 8

    def test_pipeline_module_tied(self):
        from deepspeed_tpu.runtime.pipe.module import TiedLayerSpec
        specs = [TiedLayerSpec("emb", lambda r: {"w": jnp.zeros((4,))},
                               lambda p, x: x)] + \
                [LayerSpec(lambda r: {"b": jnp.zeros((2,))},
                           lambda p, x: x)] * 3
        pm = PipelineModule(specs, num_stages=2, partition_method="uniform")
        built = pm.init(jax.random.PRNGKey(0))
        assert "emb" in built["tied"]
        assert pm.tied_keys == ["emb"]


class TestPipelineEngine:
    def _dp_reference_losses(self, n=3, layers=4):
        engine, _, _, _ = ds.initialize(
            model=tiny_model(layers), config=base_config(mesh={"data": 8}),
            rng=jax.random.PRNGKey(3))
        return [float(engine.train_step(
            fixed_batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(n)]

    def _pp_losses(self, mesh_conf, n=3, layers=4, stage=0):
        mesh = build_mesh(MeshConfig(**mesh_conf))
        cfgd = base_config(zero_optimization={"stage": stage})
        cfgd["mesh"] = mesh_conf
        engine = PipelineEngine(model=tiny_model(layers), config=cfgd,
                                mesh=mesh, rng=jax.random.PRNGKey(3))
        return engine, [float(engine.train_step(
            fixed_batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(n)]

    @pytest.mark.slow
    def test_pp2_matches_dp(self):
        ref = self._dp_reference_losses()
        _, pp = self._pp_losses({"pipe": 2, "data": 4})
        np.testing.assert_allclose(ref, pp, rtol=2e-4)

    @pytest.mark.slow
    def test_pp2_attention_layers_matches_dp(self):
        """GPT-Neo-style per-layer local windows must survive the pipeline
        stage split: each stage applies ITS slice of the window vector.
        window=4 << seq=16 so an all-global stage moves the loss."""
        neo = dict(attention_layers=("global", "local") * 2,
                   local_attention_window=4, attn_impl="xla")
        engine, _, _, _ = ds.initialize(
            model=tiny_model(4, **neo), config=base_config(mesh={"data": 8}),
            rng=jax.random.PRNGKey(3))
        ref = [float(engine.train_step(
            fixed_batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(3)]
        mesh_conf = {"pipe": 2, "data": 4}
        mesh = build_mesh(MeshConfig(**mesh_conf))
        cfgd = base_config()
        cfgd["mesh"] = mesh_conf
        peng = PipelineEngine(model=tiny_model(4, **neo), config=cfgd,
                              mesh=mesh, rng=jax.random.PRNGKey(3))
        pp = [float(peng.train_step(
            fixed_batch(peng.train_batch_size, seed=i))["loss"])
            for i in range(3)]
        np.testing.assert_allclose(ref, pp, rtol=2e-4)

    @pytest.mark.slow
    def test_pp4_matches_dp(self):
        ref = self._dp_reference_losses()
        _, pp = self._pp_losses({"pipe": 4, "data": 2})
        np.testing.assert_allclose(ref, pp, rtol=2e-4)

    @pytest.mark.slow
    def test_pp_with_tp(self):
        ref = self._dp_reference_losses()
        _, pp = self._pp_losses({"pipe": 2, "data": 2, "model": 2})
        np.testing.assert_allclose(ref, pp, rtol=2e-3)

    @pytest.mark.slow
    def test_pp_with_zero1(self):
        """BLOOM-style ZeRO-1 × PP (reference supports ZeRO-1 with pipe)."""
        ref = self._dp_reference_losses()
        _, pp = self._pp_losses({"pipe": 2, "data": 4}, stage=1)
        np.testing.assert_allclose(ref, pp, rtol=2e-4)

    @pytest.mark.slow
    def test_pp_fp16_scale_invariant(self):
        """fp16 pipeline: the update must be invariant to the loss scale —
        the loss is scaled before autodiff and the grads divided back by the
        same scale (regression for the silent 1/scale shrink bug)."""
        mesh_conf = {"pipe": 2, "data": 4}
        mesh = build_mesh(MeshConfig(**mesh_conf))
        losses = {}
        for power in (0, 8):
            cfgd = base_config(
                fp16={"enabled": True, "initial_scale_power": power,
                      "loss_scale_window": 1000})
            cfgd["mesh"] = mesh_conf
            engine = PipelineEngine(model=tiny_model(), config=cfgd,
                                    mesh=mesh, rng=jax.random.PRNGKey(3))
            losses[power] = [float(engine.train_step(
                fixed_batch(engine.train_batch_size, seed=i))["loss"])
                for i in range(3)]
            assert int(engine.skipped_steps) == 0
        # scale=1 vs scale=256 must trace the same trajectory; a missing
        # scale multiply shows up as a 256x-smaller update by step 2.
        np.testing.assert_allclose(losses[0], losses[8], rtol=5e-3)

    @pytest.mark.slow
    def test_3d_with_sharded_embeddings(self):
        """pp x dp x tp with the one-hot TP embedding: the embedding table
        must actually be SHARDED over 'model' under PP (the BLOOM-3D
        blocker from round 1)."""
        mesh_conf = {"pipe": 2, "data": 2, "model": 2}
        mesh = build_mesh(MeshConfig(**mesh_conf))
        cfgd = base_config()
        cfgd["mesh"] = mesh_conf
        engine = PipelineEngine(model=tiny_model(), config=cfgd,
                                mesh=mesh, rng=jax.random.PRNGKey(3))
        emb = engine.state["params"]["embed"]["embedding"]
        assert "model" in str(emb.sharding.spec), emb.sharding.spec
        ref = self._dp_reference_losses()
        pp = [float(engine.train_step(
            fixed_batch(engine.train_batch_size, seed=i))["loss"])
            for i in range(3)]
        np.testing.assert_allclose(ref, pp, rtol=2e-3)

    def test_rejects_indivisible_layers(self):
        mesh = build_mesh(MeshConfig(pipe=2, data=4))
        with pytest.raises(ValueError):
            PipelinedLM(tiny_model(layers=3), 2)

    def test_rejects_pipe1_mesh(self):
        mesh = build_mesh(MeshConfig(data=8))
        with pytest.raises(ValueError):
            PipelineEngine(model=tiny_model(), config=base_config(),
                           mesh=mesh)
