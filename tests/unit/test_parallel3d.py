"""3D-parallel acceptance suite (8-device CPU mesh): pipeline x tensor
x ZeRO-data composed on one topology.

Covers the composition contract end to end: a multi-hundred-M-param
config that cannot fit one chip trains at (pp=2, tp=2, dp=2); losses
match a single-device shrunk twin; checkpoints round-trip bit-exact
across the 3D mesh; the bubble probe measures a fraction at (4,2,1);
and the autotuner's joint (pp, tp, dp) winner round-trips through
``DeepSpeedConfig`` into ``ds.initialize`` with no extra step.

The chaos-marked tests replay under ``run_tests.sh``'s
``PARALLEL3D_CHAOS_MATRIX`` (one transient + one fatal
``checkpoint.publish`` plan): a torn save under the 3D topology must
never move 'latest' — same commit contract as docs/resilience.md,
exercised through the engine's own save path instead of bare
``_publish``.

Heavy cases (engine builds, 3D region compiles) are slow-marked so the
tier-1 sweep stays inside its box; the fast cases here are pure
bookkeeping/cost-model checks.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.autotuning.autotuner import Autotuner
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.parallel.topology import build_mesh, pp_world_size
from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig
from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
from deepspeed_tpu.runtime.pipe.topology import (PipelineParallelGrid,
                                                 grid_sizes_from_mesh)
from deepspeed_tpu.runtime.resilience import (FatalIOError, FaultInjector,
                                              install_fault_injector,
                                              verify_manifest)

pytestmark = pytest.mark.parallel3d


def tiny_model(layers=4, **kw):
    cfg = gpt2_config("125m", num_layers=layers, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=16, dtype=jnp.float32, **kw)
    return TransformerLM(cfg)


def cfg_3d(pp=2, tp=2, dp=2, micro=2, gas=2, **over):
    cfg = {
        "train_batch_size": micro * gas * dp,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "mesh": {"pipe": pp, "model": tp, "data": dp},
    }
    cfg.update(over)
    return cfg


def fixed_batch(n, seq=16, vocab=64, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, vocab, (n, seq), dtype=np.int32)}


def single_device_mesh():
    """A true 1-chip mesh (first device only) — the shrunk twin's home."""
    return build_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def shard_and_full_bytes(tree):
    """(per-chip shard bytes, unsharded bytes) over a pytree."""
    per = full = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not hasattr(leaf, "sharding"):
            continue
        per += int(np.prod(leaf.sharding.shard_shape(leaf.shape))) \
            * leaf.dtype.itemsize
        full += leaf.nbytes
    return per, full


def assert_trees_bitwise_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def env_injector():
    """Injector from DSTPU_FAULTS (empty when unset) so the run_tests.sh
    3D chaos matrix steers the suite; restored afterwards."""
    fi = install_fault_injector(FaultInjector.from_env())
    yield fi
    install_fault_injector(FaultInjector())


# -- fast bookkeeping / cost-model checks (tier-1) -------------------------

class TestGrid:
    def test_grid_sizes_from_mesh(self):
        mesh = build_mesh(MeshConfig(pipe=2, model=2, data=2))
        assert grid_sizes_from_mesh(mesh) == (2, 2, 2)

    def test_grid_coordinates_partition_world(self):
        grid = PipelineParallelGrid(
            mesh=build_mesh(MeshConfig(pipe=2, model=2, data=2)))
        assert grid.world_size == 8
        assert (grid.pipe_parallel_size, grid.data_parallel_size,
                grid.model_parallel_size) == (2, 2, 2)
        # every rank has exactly one (stage, replica, shard) coordinate
        coords = {(grid.get_stage_id(r), grid.get_data_parallel_id(r),
                   grid.get_model_parallel_id(r)) for r in range(8)}
        assert len(coords) == 8
        # comm groups along each axis partition the world
        for groups in (grid.pipe_groups(), grid.data_groups(),
                       grid.model_groups()):
            flat = sorted(r for g in groups for r in g)
            assert flat == list(range(8))
        assert grid.ppermute_ring() == [(0, 1), (1, 0)]
        assert grid.stage_neighbors(0) == (None, 1)
        assert grid.stage_neighbors(1) == (0, None)
        assert grid.is_first_stage(0) and not grid.is_last_stage(0)


class TestJointSearchSpace:
    def test_3d_shapes_pruned_by_device_and_divisibility(self):
        tuner = Autotuner(tiny_model(), {"gradient_accumulation_steps": 2},
                          micro_batches=(1,), zero_stages=(1,),
                          tuner_type="grid",
                          mesh_shapes=((2, 2, 2), (4, 2, 1), (3, 2, 1),
                                       (2, 2, 4), (8, 1, 1), (2, 3, 1)))
        exps = tuner.generate_experiments()
        kept = {tuple(e["mesh"]) for e in exps}
        # (3,2,1)/(2,3,1): product != 8 (and tp=3 splits neither heads
        # nor vocab); (2,2,4): 16 devices; (8,1,1): 4 layers % 8 stages
        assert kept == {(2, 2, 2), (4, 2, 1)}
        for e in exps:
            pp, tp, dp = e["mesh"]
            assert e["cfg"]["mesh"] == {"pipe": pp, "model": tp, "data": dp}
            if pp > 1:
                assert e["cfg"]["pipeline"]["stages"] == pp

    def test_legacy_2tuple_semantics_kept(self):
        tuner = Autotuner(tiny_model(), {}, micro_batches=(1,),
                          zero_stages=(0,), tuner_type="grid",
                          mesh_shapes=((4, 2), (16, 2)))
        exps = tuner.generate_experiments()
        assert [e["cfg"]["mesh"] for e in exps] == [{"data": 4, "model": 2}]

    def test_per_chip_state_bytes_shrinks_with_sharding(self):
        tuner = Autotuner(tiny_model(), {}, tuner_type="grid")

        def bytes_at(pp, tp, dp, stage=1, offload=False, remat=None):
            cfg = {"mesh": {"pipe": pp, "model": tp, "data": dp},
                   "train_micro_batch_size_per_gpu": 2,
                   "zero_optimization": {"stage": stage}}
            if offload:
                cfg["zero_optimization"]["offload_optimizer"] = {
                    "device": "cpu"}
            kw = {"remat": remat} if remat else None
            return tuner.per_chip_state_bytes(cfg, kw)

        flat = bytes_at(1, 1, 1)
        assert bytes_at(2, 2, 2) < bytes_at(2, 2, 1) < flat
        assert bytes_at(1, 2, 1) < flat and bytes_at(2, 1, 1) < flat
        # offload drops the on-chip moments; remat drops activations
        assert bytes_at(2, 2, 2, offload=True) < bytes_at(2, 2, 2)
        assert bytes_at(2, 2, 2, remat="full") < bytes_at(2, 2, 2)
        # ZeRO-2 shards the gradient term over data on top of ZeRO-1
        assert bytes_at(2, 2, 2, stage=2) < bytes_at(2, 2, 2, stage=1)

    def test_model_based_pruning_uses_per_chip_bytes(self):
        """The 'cannot fit one chip' pruning wall: with an HBM budget
        between the flat and the 3D-sharded footprint, only the shapes
        that shard enough survive generation."""
        model = TransformerLM(gpt2_config(
            "350m", num_layers=16, max_seq_len=128, dtype=jnp.float32))
        tuner = Autotuner(model, {"gradient_accumulation_steps": 2},
                          micro_batches=(1,), zero_stages=(1,),
                          mesh_shapes=((1, 1, 8), (2, 2, 2)),
                          tuner_type="model_based",
                          hbm_bytes=int(1.5 * 2 ** 30))
        exps = tuner.generate_experiments()
        assert {tuple(e["mesh"]) for e in exps} == {(2, 2, 2)}
        flat = tuner.per_chip_state_bytes(
            {"mesh": {"pipe": 1, "model": 1, "data": 8},
             "train_micro_batch_size_per_gpu": 1,
             "zero_optimization": {"stage": 1}})
        assert flat * 1.3 > tuner.hbm_bytes      # one chip: does not fit


class TestConfigSurface:
    def test_pipeline_stages_parses_int_and_auto(self):
        assert DeepSpeedConfig(
            {"train_batch_size": 8,
             "pipeline": {"stages": 2}}).pipeline.stages == 2
        assert DeepSpeedConfig(
            {"train_batch_size": 8,
             "pipeline": {"stages": "4"}}).pipeline.stages == 4
        assert DeepSpeedConfig(
            {"train_batch_size": 8}).pipeline.stages == "auto"
        with pytest.raises(ValueError):
            DeepSpeedConfig({"train_batch_size": 8,
                             "pipeline": {"stages": 0}})
        with pytest.raises(ValueError):
            DeepSpeedConfig({"train_batch_size": 8,
                             "pipeline": {"stages": "two"}})

    def test_stage_mesh_mismatch_raises(self):
        cfg = cfg_3d()
        cfg["pipeline"] = {"stages": 4}    # mesh pipe axis is 2
        with pytest.raises(ValueError, match="different topology"):
            ds.initialize(model=tiny_model(), config=cfg)


# -- heavy acceptance cases (slow: engine builds + 3D region compiles) -----

@pytest.mark.slow
class Test3DTraining:
    def test_multi_hundred_m_trains_e2e_at_222(self):
        """The headline acceptance case: a >200M-param config — too big
        for the pruner's one-chip budget above — trains end to end at
        (pp=2, tp=2, dp=2) with the state genuinely spread over the
        mesh."""
        model = TransformerLM(gpt2_config(
            "350m", num_layers=16, max_seq_len=128, dtype=jnp.float32))
        assert model.config.num_params() > 2e8
        cfg = cfg_3d(micro=1, gas=2,
                     zero_optimization={"stage": 1},
                     optimizer={"type": "AdamW", "params": {"lr": 1e-4}})
        engine, _, _, _ = ds.initialize(model=model, config=cfg)
        assert isinstance(engine, PipelineEngine)
        assert engine.num_stages == 2
        batch = fixed_batch(engine.train_batch_size, seq=32,
                            vocab=model.config.vocab_size)
        m = engine.train_step(batch)
        assert np.isfinite(float(m["loss"]))
        # params shard over pipe x model, moments additionally over data:
        # one chip holds a small fraction of the full state
        per, full = shard_and_full_bytes(
            {"params": engine.state["params"], "opt": engine.state["opt"]})
        assert per * 4 < full
        assert per > 0

    def test_loss_parity_vs_single_device_twin(self):
        """The same shrunk model trained on the same global batches must
        produce the same losses at (2,2,2) as on one chip — pipeline
        chunking, TP psums, and the data-axis reduce are all
        arrangement, not math."""
        losses = {}
        for name, mesh, cfg in (
                ("3d", None, cfg_3d(micro=2, gas=2)),
                ("one_chip", single_device_mesh(),
                 {"train_batch_size": 8, "gradient_accumulation_steps": 2,
                  "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                  "gradient_clipping": 1.0, "steps_per_print": 0})):
            engine, _, _, _ = ds.initialize(model=tiny_model(), config=cfg,
                                            mesh=mesh)
            assert engine.train_batch_size == 8
            losses[name] = [
                float(engine.train_step(fixed_batch(8, seed=s))["loss"])
                for s in range(3)]
        np.testing.assert_allclose(losses["3d"], losses["one_chip"],
                                   rtol=2e-4)

    def test_sgd_update_scale_parity(self):
        """SGD has no per-parameter normalizer, so any gradient
        over-/under-count across the three reduce families shows up
        directly in the weights after one step."""
        updated = {}
        for name, mesh, cfg in (
                ("3d", None, cfg_3d(
                    micro=2, gas=2, gradient_clipping=0.0,
                    optimizer={"type": "SGD", "params": {"lr": 0.1}})),
                ("one_chip", single_device_mesh(),
                 {"train_batch_size": 8, "gradient_accumulation_steps": 2,
                  "optimizer": {"type": "SGD", "params": {"lr": 0.1}},
                  "gradient_clipping": 0.0, "steps_per_print": 0})):
            engine, _, _, _ = ds.initialize(
                model=tiny_model(layers=2), config=cfg, mesh=mesh)
            engine.train_step(fixed_batch(8, seed=7))
            updated[name] = jax.tree_util.tree_map(
                lambda x: np.asarray(x), engine.state["params"])
        la = jax.tree_util.tree_leaves(updated["3d"])
        lb = jax.tree_util.tree_leaves(updated["one_chip"])
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            # the pipeline engine stacks block leaves as (stages,
            # layers_per_stage, ...); the flat twin keeps (layers, ...) —
            # same values, different leading fold
            assert x.size == y.size
            np.testing.assert_allclose(x.reshape(-1), y.reshape(-1),
                                       atol=1e-5, rtol=1e-4)

    def test_zero2_shards_moments_and_grad_layout(self):
        """ZeRO-2 under the 3D mesh: training stays finite and the
        optimizer state per chip is a fraction of the full tree (pipe x
        model x data all contribute)."""
        engine, _, _, _ = ds.initialize(
            model=tiny_model(), config=cfg_3d(
                micro=2, gas=2, zero_optimization={"stage": 2}))
        for s in range(2):
            m = engine.train_step(fixed_batch(8, seed=s))
            assert np.isfinite(float(m["loss"]))
        per, full = shard_and_full_bytes(engine.state["opt"])
        assert per * 4 < full


@pytest.mark.slow
class Test3DCheckpoint:
    def test_checkpoint_bit_exact_across_3d_mesh(self, tmp_path):
        """Save at (2,2,2), restore into a FRESH (2,2,2) engine:
        every param/optimizer leaf must come back bit-identical, and the
        next step must produce the identical loss."""
        cfg = cfg_3d(micro=2, gas=2)
        e1, _, _, _ = ds.initialize(model=tiny_model(), config=cfg)
        e1.train_step(fixed_batch(8, seed=0))
        e1.save_checkpoint(str(tmp_path), tag="t1")
        ok, problems = verify_manifest(str(tmp_path / "t1"))
        assert ok, problems

        e2, _, _, _ = ds.initialize(model=tiny_model(), config=cfg)
        e2.load_checkpoint(str(tmp_path), tag="t1")
        assert_trees_bitwise_equal(e1.state["params"], e2.state["params"])
        assert_trees_bitwise_equal(e1.state["opt"], e2.state["opt"])
        assert int(np.asarray(e2.state["step"])) == \
            int(np.asarray(e1.state["step"]))
        l1 = float(e1.train_step(fixed_batch(8, seed=1))["loss"])
        l2 = float(e2.train_step(fixed_batch(8, seed=1))["loss"])
        assert l1 == l2

    @pytest.mark.chaos
    def test_3d_train_step_torn_save_never_moves_latest(self, env_injector,
                                                        tmp_path):
        """A 3D train step followed by a checkpoint save under whatever
        the PARALLEL3D_CHAOS_MATRIX injects at ``checkpoint.publish``:
        the transient plan must be absorbed (tag commits, restore is
        bit-exact), the fatal plan must leave 'latest' at the previous
        committed tag — the same never-torn contract as the publish-level
        chaos suite, through the engine's own save path."""
        cfg = cfg_3d(micro=2, gas=2,
                     resilience={"io_retry_attempts": 4,
                                 "io_retry_base_delay_s": 0.0,
                                 "io_retry_max_delay_s": 0.0,
                                 "io_retry_jitter": 0.0})
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=cfg)
        m = engine.train_step(fixed_batch(8, seed=0))
        assert np.isfinite(float(m["loss"]))
        (tmp_path / "latest").write_text("t0")
        try:
            engine.save_checkpoint(str(tmp_path), tag="t1")
        except FatalIOError:
            # fatal matrix entry: the commit aborted before 'latest' moved
            assert (tmp_path / "latest").read_text().strip() == "t0"
            return
        # clean or transient entry: the commit completed whole
        assert (tmp_path / "latest").read_text().strip() == "t1"
        ok, problems = verify_manifest(str(tmp_path / "t1"))
        assert ok, problems
        # training continues after the absorbed faults, and a fresh 3D
        # engine restores the committed tag bit-exactly
        saved = jax.tree_util.tree_map(np.asarray, engine.state["params"])
        engine.train_step(fixed_batch(8, seed=1))
        e2, _, _, _ = ds.initialize(model=tiny_model(), config=cfg_3d())
        e2.load_checkpoint(str(tmp_path), tag="t1")
        assert_trees_bitwise_equal(saved, e2.state["params"])


@pytest.mark.slow
class TestBubbleAndAutotune:
    def test_bubble_probe_fits_a_fraction_at_421(self):
        """The two-point slope fit on the compiled region at (pp=4,
        tp=2) gives a fraction and the microbatch counts it was fitted
        from."""
        mcfg = dict(num_layers=4, d_model=128, num_heads=4, vocab_size=256,
                    max_seq_len=128, dtype=jnp.float32)
        engine, _, _, _ = ds.initialize(
            model=TransformerLM(gpt2_config("125m", **mcfg)),
            config=cfg_3d(pp=4, tp=2, dp=1, micro=8, gas=8))
        fit = engine.measure_bubble_fraction(repeats=2, seq_len=128)
        assert 0.0 <= fit["bubble_frac"] < 1.0
        assert (fit["stages"], fit["micro_counts"]) == (4, (4, 8))
        # the probe records the gauge the docs table declares
        from deepspeed_tpu.observability import get_registry
        gauge = get_registry().gauge("dstpu_train_bubble_frac")
        assert 0.0 <= gauge.value < 1.0

    def test_joint_search_winner_roundtrips_into_initialize(self, tmp_path):
        """Acceptance: the joint (pp, tp, dp) smoke sweep exports a JSON
        that feeds DeepSpeedConfig / ds.initialize directly — the 3D
        winner comes back as a PipelineEngine with no extra apply
        step."""
        model = tiny_model()
        tuner = Autotuner(model,
                          {"gradient_accumulation_steps": 2,
                           "optimizer": {"type": "AdamW",
                                         "params": {"lr": 1e-3}},
                           "steps_per_print": 0},
                          micro_batches=(1,), zero_stages=(1,),
                          mesh_shapes=((2, 2, 2),), steps_per_trial=1)
        best = tuner.tune(lambda n: fixed_batch(n))
        assert best["mesh"] == {"pipe": 2, "model": 2, "data": 2}
        assert best["pipeline"]["stages"] == 2
        _, path = Autotuner.export_best(best, path=str(tmp_path))
        engine, _, _, _ = ds.initialize(model=model, config=path)
        assert isinstance(engine, PipelineEngine)
        assert pp_world_size(engine.mesh) == 2
        assert engine.zero_stage == 1
        m = engine.train_step(fixed_batch(engine.train_batch_size, seed=3))
        assert np.isfinite(float(m["loss"]))
