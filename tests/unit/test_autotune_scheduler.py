"""Experiment-scheduler autotuning (reference ResourceManager,
`autotuning/scheduler.py:28` + `Autotuner.tune` `autotuner.py:421`):
candidates run as isolated subprocess jobs — a crashing, hanging, or
erroring candidate costs one job, not the tune."""
import json
import os

import numpy as np
import pytest

from deepspeed_tpu.autotuning import Autotuner, ResourceManager
from deepspeed_tpu.models.transformer import (TransformerConfig,
                                              TransformerLM)

CPU_ENV = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}

TINY = dict(vocab_size=64, max_seq_len=16, num_layers=2, num_heads=2,
            d_model=16, loss_chunk=0)


def tiny_model():
    return TransformerLM(TransformerConfig(**TINY))


def base_cfg():
    return {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "bf16": {"enabled": True}, "steps_per_print": 0}


class TestResourceManager:
    @pytest.mark.slow
    def test_crash_hang_ok_isolation(self, tmp_path):
        """One ok spec, one crashing spec, one hanging spec — the pool
        completes, each with the right classification."""
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(1,),
                       zero_stages=(0,), steps_per_trial=1,
                       hbm_bytes=1 << 40)
        ok = at._make_specs(seq=16, steps=1)[0]
        crash = dict(ok, inject_fault="crash")
        # Only the hang spec gets a short budget: the ok job's wall time
        # is jax-import + compile and varies a lot under full-suite load
        # (the advisor's r4 note about suite-run flakiness); its budget
        # must be generous, so the timeout under test is per-spec.
        hang = dict(ok, inject_fault="hang", timeout_s=25.0)
        rm = ResourceManager(slots=3, timeout_s=240.0, env=CPU_ENV)
        results = rm.run([ok, crash, hang], str(tmp_path))
        statuses = [r["status"] for r in results]
        assert statuses[0] == "ok" and results[0]["samples_per_sec"] > 0
        assert statuses[1] == "crash"
        assert statuses[2] == "timeout"


class TestScheduledTune:
    @pytest.mark.slow
    def test_eight_candidates_one_crash_ranked_report(self, tmp_path):
        """VERDICT r3 #6 'Done' condition: >=8 candidates, one crashes,
        the tune completes and writes a ranked report."""
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(1, 2),
                       zero_stages=(0, 1), offload_options=(False, True),
                       steps_per_trial=1, hbm_bytes=1 << 40)
        specs = at._make_specs(seq=16, steps=1)
        assert len(specs) >= 8
        specs[3]["inject_fault"] = "crash"
        best = at.tune_scheduled(str(tmp_path), slots=4, timeout_s=300.0,
                                 env=CPU_ENV, specs=specs)
        # the tune survived the crash and produced a winner
        assert best["train_micro_batch_size_per_gpu"] in (1, 2)
        assert "zero_optimization" in best
        report = json.load(open(tmp_path / "autotune_report.json"))
        assert len(report["all"]) == len(specs)
        statuses = {r["status"] for r in report["all"]}
        assert "crash" in statuses and "ok" in statuses
        ranked = report["ranked"]
        assert len(ranked) >= 1
        # ranked strictly by measured throughput
        tputs = [r["samples_per_sec"] for r in ranked]
        assert tputs == sorted(tputs, reverse=True)

    def test_model_kw_survive_the_spec_roundtrip(self, tmp_path):
        """remat/loss_chunk knobs serialize into the subprocess model
        config and come back as _model_overrides on the winner."""
        at = Autotuner(tiny_model(), base_cfg(), micro_batches=(1,),
                       zero_stages=(0,), remat_policies=("full",),
                       steps_per_trial=1, hbm_bytes=1 << 40)
        specs = at._make_specs(seq=16, steps=1)
        assert all(s["model_config"]["remat"] == "full" for s in specs)
        best = at.tune_scheduled(str(tmp_path), slots=1, timeout_s=300.0,
                                 env=CPU_ENV, specs=specs)
        assert best["_model_overrides"] == {"remat": "full"}
        model, cfg = Autotuner.apply_best(tiny_model(), best)
        assert model.config.remat == "full"
        assert "_model_overrides" not in cfg


class TestOneProcessPerChip:
    """A chip belongs to one process: a parent that holds it must not
    spawn children that need it (they fail or hang)."""

    def test_parent_holding_the_chip_refuses_to_schedule(self, tmp_path,
                                                         monkeypatch):
        from deepspeed_tpu.autotuning import scheduler
        monkeypatch.setattr(scheduler, "initialized_accelerator",
                            lambda: "tpu")
        rm = ResourceManager(slots=1)
        with pytest.raises(RuntimeError, match="holds the chip"):
            rm.run([{"cfg": {}}], str(tmp_path))

    def test_parallel_slots_are_cpu_only(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(ValueError, match="whole accelerator"):
            ResourceManager(slots=2)
        assert ResourceManager(slots=2, env=CPU_ENV).slots == 2

    def test_spawning_parents_stay_off_jax(self):
        """Importing the launcher, the elastic agents and the scheduler
        initializes no JAX backend — the property that lets them spawn
        workers that own the chip."""
        import subprocess
        import sys
        code = (
            "import deepspeed_tpu.launcher.runner, "
            "deepspeed_tpu.elasticity.elastic_agent, "
            "deepspeed_tpu.elasticity.rendezvous, "
            "deepspeed_tpu.autotuning.scheduler as s\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "assert s.initialized_accelerator() is None\n")
        subprocess.run([sys.executable, "-c", code], check=True,
                       timeout=120,
                       cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__)))))
