"""Monitor / timers / flops profiler tests.

Reference coverage model: `/root/reference/tests/unit/monitor/` (config →
writer behavior) and `tests/unit/profiling/`.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, gpt2_config


def tiny_model():
    cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=16, dtype=jnp.float32)
    return TransformerLM(cfg)


def batch(n, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, 64, (n, 16), dtype=np.int32)}


class TestMonitors:
    def test_csv_monitor_writes_files(self, tmp_path):
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "job"},
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        assert engine.monitor.enabled
        for i in range(3):
            engine.train_step(batch(16, seed=i))
        engine.monitor.flush()
        files = glob.glob(str(tmp_path / "job" / "*.csv"))
        names = {os.path.basename(f) for f in files}
        assert "Train_loss.csv" in names and "Train_lr.csv" in names
        with open(tmp_path / "job" / "Train_loss.csv") as f:
            lines = f.read().strip().splitlines()
        assert lines[0] == "step,Train/loss"
        assert len(lines) == 4  # header + 3 steps

    def test_tensorboard_monitor_writes_events(self, tmp_path):
        pytest.importorskip("torch.utils.tensorboard")
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "tensorboard": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "tb"},
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        engine.train_step(batch(16))
        engine.monitor.flush()
        assert glob.glob(str(tmp_path / "tb" / "events.out.tfevents.*"))

    def test_monitor_disabled_by_default(self):
        engine, _, _, _ = ds.initialize(model=tiny_model(), config={
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "mesh": {"data": 8}, "steps_per_print": 0})
        assert not engine.monitor.enabled


class TestTimers:
    def test_throughput_timer(self):
        from deepspeed_tpu.utils.timer import ThroughputTimer
        t = ThroughputTimer(batch_size=8, seq_length=16, start_step=1)
        import time
        for _ in range(4):
            t.start()
            time.sleep(0.01)
            t.stop()
        assert t.timed_steps == 3  # first skipped as warmup
        assert 0 < t.samples_per_sec < 8 / 0.01
        assert t.tokens_per_sec == t.samples_per_sec * 16

    def test_wallclock_timer_registry(self):
        from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
        timers = SynchronizedWallClockTimer()
        timers("fwd").start()
        timers("fwd").stop()
        assert timers("fwd").count == 1
        line = timers.log(["fwd", "missing"])
        assert "fwd" in line and "missing" not in line


@pytest.fixture
def cpu_peak(monkeypatch):
    """The peak table has no row for the CPU mesh (chip_peak_flops
    raises there); tests of the MFU plumbing bring their own."""
    from deepspeed_tpu.profiling.flops_profiler import profiler
    monkeypatch.setitem(profiler.PEAK_FLOPS, "cpu", 1e12)


class TestFlopsProfiler:
    def test_unknown_device_has_no_peak(self):
        from deepspeed_tpu.profiling.flops_profiler import chip_peak_flops
        with pytest.raises(ValueError, match="no published peak"):
            chip_peak_flops()

    def test_profile_and_mfu(self, cpu_peak):
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
        engine, _, _, _ = ds.initialize(model=tiny_model(), config={
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "mesh": {"data": 8}, "steps_per_print": 0})
        engine.train_step(batch(16))
        prof = FlopsProfiler(engine)
        out = prof.profile(batch(16))
        assert out["params"] == engine.num_parameters()
        assert out["analytic_flops_per_step"] > 0
        # analytic: 16*16 tokens * (6N + attn)
        mcfg = engine.model.config
        want = 16 * 16 * (6 * out["params"]
                          + 12 * mcfg.num_layers * mcfg.d_model * 16)
        assert abs(out["analytic_flops_per_step"] - want) < 1e-3 * want
        mfu = prof.mfu(step_time_s=1.0)
        assert 0 < mfu < 1

    def test_engine_reports_mfu_in_monitor(self, tmp_path, cpu_peak):
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                            "job_name": "m"},
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        for i in range(4):
            engine.train_step(batch(16, seed=i))
        engine.monitor.flush()
        assert os.path.exists(tmp_path / "m" / "Train_mfu.csv")
        assert os.path.exists(tmp_path / "m" / "Train_tokens_per_sec.csv")
