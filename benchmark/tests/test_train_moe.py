"""The expert-training cell (``zaya1-8b.train-moe-1chip``, runner
``train_moe``) on the CPU: its configuration file against what the
program builds, and a rehearsal at a tiny size through the harness's own
``run_cell``.  A shape check, not a measurement."""
import jax.numpy as jnp
import pytest

from benchmark import run as harness
from benchmark.lib import device, model as model_lib
from benchmark.runners import train_moe

CELL = "zaya1-8b.train-moe-1chip"
BENCH = harness.load_benchmark()
TINY = {"model": dict(num_layers=2, num_heads=4, num_kv_heads=2,
                      head_dim=16, d_model=64, d_ff=32, expert_d_ff=32,
                      vocab_size=128, max_seq_len=64, router_hidden=16,
                      n_routed_experts=8, experts_held=[0, 4],
                      loss_chunk=16, dtype="float32")}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def test_published_sizes_match_what_the_program_builds():
    """The contract test's check for the family ``zaya``: the file's
    published widths are what ``zaya_config`` builds, cut as it says."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "zaya1-8b")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == "zaya"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"])
    mc, ref = train_moe.build(config)
    assert ref["held"] == (0, 8) and mc.n_routed_experts == 16
    assert mc.num_params() == 601_888_346
    # lib/model.py's own check (layers, hidden, heads, ffn, vocab,
    # positions, tied) holds for this file too
    assert model_lib.build(config)[0].num_layers == 5
    with pytest.raises(ValueError, match="the program built"):
        train_moe.build(dict(config, head_dim=64))


@pytest.mark.parametrize("trace_on", (False, True))
def test_the_cell_rehearses_through_the_harness(trace_on):
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, obs = harness.run_cell(
        BENCH, CELL, seed=2**31 + 7, seconds=4.0, trace_on=trace_on,
        peaks=PEAKS, compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides={"trace_seconds": 2.0, "bf16": False,
                       "control": "all" if trace_on else None})
    diag = line["diag"]
    assert line["correct"] is True and line["failed"] == 0, diag
    assert line["attempted"] > 0
    assert diag["loss_abs_err"] < 1e-4 and diag["expert_rel_err"] < 1e-4
    assert diag["pick_flip_share"] < 0.01
    assert diag["step_pick_flip_share"] < 0.01
    assert diag["grad_rel_err"] < 1e-3, diag["grad_rel_err_by_name"]
    assert diag["grad_norm_rel_err"] < 1e-4 and diag["clip_factor"] < 1.0
    assert diag["update_rel_err"] < 1e-3
    # the clip left out of the reference's side reads what it scales by
    assert diag["grad_rel_err_no_clip"] == pytest.approx(
        1.0 - diag["clip_factor"], rel=0.05)
    # the step check's own controls: the state left as it was, the
    # learning rate without its warm-up, no decay
    assert all(err > train_moe.LIMITS["update_rel_err"]
               for err in diag["update_controls"].values()), diag
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
        return
    for name in ("train.moe_held_share", "train.moe_imbalance",
                 "train.moe_rows_per_expert", "train.moe_touched_share",
                 "train.mfu", "train.step_p50_ms"):
        assert name in line["metrics"], sorted(line["metrics"])
    assert set(diag["controls"]) == set(train_moe.CONTROLS)
    # at float32 with learned scalars at their initial values the forward
    # pass cannot see gamma or tau: their gradients can
    for name in ("half_batch", "conv", "qk_mean", "value_shift",
                 "key_temperature", "router_carry", "expert_dw"):
        assert diag["controls"][name]["refused"], (name,
                                                   diag["controls"][name])
    assert isinstance(obs["values"]["flops_per_token"], float)
    assert jnp.isfinite(obs["values"]["moe_imbalance"])
