"""The Mamba-2 hybrid cell (``granite-4.0-h-micro.serve-chat-sat``, runner
``serve_ssd_hybrid``) on the CPU: its configuration file against what the
program builds and against the catalog's numbers, the order of its
traffic, the work counts, and a rehearsal at a tiny size through the
harness's own ``run_cell``.  A shape check, not a measurement."""
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import costs_ssd, device, model as model_lib, traffic
from benchmark.runners import serve_ssd_hybrid

CELL = "granite-4.0-h-micro.serve-chat-sat"
BENCH = harness.load_benchmark()
PATTERN = ["mamba", "mamba", "attention", "mamba"] * 2
TINY = {"model": dict(num_layers=8, layer_types=PATTERN, num_heads=8,
                      num_kv_heads=2, d_model=32, d_ff=64, vocab_size=128,
                      max_seq_len=320, ssm_heads=4, ssm_head_dim=8,
                      ssm_state=16, dtype="float32"),
        "num_kv_blocks": 512, "shrink": 16}
MIX = {"clients": 8, "trace_seconds": 1.5, "lead_in_s": 4.0,
       "engine": {"dtype": "float32", "max_out_tokens": 320,
                  "temperature": 0.0,
                  "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                              "max_batch_slots": 4, "num_kv_blocks": 512}}}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def test_the_file_holds_the_published_numbers_and_the_program_builds_them():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == "granitemoehybrid"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"]) == {"max_position_embeddings"}
    assert config["source"] == entry["source"]
    mc, ref = serve_ssd_hybrid.build(config)
    assert mc.num_params() == 3_191_396_096
    assert (mc.num_layers, mc.vocab_size, mc.d_inner) == (40, 100352, 4096)
    assert mc.layer_types.count("mamba") == 36
    assert [i for i, k in enumerate(mc.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    assert ref["attention_multiplier"] == 1 / 64 and ref["state"] == 128
    with pytest.raises(ValueError, match="the program built"):
        serve_ssd_hybrid.build(dict(config, mamba_d_state=64))
    with pytest.raises(ValueError, match="the program built"):
        serve_ssd_hybrid.build(dict(
            config, layer_types=config["layer_types"][::-1]))


def test_the_cells_order_gives_every_stretch_the_same_work():
    """Every run of 16 consecutive requests that starts at a multiple of
    16 holds each of the 16 (prompt, output) pairs once, whatever the
    seed, and two seeds differ in order only; the pool holds every slot's
    longest context at once."""
    mix = traffic.load("serve-chat-sat")
    assert (mix["clients"], mix["block"], mix["blocks"]) == (128, 16, 64)
    serving = mix["engine"]["serving"]
    assert (serving["max_batch_slots"], serving["prefill_chunk_tokens"],
            serving["kv_block_size"]) == (64, 512, 16)
    every = sorted((p, o) for p in (256, 512, 1024, 2048)
                   for o in (128, 256, 384, 512))
    orders = []
    for seed in (3, 2**31 + 11):
        work = traffic.requests(mix, seed, 100352)
        got = list(zip(work["prompt_len"].tolist(),
                       work["max_new"].tolist()))
        for at in range(0, len(got), 16):
            assert sorted(got[at:at + 16]) == every
        assert max(p + o for p, o in got) <= mix["engine"]["max_out_tokens"]
        orders.append(got)
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1])
    longest = max(p + o for p, o in every)
    assert serving["num_kv_blocks"] >= 64 * -(-longest // 16)


def test_the_recurrences_work_from_its_shapes():
    """A chunk of 512 rows in one layer at the published widths: 1.6 GFLOP
    in blocks of 128 (2.2 at the published 256); a decode row: the whole
    2 MiB state in and out."""
    f, b = costs_ssd.ssd_chunk_scan_cost(512, 1, 64, 64, 128, 128)
    assert f == 512 * (2 * 128 * 128 + 2 * 128 * 4096 + 4 * 4096 * 128)
    assert b == 512 * (4096 * 6 + 256 + 512) + 2 * 4096 * 128 * 4
    f256, _ = costs_ssd.ssd_chunk_scan_cost(512, 1, 64, 64, 128, 256)
    assert 2.1e9 < f256 < 2.3e9
    f, b = costs_ssd.ssd_decode_update_cost(1, 64, 64, 128)
    assert f == 5 * 4096 * 128
    assert b == 2 * 2**21 + 4096 * 6 + 256 + 512
    assert costs_ssd.state_bytes(36, 64, 64, 128, 4) == 76_437_504
    # a decode-only step at 64 slots: 4.83 GB in and out; a chunk's slot
    # once more
    assert costs_ssd.state_bytes_moved(1, 0, 64, 36, 64, 64, 128) \
        == 2 * 36 * 64 * 2**21
    assert costs_ssd.state_bytes_moved(2, 1, 64, 36, 64, 64, 128) \
        == 2 * 36 * 129 * 2**21


@pytest.mark.parametrize("trace_on", (False, True))
def test_the_cell_rehearses_through_the_harness(trace_on):
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, obs = harness.run_cell(
        BENCH, CELL, seed=2**31 + 7, seconds=6.0, trace_on=trace_on,
        peaks=PEAKS, compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=MIX)
    diag = line["diag"]
    assert line["correct"] is True and line["failed"] == 0, diag
    assert line["attempted"] > 0
    assert diag["logit_gap_worst"] < 1e-4
    assert diag["ssm_state_rel_err"] < 1e-5
    assert diag["ssm_state_path_rel_err"] < 1e-5
    assert diag["ssm_states_rel_err"] < 1e-5
    assert diag["attn_kv_rel_err"] < 1e-5
    # the same numbers beside what the window left decoding (at this size
    # the others may have finished before the check's 24 tokens have)
    live = diag["live"]
    assert live["logit_gap_worst"] < 1e-4 and live["slots_live_least"] >= 1
    assert max(live["ssm_states_rel_err"], live["attn_kv_rel_err"]) < 1e-5
    assert diag["held_after_drain"] == {"full": 0, "window": 0, "state": 0}
    assert 0 < diag["state_bytes_share"] < 100
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in ("sat.batch_occupancy", "sat.preemptions",
                 "sat.chunk_dispatch_share", "sat.peak_hbm_gib"):
        assert name in line["metrics"], sorted(line["metrics"])
    # what the benchmark's full list of per-layer metrics has no entry
    # for is in `diag`
    assert diag["ssm_decode_rows_per_s"] > 0
    assert diag["state_bytes_moved_per_s"] > 0
    assert np.isfinite(diag["chunk_dispatch_share"])


@pytest.mark.parametrize("control,number", [
    ("state_carry", "ssm_state_rel_err"), ("decay", "ssm_state_rel_err"),
    ("d_skip", "ssm_states_rel_err"), ("gate_order", "ssm_states_rel_err"),
    ("attn_scale", "attn_kv_rel_err"), ("rotary", "attn_kv_rel_err"),
    ("residual_multiplier", "logit_gap_worst"),
    ("bf16_state", "ssm_state_path_rel_err")])
def test_a_reference_that_lacks_a_mechanism_refuses_the_run(control, number):
    """``reference_leaves_out`` (never a cell's): the check's own number
    moves by orders of magnitude (at the tiny size's float32 readings,
    2e-7 for the four errors and 0 for the gap; the cell's limits and
    what each control reads at the published widths are in ``PERF.md``
    section 4)."""
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, _ = harness.run_cell(
        BENCH, CELL, seed=5, seconds=1.0, trace_on=False, peaks=PEAKS,
        compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=dict(MIX, lead_in_s=0.0,
                           reference_leaves_out=[control]))
    sound = 1e-5 if number == "logit_gap_worst" else 2e-7
    assert line["diag"][number] > 100 * sound, line["diag"]


def test_a_program_whose_state_is_bfloat16_refuses_the_run():
    """``program_fault`` (never a cell's) puts the fault into the PROGRAM:
    the state rounded to bfloat16 after every step.  The run is refused,
    by the first layer's state against the one made from inputs in the
    served type (the number that holds the state path to the float32 the
    configuration states); what the attention layers wrote does not see
    it."""
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, _ = harness.run_cell(
        BENCH, CELL, seed=5, seconds=1.0, trace_on=False, peaks=PEAKS,
        compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=dict(MIX, lead_in_s=0.0, program_fault="bf16_state"))
    diag = line["diag"]
    assert line["correct"] is False
    # 2e-7 with a float32 state
    assert diag["ssm_state_path_rel_err"] \
        > serve_ssd_hybrid.STATE_PATH_REL_ERR_MAX
    assert diag["ssm_states_rel_err"] > 1e-3
    assert diag["attn_kv_rel_err"] < 1e-5


@pytest.mark.parametrize("served,least,most", [
    ("float32", 0.0, 1e-6), ("bfloat16", 1e-3, 2e-2)])
def test_the_first_state_from_inputs_in_the_served_type(served, least, most):
    """``reference.first_state`` against the plain reference's first
    state: the same at float32 (it rounds nothing), a few thousandths
    apart at bfloat16 — the rounding of what the layer is fed, which the
    cell's ``ssm_state_path_rel_err`` leaves out of the program's
    account."""
    import jax
    import jax.numpy as jnp
    from benchmark.lib import reference_granite_hybrid as reference
    from deepspeed_tpu.models import build_model
    config = model_lib.load_config(next(
        c["file"] for c in BENCH["configs"]
        if c["name"] == "granite-4.0-h-micro"))
    mc, ref = serve_ssd_hybrid.build(config, TINY)
    params = build_model(mc).init(jax.random.PRNGKey(2))
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 70), 0, 128)
    ref["without"] = ()
    _, states, _ = reference.logits(params, ids, ref, states=True, last=1)
    got = reference.first_state(params, ids[0], ref, jnp.dtype(served))
    err = float(jnp.linalg.norm(got - states[0, 0])
                / jnp.linalg.norm(states[0, 0]))
    assert least <= err <= most, err
