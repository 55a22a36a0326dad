"""The hybrid state-space cell (``phi-4-mini-flash-reasoning.serve-
reason8k-sat``, runner ``serve_hybrid``) on the CPU: its configuration
file against what the program builds and against the catalog's numbers,
the order of its traffic, the work counts, and a rehearsal at a tiny size
through the harness's own ``run_cell``.  A shape check, not a
measurement."""
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import costs_hybrid, device, model as model_lib, traffic
from benchmark.runners import serve_hybrid

CELL = "phi-4-mini-flash-reasoning.serve-reason8k-sat"
BENCH = harness.load_benchmark()
TINY = {"model": dict(num_layers=10, pairs_self=2, pairs_cross=2,
                      num_heads=4, num_kv_heads=2, d_model=32, d_ff=64,
                      vocab_size=128, max_seq_len=640, sliding_window=24,
                      ssm_state=4, dtype="float32"),
        "num_kv_blocks": 512, "shrink": 16}
MIX = {"clients": 8, "trace_seconds": 1.5, "lead_in_s": 4.0,
       "engine": {"dtype": "float32", "max_out_tokens": 640,
                  "temperature": 0.0,
                  "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                              "max_batch_slots": 4, "num_kv_blocks": 512}}}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def test_the_file_holds_the_published_numbers_and_the_program_builds_them():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "phi-4-mini-flash-reasoning")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == "phi4flash"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"]) == {"max_position_embeddings"}
    assert config["source"] == entry["source"]
    mc, ref = serve_hybrid.build(config)
    assert mc.num_params() == 3_852_556_800
    assert (mc.num_layers, mc.vocab_size, mc.d_inner) == (32, 200064, 5120)
    assert mc.layer_kinds.count("ssm") == 9
    assert mc.layer_kinds.count("window") == 8
    assert mc.layer_kinds[17] == "full" and mc.layer_kinds[31] == "cross"
    assert ref["dt_rank"] == 160 and ref["window"] == 512
    with pytest.raises(ValueError, match="the program built"):
        serve_hybrid.build(dict(config, sliding_window=256))


def test_the_cells_order_gives_every_stretch_the_same_work():
    """The issue's one fallback (every prompt 4,096; its first mix of
    2,048-8,192 spread 1.02 / 1.45 % in the builder's two sets of six):
    every run of 16 consecutive requests that starts at a multiple of 16
    holds every output length four times, whatever the seed, and two
    seeds differ in order only."""
    mix = traffic.load("serve-reason8k-sat")
    assert (mix["clients"], mix["block"]) == (128, 16)
    serving = mix["engine"]["serving"]
    assert (serving["max_batch_slots"], serving["prefill_chunk_tokens"],
            serving["kv_block_size"]) == (64, 512, 16)
    every = sorted((4096, o) for o in (512, 1024, 1536, 2048)) * 4
    orders = []
    for seed in (3, 2**31 + 11):
        work = traffic.requests(mix, seed, 200064)
        got = list(zip(work["prompt_len"].tolist(),
                       work["max_new"].tolist()))
        for at in range(0, len(got), 16):
            assert sorted(got[at:at + 16]) == sorted(every)
        assert max(p + o for p, o in got) <= mix["engine"]["max_out_tokens"]
        orders.append(got)
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1])


def test_the_walks_work_by_kind_of_layer():
    """A decode row: all of the context in a layer that walks the full
    layer's pages, the window's keys in a window layer; a chunk: its last
    row alone walks the full pages."""
    f, b = costs_hybrid.paged_walk_cost("full", 6000, 1, 40, 20, 64, 512)
    assert f == 4.0 * 6000 * 40 * 64
    assert b == 6000 * 5120 + 2 * 40 * 64 * 2
    f, b = costs_hybrid.paged_walk_cost("window", 6000, 1, 40, 20, 64, 512)
    assert f == 4.0 * 512 * 40 * 64 and b == 512 * 5120 + 2 * 40 * 64 * 2
    assert costs_hybrid.paged_walk_cost("full", 1024, 512, 40, 20, 64, 512) \
        == costs_hybrid.paged_walk_cost("full", 1024, 1, 40, 20, 64, 512)
    f, b = costs_hybrid.paged_walk_cost("window", 1024, 512, 40, 20, 64, 512)
    assert f == 4.0 * 512 * 512 * 40 * 64         # every row a full window
    assert b == (1024 - 1) * 5120 + 2 * 512 * 40 * 64 * 2
    # a prompt's first chunk: the ramp
    f, _ = costs_hybrid.paged_walk_cost("window", 512, 512, 1, 1, 1, 512)
    assert f == 4.0 * 512 * 513 / 2
    assert costs_hybrid.state_bytes(9, 5120, 16, 4) == 9 * 358_400
    f, b = costs_hybrid.ssm_chunk_scan_cost(512 * 9, 9, 5120, 16)
    assert b == 512 * 9 * (3 * 5120 + 32) * 4 + 9 * 2 * 5120 * 16 * 4


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
    assert diag["ssm_states_rel_err"] < 1e-5
    assert diag["full_kv_rel_err"] < 1e-5
    assert diag["cross_read_rel_err"] < 1e-5
    assert len(diag["cross_read_by_layer"]) == 1 + 2
    # the same numbers beside what the window left decoding (at this size
    # the others may have finished before the check's 24 tokens have)
    live = diag["live"]
    assert live["logit_gap_worst"] < 1e-4 and live["slots_live_least"] >= 1
    assert max(live["ssm_states_rel_err"], live["full_kv_rel_err"]) < 1e-5
    assert diag["window_blocks_held"]["decode"] <= 24 // 8 + 1
    assert diag["window_blocks_held"]["chunk"] <= (23 + 31) // 8 + 2
    assert diag["held_after_drain"] == {"full": 0, "window": 0, "state": 0}
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in ("sat.batch_occupancy", "sat.preemptions",
                 "sat.chunk_dispatch_share", "sat.peak_hbm_gib"):
        assert name in line["metrics"], sorted(line["metrics"])
    # the counters' ratios are in `diag` (the benchmark's list of per-layer
    # metrics is full at 128: four of this cell's own fit)
    assert 0 < diag["shared_kv_read_share"] < 100
    assert 0 < diag["cross_rows_spared_share"] < 100
    assert 0 < diag["window_blocks_per_slot"] <= 24 // 8 + 1
    assert np.isfinite(diag["state_bytes_share"])


@pytest.mark.parametrize("control,number", [
    ("window", "logit_gap_worst"), ("memory", "logit_gap_worst"),
    ("cross_kv", "cross_read_rel_err"), ("state_carry", "ssm_states_rel_err"),
    ("bf16_state", "ssm_state_rel_err")])
def test_a_reference_that_lacks_a_mechanism_refuses_the_run(control, number):
    """``reference_leaves_out`` (never a cell's): the check's own number
    moves by orders of magnitude (at the tiny size's float32 readings;
    the cell's limits and what each control reads at the published widths
    are in ``PERF.md`` section 4)."""
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, _ = harness.run_cell(
        BENCH, CELL, seed=5, seconds=1.0, trace_on=False, peaks=PEAKS,
        compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=dict(MIX, lead_in_s=0.0,
                           reference_leaves_out=[control]))
    sound = 1e-4 if number == "logit_gap_worst" else 1e-5
    assert line["diag"][number] > 100 * sound, line["diag"]


@pytest.mark.parametrize("fault", serve_hybrid.PROGRAM_FAULTS)
def test_a_program_whose_cross_layers_misread_refuses_the_run(fault):
    """``program_fault`` (never a cell's) puts the fault into the PROGRAM,
    the engine's and the check's alike: cross layers that walk the null
    block's table, or stop a page short.  The walks' own number refuses
    the run; what layer 17 wrote, the states and (at the published
    widths, ``PERF.md`` section 4) the logits do not see it."""
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, _ = harness.run_cell(
        BENCH, CELL, seed=5, seconds=1.0, trace_on=False, peaks=PEAKS,
        compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=dict(MIX, lead_in_s=0.0, program_fault=fault))
    diag = line["diag"]
    assert line["correct"] is False
    assert diag["cross_read_rel_err"] > serve_hybrid.CROSS_READ_REL_ERR_MAX
    assert diag["cross_read_by_layer"][0] < 1e-5       # the full layer's own
    assert diag["full_kv_rel_err"] < 1e-5
    assert diag["ssm_states_rel_err"] < 1e-5
