"""The window / full attention cell over experts (``trinity-mini.serve-
mixedlen-sat``, runner ``serve_window_moe``) on the CPU: its configuration
file against what the program builds and against the catalog's numbers,
the order of its traffic, the work counts, and a rehearsal at a tiny size
through the harness's own ``run_cell``.  A shape check, not a
measurement."""
import pytest

from benchmark import run as harness
from benchmark.lib import (costs_window_moe, device, model as model_lib,
                           traffic)
from benchmark.runners import serve_window_moe

CELL = "trinity-mini.serve-mixedlen-sat"
BENCH = harness.load_benchmark()
#: the lead, one whole period and the boundary period; a window of 3 pages
TINY = {"model": dict(num_layers=8,
                      layer_types=["window", "window", "window", "full"] * 2,
                      first_k_dense=2, num_heads=4, num_kv_heads=2,
                      head_dim=8, d_model=32, d_ff=64, vocab_size=128,
                      max_seq_len=640, sliding_window=24, expert_d_ff=16,
                      n_routed_experts=16, moe_topk=4, experts_held=[0, 4],
                      dtype="float32"),
        "num_kv_blocks": 512, "shrink": 16}
MIX = {"clients": 8, "trace_seconds": 1.5, "lead_in_s": 4.0,
       "engine": {"dtype": "float32", "max_out_tokens": 640,
                  "temperature": 0.0,
                  "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                              "max_batch_slots": 4, "num_kv_blocks": 512,
                              "prefix_cache": False}}}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


def test_the_file_holds_the_published_numbers_and_the_program_builds_them():
    entry = next(c for c in BENCH["configs"] if c["name"] == "trinity-mini")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == config["model_type"] == "afmoe"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"]) == {"num_experts", "max_position_embeddings"}
    assert config["source"] == entry["source"]
    mc, ref, held = serve_window_moe.build(config)
    assert mc.num_params() == serve_window_moe.NUM_PARAMS == 4_984_682_240
    assert (mc.num_layers, mc.vocab_size, mc.d_model) == (32, 200192, 2048)
    assert (mc.window_layers, mc.full_layers, held) == (24, 8, (0, 16))
    assert all(kind == ("full" if at % 4 == 3 else "window")
               for at, kind in enumerate(mc.layer_types))
    assert [(len(sigs), passes) for sigs, passes in mc.layer_plan] == [
        (2, 1), (4, 7), (2, 1)]
    assert (ref["window"], ref["scale"], ref["experts"]) == (2048, 2.826, 128)
    with pytest.raises(ValueError, match="the program built"):
        serve_window_moe.build(dict(config, sliding_window=1024))


def test_the_cells_order_gives_every_stretch_the_same_work():
    """The issue's traffic: every run of 16 consecutive requests that
    starts at a multiple of 16 holds every (prompt, output) pair once,
    whatever the seed, and two seeds differ in order only; the longest
    request fits the served positions."""
    mix = traffic.load("serve-mixedlen-sat")
    assert (mix["clients"], mix["block"]) == (40, 16)
    serving = mix["engine"]["serving"]
    assert (serving["max_batch_slots"], serving["prefill_chunk_tokens"],
            serving["kv_block_size"], serving["prefix_cache"]) == (
                20, 512, 16, False)
    every = sorted((p, o) for p in (512, 2048, 4096, 8192)
                   for o in (128, 256, 384, 512))
    orders = []
    for seed in (3, 2**31 + 11):
        work = traffic.requests(mix, seed, 200192)
        got = list(zip(work["prompt_len"].tolist(),
                       work["max_new"].tolist()))
        for at in range(0, len(got), 16):
            assert sorted(got[at:at + 16]) == every
        assert max(p + o for p, o in got) <= mix["engine"]["max_out_tokens"]
        orders.append(got)
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1])


@pytest.mark.parametrize("kind,context,rows,visible,read", [
    # a decode row: all of the context in a full layer, the window's keys
    # in a window layer
    ("full", 6000, 1, 6000, 6000), ("window", 6000, 1, 2048, 2048),
    # under the window the two kinds do the same work
    ("full", 1500, 1, 1500, 1500), ("window", 1500, 1, 1500, 1500),
    # a chunk of 512 rows far past the window, and one that straddles it
    ("full", 4096, 512, 512 * (3585 + 4096) / 2, 4096),
    ("window", 4096, 512, 512 * 2048, 2048 + 511),
    ("window", 2304, 512, 255 * (1793 + 2047) / 2 + 257 * 2048, 2304)])
def test_the_walks_work_by_kind_of_layer(kind, context, rows, visible, read):
    f, b = costs_window_moe.paged_walk_cost(kind, context, rows, 32, 4, 128,
                                            2048)
    assert f == 4.0 * visible * 32 * 128
    assert b == 2 * read * 4 * 128 * 2 + 2 * rows * 32 * 128 * 2


def test_a_slot_holds_less_where_the_window_trims():
    full, window = costs_window_moe.slot_bytes(8704, 2048, 8, 24, 4, 128)
    assert (full, window) == (8704 * 16384, 2048 * 49152)
    assert round((full + window) / 1e6) == 243
    assert round(sum(costs_window_moe.slot_bytes(8704, 8704, 32, 0, 4, 128))
                 / 1e6) == 570


@pytest.mark.parametrize("trace_on", [False, True])
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
    live = diag["live"]
    for judged in (diag, live):
        assert judged["logit_gap_worst"] < 1e-4
        assert judged["kv_rel_err"] < 1e-5
        assert judged["argmax_share"] == 1.0
    assert live["slots_live_least"] >= 1
    assert diag["window_blocks_held"]["decode"] <= 23 // 8 + 2
    assert diag["window_blocks_held"]["chunk"] <= (23 + 31) // 8 + 2
    assert diag["held_after_drain"] == {"full": 0, "window": 0, "state": 0}
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in ("sat.batch_occupancy", "sat.preemptions",
                 "sat.chunk_dispatch_share", "sat.peak_hbm_gib",
                 "sat.moe_held_share", "sat.moe_imbalance",
                 "sat.moe_rows_per_expert", "sat.moe_touched_share",
                 "sat.moe_shared_share"):
        assert name in line["metrics"], sorted(line["metrics"])
    # a quarter of the experts held: about a quarter of the picks
    assert 10 < line["metrics"]["sat.moe_held_share"]["value"] < 45
    assert 0 < diag["window_tokens_read_share"] < 100
    assert 0 < diag["window_blocks_per_slot"] <= (23 + 31) // 8 + 2
    assert 0 < diag["window_bytes_share"] < 100
    assert diag["window_blocks_freed"] > 0
    # the lanes by the program's own names (the CPU's trace has no device
    # plane to join them to)
    from deepspeed_tpu.observability.overlap import get_overlap_profiler
    lanes = {scope for scope, _ in
             get_overlap_profiler().program_scopes(lanes=True).values()}
    assert {"attn_kernel/window", "attn_kernel/full"} <= lanes


@pytest.mark.parametrize("control", ["window", "gate", "nope", "bias",
                                     "scale"])
def test_a_reference_that_lacks_a_mechanism_refuses_the_run(control):
    """Judged by a reference that lacks one mechanism the same served
    tokens and pool rows read ``correct`` false: the attention's three by
    the rows the full layers' pool was left, the router's two by the
    first expert layer's ``f``."""
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    line, _ = harness.run_cell(
        BENCH, CELL, seed=11, seconds=2.0, trace_on=False, peaks=PEAKS,
        compile_log=device.CompileLog(), tiny=TINY,
        mix_overrides=dict(MIX, reference_leaves_out=[control]))
    assert line["correct"] is False
    name = "expert_rel_err" if control in ("bias", "scale") else "kv_rel_err"
    assert line["diag"][name] > serve_window_moe.LIMITS[name], line["diag"]
