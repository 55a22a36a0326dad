"""The linear / latent hybrid cell (``kimi-linear-48b-a3b.serve-longgen-
sat``, runner ``serve_kda_latent``) on the CPU: its configuration file
against what the program builds and against the catalog's numbers, the
order of its traffic, the work counts, the contract's counts, and a
rehearsal at a tiny size through the harness's own ``run_cell`` — sound,
against a reference that lacks one mechanism at a time, and with a fault
in the program.  A shape check, not a measurement."""
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import costs_kda, device, model as model_lib, traffic
from benchmark.runners import serve_kda_latent

CELL = "kimi-linear-48b-a3b.serve-longgen-sat"
BENCH = harness.load_benchmark()
PATTERN = ["kda"] + ["kda", "mla", "kda"] * 2 + ["kda", "mla"]
TINY = {"model": dict(num_layers=9, layer_types=PATTERN, num_heads=4,
                      d_model=32, d_ff=64, vocab_size=128, max_seq_len=320,
                      kv_lora_rank=16, qk_nope_head_dim=8,
                      qk_rope_head_dim=4, v_head_dim=8, kda_heads=2,
                      kda_head_dim=8, expert_d_ff=16, n_routed_experts=8,
                      moe_topk=2, experts_held=[0, 4], dtype="float32"),
        "num_kv_blocks": 512, "shrink": 16}
MIX = {"clients": 8, "trace_seconds": 1.5, "lead_in_s": 4.0,
       "engine": {"dtype": "float32", "max_out_tokens": 320,
                  "temperature": 0.0,
                  "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                              "max_batch_slots": 4, "num_kv_blocks": 512}}}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
NEW = ("sat.scope_kda_proj_share", "sat.scope_kda_scan_share",
       "sat.kda_decode_bw_share", "sat.kda_chunk_roofline")


def test_the_file_holds_the_published_numbers_and_the_program_builds_them():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == "kimi_linear"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"]) == {"num_experts", "model_max_length"}
    assert config["source"] == entry["source"]
    mc, ref, held = serve_kda_latent.build(config)
    assert mc.num_params() == serve_kda_latent.NUM_PARAMS == 4_956_660_608
    assert (mc.num_layers, mc.vocab_size, mc.d_model) == (27, 163840, 2304)
    assert (mc.kda_layers, mc.mla_layers, held) == (20, 7, (0, 16))
    assert ref["layer_types"] == mc.layer_types and ref["scale"] == 2.446
    with pytest.raises(ValueError, match="the program built"):
        serve_kda_latent.build(dict(config, kv_lora_rank=256))
    with pytest.raises(ValueError, match="the program built"):
        serve_kda_latent.build(dict(config, linear_attn_config=dict(
            config["linear_attn_config"], head_dim=64)))


def test_the_contract_counts():
    """126 of 128 per-layer entries; the four this cell brought list it
    alone; the cell reports the saturated cells' common entries, the
    latent and expert layers' and the cache manager's."""
    assert len(BENCH["per_layer"]) == 126
    assert len(BENCH["workloads"]) == 11 and len(BENCH["configs"]) == 9
    mine = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                     CELL)}
    for name in NEW:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["layer"] == "delta-rule layer"
    for name in ("sat.mla_roofline", "sat.moe_roofline",
                 "sat.scope_state_io_share", "sat.scope_unnamed_share",
                 "sat.moe_shared_share", "sat.chunk_dispatch_share"):
        assert name in mine
    assert [m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)
            ] == ["serve_tokens_per_s", "setup_s"]


def test_the_cells_order_gives_every_stretch_the_same_work():
    mix = traffic.load("serve-longgen-sat")
    assert (mix["clients"], mix["block"], mix["blocks"]) == (96, 16, 64)
    serving = mix["engine"]["serving"]
    assert (serving["max_batch_slots"], serving["prefill_chunk_tokens"],
            serving["kv_block_size"], serving["num_kv_blocks"]) == (
                48, 512, 16, 9216)
    every = sorted((p, o) for p in (512, 1024, 2048, 4096)
                   for o in (256, 512, 768, 1024))
    orders = []
    for seed in (3, 2**31 + 11):
        work = traffic.requests(mix, seed, 163840)
        got = list(zip(work["prompt_len"].tolist(),
                       work["max_new"].tolist()))
        for at in range(0, len(got), 16):
            assert sorted(got[at:at + 16]) == every
        assert max(p + o for p, o in got) <= mix["engine"]["max_out_tokens"]
        orders.append(got)
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1])
    # the pool holds every slot's mean context with room, not every
    # slot's longest at once (48 x 5,120 tokens would be 15,360 blocks)
    mean = np.mean([p + o / 2 for p, o in every])
    assert serving["num_kv_blocks"] >= 1.3 * 48 * mean / 16


def test_the_recurrences_work_from_its_shapes():
    """A chunk of 512 rows in one layer at the published widths: 2.3
    GFLOP, counted at the algorithm's 64 rows a block whatever block the
    program takes; a decode row: a head's 64 KiB in and out, 32 heads."""
    assert costs_kda.BLOCK_ROWS == 64
    f, b = costs_kda.kda_chunk_scan_cost(512, 1, 32, 128, 128)
    assert f == 512 * 32 * (64 * 640 + 6 * 128 * 128)
    assert b == 512 * 32 * 4 * 641 + 2 * 32 * 2**16
    f, b = costs_kda.kda_decode_update_cost(1, 32, 128, 128)
    assert f == 7 * 32 * 2**14
    assert b == 2 * 2**21 + 32 * 4 * 641
    assert costs_kda.state_bytes(20, 32, 128, 128, 4) == 20 * (
        2**21 + 3 * 12288 * 2) == 43_417_600


def _rehearse(trace_on=False, **mix):
    from deepspeed_tpu.ops import interpret_kernels
    interpret_kernels(True)
    return harness.run_cell(
        BENCH, CELL, seed=2**31 + 7, seconds=6.0 if trace_on else 1.0,
        trace_on=trace_on, peaks=PEAKS, compile_log=device.CompileLog(),
        tiny=TINY, mix_overrides=dict(
            MIX, **({} if trace_on else {"lead_in_s": 0.0}), **mix))


@pytest.mark.parametrize("trace_on", (False, True))
def test_the_cell_rehearses_through_the_harness(trace_on):
    line, obs = _rehearse(trace_on)
    diag = line["diag"]
    assert line["correct"] is True and line["failed"] == 0, diag
    assert line["attempted"] > 0
    assert diag["logit_gap_worst"] < 1e-4
    for name in ("kda_state_rel_err", "kda_state_path_rel_err",
                 "kda_states_rel_err", "latent_rel_err", "expert_rel_err"):
        assert diag[name] < 1e-5, (name, diag[name])
    live = diag["live"]
    assert live["logit_gap_worst"] < 1e-4 and live["slots_live_least"] >= 1
    assert max(live["kda_states_rel_err"], live["latent_rel_err"]) < 1e-5
    assert diag["held_after_drain"] == {"full": 0, "window": 0, "state": 0}
    assert 0 < diag["state_bytes_share"] < 100
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in ("sat.batch_occupancy", "sat.preemptions",
                 "sat.chunk_dispatch_share", "sat.peak_hbm_gib",
                 "sat.moe_held_share", "sat.moe_rows_per_expert",
                 "sat.moe_shared_share"):
        assert name in line["metrics"], sorted(line["metrics"])
    assert line["metrics"]["sat.moe_held_share"]["value"] == pytest.approx(
        50.0, abs=15.0)
    assert diag["kda_decode_rows_per_s"] > 0
    assert diag["state_bytes_moved_per_s"] > 0
    # the lanes by the program's own names (the CPU's trace has no device
    # plane: the shares themselves are the chip's)
    from deepspeed_tpu.observability.overlap import get_overlap_profiler
    lanes = {scope for scope, _ in
             get_overlap_profiler().program_scopes(lanes=True).values()}
    assert {"kda_scan/decode", "kda_scan/chunk"} <= lanes


@pytest.mark.parametrize("control,number", [
    ("delta", "kda_state_rel_err"), ("scalar_decay", "kda_state_rel_err"),
    ("conv", "kda_state_rel_err"), ("l2norm", "kda_state_rel_err"),
    ("out_gate", "kda_states_rel_err"), ("rotary", "latent_rel_err"),
    ("renorm", "expert_rel_err"), ("scale", "expert_rel_err"),
    ("shared", "expert_rel_err"), ("bf16_state", "kda_state_path_rel_err")])
def test_a_reference_that_lacks_a_mechanism_refuses_the_run(control, number):
    """``reference_leaves_out`` (never a cell's): the check's own number
    moves by orders of magnitude from the tiny size's float32 readings
    (under 1e-5); the cell's limits and what each control reads at the
    published widths are in ``PERF.md`` section 4."""
    line, _ = _rehearse(reference_leaves_out=[control])
    assert line["diag"][number] > 1e-3, line["diag"]


def test_every_control_passes_through_correct():
    """``controls=[..]`` judges the same served tokens against a reference
    that lacks one mechanism at a time and records what ``correct`` would
    have read, by the cell's own limits: false (a NaN, the reading without
    the l2norm at the published widths, is within no limit), beside a
    sound run that is correct.  At this size one control stays inside the
    published size's limits — a rotation of 4 lanes over contexts of 80
    tokens moves the latent rows by less than 0.11 (on the chip, 64 lanes
    over 1,356 tokens: 0.33, ``PERF.md`` section 4) — and is held to this
    size's own reading instead."""
    line, _ = _rehearse(controls=list(serve_kda_latent.CONTROLS))
    assert line["correct"] is True
    controls = line["diag"]["controls"]
    assert set(controls) == set(serve_kda_latent.CONTROLS)
    assert len(controls) == 12
    assert {name for name, read in controls.items()
            if read["correct"]} <= {"rotary"}, controls
    assert controls["rotary"]["latent_rel_err"] > 1e3 * max(
        line["diag"]["latent_rel_err"], 1e-9)
    sound = {k: 0.0 for k in serve_kda_latent.LIMITS}
    assert serve_kda_latent._within_limits(sound)
    for k in sound:
        assert not serve_kda_latent._within_limits(
            dict(sound, **{k: float("nan")}))
        assert not serve_kda_latent._within_limits(
            dict(sound, **{k: float("inf")}))


@pytest.mark.parametrize("fault,number", [
    ("bf16_state", "kda_state_path_rel_err"),
    ("state_reset_at_chunk", "kda_state_rel_err")])
def test_a_fault_in_the_program_refuses_the_run(fault, number):
    """``program_fault`` (never a cell's) puts the fault into the PROGRAM;
    the run is refused by the first layer's state."""
    line, _ = _rehearse(program_fault=fault)
    diag = line["diag"]
    assert line["correct"] is False
    # (under 1e-5 without the fault, at this size's float32)
    assert diag[number] > serve_kda_latent.LIMITS[number] > 1e-3, diag
    assert diag["expert_rel_err"] < 1e-5
