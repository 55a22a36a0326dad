"""The block-diffusion cell (``sdar-30b-a3b-chat.serve-blockgen-sat``,
runner ``serve_block_diffusion``) on the CPU: its configuration file
against what the program builds and against the catalog's numbers, the
order of its traffic, the work counts, the contract's counts, and a
rehearsal at a tiny size through the harness's own ``run_cell`` — sound,
against a reference that lacks one mechanism at a time, and with a fault
in the program.  A shape check, not a measurement."""
import json

import numpy as np
import pytest

from benchmark import run as harness
from benchmark.lib import (costs_block_diffusion, device, model as model_lib,
                           traffic)
from benchmark.runners import serve_block_diffusion as runner

CELL = "sdar-30b-a3b-chat.serve-blockgen-sat"
BENCH = harness.load_benchmark()
TINY = {"model": dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                      d_model=32, vocab_size=128, max_seq_len=128,
                      expert_d_ff=16, n_routed_experts=8, moe_topk=2,
                      experts_held=[0, 4], mask_token_id=127,
                      dtype="float32"),
        "num_kv_blocks": 256, "shrink": 16}
MIX = {"clients": 8, "trace_seconds": 1.5, "lead_in_s": 4.0,
       "engine": {"dtype": "float32", "max_out_tokens": 128,
                  "temperature": 0.0,
                  "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                              "max_batch_slots": 4, "num_kv_blocks": 256,
                              "denoising_steps": 2,
                              "remasking_strategy": "low_confidence_static"}}}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_file_holds_the_published_numbers_and_the_program_builds_them():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    config = model_lib.load_config(entry["file"])
    assert config["family"] == "sdar_moe"
    assert set(config["changed"]) == set(entry["reduced"]) == set(
        config["published"]) == {"num_experts", "max_position_embeddings"}
    assert config["source"] == entry["source"]
    mc, ref, held = runner.build(config)
    assert mc.num_params() == runner.NUM_PARAMS == 5_164_972_032
    assert (mc.num_layers, mc.vocab_size, mc.d_model) == (48, 151936, 2048)
    assert (mc.block_length, mc.mask_token_id, held) == (4, 151669, (0, 16))
    assert (ref["experts"], ref["topk"], ref["block_length"]) == (128, 8, 4)
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, num_key_value_heads=8))
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, generation=dict(config["generation"],
                                                  block_length=8)))
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        return
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert row["source_url"] == config["source"]
    for key, value in row["config"].items():
        if key not in entry["reduced"]:
            assert config[key] == value, key
        else:
            assert config["published"][key] == value, key


def test_the_contract_counts():
    """One new per-layer entry, listing this cell alone; the cell reports
    the saturated cells' common entries, the paged kernel's and the expert
    layers', and neither a dense FFN's nor a shared expert's."""
    assert len(BENCH["per_layer"]) <= 128
    mine = {m["name"]: m for m in harness.metrics_of(BENCH, "per_layer",
                                                     CELL)}
    new = mine["sat.block_rows_per_token"]
    assert new["workloads"] == [CELL] and new["layer"] == "block lane"
    assert new["moves"] == "serve_tokens_per_s"
    for name in ("sat.paged_roofline", "sat.paged_time_share",
                 "sat.moe_roofline", "sat.moe_rows_per_expert",
                 "sat.scope_unnamed_share", "sat.chunk_dispatch_share",
                 "sat.ahead_dispatch_share", "sat.mixed_rows_useful_share"):
        assert name in mine
    for name in ("sat.scope_mlp_share", "sat.moe_shared_share",
                 "sat.scope_shared_expert_share", "sat.mla_roofline"):
        assert name not in mine
    assert [m["name"] for m in harness.metrics_of(BENCH, "end_to_end", CELL)
            ] == ["serve_tokens_per_s", "setup_s"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell == BENCH["workloads"][-1]


def test_the_cells_order_gives_every_stretch_the_same_work():
    mix = traffic.load("serve-blockgen-sat")
    assert (mix["clients"], mix["block"], mix["blocks"]) == (80, 16, 64)
    serving = mix["engine"]["serving"]
    assert (serving["max_batch_slots"], serving["prefill_chunk_tokens"],
            serving["kv_block_size"], serving["num_kv_blocks"],
            serving["denoising_steps"], serving["remasking_strategy"]) == (
                40, 512, 16, 2176, 2, "low_confidence_static")
    every = sorted((p, o) for p in (128, 256, 512, 1024)
                   for o in (128, 256, 384, 512))
    orders = []
    for seed in (3, 2**31 + 11):
        work = traffic.requests(mix, seed, 151643)
        got = list(zip(work["prompt_len"].tolist(),
                       work["max_new"].tolist()))
        for at in range(0, len(got), 16):
            assert sorted(got[at:at + 16]) == every
        assert max(p + o for p, o in got) <= mix["engine"]["max_out_tokens"]
        assert max(int(p.max()) for p in work["prompts"]) < 151643
        orders.append(got)
    assert orders[0] != orders[1]
    assert sorted(orders[0]) == sorted(orders[1])
    # the pool holds every slot at its full length at once
    assert serving["num_kv_blocks"] - 1 >= 40 * np.mean(
        [-(-(p + o) // 16) for p, o in every])


def test_the_block_lanes_work_from_its_shapes():
    """A block of 4 rows over a context of 1,000 at the published widths
    in one layer: the context's K and V once for all four rows."""
    f, b = costs_block_diffusion.block_forward_cost(1000, 4, 32, 4, 128)
    assert f == 4 * 4 * 1000 * 32 * 128
    assert b == 2 * 1000 * 4 * 128 * 2 + 2 * 4 * 32 * 128 * 2
    one = costs_block_diffusion.block_forward_cost(1000, 1, 32, 4, 128)
    assert 3.9 < 4 * one[1] / b < 4.0      # a walk a row reads 4x the bytes
    f, b = costs_block_diffusion.block_causal_chunk_cost(512, 512, 4, 32, 4,
                                                         128)
    assert f == 4 * (512 * 516 / 2) * 32 * 128


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
    for read in (diag, diag["live"]):
        assert read["logit_gap_worst"] < 1e-4
        assert read["order_gap_worst"] <= 1e-6 and read["rows_agree"] == 1.0
        assert max(read["kv_first_rel_err"], read["kv_rel_err"],
                   read["kv_short_rel_err"]) < 1e-5
    assert diag["expert_rel_err"] < 1e-5
    assert diag["live"]["slots_live_least"] >= 1
    assert diag["blocks_held_after_drain"] == 0
    counts = diag["block_counts"]
    assert counts["rows"] == 4 * (counts["denoise"] + counts["commit"])
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"] for m in harness.metrics_of(BENCH, group, CELL)}
    assert set(line["metrics"]) <= declared
    if not trace_on:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        return
    for name in ("sat.batch_occupancy", "sat.preemptions",
                 "sat.chunk_dispatch_share", "sat.peak_hbm_gib",
                 "sat.moe_held_share", "sat.moe_rows_per_expert",
                 "sat.block_rows_per_token", "sat.ahead_dispatch_share"):
        assert name in line["metrics"], sorted(line["metrics"])
    # 2 denoise forwards + a commit a block of 4: 3 rows a token (the
    # first block of a check request may take fewer)
    assert 2.5 < line["metrics"]["sat.block_rows_per_token"]["value"] < 3.2
    assert line["metrics"]["sat.ahead_dispatch_share"]["value"] > 95.0
    assert line["metrics"]["sat.moe_held_share"]["value"] == pytest.approx(
        50.0, abs=15.0)
    from deepspeed_tpu.observability.overlap import get_overlap_profiler
    scopes = {scope for scope, _ in
              get_overlap_profiler().program_scopes().values()}
    assert "block_unmask" in scopes


def test_every_control_and_the_fault_pass_through_correct():
    """``controls=[..]`` judges the same served forwards against a
    reference that lacks one mechanism at a time and records what
    ``correct`` would have read, beside a sound run that is correct;
    ``program_fault`` (never a cell's) puts the fault into the PROGRAM
    (the block lane run causally).  The cell's limits are seated on the
    chip, where bfloat16 is the floor (``PERF.md`` section 4); at this
    size, in float32 and with logits within a unit of each other, every
    control and the fault are held to this size's own readings: a check's
    number moves from under 1e-5 by orders of magnitude."""
    line, _ = _rehearse(controls=list(runner.CONTROLS))
    assert line["correct"] is True
    diag, controls = line["diag"], line["diag"]["controls"]
    assert set(controls) == set(runner.CONTROLS) and len(controls) == 8
    numbers = [k for k in runner.LIMITS if k != "order_gap_worst"]
    assert max(diag[k] for k in numbers) < 1e-5
    moved = {"causal": "kv_rel_err", "qk_norm": "kv_first_rel_err",
             "rotary": "kv_first_rel_err", "renorm": "expert_rel_err",
             "shift": "logit_gap_worst", "commit": "kv_first_rel_err",
             "float8": "expert_rel_err", "bf16_softmax": "kv_rel_err"}
    for name, number in moved.items():
        assert controls[name][number] > 3e-4, (name, controls[name])
    assert not any(controls[name]["correct"] for name in (
        "qk_norm", "rotary", "renorm", "commit", "float8")), controls
    sound = {k: 0.0 for k in runner.LIMITS}
    assert runner._within_limits(sound)
    for k in sound:
        assert not runner._within_limits(dict(sound, **{k: float("nan")}))
        assert not runner._within_limits(dict(sound, **{k: float("inf")}))
    from deepspeed_tpu.ops.transformer import paged_decode_attention as pda
    sound_lane = pda.paged_block_attention
    try:
        line, _ = _rehearse(program_fault="causal_block")
    finally:
        pda.paged_block_attention = sound_lane
    assert line["diag"]["logit_gap_worst"] > 1e-3
    assert line["diag"]["kv_short_rel_err"] > 1e-3 > line["diag"][
        "kv_first_rel_err"]
