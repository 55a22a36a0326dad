"""The trace reduction on two recorded traces (0.65 s of the saturated
serving cell on one v5e chip: six iterations of the mixed step; one step of
the ZeRO-3 cell on four) and on hand-made events."""
import gzip
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ("serve_step", "plan_submit")


#: any compiled Pallas kernel, and what moved pythia-1.4b's pool rows
#: ([layers, blocks, 16, 2048]) before the step wrote them in place
PALLAS = r'custom_call_target="tpu_custom_call"'
POOL_COPY = (r"^%(copy|constant_dynamic-slice_fusion|constant_dynamic-update-"
             r"slice_fusion)[.\d]* = bf16\[\d+,\d+,16,WIDTH\]")


def _pattern(metric):
    return harness.declaration(metric)["args"]["pattern"]


def _reduce_recorded(tmp_path_factory, name, spans):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", name)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path), spans)


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    return _reduce_recorded(tmp_path_factory,
                            "serve_v5e_1chip.xplane.pb.gz", SPANS)


def test_four_chips_exposed_collectives(tmp_path_factory):
    red4 = _reduce_recorded(tmp_path_factory,
                            "train_v5e_4chip.xplane.pb.gz", ("train_step",))
    assert red4["devices"] == 4
    assert red4["window_s"] == pytest.approx(2.11931, abs=1e-4)
    assert red4["busy_s"] == pytest.approx(2.11549, abs=1e-4)
    # seconds are per chip: the collectives sit on the core's own timeline
    # (all-to-all, all-gather), so all of their time is exposed
    assert red4["exposed_collective_s"] == pytest.approx(0.40056, abs=1e-4)
    assert trace.matching(
        red4, r"^%(all-gather|all-to-all|all-reduce|reduce-scatter)") == \
        pytest.approx(red4["exposed_collective_s"], rel=1e-3)
    # 24 layers x (forward, recomputed forward, two backward passes)
    assert trace.matching(red4, PALLAS, "op_calls") == 96
    assert trace.matching(red4, PALLAS) == pytest.approx(0.20507, abs=1e-4)


def test_busy_union_and_window(red):
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.652988, abs=1e-5)
    assert red["busy_s"] == pytest.approx(0.604575, abs=1e-5)
    # one core runs one operation at a time: own times add up to the union
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"],
                                                      rel=1e-3)
    assert red["exposed_collective_s"] == 0.0


def test_kernel_time_by_pattern(red):
    paged = _pattern("sat.paged_time_share")
    # 6 iterations x 24 layers x (decode lane + chunk lane)
    assert trace.matching(red, paged, "op_calls") == 288
    assert trace.matching(red, paged) == pytest.approx(0.270694, abs=1e-5)
    # 6 x (24 layers x 4 slices of the pool + 2 whole-pool copies)
    copy = POOL_COPY.replace("WIDTH", "2048")
    assert trace.matching(red, copy, "op_calls") == 588
    assert 0.2 < trace.matching(red, copy) < 0.3
    # a pattern holds the result's shape: another pool matches nothing
    assert trace.matching(red, POOL_COPY.replace("WIDTH", "1024")) == 0.0
    # the one Pallas kernel of the serving step is the paged kernel
    assert trace.matching(red, PALLAS) == pytest.approx(
        trace.matching(red, paged))


def test_idle_gaps_are_attributed_to_the_clients_spans(red):
    gaps = red["idle_gap_s"]
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert gaps["serve_step"] > 10 * gaps["none"]
    top = trace.breakdown(red)
    assert top["device_ops"][0][0] == "paged_attention (pallas kernel)"
    assert len(top["device_ops"]) == 10
    assert [n for n, _ in top["idle_gaps"]][0] == "serve_step"


def test_exposed_collective_and_nesting_on_hand_made_events():
    ms = 1e6
    raw = {"spans": [("train_step", 0.0, 100 * ms)], "devices": {
        "/device:TPU:0": [
            ("%while.1 = while(...)", 0.0, 90 * ms),          # parent
            ("%fusion.1 = fusion(...)", 0.0, 40 * ms),
            ("%all-gather.3 = all-gather(...)", 40 * ms, 20 * ms),
            ("%fusion.2 = fusion(%all-gather.3)", 60 * ms, 30 * ms),
        ],
        "/device:TPU:1": [
            ("%fusion.1 = fusion(...)", 0.0, 50 * ms),
            ("%all-gather.3 = all-gather(...)", 40 * ms, 20 * ms),  # 10 hidden
            ("%fusion.2 = fusion(...)", 60 * ms, 30 * ms),
        ]}}
    red = trace.reduce_events(raw)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.09)
    # device 0: 20 ms exposed; device 1: 10 of 20 ms run beside a compute op
    assert red["exposed_collective_s"] == pytest.approx(0.015)
    # the while's own time is what its children leave: nothing
    assert trace.matching(red, r"^%while") == pytest.approx(0.0, abs=1e-12)
    assert trace.matching(red, r"^%fusion") == pytest.approx(0.075)
    # a consumer that names the collective as its operand is no collective
    assert trace.matching(red, r"^%all-gather") == pytest.approx(0.02)
    assert red["idle_gap_s"]["train_step"] == pytest.approx(0.01)


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace.subtract([(0, 10)], [[1, 2], [3, 4]]) == [
        (0, 1), (2, 3), (4, 10)]
    assert trace.subtract([(0, 1), (2, 3)], [[0, 5]]) == []
    assert trace.total(trace.clip([(0, 10), (20, 30)], 5, 25)) == 10
    assert trace.op_key("%fusion.12 = bf16[2]{0} fusion(...)") == "fusion"
    assert trace.op_key("dot_general.1") == "dot_general"
