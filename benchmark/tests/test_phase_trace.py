"""Two traces recorded on one v5e chip with the program's own names in
them (PR 24): 0.61 s of the saturated serving cell (four iterations of
the mixed step, each with the engine's ``serving/<phase>`` annotations
inside the client's ``serve_step``) and one step of the one-chip training
cell (flash kernels named ``flash_fwd`` / ``flash_bwd``).  The unchanged
reduction ``lib/trace.reduce`` attributes the idle gaps to the phases, and
the kernel patterns match a kernel's own event only."""
import gzip
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("serving/plan", "serving/operands", "serving/enqueue",
          "serving/device_wait", "serving/apply")
ITERATIONS = 4


def _unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", name)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def serve_path(tmp_path_factory):
    return _unpacked(tmp_path_factory, "serve_phases_v5e_1chip.xplane.pb.gz")


@pytest.fixture(scope="module")
def train_red(tmp_path_factory):
    return trace.reduce(_unpacked(
        tmp_path_factory, "train_flash_v5e_1chip.xplane.pb.gz"),
        ("train_step",))


def _pattern(metric):
    return harness.declaration(metric)["args"]["pattern"]


def test_idle_gaps_are_attributed_to_the_engines_phases(serve_path):
    red = trace.reduce(serve_path, PHASES)
    gaps = red["idle_gap_s"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert idle == pytest.approx(0.030496, abs=1e-5)
    assert set(gaps) == set(PHASES) | {"none"}
    # what is in no phase is the caller between two step()s
    assert gaps["none"] < idle / 10
    per_iteration_ms = {k.split("/")[-1]: 1e3 * v / ITERATIONS
                        for k, v in gaps.items()}
    # operands: the device waits all through it; enqueue: until the
    # program launches, 0.8 ms in; device_wait: the 2.9 ms after the
    # program's end until the second np.asarray has its result
    assert per_iteration_ms["operands"] == pytest.approx(3.28, abs=0.01)
    assert per_iteration_ms["enqueue"] == pytest.approx(0.77, abs=0.01)
    assert per_iteration_ms["device_wait"] == pytest.approx(2.88, abs=0.01)
    assert per_iteration_ms["apply"] == pytest.approx(0.30, abs=0.01)
    assert per_iteration_ms["plan"] == pytest.approx(0.12, abs=0.01)
    # the same idle time the client's spans see as one lump
    seen_by_client = trace.reduce(serve_path, ("serve_step", "plan_submit"))
    assert seen_by_client["idle_gap_s"]["serve_step"] == pytest.approx(
        idle - gaps["none"], abs=3e-4)


def test_phase_annotations_lie_inside_serve_step(serve_path):
    raw = trace.read(serve_path, set(PHASES) | {"serve_step",
                                                "serving/iteration"})
    by_name = {}
    for name, start, end in raw["spans"]:
        by_name.setdefault(name, []).append((start, end))
    steps, its = by_name["serve_step"], by_name["serving/iteration"]
    assert len(steps) == len(its) == ITERATIONS
    for (s0, s1), (i0, i1) in zip(steps, its):
        assert s0 <= i0 and i1 <= s1
        # the client's span is the iteration plus under 0.1 ms
        assert (s1 - s0) - (i1 - i0) < 1e5
        inside = sorted((a, b) for p in PHASES for a, b in by_name[p]
                        if i0 <= a and b <= i1)
        assert len(inside) == len(PHASES)      # one dispatch each
        assert all(x[1] <= y[0] for x, y in zip(inside, inside[1:]))
        # the five cover the iteration but for the marks between them
        assert sum(b - a for a, b in inside) > 0.999 * (i1 - i0)


def test_flash_patterns_match_the_kernels_own_events(train_red):
    fwd = _pattern("train.flash_fwd_time_share")
    bwd = _pattern("train.flash_bwd_time_share")
    both = _pattern("train.flash_time_share")
    # one step: 24 layers x (forward, recomputed forward), 24 x backward
    assert trace.matching(train_red, fwd, "op_calls") == 48
    assert trace.matching(train_red, bwd, "op_calls") == 24
    assert trace.matching(train_red, both, "op_calls") == 72
    assert trace.matching(train_red, fwd) == pytest.approx(0.045945,
                                                           abs=1e-5)
    assert trace.matching(train_red, bwd) == pytest.approx(0.043620,
                                                           abs=1e-5)
    assert trace.matching(train_red, fwd) + trace.matching(train_red, bwd) \
        == pytest.approx(trace.matching(train_red, both), rel=1e-9)
    # every Pallas kernel of the step starts with its own name
    kernels = {k.split(" = ")[0] for k in train_red["op_s"]
               if 'custom_call_target="tpu_custom_call"' in k}
    assert {k.split(".")[0] for k in kernels} == {"%flash_fwd",
                                                  "%flash_bwd"}
    top = dict(trace.breakdown(train_red)["device_ops"])
    assert "flash_fwd (pallas kernel)" in top
    assert "flash_bwd (pallas kernel)" in top


def test_flash_patterns_on_hand_made_events():
    """Anchored at the instruction's own name: an operation that names a
    kernel as its operand, and a kernel whose name merely begins alike, do
    not count; the two-kernel backward does."""
    ms = 1e6
    call = 'custom-call(%x), custom_call_target="tpu_custom_call"'
    raw = {"spans": [("train_step", 0.0, 100 * ms)], "devices": {
        "/device:TPU:0": [
            (f"%flash_fwd.17 = bf16[8] {call}", 0.0, 10 * ms),
            (f"%flash_fwd = bf16[8] {call}", 10 * ms, 10 * ms),
            ("%fusion.3 = bf16[8] fusion(%flash_fwd.17)", 20 * ms, 5 * ms),
            (f"%flash_fwd_v2.1 = bf16[8] {call}", 25 * ms, 5 * ms),
            (f"%flash_bwd.9 = bf16[8] {call}", 30 * ms, 20 * ms),
            (f"%flash_bwd_dq.2 = bf16[8] {call}", 50 * ms, 7 * ms),
            (f"%flash_bwd_dkv.2 = bf16[8] {call}", 57 * ms, 9 * ms),
            ("%copy.4 = bf16[8] copy(%flash_bwd_dq.2)", 66 * ms, 4 * ms),
            (f"%closed_call.5 = bf16[8] {call}", 70 * ms, 3 * ms),
        ]}}
    red = trace.reduce_events(raw)
    fwd = _pattern("train.flash_fwd_time_share")
    bwd = _pattern("train.flash_bwd_time_share")
    assert trace.matching(red, fwd) == pytest.approx(0.020)
    assert trace.matching(red, bwd) == pytest.approx(0.036)
    assert trace.matching(red, _pattern("train.flash_time_share")) == \
        pytest.approx(0.064)


def test_traces_from_before_the_names_match_nothing(tmp_path_factory):
    """The parent's kernels are ``closed_call`` / ``checkpoint`` /
    ``shard_map``: the new metrics read 0 there and nothing raises."""
    old = trace.reduce(_unpacked(
        tmp_path_factory, "train_v5e_4chip.xplane.pb.gz"), ("train_step",))
    assert trace.matching(old, _pattern("train.flash_time_share")) > 0.2
    for metric in ("train.flash_fwd_time_share",
                   "train.flash_bwd_time_share"):
        assert trace.matching(old, _pattern(metric)) == 0.0
