"""Two traces recorded on one v5e chip with the program's own names in
them (PR 24): 0.61 s of the saturated serving cell (four iterations of
the mixed step, each with the engine's ``serving/<phase>`` annotations
inside the client's ``serve_step``) and one step of the one-chip training
cell (flash kernels named ``flash_fwd`` / ``flash_bwd``).  The reduction
``lib/trace.reduce`` attributes the idle gaps to the phases — alone, or
beside the client's spans that hold them, each gap once — and the kernel
patterns match a kernel's own event only."""
import gzip
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import trace
from benchmark.runners import serve

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("serving/plan", "serving/operands", "serving/enqueue",
          "serving/device_wait", "serving/apply")
ITERATIONS = 4
#: any compiled Pallas kernel
PALLAS = r'custom_call_target="tpu_custom_call"'


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


def test_client_spans_and_phases_together_book_each_gap_once(serve_path):
    """What a serving runner hands ``stop_trace``: the client's spans, the
    engine's iteration and its phases, nested.  Each part of a gap goes to
    the innermost, so the names sum to the idle time exactly once and a
    phase reads what it reads alone."""
    assert set(serve.SPANS) == set(PHASES) | {
        "serve_step", "plan_submit", "serving/iteration"}
    alone = trace.reduce(serve_path, PHASES)
    red = trace.reduce(serve_path, serve.SPANS, serve.CLIENT_SPANS)
    by_client = trace.reduce(serve_path, serve.CLIENT_SPANS)
    # the window is the client's, whatever else is read
    for key in ("window_s", "busy_s", "op_s"):
        assert red[key] == by_client[key]
    gaps = red["idle_gap_s"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    assert min(gaps.values()) >= 0.0
    for phase, ms in (("operands", 3.28), ("enqueue", 0.77),
                      ("device_wait", 2.88), ("apply", 0.30),
                      ("plan", 0.12)):
        assert 1e3 * gaps["serving/" + phase] / ITERATIONS == \
            pytest.approx(ms, abs=0.01)
    # the phases' window is the first phase to the last, the client's the
    # first serve_step to the last: the same but for the span's own edge
    for phase in PHASES:
        assert gaps[phase] == pytest.approx(alone["idle_gap_s"][phase],
                                            abs=1e-5)
    # inside the client's span and outside the engine, inside the engine
    # between two phases: the marks, under 0.1 ms an iteration together
    assert gaps["serve_step"] + gaps["serving/iteration"] < 4e-4
    assert sum(gaps[p] for p in PHASES) + gaps["serve_step"] \
        + gaps["serving/iteration"] == pytest.approx(
            by_client["idle_gap_s"]["serve_step"], abs=1e-9)
    assert gaps["none"] == pytest.approx(by_client["idle_gap_s"]["none"],
                                         abs=1e-9)
    top = [n for n, _ in trace.breakdown(red)["idle_gaps"]]
    assert top[:2] == ["serving/operands", "serving/device_wait"]
    # one stall or a hundred: each whole gap under the name that holds
    # most of it.  The device idles from one program's end to the next
    # one's launch, 7.4 ms an iteration, most of it in ``operands``; the
    # window closes on the last iteration's ``device_wait``
    assert red["idle_gaps_over_1ms"] == {"serving/operands": ITERATIONS,
                                         "serving/device_wait": 1}
    seconds, at = red["idle_gap_longest"]["serving/operands"]
    assert seconds == pytest.approx(0.0075, abs=5e-4)
    assert 0.0 < at < red["window_s"] - seconds


def test_nested_and_overlapping_spans_on_hand_made_events():
    ms = 1e6
    spans = [("serve_step", 0.0, 50 * ms),
             ("serving/iteration", 1 * ms, 49 * ms),
             ("serving/plan", 1 * ms, 10 * ms),
             ("serving/apply", 12 * ms, 49 * ms),
             ("plan_submit", 60 * ms, 70 * ms),
             ("serve_step", 70 * ms, 100 * ms)]
    assert trace.innermost(spans) == [
        (0.0, 1 * ms, "serve_step"), (1 * ms, 10 * ms, "serving/plan"),
        (10 * ms, 12 * ms, "serving/iteration"),
        (12 * ms, 49 * ms, "serving/apply"), (49 * ms, 50 * ms, "serve_step"),
        (60 * ms, 70 * ms, "plan_submit"), (70 * ms, 100 * ms, "serve_step")]
    # a span that outlives the one it began in keeps what it covers
    assert trace.innermost([("a", 0.0, 10.0), ("b", 5.0, 20.0),
                            ("c", 6.0, 7.0)]) == [
        (0.0, 5.0, "a"), (5.0, 6.0, "b"), (6.0, 7.0, "c"), (7.0, 20.0, "b")]
    raw = {"spans": [("serving/apply", -5 * ms, -1 * ms)] + spans,
           "devices": {"/device:TPU:0": [
               ("%fusion.1 = fusion(...)", 5 * ms, 40 * ms),
               ("%fusion.2 = fusion(...)", 55 * ms, 40 * ms)]}}
    red = trace.reduce_events(raw, ("serve_step", "plan_submit"))
    assert red["window_s"] == pytest.approx(0.1)      # the client's spans
    assert red["idle_gap_s"] == pytest.approx({
        "serve_step": 0.001 + 0.001 + 0.005, "serving/plan": 0.004,
        "serving/apply": 0.004, "none": 0.005})
    assert red["idle_gaps_over_1ms"] == {"serving/plan": 1, "none": 1,
                                         "serve_step": 1}
    # (seconds, seconds into the window at which the gap opens)
    assert red["idle_gap_longest"] == {
        "serving/plan": pytest.approx((0.005, 0.0)),
        "none": pytest.approx((0.010, 0.045)),
        "serve_step": pytest.approx((0.005, 0.095))}
    # every name sets the window unless some are named
    assert trace.reduce_events(raw)["window_s"] == pytest.approx(0.105)


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
    both = PALLAS
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
    assert trace.matching(red, PALLAS) == pytest.approx(0.064)


def test_traces_from_before_the_names_match_nothing(tmp_path_factory):
    """The parent's kernels are ``closed_call`` / ``checkpoint`` /
    ``shard_map``: the new metrics read 0 there and nothing raises."""
    old = trace.reduce(_unpacked(
        tmp_path_factory, "train_v5e_4chip.xplane.pb.gz"), ("train_step",))
    assert trace.matching(old, PALLAS) > 0.2
    for metric in ("train.flash_fwd_time_share",
                   "train.flash_bwd_time_share"):
        assert trace.matching(old, _pattern(metric)) == 0.0
