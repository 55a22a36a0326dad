"""The five readers of the program's set-up log (``readers/setup_log.py``)
on a log made by hand through the engine's one instrument, on a clock the
test sets: what lies before the window, what an absent accessor gives, and
on two rehearsed cells that the engine's share of tracing and lowering
never exceeds the process-wide sum beside it."""
import types

import pytest

import test_rehearsal
from benchmark import run as harness
from benchmark.readers import setup_log
from deepspeed_tpu.observability import overlap

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
METRICS = {"setup_program_share": "program_share",
           "setup_step_build_s": "step_build_s",
           "setup_step_trace_lower_s": "step_trace_lower_s",
           "setup_cache_miss_programs": "cache_miss_programs",
           "setup_place_s": "place_s"}


@pytest.fixture
def prof(monkeypatch):
    """The process-global profiler with an empty set-up log, on a clock
    the test sets (seconds)."""
    clock = types.SimpleNamespace(t=0.0)
    monkeypatch.setattr(overlap, "time", types.SimpleNamespace(
        perf_counter=lambda: clock.t))
    p = overlap.get_overlap_profiler()
    p.clear_setup_log()
    p.clock = clock
    yield p
    del p.clock
    p.clear_setup_log()


def build(p, fun, trace_s, lower_s, compile_s, cache=None):
    """One program's events as JAX sends them, the clock moving with
    them."""
    p.clock.t += trace_s
    p._on_duration(TRACE, trace_s, fun_name=fun)
    p.clock.t += lower_s
    p._on_duration(LOWER, lower_s, fun_name=f"jit({fun})")
    if cache is not None:
        p._on_event(cache)
    p.clock.t += compile_s
    p._on_duration(COMPILE, compile_s, fun_name=f"jit({fun})")


def a_serving_set_up(p):
    """10 s of imports; init_inference 10-14 (place 11-13, of it 1 s a
    program of the benchmark's under the span); serving_engine 14-16
    (pools 14.5-15.5); the benchmark's own 16-20 (a program of its own, a
    miss); build_step 20-27 (the step's idle shape: 1 + 2 + 4, a miss);
    the other shape by its launch, outside every span, 27-30 (0.5 + 1 +
    1.5, a hit); the window opens at 40.  After it: a rebuild 41-44."""
    p.own_program("serving_step")
    p.clock.t = 10.0
    with p.setup_span("setup/init_inference"):
        p.clock.t = 11.0
        with p.setup_span("setup/place_params"):
            build(p, "cast", 0.25, 0.25, 0.5, MISS)
            p.clock.t = 13.0
        p.clock.t = 14.0
    with p.setup_span("setup/serving_engine"):
        p.clock.t = 14.5
        with p.setup_span("setup/pools"):
            p.clock.t = 15.5
        p.clock.t = 16.0
    build(p, "reference", 1.0, 1.0, 2.0, MISS)
    assert p.clock.t == 20.0
    with p.setup_span("setup/build_step"):
        build(p, "serving_step", 1.0, 2.0, 4.0, MISS)
    build(p, "serving_step", 0.5, 1.0, 1.5, HIT)
    assert p.clock.t == 30.0
    p.clock.t = 41.0
    build(p, "serving_step", 0.5, 1.0, 1.5, MISS)
    return {"window": (40.0, 50.0), "values": {"setup_s": 40.0},
            "diag": {}}


def test_the_five_numbers_of_a_hand_made_set_up(prof):
    obs = a_serving_set_up(prof)
    got = {name: setup_log.read(obs, of) for name, of in METRICS.items()}
    # init_inference 4 + serving_engine 2 + build_step 7 + the step's
    # other shape 3, of 40
    assert got["setup_program_share"] == pytest.approx(100 * 16 / 40)
    assert got["setup_step_build_s"] == pytest.approx(7.0 + 3.0)
    assert got["setup_step_trace_lower_s"] == pytest.approx(3.0 + 1.5)
    # cast, reference, the idle shape; the rebuild came after w0
    assert got["setup_cache_miss_programs"] == 3.0
    # place_params 2 less the second of the program under it, pools 1
    assert got["setup_place_s"] == pytest.approx(1.0 + 1.0)
    table = obs["diag"]["setup"]
    assert table["builds_in_window"] == ["serving_step"]
    assert table["cache"] == {"hit": 1, "miss": 3, "off": 0}
    assert [b["span"] for b in table["own_builds"]] == ["setup/build_step",
                                                        ""]
    spans = {s["name"]: s for s in table["spans"]}
    assert spans["setup/init_inference"]["self_s"] == pytest.approx(2.0)
    assert spans["setup/place_params"]["parent"] == "setup/init_inference"
    assert spans["setup/serving_engine"]["s"] == pytest.approx(2.0)
    assert spans["setup/serving_engine"]["self_s"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", list(METRICS))
def test_what_ends_after_the_window_opens_is_left_out(prof, name):
    obs = a_serving_set_up(prof)
    early = dict(obs, window=(19.0, 50.0), diag={})
    early.pop("setup_log", None)
    got = setup_log.read(early, METRICS[name])
    assert got == pytest.approx({
        # init_inference and serving_engine alone: 6 of 40
        "setup_program_share": 15.0, "setup_step_build_s": 0.0,
        "setup_step_trace_lower_s": 0.0,
        "setup_cache_miss_programs": 1.0,       # reference ends at 20
        "setup_place_s": 2.0}[name])
    assert early["diag"]["setup"]["builds_in_window"] == [
        "reference", "serving_step", "serving_step", "serving_step"]


@pytest.mark.parametrize("name", list(METRICS))
def test_a_program_without_the_accessors_gives_none(monkeypatch, name):
    """The parent commit: a profiler that has no set-up log."""
    monkeypatch.setattr(setup_log, "get_overlap_profiler",
                        lambda: types.SimpleNamespace(enabled=False))
    obs = {"window": (0.0, 1.0), "values": {"setup_s": 1.0}, "diag": {}}
    assert setup_log.read(obs, METRICS[name]) is None
    assert "setup" not in obs["diag"]
    assert harness.evaluate({"name": name, "unit": "s"}, obs) is None


def test_every_reader_has_its_declaration():
    for name, of in METRICS.items():
        assert harness.declaration(name) == {"reader": "setup_log",
                                             "args": {"of": of}}
    with pytest.raises(ValueError):
        setup_log.read({"setup_log": {"spans": [], "builds": overlap.
                                      get_overlap_profiler().builds()}},
                       "nothing")


@pytest.mark.parametrize("workload", ["gpt2-medium.train-1chip",
                                      "pythia-1.4b.serve-decode-sat"])
def test_a_rehearsed_cell_carries_the_five(workload):
    line = test_rehearsal._rehearse(workload, 1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(METRICS) <= set(got)
    assert 0.0 < got["setup_program_share"] <= 100.0
    assert 0.0 < got["setup_step_trace_lower_s"] <= got["trace_lower_s"]
    assert got["setup_step_trace_lower_s"] < got["setup_step_build_s"]
    assert got["setup_place_s"] > 0.0
    table = line["diag"]["setup"]
    assert table["builds_in_window"] == [] and table["dropped"] == 0
    step = "train_step" if "train" in workload else "serving_step"
    assert step in [b["name"] for b in table["own_builds"]]
