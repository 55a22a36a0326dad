"""The set-up log of the overlap profiler (observability/overlap.py):
spans around the entry points' and the engines' set-up steps, and one
build record a program traced, lowered, compiled or fetched — always on,
bounded, and never called from an iteration."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability import overlap
from deepspeed_tpu.observability.overlap import (OverlapProfiler,
                                                 get_overlap_profiler)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"


def tiny_lm(layers=2):
    return TransformerLM(gpt2_config(
        "125m", num_layers=layers, d_model=32, num_heads=4, vocab_size=64,
        max_seq_len=64, dtype=jnp.float32))


def names_and_parents(spans):
    name_of = {int(s["id"]): str(s["name"]) for s in spans}
    return [(str(s["name"]), name_of.get(int(s["parent"])))
            for s in spans]


@pytest.fixture(scope="module")
def served():
    """One tiny engine, built and stepped once on a cleared log: ``(srv,
    the log's spans and builds right after the first ``run()``)``."""
    prof = get_overlap_profiler()
    prof.clear_setup_log()
    eng = ds.init_inference(
        tiny_lm(), config={
            "dtype": "float32", "max_out_tokens": 64, "temperature": 0.0,
            "replace_with_kernel_inject": False,
            "serving": {"enabled": True, "kv_block_size": 8,
                        "num_kv_blocks": 48, "max_batch_slots": 4,
                        "prefill_chunk_tokens": 16}})
    srv = eng.serving_engine()
    before_step = len(prof.builds())
    srv.submit(list(range(1, 20)), max_new_tokens=4)
    assert srv.step()
    first = {"spans": prof.setup_spans(), "builds": prof.builds(),
             "builds_before_step": before_step}
    srv.run()
    yield srv, first
    prof.configure(enabled=False)


def test_serving_spans_nest_as_the_contract_names_them(served):
    _, first = served
    assert names_and_parents(first["spans"]) == [
        ("setup/init_inference", None),
        ("setup/param_specs", "setup/init_inference"),
        ("setup/place_params", "setup/init_inference"),
        ("setup/serving_params", "setup/init_inference"),
        ("setup/serving_engine", None),
        ("setup/pools", "setup/serving_engine"),
        ("setup/build_step", None)]
    spans = first["spans"]
    assert (spans["end_s"] >= spans["begin_s"]).all()
    by_id = {int(s["id"]): s for s in spans}
    for s in spans[spans["parent"] >= 0]:        # a child lies in its parent
        parent = by_id[int(s["parent"])]
        assert parent["begin_s"] <= s["begin_s"] <= s["end_s"] \
            <= parent["end_s"]


def test_first_step_leaves_two_own_builds_of_the_serving_step(served):
    srv, first = served
    builds = first["builds"]
    assert not builds[:first["builds_before_step"]]["own"].any()
    own = builds[builds["own"]]
    assert [str(n) for n in own["fun_name"]] == ["serving_step"] * 2
    assert srv.decode_builds == 2
    (build_step,) = [s for s in first["spans"]
                     if s["name"] == "setup/build_step"]
    # the idle shape is built under the span; the calling dispatch's own
    # shape by its launch right after, outside every span
    assert [int(s) for s in own["span"]] == [int(build_step["id"]), -1]
    assert build_step["begin_s"] <= own[0]["begin_s"] \
        and own[0]["end_s"] <= build_step["end_s"]
    assert own[1]["begin_s"] >= build_step["end_s"]
    for b in own:
        assert b["trace_s"] > 0 and b["lower_s"] > 0 and b["compile_s"] > 0
        assert b["end_s"] - b["begin_s"] >= \
            b["trace_s"] + b["lower_s"] + b["compile_s"] - 1e-3
        assert b["cache"] == "off" and b["iteration"] == -1


def test_fifty_steady_iterations_build_nothing_and_wake_no_listener(served):
    """Whatever JAX reports, it reports to every listener: one of the
    test's own that hears nothing says the profiler's heard nothing."""
    from jax._src import monitoring
    srv, _ = served
    prof = get_overlap_profiler()
    heard = []

    def on_duration(event, secs, **kw):
        heard.append(event)

    def on_event(event, **kw):
        heard.append(event)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    try:
        builds_before = len(prof.builds())
        spans_before = len(prof.setup_spans())
        shapes = set()
        real = srv._launch

        def launch(operands):
            shapes.add(len(operands[-1]))
            return real(operands)
        srv._launch = launch
        iterations = 0
        for wave in range(3):
            for k in range(5):
                srv.submit(list(range(1, 20 + k)), max_new_tokens=12)
            while srv.step():
                iterations += 1
        del srv._launch
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        monitoring.unregister_event_listener(on_event)
    assert iterations >= 50 and len(shapes) == 2   # mixed and decode-only
    assert heard == []
    assert len(prof.builds()) == builds_before
    assert len(prof.setup_spans()) == spans_before
    assert srv.decode_builds == 2


def test_a_rebuild_under_traffic_names_its_iteration(served):
    srv, _ = served
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        srv.submit(list(range(1, 12)), max_new_tokens=3)
        srv.run()                   # iterations 0.. with nothing built
        t0 = time.perf_counter()
        srv._step_fn = None         # the next dispatch builds again
        srv.submit(list(range(1, 12)), max_new_tokens=3)
        assert srv.step()
        rebuilt = prof.builds(t0, time.perf_counter())
        srv.run()
    finally:
        prof.configure(enabled=False)
    assert [str(n) for n in rebuilt["fun_name"]] == ["serving_step"] * 2
    assert rebuilt["own"].all()
    assert (rebuilt["iteration"] >= 1).all()
    assert (rebuilt["iteration"] == prof.iterations(
        t0, time.perf_counter())[0]["n"][0]).all()


def test_training_spans_and_the_step_found_by_its_own_record():
    prof = get_overlap_profiler()
    prof.clear_setup_log()
    model = TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=64, num_heads=4, vocab_size=128,
        max_seq_len=32, dtype=jnp.float32))
    engine, *_ = ds.initialize(model=model, config={
        "train_batch_size": 16, "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 0, "mesh": {"data": 8}})
    assert names_and_parents(prof.setup_spans()) == [
        ("setup/initialize", None),
        ("setup/state_init", "setup/initialize")]
    assert not prof.builds()["own"].any()
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 128, (16, 32), dtype=np.int32)}
    engine.train_step(batch)
    assert names_and_parents(prof.setup_spans())[2:] == [
        ("setup/build_train_step", None)]
    builds = prof.builds()
    own = builds[builds["own"]]
    assert [str(n) for n in own["fun_name"]] == ["train_step"]
    # the construction is the span; the compile came with the first call
    assert own["span"][0] == -1
    assert own["begin_s"][0] >= prof.setup_spans()["end_s"][2]
    n_builds, n_spans = len(builds), len(prof.setup_spans())
    engine.train_step(batch)
    assert len(prof.builds()) == n_builds
    assert len(prof.setup_spans()) == n_spans


def test_enable_compile_cache_twice_registers_one_listener():
    from jax._src import monitoring
    prof = get_overlap_profiler()
    ds.enable_compile_cache()
    ds.enable_compile_cache()
    listeners = (monitoring.get_event_duration_listeners()
                 + monitoring.get_event_listeners())
    assert sum(getattr(cb, "__self__", None) is prof
               for cb in listeners) == 2      # one pair


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache on a directory of the test's own."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    for k, v in keys.items():
        jax.config.update(k, v)
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cache_reads_off_then_miss_then_hit(persistent_cache):
    prof = get_overlap_profiler()
    # (the log keeps the OLDEST ``SETUP_LOG_CAP`` records of a process: a
    # worker that has built a thousand programs in earlier tests would
    # record none of this one's)
    prof.clear_setup_log()
    prof.listen_for_builds()

    def build():
        # a new function object each time, so JAX traces and lowers it
        # again; the same program text, so the persistent cache knows it
        def cache_probe_program(x):
            return jnp.sin(x) * 3.0 + 1.0
        t0 = time.perf_counter()
        jax.jit(cache_probe_program)(jnp.ones((7,), jnp.float32)
                                     ).block_until_ready()
        got = prof.builds(t0, time.perf_counter())
        return [str(c) for c in
                got[got["fun_name"] == "cache_probe_program"]["cache"]]

    jax.config.update("jax_compilation_cache_dir", None)
    assert build() == ["off"]
    jax.config.update("jax_compilation_cache_dir", str(persistent_cache))
    first, second = build(), build()
    if second == ["miss"] and not any(persistent_cache.iterdir()):
        pytest.skip("XLA:CPU wrote nothing to the persistent cache here")
    assert (first, second) == (["miss"], ["hit"])


# -- the fold, on events made by hand --------------------------------------
def feed(prof, *events):
    for event in events:
        if len(event) == 1:
            prof._on_event(event[0])
        else:
            name, secs, fun = event
            prof._on_duration(name, secs, fun_name=fun)


FOLDS = {
    "whole": (
        [(TRACE, 0.25, "inner"), (TRACE, 1.0, "mine"),
         (LOWER, 2.0, "jit(mine)"), (MISS,), (COMPILE, 4.0, "jit(mine)")],
        ("mine", 1.0, 2.0, 4.0, "miss", True)),
    "fetched": (
        [(TRACE, 1.0, "mine"), (LOWER, 2.0, "jit(mine)"), (HIT,),
         (COMPILE, 0.5, "jit(mine)")],
        ("mine", 1.0, 2.0, 0.5, "hit", True)),
    "no_cache_asked": (
        [(TRACE, 1.0, "theirs"), (LOWER, 2.0, "jit(theirs)"),
         (COMPILE, 4.0, "jit(theirs)")],
        ("theirs", 1.0, 2.0, 4.0, "off", False)),
    "trace_was_cached": (
        [(LOWER, 2.0, "jit(mine)"), (COMPILE, 4.0, "jit(mine)")],
        ("mine", 0.0, 2.0, 4.0, "off", True)),
    "lowered_before_the_listener": (
        [(MISS,), (COMPILE, 4.0, "jit(mine)")],
        ("mine", 0.0, 0.0, 4.0, "off", True)),
    "anothers_trace_is_not_taken": (
        [(TRACE, 9.0, "mine"), (LOWER, 2.0, "jit(other)"),
         (COMPILE, 4.0, "jit(other)")],
        ("other", 0.0, 2.0, 4.0, "off", False)),
    "pmap_wrapper": (
        [(TRACE, 1.0, "mine"), (LOWER, 2.0, "pmap(mine)"),
         (COMPILE, 4.0, "pmap(mine)")],
        ("mine", 1.0, 2.0, 4.0, "off", True)),
}


@pytest.mark.parametrize("case", list(FOLDS))
def test_one_programs_events_fold_into_one_record(case):
    events, want = FOLDS[case]
    prof = OverlapProfiler()
    prof.own_program("mine")
    with prof.setup_span("setup/outer"):
        with prof.setup_span("setup/inner"):
            feed(prof, *events)
        feed(prof, (TRACE, 0.1, "late"), (LOWER, 0.1, "jit(late)"),
             (COMPILE, 0.1, "jit(late)"))
    (rec, late) = prof.builds()
    assert (str(rec["fun_name"]), float(rec["trace_s"]),
            float(rec["lower_s"]), float(rec["compile_s"]),
            str(rec["cache"]), bool(rec["own"])) == want
    spans = prof.setup_spans()
    assert names_and_parents(spans) == [("setup/outer", None),
                                        ("setup/inner", "setup/outer")]
    assert rec["span"] == spans["id"][1] and late["span"] == spans["id"][0]
    assert rec["iteration"] == late["iteration"] == -1
    assert late["cache"] == "off" and not late["own"]
    # by its end, in (t0, t1]
    assert len(prof.builds(rec["end_s"], late["end_s"])) == 1
    assert len(prof.builds(-np.inf, rec["end_s"])) == 1


def test_the_log_stops_at_its_cap_and_keeps_the_oldest():
    prof = OverlapProfiler()
    extra = 10
    for k in range(overlap.SETUP_LOG_CAP + extra):
        with prof.setup_span(f"setup/{k}"):
            pass
        feed(prof, (LOWER, 0.0, f"jit(f{k})"), (COMPILE, 0.0, f"jit(f{k})"))
    spans, builds = prof.setup_spans(), prof.builds()
    assert len(spans) == len(builds) == overlap.SETUP_LOG_CAP
    assert spans["name"][0] == "setup/0" and builds["fun_name"][0] == "f0"
    assert prof.setup_log_dropped == 2 * extra
    assert prof._its.rows is None and not prof.enabled   # nothing allocated
    prof.clear_setup_log()
    assert not len(prof.setup_spans()) and not len(prof.builds())
    assert prof.setup_log_dropped == 0


def test_a_span_that_raises_is_still_closed():
    prof = OverlapProfiler()
    with pytest.raises(RuntimeError):
        with prof.setup_span("setup/outer"):
            with prof.setup_span("setup/fails"):
                raise RuntimeError("boom")
    with prof.setup_span("setup/next"):
        pass
    assert names_and_parents(prof.setup_spans()) == [
        ("setup/outer", None), ("setup/fails", "setup/outer"),
        ("setup/next", None)]


def test_chrome_track_shows_the_set_up_log():
    prof = OverlapProfiler()
    prof.own_program("mine")
    with prof.setup_span("setup/outer"):
        feed(prof, (TRACE, 1.0, "mine"), (LOWER, 2.0, "jit(mine)"), (HIT,),
             (COMPILE, 0.5, "jit(mine)"))
    events = prof.chrome_events(epoch_ns=0, rank=3)
    assert {e["pid"] for e in events} == {overlap.OVERLAP_TRACK_PID_OFFSET
                                          + 3}
    threads = {e["args"]["name"]: e["tid"] for e in events
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(threads) == {"set-up spans", "programs built"}
    span, build = [e for e in events if e["ph"] == "X"]
    assert (span["name"], span["tid"], span["cat"]) == (
        "setup/outer", threads["set-up spans"], "setup")
    assert span["args"] == {"id": 0, "parent": -1}
    assert (build["name"], build["tid"]) == ("mine",
                                             threads["programs built"])
    assert build["args"] == {
        "trace_ms": 1000.0, "lower_ms": 2000.0, "compile_ms": 500.0,
        "cache": "hit", "span": 0, "iteration": -1, "own": True}
    assert span["ts"] <= build["ts"] + 3.5e6 + 1.0    # begin_s reaches back


def test_a_set_up_span_is_a_trace_span_when_the_tracer_is_on(tmp_path):
    from deepspeed_tpu.observability import get_tracer
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    try:
        seen = []
        real = tracer.span

        def span(name, cat="", **args):
            seen.append((name, cat))
            return real(name, cat, **args)
        tracer.span = span
        with OverlapProfiler().setup_span("setup/outer"):
            pass
    finally:
        del tracer.span
        tracer.enabled = was
    assert seen == [("setup/outer", "setup")]
