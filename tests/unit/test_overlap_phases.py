"""The serving iteration measured from inside (ISSUE 24): the overlap
profiler's five phases, its dispatch and row counters, the request ring
and the ``iterations`` / ``requests`` accessors, on a tiny CPU engine.
docs/observability.md "Host/device overlap profiler"."""
import glob
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import RequestStatus
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability import get_overlap_profiler, overlap
from deepspeed_tpu.observability.overlap import (ITERATION_DTYPE, PHASES,
                                                 REQUEST_DTYPE,
                                                 OverlapProfiler)

pytestmark = [pytest.mark.observability]

SLOTS, CHUNK = 4, 16


def build_engine(draft=False):
    def cfg(layers):
        return gpt2_config("125m", num_layers=layers, d_model=32,
                           num_heads=4, vocab_size=256, max_seq_len=64,
                           dtype=jnp.float32)
    eng = ds.init_inference(
        TransformerLM(cfg(2)),
        config={"dtype": "float32", "max_out_tokens": 64,
                "temperature": 0.0, "replace_with_kernel_inject": False,
                "serving": {"enabled": True, "kv_block_size": 8,
                            "num_kv_blocks": 48,
                            "max_batch_slots": SLOTS,
                            "prefill_chunk_tokens": CHUNK,
                            "prefix_cache": False,
                            **({"spec_k": 1} if draft else {})}})
    if draft:
        dm = TransformerLM(cfg(1))
        engine = eng.serving_engine(
            draft_model=dm, draft_params=dm.init(jax.random.PRNGKey(1)))
    else:
        engine = eng.serving_engine()
    engine.submit([1, 2, 3], max_new_tokens=2)       # build the program
    engine.run()
    return engine


@pytest.fixture(scope="module")
def srv():
    return build_engine()


@pytest.fixture
def ovl():
    prof = get_overlap_profiler()
    prof.reset()
    prof.configure(enabled=True)
    yield prof
    prof.configure(enabled=False)
    prof.reset()


def drain(engine):
    while engine.step():
        pass


def prompt(n, base=1):
    return [base + i for i in range(n)]


def test_five_phases_partition_every_iteration(srv, ovl):
    t0 = time.perf_counter()
    srv.submit(prompt(20), max_new_tokens=4)
    srv.submit(prompt(37, 50), max_new_tokens=4)
    lasts = []
    while True:
        more = srv.step()
        lasts.append(ovl.last())
        if not more:
            break
    its, complete = ovl.iterations(t0, time.perf_counter())
    assert complete and len(its) == len(lasts) >= 4
    assert its.dtype == ITERATION_DTYPE
    assert list(its["n"]) == list(range(len(its)))
    for rec, last in zip(its, lasts):
        total = rec["end_s"] - rec["begin_s"]
        assert sum(rec[f"{p}_s"] for p in PHASES) == pytest.approx(
            total, abs=1e-7)
        # what the benchmark's runner reads for sat.host_plan_share
        assert last["n"] == rec["n"]
        assert last["total_s"] == pytest.approx(total)
        assert last["host_plan_s"] == pytest.approx(
            rec["plan_s"] + rec["operands_s"] + rec["apply_s"])
        assert last["device_wait_s"] == rec["device_wait_s"] > 0
    # iterations do not overlap: the caller's time lies between them
    assert np.all(its["begin_s"][1:] >= its["end_s"][:-1])


def test_chunk_remainder_records_both_dispatches(srv, ovl):
    """A 20-token prompt leaves a 4-token remainder; with another request
    prefilling, the iteration that takes the remainder dispatches the
    mixed program a second time for the 12 tokens left of its budget."""
    t0 = time.perf_counter()
    srv.submit(prompt(20), max_new_tokens=2)
    srv.submit(prompt(30, 100), max_new_tokens=2)
    drain(srv)
    its, _ = ovl.iterations(t0, time.perf_counter())
    assert list(its["dispatches"][:2]) == [1, 2]
    first, second = its[0], its[1]
    assert (first["chunk_rows"], first["decode_rows"]) == (CHUNK, 0)
    assert first["rows_computed"] == SLOTS + CHUNK
    # 4 (the remainder) + 12 (the next prompt's head), no decode yet
    assert second["chunk_rows"] == 4 + 12 and second["decode_rows"] == 0
    assert second["rows_computed"] == 2 * (SLOTS + CHUNK)
    # what crossed: two host arrays in and one result array out a dispatch
    assert (first["host_arrays_in"], first["host_reads_out"]) == (2, 1)
    assert (second["host_arrays_in"], second["host_reads_out"]) == (4, 2)
    # the second dispatch re-entered plan .. apply: still a partition
    assert sum(second[f"{p}_s"] for p in PHASES) == pytest.approx(
        second["end_s"] - second["begin_s"], abs=1e-7)


@pytest.mark.parametrize("draft", [False, True], ids=["ahead", "draft"])
def test_the_next_dispatch_is_enqueued_before_this_one_is_read(
        srv, ovl, monkeypatch, draft):
    """The order of the loop itself (ISSUE 37): in a steady run the
    launch of dispatch k+1 comes BEFORE the read of k's result, so the
    device finds its next program queued — every dispatch but the first
    counts as ``ahead_dispatches`` — and ``device_wait`` is the wait for
    k with k+1 behind it.  With a draft armed the loop cannot plan from
    counts: launch, read, launch, read, and the counter stays 0."""
    engine = build_engine(draft=True) if draft else srv
    log = []
    launch, read = engine._launch, np.asarray

    def launching(operands):
        log.append("launch")
        return launch(operands)

    def reading(a, *args, **kw):
        if isinstance(a, jax.Array):
            log.append("read")
        return read(a, *args, **kw)

    monkeypatch.setattr(engine, "_launch", launching)
    monkeypatch.setattr(np, "asarray", reading)
    t0 = time.perf_counter()
    req = engine.submit(prompt(9, 30), max_new_tokens=7)
    drain(engine)
    monkeypatch.undo()
    its, _ = ovl.iterations(t0, time.perf_counter())
    assert req.status is RequestStatus.OK and len(req.output) == 7
    n = int(its["dispatches"].sum())
    assert log.count("launch") == log.count("read") == n
    launched_before_read = [log[:at].count("launch")
                            for at, what in enumerate(log) if what == "read"]
    if draft:
        assert launched_before_read == list(range(1, n + 1))
        assert its["ahead_dispatches"].sum() == 0
    else:
        # k+1 is on the device's queue when k is read; the last has no
        # successor (``max_new_tokens`` ended the request by count)
        assert launched_before_read == list(range(2, n + 1)) + [n]
        assert its["ahead_dispatches"].sum() == n - 1
        # a record holds the enqueue of one dispatch and the wait for
        # the one before it
        assert np.all(its["enqueue_s"][:-1] > 0)
    assert np.all(its["device_wait_s"] > 0)
    assert its["void_rows"].sum() == 0
    assert engine.decode_builds == 2


def test_the_benchmarks_reader_of_the_ahead_counter(srv, ovl, monkeypatch):
    """``*.ahead_dispatch_share`` (benchmark/readers/program_ahead.py) is
    the in-window ``ahead_dispatches`` over ``dispatches``; on a program
    whose records have no such counter — the parent the driver lays this
    benchmark over — it reads nothing and raises nothing."""
    from benchmark.lib import program
    from benchmark.readers import program_ahead
    t0 = time.perf_counter()
    srv.submit(prompt(9, 60), max_new_tokens=5)
    drain(srv)
    obs = {"window": (t0, time.perf_counter())}
    its, _ = ovl.iterations(*obs["window"])
    n = int(its["dispatches"].sum())
    assert n == 5 and program_ahead.read(obs) == pytest.approx(
        100.0 * (n - 1) / n)
    assert program_ahead.read({"window": (0.0, t0)}) is None   # no records
    old = [name for name in its.dtype.names
           if name not in ("ahead_dispatches", "void_rows")]
    monkeypatch.setattr(program, "records", lambda obs, what: its[old])
    assert program_ahead.read(obs) is None


def test_useful_and_computed_rows_of_a_known_batch(srv, ovl):
    """Three requests of one whole 16-token chunk each and 3 new tokens:
    a prefill iteration each (the first token rides the chunk), the later
    ones with the earlier requests decoding beside the chunk."""
    t0 = time.perf_counter()
    for k in range(3):
        srv.submit(prompt(CHUNK, 10 + 40 * k), max_new_tokens=3)
    drain(srv)
    its, _ = ovl.iterations(t0, time.perf_counter())
    assert list(its["dispatches"]) == [1] * 5
    assert list(its["chunk_rows"]) == [16, 16, 16, 0, 0]
    assert list(its["decode_rows"]) == [0, 1, 2, 2, 1]
    # the rows of the program that ran: the mixed shape while a chunk
    # rode, the decode-only shape (no chunk lane) after
    assert list(its["rows_computed"]) == [SLOTS + CHUNK] * 3 + [SLOTS] * 2
    assert np.all(its["host_arrays_in"] == 2)
    assert np.all(its["host_reads_out"] == 1)
    # every request greedy: the sampler's argmax-only side, every dispatch
    assert not its["sampled_rows"].any() and not its["filtered_rows"].any()
    useful = (its["decode_rows"] + its["chunk_rows"]).sum()
    assert useful / its["rows_computed"].sum() == pytest.approx(54 / 68)


def test_sampler_rows_of_a_known_mix(srv, ovl):
    """The same three requests, but the second samples and the third
    samples through a nucleus filter: the chunk's row counts in the
    iteration that prefills it (its first token is drawn there), a
    decode row in every iteration after."""
    t0 = time.perf_counter()
    for k, samp in enumerate((dict(), dict(temperature=0.8, seed=3),
                              dict(temperature=0.7, top_p=0.9, seed=4))):
        srv.submit(prompt(CHUNK, 10 + 40 * k), max_new_tokens=3, **samp)
    drain(srv)
    its, _ = ovl.iterations(t0, time.perf_counter())
    assert list(its["decode_rows"]) == [0, 1, 2, 2, 1]
    assert list(its["sampled_rows"]) == [0, 1, 2, 2, 1]
    assert list(its["filtered_rows"]) == [0, 0, 1, 1, 1]
    assert srv.decode_builds == 2


@pytest.mark.parametrize("cancel", (False, True), ids=("ok", "cancelled"))
def test_request_ring_holds_the_requests_own_stamps(srv, ovl, cancel):
    t0 = time.perf_counter()
    req = srv.submit(prompt(20, 7), max_new_tokens=6)
    srv.step()
    srv.step()                      # second chunk: the first token
    if cancel:
        assert srv.cancel(req)
    drain(srv)
    recs, complete = ovl.requests(t0, time.perf_counter())
    assert complete and recs.dtype == REQUEST_DTYPE and len(recs) == 1
    rec = recs[0]
    assert req.status == (RequestStatus.CANCELLED if cancel
                          else RequestStatus.OK)
    for key in ("submit_time", "admit_time", "first_token_time",
                "finish_time"):
        assert rec[key] == getattr(req, key)
    assert (rec["submit_time"] <= rec["admit_time"]
            <= rec["first_token_time"] <= rec["finish_time"])
    assert len(req.output) == (1 if cancel else 6)
    # the stamps fall in the iterations that made them: admission in the
    # first, the first token (20 tokens, two chunks) in the second
    its, _ = ovl.iterations(t0, time.perf_counter())
    assert its[0]["begin_s"] <= rec["admit_time"] <= its[0]["end_s"]
    assert its[1]["begin_s"] <= rec["first_token_time"] <= its[1]["end_s"]


def test_a_request_cancelled_while_waiting_has_no_admit_stamp(srv, ovl):
    t0 = time.perf_counter()
    req = srv.submit(prompt(5, 3), max_new_tokens=2)
    assert srv.cancel(req)
    drain(srv)
    (rec,), _ = ovl.requests(t0, time.perf_counter())
    assert req.admit_time is None and np.isnan(rec["admit_time"])
    assert np.isnan(rec["first_token_time"])
    assert rec["finish_time"] == req.finish_time >= rec["submit_time"]


def test_admit_time_is_stamped_with_the_profiler_off(srv):
    assert not get_overlap_profiler().enabled
    req = srv.submit(prompt(6, 9), max_new_tokens=2)
    drain(srv)
    assert req.submit_time <= req.admit_time <= req.first_token_time


@pytest.mark.parametrize("ring", ("iterations", "requests"))
def test_a_wrapped_ring_reports_incomplete(ring):
    prof = OverlapProfiler(capacity=4)
    prof.configure(enabled=True)

    class Req:
        admit_time = first_token_time = None

    def write(k):
        if ring == "iterations":
            prof.observe("serving", total_s=0.5, enqueue_s=0.1,
                         wait_s=0.3, t0_ns=int(k * 1e9))
        else:
            req = Req()
            req.submit_time, req.finish_time = k + 0.25, k + 0.5
            prof.note_request(req)

    read = getattr(prof, ring)
    for k in range(4):
        write(k)
    recs, complete = read(0.0, 10.0)          # ends at 0.5 .. 3.5
    assert complete and len(recs) == 4
    write(4)                                   # overwrites the first
    recs, complete = read(0.0, 10.0)
    assert not complete and len(recs) == 4
    # the oldest record still held ended at 1.5: a window that opens at
    # or after it lost nothing
    recs, complete = read(1.5, 10.0)
    assert complete and len(recs) == 3
    recs, complete = read(1.0, 10.0)
    assert complete is False
    # clipping: (t0, t1] on the record's end (a request's submit)
    recs, complete = read(2.0, 3.6)
    assert complete and len(recs) == 2
    assert prof.recorded == (4 if ring == "iterations" else 0)


def test_accessors_before_anything_was_recorded():
    prof = OverlapProfiler(capacity=4)
    for read, dtype in ((prof.iterations, ITERATION_DTYPE),
                        (prof.requests, REQUEST_DTYPE)):
        recs, complete = read(0.0, 1e9)
        assert complete and len(recs) == 0 and recs.dtype == dtype
    assert prof.last() is None


def test_train_observe_maps_onto_the_phases():
    prof = OverlapProfiler(capacity=4)
    prof.configure(enabled=True)
    prof.observe("train", total_s=0.010, enqueue_s=0.002, wait_s=0.005,
                 t0_ns=1_000_000_000)
    (rec,), _ = prof.iterations(0.0, 10.0)
    assert rec["kind"] == "train" and rec["dispatches"] == 1
    assert (rec["begin_s"], rec["end_s"]) == pytest.approx((1.0, 1.010))
    assert [rec[f"{p}_s"] for p in PHASES] == pytest.approx(
        [0.003, 0.0, 0.002, 0.005, 0.0])


@pytest.mark.parametrize("crossed", ((), (2, 1), (2, 1, 5, 3)),
                         ids=("rows", "crossed", "sampler"))
def test_count_dispatch_adds_up_over_an_iteration(crossed):
    """Two dispatches in one iteration: every counter is their sum, in
    the record, in ``last()`` and on the Chrome track; a caller that
    counts rows alone (the benchmark's reader tests) leaves the two
    host-traffic counters and the sampler's two at 0, as a training step
    does."""
    prof = OverlapProfiler(capacity=4)
    prof.configure(enabled=True)
    prof.begin()
    for rows in ((3, 16, 20), (0, 4, 20)):
        prof.mark(overlap.OPERANDS)
        prof.mark(overlap.APPLY)
        prof.count_dispatch(*rows, *crossed)
        prof.mark(overlap.PLAN)
    prof.end()
    want = dict(zip(overlap.COUNTERS, (2, 3, 20, 40) + tuple(
        2 * c for c in crossed + (0, 0, 0, 0)[len(crossed):])))
    assert list(want)[-2:] == ["sampled_rows", "filtered_rows"]
    (rec,), _ = prof.iterations(0.0, time.perf_counter())
    last = prof.last()
    (track,) = [e for e in prof.chrome_events(0, 0) if e["ph"] == "X"]
    for name, value in want.items():
        assert rec[name] == last[name] == track["args"][name] == value
    prof.observe("train", total_s=0.01, enqueue_s=0.002, wait_s=0.005)
    assert not any(prof.last()[name] for name in list(want)[4:])


def test_disabled_step_touches_no_profiler_clock_or_annotation(
        srv, monkeypatch):
    """With the profiler off a serving step reads no clock and builds no
    annotation for it: the same traffic reads ``perf_counter`` equally
    often off and on, and everything the profiler adds when on is
    ``perf_counter_ns`` reads and ``TraceAnnotation``s of its own."""
    prof = get_overlap_profiler()
    counts = {"pc": 0, "ns": 0, "ann": 0}
    real_pc, real_ns = time.perf_counter, time.perf_counter_ns

    def pc():
        counts["pc"] += 1
        return real_pc()

    def ns():
        counts["ns"] += 1
        return real_ns()

    class Annotation(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            counts["ann"] += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(time, "perf_counter", pc)
    monkeypatch.setattr(time, "perf_counter_ns", ns)
    monkeypatch.setattr(prof, "_annotation", Annotation)

    def traffic(base):
        counts.update(pc=0, ns=0, ann=0)
        srv.submit(prompt(20, base), max_new_tokens=3)
        srv.submit(prompt(9, base + 30), max_new_tokens=3)
        drain(srv)
        return dict(counts)

    assert not prof.enabled
    off = traffic(1)
    assert off["ns"] == 0 and off["ann"] == 0 and off["pc"] > 0
    prof.reset()
    prof.enabled = True          # rings not allocated: nothing is kept
    try:
        on = traffic(101)
    finally:
        prof.enabled = False
        prof.reset()
    assert on["pc"] == off["pc"]
    steps = prof.iteration + 1
    assert on["ann"] >= 6 * steps and on["ns"] >= 6 * steps


def test_phase_annotations_lie_inside_the_callers_span(srv, ovl, tmp_path):
    """Under ``jax.profiler.trace`` the iteration and its five phases are
    ``TraceAnnotation``s on the caller's host line, inside the caller's
    own annotation (the benchmark client's ``serve_step``)."""
    from jax.profiler import ProfileData
    srv.submit(prompt(20, 60), max_new_tokens=3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        more = True
        while more:
            with jax.profiler.TraceAnnotation("outer_step"):
                more = srv.step()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    wanted = {"outer_step", overlap.ITERATION_SPAN, *overlap.PHASE_SPANS}
    lines = [[e for e in line.events if e.name in wanted]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    (events,) = [evs for evs in lines if evs]      # one host thread
    spans = {name: [(e.start_ns, e.start_ns + e.duration_ns, e)
                    for e in events if e.name == name] for name in wanted}
    n_it = ovl.iteration + 1
    assert len(spans["outer_step"]) == len(
        spans[overlap.ITERATION_SPAN]) == n_it >= 3
    numbers = [dict(e.stats)["n"] for _, _, e in
               spans[overlap.ITERATION_SPAN]]
    assert numbers == list(range(n_it))
    for (o0, o1, _), (i0, i1, _) in zip(spans["outer_step"],
                                        spans[overlap.ITERATION_SPAN]):
        assert o0 <= i0 and i1 <= o1
        inside = [(s, e) for name in overlap.PHASE_SPANS
                  for s, e, _ in spans[name] if i0 <= s and e <= i1]
        # consecutive and exclusive: sorted by start, none overlaps
        inside.sort()
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))
        assert len(inside) >= 2
    # a dispatching iteration shows all five, in order
    i0, i1, _ = spans[overlap.ITERATION_SPAN][0]
    firsts = [min(s for s, e, _ in spans[name] if i0 <= s and e <= i1)
              for name in overlap.PHASE_SPANS]
    assert firsts == sorted(firsts)
    assert sum(len(spans[name]) for name in overlap.PHASE_SPANS) >= \
        5 * (n_it - 1)


# -- device scopes: a program's compiled text -> its layer names ------------
def _program(*instructions, fused=()):
    """Optimized-HLO-shaped text: an entry computation of
    ``instructions`` (``name shape opcode(operands) | op_name``) after a
    fused computation of ``fused``."""
    def line(spec):
        text, _, op_name = spec.partition(" | ")
        meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
            if op_name else ""
        return f"  {text}{meta}"
    return "\n".join(
        ["HloModule jit_step, is_scheduled=true", "",
         "%fused_computation.1 (param_0.1: bf16[8,8]) -> bf16[8,8] {"]
        + [line(s) for s in fused]
        + ["}", "", "ENTRY %main.9 (Arg_0.1: bf16[8,8]) -> bf16[8,8] {"]
        + [line(s) for s in instructions] + ["}", ""])


_DOT = "%fusion.7 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%p), " \
       "kind=kOutput, calls=%fused_computation.1"


@pytest.mark.parametrize("programs,key,want", [
    # jvp(..) / transpose(..) are looked through
    ([_program(_DOT + " | jit(step)/transpose(jvp(head))/dot_general")],
     "%fusion.7 = bf16[8,8]", ("head", False)),
    ([_program(_DOT + " | jit(step)/jvp()/while/body/closed_call/mlp/dot")],
     "%fusion.7 = bf16[8,8]", ("mlp", False)),
    # nested scopes: the innermost declared one owns the operation
    ([_program(_DOT + " | jit(step)/optimizer/zero_comm/convert")],
     "%fusion.7 = bf16[8,8]", ("zero_comm", False)),
    ([_program(_DOT + " | jit(step)/shared_expert/jit(silu)/mul")],
     "%fusion.7 = bf16[8,8]", ("shared_expert", False)),
    # a jitted function's own name is no scope
    ([_program(_DOT + " | jit(loss)/jit(norm)/mul",
               "%copy.1 = bf16[8,8]{1,0} copy(%fusion.7) | jit(f)/mlp/x")],
     "%fusion.7 = bf16[8,8]", ("mlp", False)),     # ... it feeds the mlp
    # a backward pass's recomputation of its forward
    ([_program(_DOT + " | jit(step)/transpose(jvp())/while/body/"
               "closed_call/checkpoint/rematted_computation/mlp/dot")],
     "%fusion.7 = bf16[8,8]", ("mlp", True)),
    ([_program(_DOT + " | jit(step)/transpose(jvp())/while/body/"
               "closed_call/checkpoint/mlp/dot")],
     "%fusion.7 = bf16[8,8]", ("mlp", False)),
    # two loaded programs disagree about one key
    ([_program(_DOT + " | jit(step)/mlp/dot"),
      _program(_DOT + " | jit(step)/head/dot")],
     "%fusion.7 = bf16[8,8]", ("ambiguous", False)),
    ([_program(_DOT + " | jit(step)/mlp/dot"),
      _program(_DOT + " | jit(step)/mlp/dot")],
     "%fusion.7 = bf16[8,8]", ("mlp", False)),
    # a program that declares no scope is none of the model's
    ([_program(_DOT + " | jit(step)/mlp/dot"),
      _program(_DOT + " | jit(convert_element_type)/convert")],
     "%fusion.7 = bf16[8,8]", ("mlp", False)),
    # no metadata, and feeding nothing that has a scope
    ([_program(_DOT, "%copy.1 = bf16[8,8]{1,0} copy(%p) | jit(f)/mlp/x")],
     "%fusion.7 = bf16[8,8]", ("unnamed", False)),
    # no scope of its own: what it feeds, if that is ONE scope — through
    # an instruction that is no event, and tuple shapes with layouts
    ([_program("%slice.3 = (bf16[1,8,8]{2,1,0:T(8,128)(2,1)S(1)}, u32[]"
               "{:S(2)}) fusion(%w), kind=kLoop, calls=%fused_computation.1"
               " | jit(step)/while/body/dynamic_slice",
               "%bitcast.2 = bf16[8,8]{1,0} bitcast(%slice.3)",
               _DOT.replace("(%p)", "(%p, %bitcast.2)")
               + " | jit(step)/while/body/closed_call/attn_proj/dot")],
     "%slice.3 = (bf16[1,8,8], u32[])", ("attn_proj", False)),
    ([_program("%copy.5 = bf16[8,8]{1,0} copy(%w)",
               _DOT.replace("(%p)", "(%copy.5)") + " | jit(step)/mlp/dot",
               "%fusion.8 = bf16[8,8]{1,0} fusion(%copy.5), kind=kLoop, "
               "calls=%fused_computation.1 | jit(step)/head/dot")],
     "%copy.5 = bf16[8,8]", ("unnamed", False)),
    # an instruction inside a fused computation is never an event
    ([_program(_DOT + " | jit(step)/mlp/dot", fused=[
        "%convolution.2 = bf16[8,8]{1,0} convolution(%param_0.1, "
        "%param_0.1), dim_labels=bf_io->bf | jit(step)/mlp/dot"])],
     "%convolution.2 = bf16[8,8]", None),
], ids=["transpose_jvp", "jvp_scan", "innermost", "through_jit",
        "jit_name_is_no_scope", "recompute", "checkpoint_backward",
        "ambiguous", "programs_agree", "foreign_program", "unnamed",
        "feeds_one_scope", "feeds_two_scopes", "fused_is_no_event"])
def test_scope_table_from_compiled_text(programs, key, want):
    table = overlap.scope_table(programs)
    assert table.get(key) == want
    # an event prints operands with their shapes and no metadata: same key
    event = "%fusion.7 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,8]" \
            "{1,0:T(8,128)(2,1)} %p), kind=kOutput, calls=%fused_computation.1"
    assert overlap.scope_key(event) == "%fusion.7 = bf16[8,8]"
    assert overlap.scope_key("jit_step(1234)") is None


@pytest.mark.parametrize("path,plain,laned", [
    ("jit(step)/while/body/kda_scan/decode/pallas_call",
     "kda_scan", "kda_scan/decode"),
    ("jit(step)/kda_scan/chunk/jit(_einsum)/dot_general",
     "kda_scan", "kda_scan/chunk"),
    # a lane is named directly inside its scope, and only there
    ("jit(step)/kda_scan/dot_general", "kda_scan", "kda_scan"),
    ("jit(step)/kda_scan/while/body/chunk/mul", "kda_scan", "kda_scan"),
    ("jit(step)/kda_scan/jit(f)/chunk/mul", "kda_scan", "kda_scan/chunk"),
    ("jit(step)/decode/mlp/dot_general", "mlp", "mlp"),
    ("jit(step)/chunk/add", "unnamed", "unnamed"),
], ids=["decode", "chunk", "no_lane", "not_directly_inside", "through_jit",
        "lane_outside", "lane_alone"])
def test_a_scope_names_its_lanes_for_a_reader_that_asks(path, plain, laned):
    assert overlap.scope_of(path) == (plain, False)
    assert overlap.scope_of(path, lanes=True) == (laned, False)
    text = _program(_DOT + " | " + path)
    if plain != "unnamed":
        assert overlap.scope_table([text])["%fusion.7 = bf16[8,8]"] == (
            plain, False)
        assert overlap.scope_table([text], lanes=True)[
            "%fusion.7 = bf16[8,8]"] == (laned, False)


def test_program_scopes_reads_the_loaded_step_program(srv):
    """On request, from the executables the process has loaded: the tiny
    engine's two step shapes name their layers, and the vocabulary is
    the declared one."""
    srv.submit(np.arange(3, dtype=np.int32), max_new_tokens=2)
    srv.run()
    found = {scope for scope, _ in
             get_overlap_profiler().program_scopes().values()}
    assert {"attn_proj", "attn_kernel", "mlp", "head"} <= found
    assert found <= set(overlap.SCOPES) | {overlap.UNNAMED,
                                           overlap.AMBIGUOUS}
    # PR 48: + four of a hybrid block; PR 56: + two of a delta-rule layer;
    # PR 58: + the block lane's unmasking
    assert len(overlap.SCOPES) <= 26
