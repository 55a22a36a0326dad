"""Observability suite: span tracer, metrics registry, exporters, and the
instrumented training loop (deepspeed_tpu/observability/,
docs/observability.md).

The integration test pins the PR's acceptance contract: a CPU-backend
training loop with the ``observability`` block enabled produces a
Perfetto-loadable Chrome trace with spans from ≥4 subsystems plus a
Prometheus textfile carrying the step-time histogram and resilience
counters; with the block disabled the span path is a shared no-op.
"""
import json
import math
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu import observability as obs
from deepspeed_tpu.observability.flight_recorder import FlightRecorder
from deepspeed_tpu.observability.metrics import (MetricsRegistry,
                                                 sanitize_name,
                                                 tenant_metric_name)
from deepspeed_tpu.observability.request_trace import (
    REQUEST_TRACK_PID_OFFSET, RequestTraceRecorder, get_request_tracer)
from deepspeed_tpu.observability.slo import (KIND_ITL, KIND_TTFT,
                                             SloMonitor)
from deepspeed_tpu.observability.tracer import NULL_SPAN, SpanTracer
from deepspeed_tpu.models import TransformerLM, gpt2_config

pytestmark = pytest.mark.observability


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------
class TestSpanTracer:
    def test_disabled_path_is_shared_noop(self):
        tr = SpanTracer(capacity=16)
        s1 = tr.span("a/b")
        s2 = tr.span("c/d", attr=1)
        # no span objects allocated when off: the SAME singleton each time
        assert s1 is NULL_SPAN and s2 is NULL_SPAN
        with s1:
            s1.set(x=1)
        assert tr.recorded == 0 and tr.dropped == 0

    def test_module_trace_span_disabled_identity(self):
        obs.get_tracer().configure(enabled=False)
        assert obs.trace_span("x/y") is NULL_SPAN

    def test_records_and_ring_wraparound(self, tmp_path):
        tr = SpanTracer()
        tr.configure(enabled=True, capacity=8, output_dir=str(tmp_path))
        for i in range(20):
            with tr.span("t/span", i=i):
                pass
        assert tr.recorded == 8
        assert tr.dropped == 12
        path = tr.flush()
        with open(path) as f:
            doc = json.load(f)
        xev = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(xev) == 8
        # oldest spans were overwritten: only i=12..19 survive, in order
        assert [e["args"]["i"] for e in xev] == list(range(12, 20))
        assert doc["otherData"]["dropped_spans"] == 12

    def test_chrome_trace_schema(self, tmp_path):
        """The exported JSON validates against the Chrome trace-event
        contract Perfetto requires: X events with name/ph/pid/tid/ts/dur,
        M metadata for process and thread names."""
        tr = SpanTracer()
        tr.configure(enabled=True, capacity=32, output_dir=str(tmp_path),
                     rank=3)
        with tr.span("outer/span", step=1):
            with tr.span("inner/span"):
                pass
        path = tr.flush()
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        events = doc["traceEvents"]
        xev = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in xev} == {"outer/span", "inner/span"}
        for e in xev:
            for key in ("name", "ph", "pid", "tid", "ts", "dur"):
                assert key in e, f"missing {key} in {e}"
            assert e["pid"] == 3
            assert e["ts"] >= 0 and e["dur"] >= 0
        # inner committed first (exit order), nested inside outer's window
        inner = next(e for e in xev if e["name"] == "inner/span")
        outer = next(e for e in xev if e["name"] == "outer/span")
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        meta = {e["name"] for e in events if e["ph"] == "M"}
        assert "process_name" in meta and "thread_name" in meta

    def test_thread_tracks(self, tmp_path):
        import threading
        tr = SpanTracer()
        tr.configure(enabled=True, capacity=32, output_dir=str(tmp_path))

        def work():
            with tr.span("w/span"):
                pass
        t = threading.Thread(target=work, name="swap-worker-0")
        t.start()
        t.join()
        with tr.span("m/span"):
            pass
        with open(tr.flush()) as f:
            doc = json.load(f)
        thread_names = {e["args"]["name"] for e in doc["traceEvents"]
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "swap-worker-0" in thread_names
        tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert len(tids) == 2   # two tracks

    def test_flush_sync_routes_host_transfer(self, tmp_path):
        tr = SpanTracer()
        tr.configure(enabled=True, capacity=4, output_dir=str(tmp_path))
        with tr.span("s/x"):
            pass
        # device value joined at the flush boundary (host_transfer path)
        path = tr.flush(sync=jnp.ones(()))
        assert os.path.exists(path)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_gauge_types(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total", help="h")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        assert reg.counter("c_total") is c      # get-or-create
        g = reg.gauge("g_now")
        g.set(7.0)
        assert g.value == 7.0
        with pytest.raises(TypeError):
            reg.gauge("c_total")                # kind mismatch

    def test_histogram_buckets(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        cum = dict(h.cumulative())
        assert cum[0.1] == 1 and cum[1.0] == 3 and cum[10.0] == 4
        assert cum[math.inf] == 5
        assert h.count == 5
        assert h.sum == pytest.approx(56.05)
        assert h.value == pytest.approx(56.05 / 5)

    def test_prometheus_export_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("dstpu_x_total", help="things").inc(4)
        h = reg.histogram("dstpu_t_seconds", buckets=(1.0, 2.0))
        h.observe(1.5)
        path = reg.export_prometheus(str(tmp_path / "m.prom"))
        text = open(path).read()
        assert "# TYPE dstpu_x_total counter" in text
        assert "dstpu_x_total 4.0" in text
        assert 'dstpu_t_seconds_bucket{le="1.0"} 0' in text
        assert 'dstpu_t_seconds_bucket{le="2.0"} 1' in text
        assert 'dstpu_t_seconds_bucket{le="+Inf"} 1' in text
        assert "dstpu_t_seconds_count 1" in text

    def test_json_export_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.gauge("depth").set(3.0)
        reg.histogram("lat", buckets=(1.0,)).observe(0.5)
        path = reg.export_json(str(tmp_path / "m.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["depth"] == {"kind": "gauge", "value": 3.0}
        assert doc["lat"]["count"] == 1
        assert doc["lat"]["buckets"][-1][0] == "+Inf"

    def test_to_events_for_monitor(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc(2)
        reg.gauge("b").set(1.5)
        events = reg.to_events(step=7)
        assert ("Metrics/a_total", 2.0, 7) in events
        assert ("Metrics/b", 1.5, 7) in events

    def test_collectors_keyed_replacement(self):
        reg = MetricsRegistry()
        calls = []
        reg.set_collector("engine", lambda: calls.append("old"))
        reg.set_collector("engine", lambda: calls.append("new"))
        reg.collect()
        assert calls == ["new"]       # re-registering replaced, not stacked

    def test_sanitize_name(self):
        assert sanitize_name("zero/nvme_write") == "zero_nvme_write"
        assert sanitize_name("1bad") == "_1bad"


# ---------------------------------------------------------------------------
# histogram quantiles + exemplars (satellites)
# ---------------------------------------------------------------------------
class TestHistogramQuantiles:
    def test_interpolated_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_seconds", buckets=(0.1, 1.0, 10.0))
        for _ in range(9):
            h.observe(0.05)
        h.observe(5.0)
        # p50: target 5 of 10 falls in the first bucket (9 obs, bound
        # 0..0.1) -> 0.1 * 5/9; p99: target 9.9 lands in (1.0, 10.0]
        assert h.quantile(0.50) == pytest.approx(0.1 * 5 / 9)
        assert h.quantile(0.99) == pytest.approx(1.0 + 9.0 * 0.9)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_quantile_edge_cases(self):
        h = MetricsRegistry().histogram("e", buckets=(1.0, 2.0))
        assert h.quantile(0.5) == 0.0          # empty
        h.observe(100.0)                       # +inf bucket only
        # Prometheus semantics: the +inf bucket clamps to the highest
        # finite bound rather than inventing a value
        assert h.quantile(0.99) == 2.0

    def test_exporters_carry_quantiles(self, tmp_path):
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", buckets=(1.0, 2.0))
        for v in (0.5, 1.5, 1.5, 1.5):
            h.observe(v)
        prom = reg.to_prometheus()
        for tag in ("p50", "p95", "p99"):
            assert f"lat_seconds_{tag} " in prom
        doc = reg.to_json()["lat_seconds"]
        assert doc["p50"] == pytest.approx(h.quantile(0.5))
        assert doc["p99"] == pytest.approx(h.quantile(0.99))

    def test_exemplars_newest_wins_and_export(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds", buckets=(1.0, 2.0))
        h.observe(0.5, exemplar="r0-000001")
        h.observe(0.6, exemplar="r0-000002")   # same bucket: newest wins
        h.observe(1.5)                         # no exemplar
        ex = h.exemplars()
        assert ex == {0: ("r0-000002", 0.6)}
        prom = reg.to_prometheus()
        assert '# {trace_id="r0-000002"} 0.6' in prom
        # the exemplar rides ONLY its own bucket line
        assert prom.count("trace_id=") == 1
        doc = reg.to_json()["t_seconds"]
        assert doc["exemplars"]["1.0"]["trace_id"] == "r0-000002"

    def test_no_exemplars_is_byte_identical_default(self):
        """Histograms that never see an exemplar export exactly the
        pre-exemplar textfile shape — no storage, no suffix."""
        reg = MetricsRegistry()
        h = reg.histogram("plain_seconds", buckets=(1.0,))
        h.observe(0.5)
        assert h._exemplars is None            # lazily allocated: never
        assert "trace_id" not in reg.to_prometheus()
        assert "exemplars" not in reg.to_json()["plain_seconds"]


# ---------------------------------------------------------------------------
# dynamic metric-name sanitization (satellite)
# ---------------------------------------------------------------------------
class TestTenantMetricName:
    def test_clean_name_passes_through(self):
        assert tenant_metric_name("dstpu_serving_tenant", "interactive") \
            == "dstpu_serving_tenant_interactive"
        assert tenant_metric_name("dstpu_slo_tenant", "a", "ttft") \
            == "dstpu_slo_tenant_a_ttft"

    def test_hostile_name_sanitized_with_checksum(self):
        import re
        hostile = 'evil" tenant\n} inject 1.0\nfake_metric 666'
        name = tenant_metric_name("dstpu_serving_tenant", hostile)
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", name), name
        assert "\n" not in name and '"' not in name

    def test_colliding_names_stay_distinct(self):
        a = tenant_metric_name("p", "a b")
        b = tenant_metric_name("p", "a.b")
        assert a != b, "sanitization collision merged two tenants"
        # stable: the same id always maps to the same series
        assert a == tenant_metric_name("p", "a b")

    def test_empty_name_still_valid(self):
        import re
        name = tenant_metric_name("p", "")
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", name), name


# ---------------------------------------------------------------------------
# SLO burn-rate alerting (tentpole)
# ---------------------------------------------------------------------------
def make_monitor(clock, **kw):
    """Monitor on a synthetic clock + private registry (no global
    pollution, deterministic window math)."""
    kw.setdefault("objective", 0.9)
    kw.setdefault("fast_window_s", 10.0)
    kw.setdefault("slow_window_s", 100.0)
    kw.setdefault("burn_threshold", 2.0)
    kw.setdefault("min_samples", 3)
    return SloMonitor(registry=MetricsRegistry(),
                      time_fn=lambda: clock[0], **kw)


class TestSloBurnRate:
    def test_window_burn_math(self):
        clock = [0.0]
        mon = make_monitor(clock)
        # 6 good observations early in the slow window, then 2 good +
        # 2 bad inside the fast window
        for t in (10, 20, 30, 40, 50, 60):
            clock[0] = float(t)
            mon.observe("t", KIND_TTFT, 0.05, 0.1)
        for t, lat in ((95, 0.05), (96, 0.05), (97, 0.5), (98, 0.5)):
            clock[0] = float(t)
            mon.observe("t", KIND_TTFT, lat, 0.1)
        clock[0] = 100.0
        mon.evaluate()
        snap = mon.snapshot()["t/ttft"]
        # fast: 2 bad / 4 obs / 0.1 budget = 5; slow: 2 / 10 / 0.1 = 2
        assert snap["burn_fast"] == pytest.approx(5.0)
        assert snap["burn_slow"] == pytest.approx(2.0)

    def test_fires_then_resolves_with_hysteresis(self):
        clock = [0.0]
        mon = make_monitor(clock, resolve_fraction=0.5)
        seen = []
        mon.subscribe(lambda a: seen.append((a.state, a.tenant, a.kind)))
        for i in range(3):                     # all-bad fast window
            clock[0] = float(i)
            mon.observe("hot", KIND_TTFT, 1.0, 0.1)
        assert mon.firing("hot", KIND_TTFT)
        assert mon.firing_any("hot")
        assert seen == [("firing", "hot", "ttft")]
        assert mon._m_alerts.value == 1
        assert mon._m_firing.value == 1
        # burn must fall below threshold * resolve_fraction to resolve:
        # at exactly threshold it stays firing (hysteresis)
        clock[0] = 50.0                        # fast window drained
        mon.evaluate()
        assert not mon.firing("hot", KIND_TTFT)
        assert seen[-1] == ("resolved", "hot", "ttft")
        assert mon._m_resolved.value == 1
        assert mon._m_firing.value == 0

    def test_min_samples_floor_blocks_blips(self):
        clock = [0.0]
        mon = make_monitor(clock, min_samples=5)
        for i in range(4):                     # 4 bad < 5-sample floor
            clock[0] = float(i)
            mon.observe("t", KIND_TTFT, 1.0, 0.1)
        assert not mon.firing("t", KIND_TTFT)
        clock[0] = 4.0
        mon.observe("t", KIND_TTFT, 1.0, 0.1)  # the 5th
        assert mon.firing("t", KIND_TTFT)

    def test_both_windows_required(self):
        """A fast-window burst alone must not fire while the slow
        window still shows a healthy error rate (the multi-window
        point: blip immunity)."""
        clock = [0.0]
        mon = make_monitor(clock)
        for t in range(60):                    # long healthy history
            clock[0] = float(t)
            mon.observe("t", KIND_TTFT, 0.05, 0.1)
        for t in (90, 91, 92):                 # 3-bad burst
            clock[0] = float(t)
            mon.observe("t", KIND_TTFT, 1.0, 0.1)
        clock[0] = 93.0
        mon.evaluate()
        snap = mon.snapshot()["t/ttft"]
        assert snap["burn_fast"] >= mon.burn_threshold
        assert snap["burn_slow"] < mon.burn_threshold
        assert not mon.firing("t", KIND_TTFT)

    def test_pending_hold_before_firing(self):
        clock = [0.0]
        mon = make_monitor(clock, pending_s=5.0)
        for i in range(3):
            clock[0] = float(i)
            mon.observe("t", KIND_ITL, 1.0, 0.1)
        assert not mon.firing("t", KIND_ITL)   # pending, not firing
        clock[0] = 8.0
        mon.observe("t", KIND_ITL, 1.0, 0.1)   # held > pending_s
        assert mon.firing("t", KIND_ITL)

    def test_no_target_means_no_stream(self):
        clock = [0.0]
        mon = make_monitor(clock)
        mon.observe("t", KIND_TTFT, 99.0, 0.0)     # no SLO declared
        assert mon.snapshot() == {}

    def test_callback_exception_swallowed(self):
        clock = [0.0]
        mon = make_monitor(clock)
        mon.subscribe(lambda a: 1 / 0)
        good = []
        mon.subscribe(lambda a: good.append(a))
        for i in range(3):
            clock[0] = float(i)
            mon.observe("t", KIND_TTFT, 1.0, 0.1)
        assert mon.firing("t", KIND_TTFT)      # monitor survived
        assert len(good) == 1                  # later subscribers ran

    def test_per_tenant_series_registered(self):
        clock = [0.0]
        mon = make_monitor(clock)
        for i in range(3):
            clock[0] = float(i)
            mon.observe("acme", KIND_TTFT, 1.0, 0.1)
        names = mon._registry.names()
        assert "dstpu_slo_tenant_acme_ttft_burn_fast" in names
        assert "dstpu_slo_tenant_acme_ttft_alerts_total" in names
        assert mon._registry.counter(
            "dstpu_slo_tenant_acme_ttft_alerts_total").value == 1

    def test_from_defaults_disabled_returns_none(self):
        from deepspeed_tpu.observability import slo as slo_mod
        slo_mod.set_defaults(enabled=False)
        assert slo_mod.from_defaults() is None
        slo_mod.set_defaults(enabled=True, objective=0.95,
                             fast_window_s=1.0, slow_window_s=2.0,
                             burn_threshold=1.0, resolve_fraction=0.5,
                             min_samples=2)
        try:
            mon = slo_mod.from_defaults(registry=MetricsRegistry())
            assert mon is not None and mon.objective == 0.95
            assert mon.min_samples == 2
        finally:
            slo_mod.set_defaults(enabled=False)


# ---------------------------------------------------------------------------
# request-scoped tracing (tentpole)
# ---------------------------------------------------------------------------
def serving_scheduler(slots=2, blocks=16, block_size=4, queue=0):
    from deepspeed_tpu.inference.serving.block_allocator import \
        PagedBlockAllocator
    from deepspeed_tpu.inference.serving.scheduler import \
        ContinuousBatchingScheduler
    return ContinuousBatchingScheduler(
        num_slots=slots, allocator=PagedBlockAllocator(blocks, block_size),
        max_blocks_per_seq=8, max_queue_depth=queue)


@pytest.fixture
def req_tracer():
    """The process singleton the scheduler stamps into, enabled for the
    test and restored to disabled+empty afterwards."""
    rt = get_request_tracer()
    rt.configure(enabled=True, capacity=64, max_segments=64, rank=0)
    rt.reset()
    yield rt
    rt.configure(enabled=False)
    rt.reset()


class TestRequestTrace:
    def test_waterfall_segment_ordering(self, req_tracer):
        """Drive a request through the REAL scheduler lifecycle (no
        model): submit -> admit -> prefill chunks -> decode -> terminal,
        then assert the exported track tells that story in order."""
        from deepspeed_tpu.inference.serving.scheduler import (
            Request, RequestStatus)
        sched = serving_scheduler()
        req = sched.submit(Request(prompt=[1, 2, 3, 4, 5],
                                   max_new_tokens=4, tenant="acme"))
        assert req.trace_id is not None
        admitted = sched.schedule_admissions()
        assert [r.req_id for _, r in admitted] == [req.req_id]
        # dispatch stamps reuse engine timestamps (seconds): two prefill
        # chunks then two decode batches, like the engine would emit
        t = time.perf_counter()
        req_tracer.on_prefill_chunk(req, t, 0.01, 0, 4, done=False)
        req_tracer.on_prefill_chunk(req, t + 0.01, 0.01, 4, 1, done=True)
        req_tracer.on_decode([req], t + 0.02, 0.005, 1)
        req_tracer.on_decode([req], t + 0.025, 0.005, 1)
        req.output.extend([7, 7, 7, 7])
        req.cached_tokens = req.prefill_target = 5
        sched.finish(admitted[0][0])
        assert req.status is RequestStatus.OK

        events = req_tracer.chrome_events(epoch_ns=0, rank=0)
        pid = REQUEST_TRACK_PID_OFFSET
        assert all(e["pid"] == pid for e in events)
        procs = [e for e in events if e.get("name") == "process_name"]
        assert procs[0]["args"]["name"] == "serving requests rank 0"
        threads = [e for e in events if e.get("name") == "thread_name"]
        assert threads[0]["args"]["name"] == f"{req.req_id} [acme]"
        track = [e for e in events if e["ph"] in ("X", "i")]
        names = [e["name"] for e in track]
        # the lifecycle story, in order: the queued phase closes at
        # admit, prefill hands off to decode, terminal seals the track
        assert names == ["queued", "admit", "prefill_chunk",
                         "prefill_chunk", "prefill", "decode", "decode",
                         "decode", "terminal"]
        xev = [e for e in track if e["ph"] == "X"]
        assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xev)
        # the phase segments tile forward: queued ends where admission
        # happens, prefill opens there and CONTAINS its chunk segments,
        # decode opens where prefill ends
        queued = next(e for e in xev if e["name"] == "queued")
        prefill = next(e for e in xev if e["name"] == "prefill")
        chunks = [e for e in xev if e["name"] == "prefill_chunk"]
        dec_phase = [e for e in xev if e["name"] == "decode"][-1]
        assert queued["ts"] + queued["dur"] <= prefill["ts"] + 1
        for c in chunks:
            assert prefill["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= \
                prefill["ts"] + prefill["dur"] + 1
        assert prefill["ts"] + prefill["dur"] <= dec_phase["ts"] + 1
        term = track[-1]
        assert term["args"]["status"] == "OK"
        assert term["args"]["tokens"] == 4
        assert term["args"]["trace_id"] == req.trace_id
        assert term["s"] == "t"                # Perfetto instant scope

    def test_preempt_reopens_queued_phase(self, req_tracer):
        from deepspeed_tpu.inference.serving.scheduler import Request
        sched = serving_scheduler(slots=1, blocks=8)
        a = sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=20))
        sched.schedule_admissions()
        # decode until the pool chokes, then force the preemption path
        slot = next(iter(sched.running))
        sched._preempt(slot, a)
        tl = req_tracer.get(a.trace_id)
        names = [e[1] for e in tl.events]
        assert "preempt" in names
        assert tl.phase == "queued"            # re-waiting after preempt

    def test_shed_request_still_gets_terminal(self, req_tracer):
        from deepspeed_tpu.inference.serving.scheduler import Request
        sched = serving_scheduler(queue=1)
        sched.submit(Request(prompt=[1, 2], max_new_tokens=2))
        shed = sched.submit(Request(prompt=[3, 4], max_new_tokens=2))
        assert shed.status is not None         # shed at submit
        tl = req_tracer.get(shed.trace_id)
        assert tl.done
        assert [e[1] for e in tl.events][-1] == "terminal"

    def test_capacity_evicts_completed_first(self, req_tracer):
        req_tracer.configure(enabled=True, capacity=4)

        class FakeReq:
            def __init__(self, i):
                self.req_id = f"r{i}"
                self.tenant = "t"
                self.trace_id = None
                self.output = []
                self.preemptions = 0
                self.status = None
                self.error = None

        live = [FakeReq(i) for i in range(3)]
        for r in live:
            req_tracer.on_submit(r)
        done = FakeReq(99)
        req_tracer.on_submit(done)
        req_tracer.on_terminal(done)
        req_tracer.on_submit(FakeReq(100))     # over capacity
        assert req_tracer.recorded == 4
        assert req_tracer.dropped == 1
        assert req_tracer.get(done.trace_id) is None, \
            "completed timeline must be evicted before live ones"
        assert all(req_tracer.get(r.trace_id) for r in live)

    def test_segment_cap_counts_drops(self, req_tracer):
        req_tracer.configure(enabled=True, max_segments=4)

        class FakeReq:
            req_id, tenant, trace_id = "r0", "t", None
            output, preemptions, status, error = [], 0, None, None

        r = FakeReq()
        req_tracer.on_submit(r)
        for i in range(10):
            req_tracer.on_decode([r], float(i), 0.001, 1)
        req_tracer.on_terminal(r)              # forced: always lands
        tl = req_tracer.get(r.trace_id)
        assert tl.dropped_segments > 0
        term = tl.events[-1]
        assert term[1] == "terminal"
        assert term[4]["dropped_segments"] == tl.dropped_segments

    def test_rides_span_tracer_flush(self, req_tracer, tmp_path):
        """The export contract: request tracks merge into the SAME
        trace_rank<r>.json the span tracer writes, via the event-source
        hook — one file, one clock."""
        from deepspeed_tpu.inference.serving.scheduler import Request
        tr = SpanTracer()
        tr.configure(enabled=True, capacity=16,
                     output_dir=str(tmp_path), rank=0)
        tr.set_event_source("request_trace", req_tracer.chrome_events)
        sched = serving_scheduler()
        req = sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        sched.schedule_admissions()
        with tr.span("serving/step"):
            pass
        with open(tr.flush()) as f:
            doc = json.load(f)
        ev = doc["traceEvents"]
        span_pids = {e["pid"] for e in ev if e.get("name") ==
                     "serving/step"}
        req_ev = [e for e in ev if e.get("cat") == "request"]
        assert span_pids == {0}
        assert req_ev, "request track missing from the merged trace"
        assert {e["pid"] for e in req_ev} == {REQUEST_TRACK_PID_OFFSET}
        assert any(e["args"].get("trace_id") == req.trace_id
                   for e in req_ev)

    def test_disabled_path_zero_work(self):
        """Obs-off pin: with tracing disabled the scheduler's lifecycle
        sites must not touch the recorder beyond the one attribute
        check — every recorder method is booby-trapped and a full
        submit/admit/shed/terminal cycle must not trip any of them."""
        from deepspeed_tpu.inference.serving.scheduler import Request
        rt = get_request_tracer()
        assert not rt.enabled
        trapped = [n for n in ("on_submit", "on_admit", "on_preempt",
                               "on_prefill_chunk", "on_decode", "on_spec",
                               "on_terminal", "mark")]
        originals = {n: getattr(rt, n) for n in trapped}

        def boom(*a, **k):
            raise AssertionError("recorder touched while disabled")

        for n in trapped:
            setattr(rt, n, boom)
        try:
            sched = serving_scheduler(queue=1)
            kept = sched.submit(Request(prompt=[1, 2], max_new_tokens=2))
            sched.submit(Request(prompt=[3, 4], max_new_tokens=2))  # shed
            sched.schedule_admissions()
            sched.cancel(kept)
            assert kept.trace_id is None       # no ids minted while off
        finally:
            for n, fn in originals.items():
                setattr(rt, n, fn)


# ---------------------------------------------------------------------------
# flight recorder (tentpole)
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def make(self, tmp_path, capacity=8, **kw):
        fr = FlightRecorder()
        fr.configure(enabled=True, capacity=capacity,
                     output_dir=str(tmp_path / "fr"), **kw)
        fr.min_dump_interval_s = 0.0
        return fr

    def test_ring_wraparound_oldest_first(self, tmp_path):
        fr = self.make(tmp_path, capacity=4)
        for i in range(10):
            fr.record({"step": i})
        assert fr.recorded == 4 and fr.dropped == 6
        assert [s["step"] for s in fr.snapshots()] == [6, 7, 8, 9]

    def test_terminal_ring_bounded(self, tmp_path):
        fr = self.make(tmp_path, max_terminal_events=3)
        for i in range(7):
            fr.note_terminal({"req_id": f"r{i}"})
        assert [t["req_id"] for t in fr.terminals()] == ["r4", "r5", "r6"]

    def test_dump_bundle_verifiable(self, tmp_path):
        from deepspeed_tpu.runtime.resilience.integrity import \
            verify_manifest
        fr = self.make(tmp_path)
        for i in range(5):
            fr.record({"step": i, "queue_depth": i % 3})
        fr.note_terminal({"req_id": "r1", "status": "FAILED"})
        bundle = fr.dump("serving_error", "watchdog tripped",
                         extra={"no_progress": 64})
        assert bundle is not None and os.path.isdir(bundle)
        assert fr.last_bundle == bundle
        # sealed: every file checksummed, nothing torn
        verify_manifest(bundle)
        with open(os.path.join(bundle, "reason.json")) as f:
            reason = json.load(f)
        assert reason["reason"] == "serving_error"
        assert reason["detail"] == "watchdog tripped"
        assert reason["extra"]["no_progress"] == 64
        with open(os.path.join(bundle, "snapshots.json")) as f:
            snaps = json.load(f)
        assert snaps["count"] == 5
        assert [s["step"] for s in snaps["snapshots"]] == list(range(5))
        with open(os.path.join(bundle, "terminals.json")) as f:
            assert json.load(f)[0]["req_id"] == "r1"
        assert os.path.exists(os.path.join(bundle, "metrics.prom"))

    def test_dump_rate_limited_and_disabled(self, tmp_path):
        fr = self.make(tmp_path)
        fr.min_dump_interval_s = 3600.0
        assert fr.dump("first") is not None
        assert fr.dump("second") is None, "repeat dump not rate-limited"
        off = FlightRecorder()
        assert off.dump("nope") is None

    def test_bundle_pruning_keeps_newest(self, tmp_path):
        fr = self.make(tmp_path, max_bundles=2)
        kept = [fr.dump(f"r{i}") for i in range(4)]
        base = os.path.dirname(kept[-1])
        left = sorted(d for d in os.listdir(base)
                      if d.startswith("postmortem-"))
        assert len(left) == 2
        assert os.path.basename(kept[-1]) in left
        assert os.path.basename(kept[-2]) in left

    def test_disabled_path_zero_work(self):
        from deepspeed_tpu.observability import get_flight_recorder
        fr = get_flight_recorder()
        assert not fr.enabled
        # record() on a never-enabled recorder allocates nothing
        fr.record({"step": 1})
        assert fr.recorded == 0


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------
class TestObservabilityConfig:
    def test_defaults_off(self):
        cfg = ds.DeepSpeedConfig({"train_batch_size": 8})
        assert not cfg.observability.enabled
        assert not cfg.observability.tracing.enabled
        assert not cfg.observability.metrics.enabled
        assert cfg.observability.tracing.buffer_size == 65536

    def test_parse_enabled(self):
        cfg = ds.DeepSpeedConfig({
            "train_batch_size": 8,
            "observability": {
                "tracing": {"enabled": True, "buffer_size": 128,
                            "output_dir": "/tmp/t"},
                "metrics": {"enabled": True, "prometheus_dir": "/tmp/p",
                            "export_interval_steps": 5}}})
        o = cfg.observability
        assert o.enabled and o.tracing.enabled and o.metrics.enabled
        assert o.tracing.buffer_size == 128
        assert o.metrics.export_interval_steps == 5

    def test_rejects_bad_values(self):
        with pytest.raises(Exception):
            ds.DeepSpeedConfig({"train_batch_size": 8, "observability": {
                "tracing": {"buffer_size": 0}}})
        with pytest.raises(Exception):
            ds.DeepSpeedConfig({"train_batch_size": 8, "observability": {
                "metrics": {"export_interval_steps": -1}}})
        with pytest.raises(Exception):   # typo'd key rejected
            ds.DeepSpeedConfig({"train_batch_size": 8, "observability": {
                "tracing": {"enabld": True}}})

    def test_new_blocks_default_off(self):
        o = ds.DeepSpeedConfig({"train_batch_size": 8}).observability
        assert not o.request_tracing.enabled
        assert not o.slo.enabled
        assert not o.flight.enabled
        assert not o.enabled
        assert o.slo.objective == 0.9
        assert o.flight.skip_burst_steps == 8

    def test_parse_new_blocks(self):
        o = ds.DeepSpeedConfig({
            "train_batch_size": 8,
            "observability": {
                "tracing": {"enabled": True},
                "request_tracing": {"enabled": True, "capacity": 32},
                "slo": {"enabled": True, "objective": 0.95,
                        "fast_window_s": 5.0, "slow_window_s": 50.0},
                "flight": {"enabled": True, "capacity": 16,
                           "output_dir": "/tmp/fr"}}}).observability
        assert o.request_tracing.enabled
        assert o.request_tracing.capacity == 32
        assert o.slo.objective == 0.95
        assert o.flight.capacity == 16
        assert o.enabled

    def test_request_tracing_requires_tracing(self):
        with pytest.raises(Exception, match="request_tracing"):
            ds.DeepSpeedConfig({"train_batch_size": 8, "observability": {
                "request_tracing": {"enabled": True}}})

    def test_new_blocks_reject_bad_values(self):
        for block in ({"slo": {"objective": 1.5}},
                      {"slo": {"fast_window_s": 60.0,
                               "slow_window_s": 5.0}},
                      {"slo": {"resolve_fraction": 2.0}},
                      {"flight": {"capacity": 0}},
                      {"flight": {"skip_burst_steps": 0}},
                      {"request_tracing": {"capacity": 0}}):
            with pytest.raises(Exception):
                ds.DeepSpeedConfig({"train_batch_size": 8,
                                    "observability": block})

    def test_configure_wires_singletons(self, tmp_path):
        """observability.configure() must arm/disarm all three new
        recorders alongside the tracer/registry."""
        from deepspeed_tpu.observability import (configure,
                                                 get_flight_recorder,
                                                 slo as slo_mod)
        cfg = ds.DeepSpeedConfig({
            "train_batch_size": 8,
            "observability": {
                "tracing": {"enabled": True,
                            "output_dir": str(tmp_path)},
                "request_tracing": {"enabled": True},
                "slo": {"enabled": True, "objective": 0.95},
                "flight": {"enabled": True,
                           "output_dir": str(tmp_path / "fr")}}})
        try:
            configure(cfg.observability, rank=0)
            assert get_request_tracer().enabled
            assert get_flight_recorder().enabled
            mon = slo_mod.from_defaults(registry=MetricsRegistry())
            assert mon is not None and mon.objective == 0.95
        finally:
            configure(None)
        assert not get_request_tracer().enabled
        assert not get_flight_recorder().enabled
        assert slo_mod.from_defaults() is None


# ---------------------------------------------------------------------------
# comms busbw columns (satellite: calc_bw_factor was dead code)
# ---------------------------------------------------------------------------
class TestCommsBw:
    def test_all_reduce_factor_pinned(self):
        from deepspeed_tpu.comm.comms_logging import calc_bw_factor
        for n in (2, 4, 8, 64):
            assert calc_bw_factor("all_reduce", n) == \
                pytest.approx(2 * (n - 1) / n)
        for op in ("all_gather", "reduce_scatter", "all_to_all"):
            assert calc_bw_factor(op, 8) == pytest.approx(7 / 8)
        assert calc_bw_factor("broadcast", 8) == 1.0
        assert calc_bw_factor("all_reduce", 1) == 0.0   # no wire traffic

    def test_log_summary_wire_volume_columns(self):
        from deepspeed_tpu.comm.comms_logging import CommsLogger
        cl = CommsLogger()
        cl.configure(enabled=True)
        for _ in range(3):
            cl.record("all_reduce", 1024, "data", n=4)
        out = cl.log_summary()
        assert "BW factor" in out and "Wire volume" in out
        row = next(l for l in out.splitlines() if l.startswith("all_reduce"))
        assert "1.500" in row                      # 2(n-1)/n at n=4
        assert str(int(3 * 1024 * 1.5)) in row     # wire volume column

    def test_record_without_n_reports_zero_factor(self):
        from deepspeed_tpu.comm.comms_logging import CommsLogger
        cl = CommsLogger()
        cl.configure(enabled=True)
        cl.record("all_reduce", 512, "data")       # n unknown
        row = next(l for l in cl.log_summary().splitlines()
                   if l.startswith("all_reduce"))
        assert "0.000" in row

    def test_axis_size_captured_at_trace_time(self, mesh8):
        """The WIRING, not just the formula: tracing a collective through
        deepspeed_tpu.comm records the axis size, so log_summary's wire
        volume is non-zero in production."""
        import jax
        from jax.sharding import PartitionSpec as P
        from deepspeed_tpu.parallel.shard_map_compat import shard_map
        from deepspeed_tpu.comm import comm
        from deepspeed_tpu.comm.comms_logging import (configure,
                                                      get_comms_logger)
        configure(verbose=False)
        cl = get_comms_logger()
        cl.reset()

        def f(x):
            return comm.all_reduce(x, axis_name="data")
        with mesh8:
            jax.jit(shard_map(f, mesh8, in_specs=P("data"),
                              out_specs=P()))(
                np.arange(8, dtype=np.float32))
        recs = cl.comms_dict["all_reduce"]
        assert recs, "collective was not recorded at trace time"
        rec = next(iter(recs.values()))
        assert rec.get("n") == 8       # axis size captured, not 0
        row = next(l for l in cl.log_summary().splitlines()
                   if l.startswith("all_reduce"))
        assert "1.750" in row          # 2(n-1)/n at n=8
        cl.reset()


# ---------------------------------------------------------------------------
# timer satellites
# ---------------------------------------------------------------------------
class TestTimerSatellites:
    def test_throughput_steps_per_output_emits(self, caplog):
        from deepspeed_tpu.utils.timer import ThroughputTimer
        got = []
        t = ThroughputTimer(batch_size=4, seq_length=16, start_step=1,
                            steps_per_output=3,
                            event_fn=lambda s, step: got.append((s, step)))
        for _ in range(7):
            t.start()
            t.stop()
        # emissions at steps 3 and 6 (timed_steps > 0 from step 2 on)
        assert [step for _, step in got] == [3, 6]
        s = got[-1][0]
        assert {"avg_step_time_s", "samples_per_sec",
                "tokens_per_sec"} <= set(s)
        assert t.last_step_time is not None and t.last_step_time >= 0

    def test_wallclock_log_memory_breakdown(self):
        from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer
        timers = SynchronizedWallClockTimer()
        timers("phase").start()
        timers("phase").stop()
        line = timers.log(["phase"], memory_breakdown=True)
        assert "phase:" in line
        assert "host rss" in line     # the memory snapshot rode the line
        plain = SynchronizedWallClockTimer()
        plain("p").start()
        plain("p").stop()
        assert "host rss" not in plain.log(["p"])


# ---------------------------------------------------------------------------
# wandb event batching (satellite)
# ---------------------------------------------------------------------------
class TestWandbBatching:
    def test_events_batched_per_step(self):
        from deepspeed_tpu.monitor.monitor import WandbMonitor

        class FakeWandb:
            def __init__(self):
                self.calls = []

            def log(self, payload, step=None):
                self.calls.append((dict(payload), step))

        mon = WandbMonitor.__new__(WandbMonitor)
        mon.enabled = True
        mon._wandb = FakeWandb()
        mon.write_events([("Train/loss", 1.0, 5), ("Train/lr", 0.1, 5),
                          ("Train/loss", 0.9, 6)])
        # one wandb.log per STEP, not per event — no step-clobbering
        assert mon._wandb.calls == [
            ({"Train/loss": 1.0, "Train/lr": 0.1}, 5),
            ({"Train/loss": 0.9}, 6)]


# ---------------------------------------------------------------------------
# integration: instrumented training loop (acceptance criteria)
# ---------------------------------------------------------------------------
def tiny_model(num_layers=2):
    cfg = gpt2_config("125m", num_layers=num_layers, d_model=32,
                      num_heads=4, vocab_size=64, max_seq_len=16,
                      dtype=jnp.float32)
    return TransformerLM(cfg)


def batch(n, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, 64, (n, 16), dtype=np.int32)}


class TestIntegration:
    @pytest.mark.slow
    def test_training_loop_produces_trace_and_textfile(self, tmp_path):
        """Acceptance: CPU-backend loop with tracing+metrics on → Chrome
        trace with spans from ≥4 subsystems (engine step phases,
        zero/offload I/O, checkpoint, comm) + Prometheus textfile with
        the step-time histogram and resilience counters."""
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "zero_optimization": {
                "offload_optimizer": {"device": "cpu"}},
            "observability": {
                "tracing": {"enabled": True,
                            "output_dir": str(tmp_path / "traces")},
                "metrics": {"enabled": True,
                            "prometheus_dir": str(tmp_path / "prom"),
                            "json_path": str(tmp_path / "metrics.json")}},
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        for i in range(3):
            engine.train_step(batch(16, seed=i))
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        ds.comm.comm.barrier()
        paths = engine.flush_observability()
        trace_path = tmp_path / "traces" / "trace_rank0.json"
        assert str(trace_path) in paths
        with open(trace_path) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        subsystems = {n.split("/")[0] for n in names}
        assert {"engine", "offload", "checkpoint",
                "comm"} <= subsystems, subsystems
        assert "engine/train_step" in names
        assert "offload/grads" in names and "offload/host_sweep" in names
        assert "checkpoint/publish" in names
        assert "comm/barrier" in names

        prom = open(tmp_path / "prom" / "dstpu_rank0.prom").read()
        # step-time histogram, fed at the synced GAS boundary
        assert "# TYPE dstpu_step_time_seconds histogram" in prom
        count_line = next(l for l in prom.splitlines()
                          if l.startswith("dstpu_step_time_seconds_count"))
        assert int(count_line.split()[-1]) >= 3
        # resilience counters are present even at zero (pre-registered)
        assert "dstpu_io_retries_total" in prom
        assert "dstpu_train_skipped_steps_total" in prom
        # the jit recompile watermark moved when programs were built
        jit_line = next(l for l in prom.splitlines()
                        if l.startswith("dstpu_jit_programs_built_total"))
        assert float(jit_line.split()[-1]) >= 1

        with open(tmp_path / "metrics.json") as f:
            snap = json.load(f)
        assert snap["dstpu_step_time_seconds"]["count"] >= 3

    @pytest.mark.slow
    def test_metrics_flow_into_monitor_fanout(self, tmp_path):
        """Registry scalars ride MonitorMaster: the CSV backend grows
        Metrics_* files without any backend-specific wiring."""
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "csv_monitor": {"enabled": True,
                            "output_path": str(tmp_path),
                            "job_name": "obsjob"},
            "observability": {"metrics": {"enabled": True}},
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        for i in range(2):
            engine.train_step(batch(16, seed=i))
        engine.monitor.flush()
        files = os.listdir(tmp_path / "obsjob")
        assert "Metrics_dstpu_train_steps_total.csv" in files
        assert "Metrics_dstpu_step_time_seconds.csv" in files

    @pytest.mark.slow
    def test_disabled_block_is_noop(self, tmp_path):
        """With the block absent the tracer is off, trace_span returns
        the shared null singleton, and no telemetry files appear."""
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
        }
        engine, _, _, _ = ds.initialize(model=tiny_model(), config=config)
        assert not engine._tracer.enabled
        assert obs.trace_span("engine/train_step") is NULL_SPAN
        before = engine._tracer.recorded
        engine.train_step(batch(16))
        assert engine._tracer.recorded == before   # nothing recorded
        assert engine.flush_observability() == []  # nothing exported
