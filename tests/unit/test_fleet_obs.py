"""Fleet observability plane suite (ISSUE 18): distributed trace
propagation across the disaggregated fleet, bucket-wise fleet metrics
aggregation, and the host/device overlap profiler.

Fast units pin the primitives — ``Histogram.merge`` /
``interpolate_quantile`` property tests (merge-of-splits == whole,
monotone quantiles, +Inf clamp, bounds-mismatch refusal), the trace-ring
dropped-span counter, the ``FleetTraceAssembler`` flow-arrow synthesis +
``validate_fleet_trace`` rejection paths, the aggregator's
healthy-only/fresh-swap semantics and the autoscaler's
aggregator-backed sensor path.

The ``slow`` end-to-ends are the acceptance criteria: a disaggregated
2-class fleet request (prefill leg -> fabric publish -> claim/promote ->
decode leg, plus one forced decode-replica failover) renders as ONE
merged Perfetto trace under a single fleet trace id with flow arrows
across every leg; the merged fleet TTFT quantiles equal a bucket-wise
merge of the per-replica ground-truth histograms; and the overlap
profiler populates its gauges for serving AND training while the
disabled path records nothing.  The ``run_tests.sh`` fleet-obs stage
re-opens the merged trace artifact from a SEPARATE process
(``DSTPU_FLEET_OBS_DIR``) and re-validates it — the operator's path,
not just the in-test assertions.  docs/observability.md "Fleet
observability & overlap profiling".
"""
import json
import math
import os
import random
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
import deepspeed_tpu.observability as obs
from deepspeed_tpu.inference.serving import (FleetAutoscaler, FleetRouter,
                                             ReplicaState, RequestStatus,
                                             StreamCollector)
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability import (FleetMetricsAggregator,
                                         FleetTraceAssembler,
                                         FleetTraceContext, Histogram,
                                         get_overlap_profiler,
                                         get_request_tracer,
                                         interpolate_quantile,
                                         validate_fleet_trace)
from deepspeed_tpu.observability.fleet_metrics import hist_snapshot
from deepspeed_tpu.observability.fleet_trace import FLOW_CAT
from deepspeed_tpu.observability.metrics import decumulate
from deepspeed_tpu.observability import overlap
from deepspeed_tpu.observability.overlap import OverlapProfiler
from deepspeed_tpu.runtime.config import ObservabilityConfig

pytestmark = [pytest.mark.observability, pytest.mark.fleet_obs]


@pytest.fixture
def obs_reset():
    """Restore the process-global observability state after a test that
    arms any of it (telemetry is per-process; leaking an enabled tracer
    into the next test would change ITS hot path)."""
    yield
    obs.configure(None)
    get_request_tracer().reset()
    get_overlap_profiler().reset()


# ---------------------------------------------------------------------------
# S1: histogram merge + shared quantile estimator property tests
# ---------------------------------------------------------------------------
def test_histogram_merge_of_splits_equals_whole():
    """Sharding a sample stream across N histograms and bucket-merging
    them must reproduce the un-sharded histogram EXACTLY — counts,
    buckets, and every interpolated quantile."""
    rng = random.Random(1234)
    vals = [rng.lognormvariate(-3.5, 1.5) for _ in range(3000)]
    whole = Histogram("h")
    shards = [Histogram("h") for _ in range(3)]
    for i, v in enumerate(vals):
        whole.observe(v)
        shards[i % 3].observe(v)
    merged = shards[0].merge(*shards[1:])
    assert merged.count == whole.count == len(vals)
    assert merged.sum == pytest.approx(whole.sum)
    assert merged.cumulative() == whole.cumulative()
    for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        assert merged.quantile(q) == whole.quantile(q)
    # quantiles are monotone in q
    qs = [merged.quantile(q) for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.99)]
    assert qs == sorted(qs)


def test_histogram_merge_bounds_mismatch_raises():
    a = Histogram("a", buckets=(0.1, 1.0))
    b = Histogram("b", buckets=(0.2, 1.0))
    with pytest.raises(ValueError, match="bucket bounds"):
        a.merge(b)


def test_interpolate_quantile_inf_tail_clamps():
    bounds = (0.1, 1.0)
    # everything in the +inf bucket: clamp to the highest finite bound
    assert interpolate_quantile(bounds, [0, 0, 10], 0.99) == 1.0
    # empty histogram reads 0.0, not an error
    assert interpolate_quantile(bounds, [0, 0, 0], 0.5) == 0.0
    with pytest.raises(ValueError):
        interpolate_quantile(bounds, [1, 1, 1], 1.5)


def test_decumulate_inverts_cumulative():
    h = Histogram("h")
    for v in (0.0002, 0.004, 2.0, 100.0):
        h.observe(v)
    bounds, counts = decumulate(
        [[le if le != math.inf else "+Inf", c] for le, c in h.cumulative()])
    assert bounds == h.buckets
    assert len(counts) == len(bounds) + 1
    assert sum(counts) == h.count
    assert counts[-1] == 1          # the 100.0 sample rode the +inf tail


# ---------------------------------------------------------------------------
# S2: trace ring wraparound is loud
# ---------------------------------------------------------------------------
def test_trace_ring_wraparound_counts_dropped(tmp_path, obs_reset):
    tr = obs.get_tracer()
    reg = obs.get_registry()
    before = reg.counter("dstpu_trace_dropped_spans_total").value
    tr.configure(enabled=True, capacity=4, output_dir=str(tmp_path))
    for i in range(10):
        with obs.trace_span("engine/train_step", i=i):
            pass
    assert tr.dropped == 6
    assert reg.counter("dstpu_trace_dropped_spans_total").value \
        - before == 6
    path = tr.flush()
    with open(path) as f:
        doc = json.load(f)
    assert doc["otherData"]["dropped_spans"] == 6
    # the assembler propagates the truncation into the merged artifact
    merged = FleetTraceAssembler().add_doc(doc, label="rank0").assemble()
    assert merged["otherData"]["dropped"] == 6


# ---------------------------------------------------------------------------
# fleet trace assembler / validator on synthetic legs
# ---------------------------------------------------------------------------
def _leg(pid, tid, trace_id, t0, segs):
    """One leg: consecutive request-cat X slices on a (pid, tid) track."""
    out, t = [], t0
    for name, dur in segs:
        out.append({"ph": "X", "cat": "request", "pid": pid, "tid": tid,
                    "name": name, "ts": t, "dur": dur,
                    "args": {"trace_id": trace_id}})
        t += dur + 5.0
    return out


def _three_leg_events(trace_id):
    return (_leg(1000, 1, trace_id, 0.0,
                 [("queued", 10.0), ("prefill", 50.0),
                  ("fabric_publish", 5.0)])
            + _leg(1000, 2, trace_id, 100.0,
                   [("promote", 8.0), ("decode", 40.0)])
            + _leg(1000, 3, trace_id, 200.0, [("decode", 30.0)]))


def test_assembler_draws_flow_chain_across_legs():
    tid = FleetTraceContext("7").mint()
    assert tid == "fleet-7-000000"
    doc = FleetTraceAssembler().add_events(
        _three_leg_events(tid), label="rank0").assemble()
    report = validate_fleet_trace(doc)
    assert report[tid]["legs"] == 3
    flows = [e for e in doc["traceEvents"] if e.get("cat") == FLOW_CAT]
    assert len(flows) == report[tid]["flow_events"] >= 4
    # one chain: s ... t ... f, binding-point e on the finish, one flow id
    assert flows[0]["ph"] == "s"
    assert flows[-1]["ph"] == "f" and flows[-1]["bp"] == "e"
    assert {e["ph"] for e in flows[1:-1]} == {"t"}
    assert len({e["id"] for e in flows}) == 1
    assert [e["ts"] for e in flows] == sorted(e["ts"] for e in flows)
    # the fabric publish / promote windows are explicit chain anchors
    anchor_ts = {e["ts"] for e in flows}
    pub = next(e for e in doc["traceEvents"]
               if e.get("name") == "fabric_publish")
    pro = next(e for e in doc["traceEvents"] if e.get("name") == "promote")
    assert pub["ts"] in anchor_ts and pro["ts"] in anchor_ts


def test_assembler_single_leg_trace_gets_no_flow():
    doc = FleetTraceAssembler().add_events(
        _leg(1000, 1, "r0-000001", 0.0,
             [("queued", 5.0), ("decode", 20.0)])).assemble()
    assert not [e for e in doc["traceEvents"] if e.get("cat") == FLOW_CAT]
    report = validate_fleet_trace(doc)
    assert report["r0-000001"] == {"legs": 1, "flow_events": 0}


def test_assembler_remaps_pids_across_sources():
    """Two single-process exports both at pid 1000 must not merge their
    tracks: the second source lands a SOURCE_PID_STRIDE away, and the
    flow chain still spans both."""
    tid = "fleet-0-00000a"
    a = _leg(1000, 1, tid, 0.0, [("prefill", 50.0),
                                 ("fabric_publish", 5.0)])
    b = _leg(1000, 1, tid, 100.0, [("promote", 8.0), ("decode", 40.0)])
    doc = (FleetTraceAssembler().add_events(a, label="p0")
           .add_events(b, label="d0").assemble())
    pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert pids == {1000, 1_001_000}
    report = validate_fleet_trace(doc)
    assert report[tid]["legs"] == 2
    assert doc["otherData"]["sources"] == ["p0", "d0"]


def test_validator_rejects_orphan_leg():
    tid = "fleet-0-00000b"
    doc = FleetTraceAssembler().add_events(
        _three_leg_events(tid)).assemble()
    # a leg that appears AFTER assembly never got onto the flow chain
    doc["traceEvents"].extend(_leg(1000, 9, tid, 400.0, [("decode", 9.0)]))
    with pytest.raises(ValueError, match="orphan"):
        validate_fleet_trace(doc)


def test_validator_rejects_unresolvable_flow_endpoint():
    tid = "fleet-0-00000c"
    doc = FleetTraceAssembler().add_events(
        _three_leg_events(tid)).assemble()
    flow = next(e for e in doc["traceEvents"] if e.get("cat") == FLOW_CAT)
    flow["ts"] = 1e9                 # off every slice of that track
    with pytest.raises(ValueError, match="does not resolve"):
        validate_fleet_trace(doc)


def test_validator_rejects_multi_leg_trace_without_chain():
    tid = "fleet-0-00000d"
    events = _three_leg_events(tid)   # raw legs, no assembly -> no flows
    with pytest.raises(ValueError, match="continuity"):
        validate_fleet_trace(events)


# ---------------------------------------------------------------------------
# fleet metrics aggregation
# ---------------------------------------------------------------------------
def test_aggregator_sums_counters_and_labels_gauges():
    agg = FleetMetricsAggregator()
    for ridx, role in enumerate(("prefill", "decode", "decode")):
        agg.add_snapshot(f"r{ridx}", {
            "dstpu_requests_total": {"kind": "counter",
                                     "value": 100.0 + ridx},
            "dstpu_serving_queue_depth": {"kind": "gauge",
                                          "value": float(ridx)},
        }, role=role)
    merged = agg.merged()
    assert merged["dstpu_requests_total"]["value"] == 303.0
    gauge = merged["dstpu_serving_queue_depth"]
    assert gauge["replicas"] == {"r0": 0.0, "r1": 1.0, "r2": 2.0}
    assert gauge["classes"] == {"prefill": 0.0, "decode": 3.0}
    prom = agg.to_prometheus()
    assert 'dstpu_serving_queue_depth{replica="r1"} 1.0' in prom
    assert 'dstpu_serving_queue_depth{fleet_class="decode"} 3.0' in prom


def test_aggregator_bucket_merge_matches_ground_truth():
    """The acceptance pin: fleet p50/p95/p99 from MERGED buckets equal
    the quantiles of a single histogram fed every replica's samples, and
    land within one bucket boundary of the exact sample quantile —
    never an average of per-replica quantiles."""
    rng = random.Random(7)
    agg = FleetMetricsAggregator()
    whole = Histogram("dstpu_serving_ttft_seconds")
    samples = []
    for ridx in range(3):
        h = Histogram("dstpu_serving_ttft_seconds")
        # deliberately skewed per-replica load: replica 2 is ~7x slower,
        # exactly the regime where averaging per-replica p99s lies
        vals = [rng.lognormvariate(-4.0 + ridx, 0.8) for _ in range(500)]
        for v in vals:
            h.observe(v)
            whole.observe(v)
        samples.extend(vals)
        agg.add_snapshot(
            f"r{ridx}",
            {"dstpu_serving_ttft_seconds": hist_snapshot(h)},
            role="decode")
    ent = agg.merged()["dstpu_serving_ttft_seconds"]
    assert ent["count"] == whole.count == 1500
    for tag, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
        assert ent[tag] == pytest.approx(whole.quantile(q))
    # within one bucket boundary of the exact order-statistic p99
    exact = float(np.percentile(samples, 99))
    bounds = list(whole.buckets)
    idx_exact = next((i for i, b in enumerate(bounds) if exact <= b),
                     len(bounds))
    idx_merged = next((i for i, b in enumerate(bounds)
                       if ent["p99"] <= b), len(bounds))
    assert abs(idx_merged - idx_exact) <= 1, \
        (ent["p99"], exact, idx_merged, idx_exact)
    # averaging per-replica p99s would NOT reproduce the merged value
    naive = sum(
        interpolate_quantile(*decumulate(
            agg._snapshots[f"r{i}"]
            ["dstpu_serving_ttft_seconds"]["buckets"]), 0.99)
        for i in range(3)) / 3
    assert naive != pytest.approx(ent["p99"], rel=0.05)


def test_aggregator_rejects_mismatched_bucket_bounds():
    agg = FleetMetricsAggregator()
    a = Histogram("h", buckets=(0.1, 1.0))
    b = Histogram("h", buckets=(0.2, 1.0))
    a.observe(0.05)
    b.observe(0.05)
    agg.add_snapshot("r0", {"h": hist_snapshot(a)})
    agg.add_snapshot("r1", {"h": hist_snapshot(b)})
    with pytest.raises(ValueError, match="bucket bounds differ"):
        agg.merged()


def test_aggregator_healthy_only_and_fresh_swap():
    """Stub handles without ``metrics_snapshot`` contribute the minimal
    gauge-only snapshot; ``healthy_only`` reads skip non-routable
    replicas; a replica the router stops listing vanishes wholesale."""
    r1 = types.SimpleNamespace(replica_id="r1", role="decode",
                               queue_depth=4, healthy=True)
    r2 = types.SimpleNamespace(replica_id="r2", role="decode",
                               queue_depth=9, healthy=False)
    router = types.SimpleNamespace(replicas=[r1, r2])
    agg = FleetMetricsAggregator()
    assert agg.observe_router(router) == 2
    assert agg.class_queue_depth("decode") == 13.0
    assert agg.class_queue_depth("decode", healthy_only=True) == 4.0
    assert agg.class_replicas("decode") == 2
    assert agg.class_replicas("decode", healthy_only=True) == 1
    # ReplicaState-shaped stubs: routable == state "healthy"
    r3 = types.SimpleNamespace(replica_id="r3", role="prefill",
                               queue_depth=2,
                               state=ReplicaState.HEALTHY)
    router.replicas = [r1, r3]        # r2 gone: must not linger
    assert agg.observe_router(router) == 2
    assert agg.replica_ids == ["r1", "r3"]
    assert agg.class_queue_depth(healthy_only=True) == 6.0
    assert agg.class_replicas("prefill", healthy_only=True) == 1


def test_aggregator_burn_rate_is_worst_over_fleet():
    agg = FleetMetricsAggregator()
    agg.add_snapshot("r0", {"dstpu_slo_tenant_a_ttft_burn_fast":
                            {"kind": "gauge", "value": 1.5}})
    agg.add_snapshot("r1", {"dstpu_slo_tenant_b_ttft_burn_fast":
                            {"kind": "gauge", "value": 3.25}})
    assert agg.burn_rate("ttft", "fast") == 3.25
    assert agg.burn_rate("itl", "fast") == 0.0


class _ObsStubReplica:
    def __init__(self, rid, role="mixed", depth=0):
        self.replica_id, self.role = rid, role
        self.queue_depth = depth
        self.state = ReplicaState.HEALTHY
        self.alive = True

    def has_work(self):
        return False


def test_autoscaler_reads_sensor_inputs_from_aggregator():
    """The sensor path: tick() refreshes the router's aggregator and the
    policy inputs come from IT — the same numbers the dashboards see."""
    router = types.SimpleNamespace(
        replicas=[_ObsStubReplica("m0", depth=1),
                  _ObsStubReplica("m1", depth=0)])
    auto = FleetAutoscaler(router, spawn_fn=lambda role: None,
                           clock=lambda: 0.0)
    assert isinstance(auto.aggregator, FleetMetricsAggregator)
    auto.tick(now=0.0)
    assert auto.aggregator.class_replicas("mixed", healthy_only=True) == 2
    assert auto.aggregator.class_queue_depth(
        "mixed", healthy_only=True) == 1.0
    # a real router shares its own aggregator with the autoscaler
    shared = FleetMetricsAggregator()
    router2 = types.SimpleNamespace(replicas=[], aggregator=shared)
    auto2 = FleetAutoscaler(router2, spawn_fn=lambda role: None)
    assert auto2.aggregator is shared


# ---------------------------------------------------------------------------
# host/device overlap profiler
# ---------------------------------------------------------------------------
def test_overlap_profiler_accounting_and_metrics(obs_reset):
    ovl = OverlapProfiler(capacity=8)
    ovl.configure(enabled=True)
    ovl.observe("serving", total_s=0.010, enqueue_s=0.002, wait_s=0.005)
    reg = obs.get_registry()
    assert reg.gauge("dstpu_serving_host_plan_ms").value == \
        pytest.approx(3.0)
    assert reg.gauge("dstpu_serving_device_wait_ms").value == \
        pytest.approx(5.0)
    assert reg.histogram("dstpu_serving_device_wait_seconds").count >= 1
    last = ovl.last()
    assert last["kind"] == "serving" and last["dispatches"] == 1
    assert last["host_plan_s"] == pytest.approx(0.003)
    # a one-shot record has three of the five phases: the rest of the
    # wall time is plan
    assert (last["plan_s"], last["enqueue_s"], last["device_wait_s"]) == \
        pytest.approx((0.003, 0.002, 0.005))
    assert last["operands_s"] == last["apply_s"] == 0.0
    # inconsistent inputs clamp (never a negative plan or wait > wall)
    ovl.observe("train", total_s=0.001, enqueue_s=0.005, wait_s=0.005)
    last = ovl.last()
    assert last["kind"] == "train"
    assert last["device_wait_s"] == 0.0
    assert (last["enqueue_s"], last["total_s"]) == \
        pytest.approx((0.001, 0.001))
    assert reg.gauge("dstpu_train_device_wait_ms").value == 0.0
    # the serving begin/mark/end protocol records a real iteration whose
    # phases add up over both dispatches
    ovl.begin()
    for _ in range(2):
        ovl.mark(overlap.OPERANDS)
        ovl.mark(overlap.ENQUEUE)
        ovl.mark(overlap.DEVICE_WAIT)
        ovl.mark(overlap.APPLY)
        ovl.count_dispatch(3, 5, 12)
        ovl.mark(overlap.PLAN)
    ovl.mark(overlap.APPLY)
    ovl.end("serving")
    last = ovl.last()
    assert last["dispatches"] == 2 and last["n"] == 0
    assert (last["decode_rows"], last["chunk_rows"],
            last["rows_computed"]) == (6, 10, 24)
    assert sum(last[f"{p}_s"] for p in overlap.PHASES) == \
        pytest.approx(last["total_s"], abs=1e-7)
    assert all(last[f"{p}_s"] > 0 for p in overlap.PHASES)
    assert ovl.recorded == 3


def test_overlap_profiler_disabled_is_inert():
    ovl = OverlapProfiler()
    assert not ovl.enabled
    # neither ring is allocated and no annotation class is bound until
    # enable — the engines' guard (`if ovl.enabled:`) is the entire
    # disabled-path cost (tests/unit/test_overlap_phases.py counts the
    # clock reads and annotations of a disabled serving step: none)
    assert ovl._its.rows is None and ovl._reqs.rows is None
    assert ovl._annotation is None and ovl.recorded == 0
    assert ovl.last() is None
    recs, complete = ovl.iterations(0.0, float("inf"))
    assert len(recs) == 0 and complete
    # disabling again drops nothing and allocates nothing
    ovl.configure(enabled=False)
    assert ovl._its.rows is None and not ovl.enabled


def test_overlap_chrome_events_render_iteration_track(obs_reset):
    ovl = OverlapProfiler(capacity=8)
    ovl.configure(enabled=True, rank=0)
    ovl.observe("serving", total_s=0.010, enqueue_s=0.002, wait_s=0.005,
                t0_ns=1_000_000)
    evs = ovl.chrome_events(epoch_ns=0, rank=0)
    assert {e["pid"] for e in evs} == {2000}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "serving_iteration"
    assert x["dur"] == pytest.approx(10_000.0)
    assert x["args"]["device_wait_ms"] == pytest.approx(5.0)
    assert x["args"]["host_plan_ms"] == pytest.approx(3.0)
    assert {f"{p}_ms" for p in overlap.PHASES} <= set(x["args"])
    assert "overlap_frac" not in x["args"]
    assert any(e["ph"] == "C" and e["name"] == "serving_overlap"
               for e in evs)
    assert any(e["ph"] == "M" and e["args"].get("name")
               == "overlap profiler rank 0" for e in evs)


def test_inference_config_accepts_observability_block():
    """``init_inference`` takes the SAME observability block as
    training; None (the default) must leave the process-global
    singletons untouched."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    cfg = DeepSpeedInferenceConfig(
        observability={"metrics": {"enabled": True},
                       "overlap": {"enabled": True, "capacity": 16}})
    assert isinstance(cfg.observability, ObservabilityConfig)
    assert cfg.observability.overlap.capacity == 16
    assert DeepSpeedInferenceConfig().observability is None
    # the block's own validation still applies through this path
    with pytest.raises(Exception):
        DeepSpeedInferenceConfig(
            observability={"request_tracing": {"enabled": True}})


# ---------------------------------------------------------------------------
# end-to-end acceptance (slow): disaggregated fleet -> ONE merged trace
# ---------------------------------------------------------------------------
def _disagg_obs_engine(tmp_path):
    # serving engines pick the process-global observability singletons
    # up at build time — arm them BEFORE init_inference (the inference
    # config has no observability block; training's DeepSpeedConfig does)
    obs.configure(ObservabilityConfig(
        tracing={"enabled": True, "output_dir": str(tmp_path / "traces")},
        request_tracing={"enabled": True},
        metrics={"enabled": True},
        overlap={"enabled": True}), rank=0)
    cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=64, dtype=jnp.float32)
    serving = {"enabled": True, "kv_block_size": 4, "num_kv_blocks": 32,
               "max_batch_slots": 3, "prefill_chunk_tokens": 8,
               "max_preemptions": 4, "max_queue_depth": 16,
               "fleet": {"enabled": True, "replicas": 3,
                         "prefill_replicas": 1},
               "host_cache": {"enabled": True,
                              "dram_budget_bytes": 1 << 20,
                              "wire_bits": 0}}
    return ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 48, "temperature": 0.0,
        "replace_with_kernel_inject": False, "serving": serving})


_OBS_WAVE = [([1, 2, 3, 4, 5, 6, 7, 8, 9], dict(temperature=0.0)),
             ([10, 11, 12, 13, 14], dict(temperature=0.0)),
             ([22, 23, 24, 25, 26], dict(temperature=0.8, seed=7))]


@pytest.mark.slow
def test_disagg_fleet_merged_trace_with_failover(tmp_path, obs_reset):
    """THE acceptance e2e: a 2-class fleet serves a wave through the
    two-leg handoff, one decode replica is killed mid-decode, and the
    whole story — prefill leg, fabric publish, claim/promote, decode
    leg, failover replay — lands in ONE merged Perfetto file under a
    single fleet trace id with a validated flow chain.  The merged
    fleet metrics reproduce the per-replica ground-truth histograms
    bucket-for-bucket, and the serving overlap gauges populate."""
    eng = _disagg_obs_engine(tmp_path)
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    reqs = []
    sinks = []
    for prompt, samp in _OBS_WAVE:
        sink = StreamCollector()
        sinks.append(sink)
        reqs.append(fleet.submit(prompt, max_new_tokens=8,
                                 on_token=sink, **samp))
    # pump until a handed-off request is actually decoding (tokens
    # delivered), then kill its decode replica mid-stream
    victim = None
    for _ in range(256):
        fleet.pump()
        victim = next(
            (f for f in reqs if f.status is None and f.leg == "decode"
             and f.replica is not None
             and f.replica.role == "decode"
             and f.deduper.high_water > 0), None)
        if victim is not None:
            break
    assert victim is not None, "no request reached mid-decode"
    dead = victim.replica
    dead.mark_dead("chaos: injected decode-replica death (fleet-obs e2e)")
    fleet.run()

    assert dead.state is ReplicaState.DEAD
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert victim.failovers >= 1
    assert victim.replica is not dead
    assert fleet.fleet_counts["handoffs"] >= 1
    assert fleet.fleet_counts["failovers"] >= 1
    # token-exact through handoff AND failover
    for (prompt, samp), f, sink in zip(_OBS_WAVE, reqs, sinks):
        seed = samp.pop("seed", None)
        rng = jax.random.PRNGKey(seed) if seed is not None else None
        ref = np.asarray(eng.generate(
            np.asarray(prompt, np.int32)[None], max_new_tokens=8,
            rng=rng, **samp))[0]
        assert np.array_equal(f.output, ref), f.req_id
        assert sink.tokens == list(ref)
    for r in fleet.replicas:
        assert r.srv.decode_builds in (0, 2)

    # ---- ONE merged Perfetto trace, single trace id, flow arrows ----
    outdir = os.environ.get("DSTPU_FLEET_OBS_DIR") or str(tmp_path)
    trace_path = fleet.export_fleet_trace(
        os.path.join(outdir, "fleet_trace.json"))
    with open(trace_path) as f:
        doc = json.load(f)
    report = validate_fleet_trace(doc)
    for f in reqs:
        assert f.trace_id and f.trace_id.startswith("fleet-")
        assert f.trace_id in report
    # the victim's story: prefill leg + decode leg + failover replay
    assert report[victim.trace_id]["legs"] >= 3
    assert report[victim.trace_id]["flow_events"] >= \
        report[victim.trace_id]["legs"]
    vev = [e for e in doc["traceEvents"]
           if (e.get("args") or {}).get("trace_id") == victim.trace_id]
    names = {e["name"] for e in vev if e.get("ph") == "X"}
    assert "fabric_publish" in names
    assert {e["name"] for e in vev if e.get("ph") == "i"} >= \
        {"failover_resubmit", "terminal"}
    # the overlap iteration track rode the same flush
    assert any(e.get("pid") == 2000 and e.get("ph") == "X"
               and e.get("name") == "serving_iteration"
               for e in doc["traceEvents"])

    # ---- merged fleet metrics == per-replica ground truth ----
    prom_path = os.path.join(outdir, "fleet.prom")
    fleet.export_fleet_metrics(
        prometheus_path=prom_path,
        json_path=os.path.join(outdir, "fleet.json"))
    merged = fleet.aggregator.merged()
    ttft = merged["dstpu_serving_ttft_seconds"]
    mirrors = [r._m_ttft for r in fleet.replicas]
    truth = mirrors[0].merge(*mirrors[1:])
    assert ttft["count"] == truth.count >= len(reqs)
    for tag, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99)):
        assert ttft[tag] == pytest.approx(truth.quantile(q)), tag
    prom = open(prom_path).read()
    assert 'dstpu_fleet_replica_up{replica="' in prom
    assert 'fleet_class="decode"' in prom
    assert "dstpu_serving_ttft_seconds_p99" in prom

    # ---- serving overlap gauges populated ----
    reg = obs.get_registry()
    assert reg.histogram("dstpu_serving_host_plan_seconds").count > 0
    assert reg.histogram("dstpu_serving_device_wait_seconds").count > 0
    assert reg.gauge("dstpu_serving_device_wait_ms").value > 0.0
    assert get_overlap_profiler().recorded > 0


@pytest.mark.slow
def test_train_overlap_records_on_synced_steps(tmp_path, obs_reset):
    """Training side of the overlap acceptance: with the profiler armed
    every GAS-boundary step records a host-plan/enqueue/device-wait
    split; disabled, the profiler sees nothing from the same loop."""
    def tiny_engine(overlap):
        config = {
            "train_batch_size": 16, "gradient_accumulation_steps": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0,
            "observability": {
                "metrics": {"enabled": True},
                "overlap": {"enabled": overlap},
            },
        }
        cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                          vocab_size=64, max_seq_len=16,
                          dtype=jnp.float32)
        engine, _, _, _ = ds.initialize(model=TransformerLM(cfg),
                                        config=config)
        return engine

    def batch(seed):
        rs = np.random.RandomState(seed)
        return {"input_ids": rs.randint(0, 64, (16, 16), dtype=np.int32)}

    engine = tiny_engine(overlap=True)
    ovl = get_overlap_profiler()
    for i in range(4):
        engine.train_step(batch(i))
    assert ovl.recorded >= 2            # one record per GAS boundary
    assert ovl.last()["kind"] == "train"
    reg = obs.get_registry()
    assert reg.histogram("dstpu_train_device_wait_seconds").count >= 2
    assert reg.histogram("dstpu_train_host_plan_seconds").count >= 2
    assert reg.gauge("dstpu_train_device_wait_ms").value >= 0.0

    # disabled path: the same loop records NOTHING new
    engine2 = tiny_engine(overlap=False)
    assert not ovl.enabled
    before = ovl._its.n
    for i in range(2):
        engine2.train_step(batch(i))
    assert ovl._its.n == before
