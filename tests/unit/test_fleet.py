"""Resilient serving fleet suite (ISSUE 15): health-checked replicas
behind :class:`FleetRouter`, pinned on the robustness core — token-exact
failover.  Kill a replica mid-wave with staggered in-flight requests
(greedy AND seeded-sampled) and every stream that ends OK must be
token-identical to sequential ``generate()`` with zero duplicated and
zero dropped tokens at the client (the :class:`StreamDeduper` high-water
mark is the exactly-once filter).  Plus: drain completes running work
without terminalizing any of it, a live join becomes routable and
inherits warm prefixes through the shared host tier, placement trades
prefix affinity against queue depth, and SHED responses are absorbed
through the ``retry_after_s`` drain-rate hint instead of surfacing.

The ``chaos``-marked scenario also runs under the ``run_tests.sh``
fleet chaos matrix (transient ``serving.fleet.route`` /
fatal ``serving.fleet.replica_step`` plans via ``DSTPU_FAULTS``).
docs/serving.md "Fleet serving & failover" describes the semantics.
"""
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.elasticity import ReplicaLivenessMonitor
from deepspeed_tpu.inference.config import FleetConfig
from deepspeed_tpu.inference.serving import (FleetRouter, ReplicaHandle,
                                             ReplicaState, RequestStatus,
                                             StreamCollector, StreamDeduper,
                                             placement_score)
from deepspeed_tpu.inference.serving.engine import ServingEngine
from deepspeed_tpu.inference.serving.frontend.streaming import (
    StreamReplayError, TokenEvent)
from deepspeed_tpu.inference.serving.scheduler import estimate_retry_after_s
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability import (get_flight_recorder,
                                         get_request_tracer)
from deepspeed_tpu.runtime.resilience import (FaultInjector, RetryPolicy,
                                              install_fault_injector)
from deepspeed_tpu.runtime.resilience.heartbeat import beat

pytestmark = [pytest.mark.inference, pytest.mark.fleet]


@pytest.fixture
def injector():
    """A fresh empty injector tests add plans to; restored after."""
    fi = install_fault_injector(FaultInjector())
    yield fi
    install_fault_injector(FaultInjector())


@pytest.fixture
def env_injector():
    """Injector built from DSTPU_FAULTS (empty when unset) so the
    run_tests.sh fleet chaos matrix steers the scenario."""
    fi = install_fault_injector(FaultInjector.from_env())
    yield fi
    install_fault_injector(FaultInjector())


def ev(token, index, final=False, status=None, request=None):
    return TokenEvent(request=request, token=token, index=index,
                      status=status, final=final, tenant="default",
                      time_s=0.0, prev_time_s=None)


# ---------------------------------------------------------------------------
# fast units: score math, dedup filter, retry-after estimate, config
# ---------------------------------------------------------------------------
def test_placement_score_trades_affinity_against_queue():
    # a warm prefix is worth its token count; a queued request costs
    # queue_cost_tokens — affinity wins only past the imbalance it makes
    assert placement_score(64, 1) > placement_score(0, 0)
    assert placement_score(16, 2) < placement_score(0, 0)
    assert placement_score(0, 3) == -96.0
    assert placement_score(64, 1, affinity_weight=0.0) == -32.0
    assert placement_score(64, 1, queue_cost_tokens=100.0) == -36.0


def test_stream_deduper_exactly_once():
    d = StreamDeduper()
    assert d.admit(ev(5, 0)) is not None
    assert d.admit(ev(7, 1)) is not None
    assert d.delivered == [5, 7] and d.high_water == 2
    # replayed duplicates below the high-water mark are swallowed
    assert d.admit(ev(5, 0)) is None
    assert d.admit(ev(7, 1)) is None
    assert d.duplicates == 2 and d.delivered == [5, 7]
    # the replay continues exactly where delivery stopped
    assert d.admit(ev(9, 2)) is not None
    assert d.delivered == [5, 7, 9]
    # tokenless terminal events carry no index: pass through untouched
    term = ev(None, 3, final=True, status=RequestStatus.SHED)
    assert d.admit(term) is term


def test_stream_deduper_divergence_and_gap_are_loud():
    d = StreamDeduper()
    d.admit(ev(5, 0))
    with pytest.raises(StreamReplayError, match="diverged"):
        d.admit(ev(6, 0))            # replay disagrees with delivery
    with pytest.raises(StreamReplayError, match="gap"):
        d.admit(ev(8, 2))            # skipped index 1


def test_estimate_retry_after_bounds():
    assert estimate_retry_after_s(None) == 0.05          # no signal: floor
    assert estimate_retry_after_s(0.0) == 0.05
    assert estimate_retry_after_s(0.001) == 0.05         # floor clamps
    assert estimate_retry_after_s(0.4) == 0.4            # drain rate rules
    assert estimate_retry_after_s(1e6) == 30.0           # cap clamps


def test_fleet_config_validation():
    cfg = FleetConfig()
    assert cfg.enabled is False and cfg.replicas == 2
    assert cfg.heartbeat_timeout_s == 0.0                # staleness off
    with pytest.raises(ValueError):
        FleetConfig(replicas=0)
    with pytest.raises(ValueError):
        FleetConfig(heartbeat_interval_s=0.0)
    with pytest.raises(ValueError):
        # a timeout tighter than two beat intervals kills healthy replicas
        FleetConfig(heartbeat_interval_s=1.0, heartbeat_timeout_s=1.5)
    with pytest.raises(ValueError):
        FleetConfig(affinity_weight=-1.0)
    with pytest.raises(ValueError):
        FleetConfig(max_failovers=-1)
    with pytest.raises(ValueError):
        FleetConfig(retry_base_delay_s=1.0, retry_max_delay_s=0.5)


def test_replica_liveness_monitor(tmp_path):
    mon = ReplicaLivenessMonitor(str(tmp_path / "beats"), timeout_s=30.0)
    p = mon.path_for("r0")
    assert p.endswith("r0.heartbeat")
    # a replica that never checked in is indistinguishable from hung
    assert mon.stale_replicas(["r0"]) == ["r0"]
    beat(p)
    assert mon.stale_replicas(["r0"]) == []


def test_scheduler_stamps_retry_after_on_shed():
    """Satellite 2: the SHED terminal carries the drain-rate hint."""
    from deepspeed_tpu.inference.serving.block_allocator import \
        PagedBlockAllocator
    from deepspeed_tpu.inference.serving.scheduler import (
        ContinuousBatchingScheduler, Request)
    sched = ContinuousBatchingScheduler(
        num_slots=2, allocator=PagedBlockAllocator(16, 4),
        max_blocks_per_seq=8, max_queue_depth=1)
    sched.retry_after_hint = lambda: 0.25
    sched.submit(Request(prompt=[1, 2], max_new_tokens=2))
    shed = sched.submit(Request(prompt=[3, 4], max_new_tokens=2))
    assert shed.status is RequestStatus.SHED
    assert shed.retry_after_s == 0.25


# ---------------------------------------------------------------------------
# fast units: router placement + shed backoff over stub replicas
# ---------------------------------------------------------------------------
class _StubReplica:
    """Duck-typed ReplicaHandle: scripted coverage / queue depth, and a
    shed budget so the router's absorb-and-retry path runs without an
    engine."""

    def __init__(self, rid, cov=0, depth=0, shed_next=0,
                 retry_after=None):
        self.replica_id = rid
        self.state = ReplicaState.HEALTHY
        self.cov, self.depth = cov, depth
        self.shed_next, self.retry_after = shed_next, retry_after
        self.srv = types.SimpleNamespace(host_cache=None)
        self.specs = []

    @property
    def routable(self):
        return self.state is ReplicaState.HEALTHY

    @property
    def alive(self):
        return self.state in (ReplicaState.STARTING, ReplicaState.HEALTHY,
                              ReplicaState.DRAINING)

    @property
    def threaded(self):
        return False

    @property
    def queue_depth(self):
        return self.depth

    def prefix_coverage(self, toks):
        return self.cov

    def join(self):
        self.state = ReplicaState.HEALTHY

    def has_work(self):
        return False

    def beat_stale(self):
        return False

    def step(self):
        return False

    def in_flight(self):
        return []

    def submit(self, spec):
        self.specs.append(spec)
        if self.shed_next:
            self.shed_next -= 1
            fake = types.SimpleNamespace(retry_after_s=self.retry_after,
                                         error="shed")
            spec.on_token(ev(None, 0, final=True,
                             status=RequestStatus.SHED, request=fake))
            return fake
        req = types.SimpleNamespace(prng_key=(7, 9), retry_after_s=None,
                                    error=None)
        if spec.on_submitted is not None:
            spec.on_submitted(req)
        return req


def test_router_places_by_affinity_then_queue():
    warm = _StubReplica("warm", cov=100, depth=1)
    cold = _StubReplica("cold", cov=0, depth=0)
    fleet = FleetRouter([warm, cold])
    freq = fleet.submit([1, 2, 3, 4])
    assert freq.replica is warm          # 100 - 32 > 0
    # a thin warm prefix does not justify joining a deeper queue
    warm.cov, warm.depth = 16, 2
    assert fleet.submit([1, 2, 3, 4]).replica is cold
    # the first placement pins the fold-in key for every later replay
    assert freq.prng_key == (7, 9)


def test_router_transient_route_fault_degrades_to_queue_depth(injector):
    injector.add_plan("serving.fleet.route", "fail", at=1)
    warm = _StubReplica("warm", cov=1000, depth=1)
    cold = _StubReplica("cold", cov=0, depth=0)
    fleet = FleetRouter([warm, cold])
    # affinity is ignored for THIS decision only: lowest queue wins
    assert fleet.submit([1, 2, 3]).replica is cold
    assert fleet.submit([1, 2, 3]).replica is warm   # affinity is back


def test_router_fatal_route_fault_fails_the_one_request(injector):
    injector.add_plan("serving.fleet.route", "fatal", at=1)
    fleet = FleetRouter([_StubReplica("r0")])
    sink = StreamCollector()
    freq = fleet.submit([1, 2], on_token=sink)
    assert freq.status is RequestStatus.FAILED
    assert "serving.fleet.route" in freq.error
    # the client stream closed with a tokenless terminal event
    assert sink.finished and sink.tokens == []
    # the fleet itself is unharmed
    assert fleet.submit([1, 2]).replica is not None


def test_router_unroutable_fleet_pends_then_places():
    t = [100.0]
    r = _StubReplica("r0")
    r.state = ReplicaState.DRAINING      # alive but not routable
    fleet = FleetRouter([r], clock=lambda: t[0],
                        retry_policy=RetryPolicy(base_delay_s=0.5,
                                                 max_delay_s=0.5,
                                                 jitter=0.0))
    freq = fleet.submit([1, 2])
    assert freq.status is None and freq.replica is None
    fleet.pump()
    assert not r.specs                   # backoff not yet expired
    r.state = ReplicaState.HEALTHY
    t[0] += 1.0
    fleet.pump()
    assert freq.replica is r             # re-placed once routable + due


def test_router_dead_fleet_fails_fast():
    r = _StubReplica("r0")
    r.state = ReplicaState.DEAD
    fleet = FleetRouter([r])
    freq = fleet.submit([1, 2])
    assert freq.status is RequestStatus.FAILED
    assert "no live replicas" in freq.error


def test_router_absorbs_shed_with_retry_after_floor():
    """Satellite 2 end to end at the router: the drain-rate hint floors
    the jittered policy delay, and the retried placement succeeds."""
    t = [0.0]
    r = _StubReplica("r0", shed_next=1, retry_after=0.5)
    fleet = FleetRouter([r], clock=lambda: t[0],
                        retry_policy=RetryPolicy(max_attempts=3,
                                                 base_delay_s=0.01,
                                                 max_delay_s=0.02,
                                                 jitter=0.0))
    freq = fleet.submit([1, 2, 3])
    assert freq.status is None           # shed absorbed, NOT terminal
    assert fleet.fleet_counts["shed_retries"] == 1
    assert freq.retry_at == pytest.approx(0.5)   # hint > policy delay
    t[0] = 0.4
    fleet.pump()
    assert len(r.specs) == 1             # still backing off
    t[0] = 0.6
    fleet.pump()
    assert len(r.specs) == 2 and freq.replica is r
    assert freq.prng_key == (7, 9)


def test_router_shed_budget_exhausts_to_terminal_shed():
    r = _StubReplica("r0", shed_next=99)
    t = [0.0]
    fleet = FleetRouter([r], clock=lambda: t[0],
                        retry_policy=RetryPolicy(max_attempts=2,
                                                 base_delay_s=0.01,
                                                 max_delay_s=0.01,
                                                 jitter=0.0))
    sink = StreamCollector()
    freq = fleet.submit([1, 2], on_token=sink)
    for _ in range(10):
        if freq.status is not None:
            break
        t[0] += 1.0
        fleet.pump()
    assert freq.status is RequestStatus.SHED
    assert "retry budget" in freq.error
    assert sink.finished and sink.events[-1].status is RequestStatus.SHED
    assert fleet.fleet_counts["shed_retries"] == 3   # 2 retries + giveup


# ---------------------------------------------------------------------------
# engine-backed end-to-ends (slow): parity, failover, drain, join, chaos
# ---------------------------------------------------------------------------
def fleet_engine(replicas=2, slots=3, num_kv_blocks=32, max_queue_depth=16,
                 host_cache=True, **fleet_kw):
    cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=64, dtype=jnp.float32)
    serving = {"enabled": True, "kv_block_size": 4,
               "num_kv_blocks": num_kv_blocks,
               "max_batch_slots": slots,
               "prefill_chunk_tokens": 8,
               "max_preemptions": 4,
               "max_queue_depth": max_queue_depth,
               "fleet": {"enabled": True, "replicas": replicas,
                         **fleet_kw}}
    if host_cache:
        # wire_bits 0 keeps spill/promote LOSSLESS: failover + warm-join
        # streams must stay token-exact whatever tier the KV lives in
        serving["host_cache"] = {"enabled": True,
                                 "dram_budget_bytes": 1 << 20,
                                 "wire_bits": 0}
    return ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 48, "temperature": 0.0,
        "replace_with_kernel_inject": False, "serving": serving})


def _generate(eng, prompt, n, seed=None, **samp):
    rng = jax.random.PRNGKey(seed) if seed is not None else None
    return np.asarray(eng.generate(np.asarray(prompt, np.int32)[None],
                                   max_new_tokens=n, rng=rng, **samp))[0]


WAVE = [([1, 2, 3], dict(temperature=0.0)),
        ([4, 5], dict(temperature=0.0)),
        ([6, 7, 8, 9], dict(temperature=0.0)),
        ([10, 11], dict(temperature=0.8, seed=7)),
        ([12, 13, 14], dict(temperature=0.6, top_k=12, seed=9)),
        ([15, 16], dict(temperature=0.9, top_p=0.9, seed=11))]


def submit_wave(fleet, wave, n=8):
    sinks, reqs = [], []
    for prompt, samp in wave:
        sink = StreamCollector()
        sinks.append(sink)
        reqs.append(fleet.submit(prompt, max_new_tokens=n,
                                 on_token=sink, **samp))
    return reqs, sinks


def assert_wave_exact(eng, fleet, wave, reqs, sinks, n=8):
    """Every OK stream token-identical to its (seeded) generate() twin;
    the client saw each token exactly once, in order."""
    assert all(f.done for f in reqs), "in-flight after drain"
    for (prompt, samp), freq, sink in zip(wave, reqs, sinks):
        if freq.status is not RequestStatus.OK:
            continue
        ref = _generate(eng, prompt, n, **samp)
        assert np.array_equal(freq.output, ref), \
            f"{freq.req_id}: fleet {freq.output} != generate {list(ref)}"
        # exactly-once at the CLIENT: contiguous indices, no dup/drop
        assert sink.tokens == freq.output
        toks = [e for e in sink.events if e.token is not None]
        assert [e.index for e in toks] == list(range(len(freq.output)))
        assert sink.finished
    for r in fleet.replicas:
        if r.state is not ReplicaState.DEAD:
            assert r.srv.decode_builds == 2
            r.srv.allocator.assert_consistent()
            assert r.srv.allocator.num_used == 0


@pytest.mark.slow
def test_fleet_parity_across_replicas_no_faults():
    """Baseline: a mixed greedy + seeded-sampled wave routed across two
    replicas is token-identical to sequential generate() — placement
    must be invisible to the stream."""
    eng = fleet_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    reqs, sinks = submit_wave(fleet, WAVE)
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, WAVE, reqs, sinks)
    # placement actually spread the wave (cold prompts go by queue depth)
    assert len({f.replica.replica_id for f in reqs}) == 2
    assert fleet.fleet_counts["failovers"] == 0


@pytest.mark.slow
def test_fleet_failover_token_exact(injector, tmp_path):
    """The acceptance pin: a fatal at ``serving.fleet.replica_step``
    kills r0 mid-wave with staggered in-flight requests; every request
    fails over and still streams token-identical to generate() with
    exactly-once client delivery; the dead replica seals its
    flight-recorder bundle — and the bundle's fleet trace ids are
    exactly the in-flight set the router resubmits."""
    from deepspeed_tpu.runtime.resilience.integrity import verify_manifest
    injector.add_plan("serving.fleet.replica_step", "fatal", at=5)
    fr = get_flight_recorder()
    fr.configure(enabled=True, capacity=64,
                 output_dir=str(tmp_path / "fr"))
    fr.min_dump_interval_s = 0.0
    # arm the request tracer so the router mints fleet trace ids — the
    # post-mortem bundle must name the trace of every victim it strands
    rt = get_request_tracer()
    rt.configure(enabled=True, capacity=64)
    try:
        eng = fleet_engine()
        fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
        # staggered: half the wave in flight before the kill, half after
        reqs, sinks = submit_wave(fleet, WAVE[:3])
        fleet.pump()
        fleet.pump()                     # site calls 1..4: both healthy
        late_reqs, late_sinks = submit_wave(fleet, WAVE[3:])
        reqs, sinks = reqs + late_reqs, sinks + late_sinks
        fleet.run()                      # call 5 = r0's next step: fatal

        assert fleet.replica("r0").state is ReplicaState.DEAD
        assert "serving.fleet.replica_step" in \
            fleet.replica("r0").death_reason
        assert fleet.fleet_counts["dead_replicas"] == 1
        assert fleet.fleet_counts["failovers"] >= 1
        # zero dropped, zero double-delivered: every request OK + exact
        assert all(f.status is RequestStatus.OK for f in reqs)
        assert_wave_exact(eng, fleet, WAVE, reqs, sinks)
        # the replay re-emitted already-delivered tokens; the dedup
        # high-water mark swallowed every one of them
        assert fleet.fleet_counts["replayed_tokens"] >= 1
        # failed-over requests kept their ORIGINAL fold-in key
        for f in reqs:
            if f.failovers:
                assert f.replica.replica_id != "r0"
                assert tuple(f.engine_req.prng_key) == f.prng_key
        # the black box: r0's post-mortem bundle sealed + verifiable
        bundle = fr.last_bundle
        assert bundle is not None and os.path.isdir(bundle)
        ok, problems = verify_manifest(bundle)
        assert ok, problems
        with open(os.path.join(bundle, "reason.json")) as fh:
            reason = json.load(fh)
        assert reason["reason"] == "replica_dead"
        assert reason["extra"]["replica"] == "r0"
        assert reason["extra"]["in_flight"], "kill was not mid-wave"
        # the sealed trace ids ARE the resubmitted set: every request
        # stranded on r0 (== every request that failed over) appears in
        # the bundle under its fleet trace id, and nothing else does
        sealed = reason["extra"]["trace_ids"]
        assert sealed and all(t and t.startswith("fleet-")
                              for t in sealed.values()), sealed
        assert set(sealed.values()) == \
            {f.trace_id for f in reqs if f.failovers}
        # the recent fleet-event ring rode along: r0's death is on it
        with open(os.path.join(bundle, "fleet_events.json")) as fh:
            fleet_events = json.load(fh)
        assert any(e.get("fleet_event") == "replica_dead"
                   and e.get("replica") == "r0" for e in fleet_events)
        # the failover itself is in the snapshot ring for the NEXT dump
        assert any(s.get("fleet_event") == "failover"
                   for s in fr.snapshots() if s)
    finally:
        fr.configure(enabled=False)
        rt.configure(enabled=False)
        rt.reset()


@pytest.mark.slow
def test_fleet_drain_completes_running_work():
    eng = fleet_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    reqs, sinks = submit_wave(fleet, WAVE)
    fleet.pump()                          # some work actually running
    target = next(f.replica for f in reqs if f.status is None)
    victims = [f for f in reqs if f.replica is target]
    assert victims, "nothing in flight on the drain target"
    fleet.drain(target)
    assert target.state is ReplicaState.RETIRED
    assert not target.routable
    assert fleet.fleet_counts["drains"] == 1
    # the drain terminalized NOTHING: every request it was running
    # finished OK on that same replica through the normal lifecycle
    for f in victims:
        assert f.status is RequestStatus.OK
        assert f.failovers == 0 and f.replica is target
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, WAVE, reqs, sinks)


@pytest.mark.slow
def test_fleet_join_becomes_routable_and_inherits_warm_prefixes():
    """Live join: a cold replica built against the shared host tier is
    immediately routable and already covers prefixes the fleet spilled
    — warmth travels as content-addressed digests, not device state."""
    eng = fleet_engine(replicas=1, num_kv_blocks=12, slots=2)
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    warm = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    sink0 = StreamCollector()
    fleet.submit(warm, max_new_tokens=8, on_token=sink0)
    fleet.run()
    # filler traffic evicts the warm chain out of the 12-block pool —
    # eviction spills full cached blocks into the SHARED host tier
    for p in ([20, 21, 22, 23, 24], [30, 31, 32, 33, 34],
              [40, 41, 42, 43, 44], [50, 51, 52, 53, 54]):
        fleet.submit(p, max_new_tokens=8)
    fleet.run()

    srv2 = ServingEngine(eng, rng=jax.random.PRNGKey(0),
                         shared_host_cache=fleet.shared_host_cache)
    h = ReplicaHandle("rj", srv2)
    assert not h.routable                 # STARTING until the join
    fleet.join(h)
    assert h.routable and h in fleet.routable_replicas
    assert fleet.fleet_counts["joins"] == 1
    # the joiner never served a token, yet covers the spilled prefix
    assert h.prefix_coverage(warm) >= 4
    sink = StreamCollector()
    freq = fleet.submit(warm, max_new_tokens=8, on_token=sink)
    fleet.run()
    assert freq.status is RequestStatus.OK
    ref = _generate(eng, warm, 8, temperature=0.0)
    assert np.array_equal(freq.output, ref)
    assert sink.tokens == list(ref)


@pytest.mark.slow
def test_fleet_absorbs_engine_shed_and_recovers():
    """Oversubscribe two tiny replicas: submit-time SHEDs are absorbed
    by the router's retry_after backoff and every request still ends
    OK + token-exact once queues drain."""
    eng = fleet_engine(slots=2, max_queue_depth=2,
                       retry_base_delay_s=0.01, retry_max_delay_s=0.05)
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    fleet.retry_policy = RetryPolicy(max_attempts=10, base_delay_s=0.01,
                                     max_delay_s=0.05, jitter=0.0)
    prompts = [[i + 1, i + 2, i + 3] for i in range(0, 30, 3)]
    sinks, reqs = [], []
    for p in prompts:
        sink = StreamCollector()
        sinks.append(sink)
        reqs.append(fleet.submit(p, max_new_tokens=8, on_token=sink))
    # 10 submissions into 2x(2 slots + 2 queue) capacity MUST shed
    assert fleet.fleet_counts["shed_retries"] >= 1
    assert all(f.status is None for f in reqs), \
        "a shed surfaced as terminal instead of being absorbed"
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    for p, f, sink in zip(prompts, reqs, sinks):
        ref = _generate(eng, p, 8, temperature=0.0)
        assert np.array_equal(f.output, ref)
        assert sink.tokens == list(ref)


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_chaos_wave(env_injector):
    """The matrix scenario (run_tests.sh replays it under transient
    ``serving.fleet.route`` and fatal ``serving.fleet.replica_step``
    plans): a staggered greedy wave over two replicas, then a live
    drain — whatever the fault schedule, every stream is token-exact,
    exactly-once, and the drain terminalizes nothing."""
    eng = fleet_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    wave = [([i + 1, i + 2, i + 3], dict(temperature=0.0))
            for i in range(0, 18, 3)]
    reqs, sinks = submit_wave(fleet, wave[:4])
    fleet.pump()
    fleet.pump()
    late_reqs, late_sinks = submit_wave(fleet, wave[4:])
    reqs, sinks = reqs + late_reqs, sinks + late_sinks
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, wave, reqs, sinks)
    dead = [r for r in fleet.replicas if r.state is ReplicaState.DEAD]
    assert fleet.fleet_counts["dead_replicas"] == len(dead)
    if dead:
        assert fleet.fleet_counts["failovers"] >= 1
    # live drain of a (still-)healthy replica under the same schedule
    victim = fleet.routable_replicas[-1]
    extra, extra_sinks = submit_wave(fleet, wave[:2])
    fleet.pump()
    fleet.drain(victim)
    assert victim.state is ReplicaState.RETIRED
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in extra)
    assert_wave_exact(eng, fleet, wave[:2], extra, extra_sinks)


# ---------------------------------------------------------------------------
# satellite (ISSUE 16): the high-water mark must survive a SECOND failover
# ---------------------------------------------------------------------------
def test_stream_deduper_survives_double_failover_replay():
    """Regression: after a first failover's replay + new progress, a
    second failover replays the union of both deliveries — the mark
    must reflect everything the client has seen, not just the first
    replica's output."""
    d = StreamDeduper()
    for i, tok in enumerate([5, 7, 9]):
        assert d.admit(ev(tok, i)) is not None
    # first failover: full replay swallowed, then new progress
    for i, tok in enumerate([5, 7, 9]):
        assert d.admit(ev(tok, i)) is None
    assert d.admit(ev(11, 3)) is not None
    assert d.high_water == 4
    # second failover: the replay now spans BOTH replicas' deliveries
    for i, tok in enumerate([5, 7, 9, 11]):
        assert d.admit(ev(tok, i)) is None
    assert d.admit(ev(13, 4)) is not None
    assert d.delivered == [5, 7, 9, 11, 13]
    assert d.high_water == 5 and d.duplicates == 7


def _kill_on_next_step(fleet, injector, target):
    """Arm a fatal so ``target`` dies on ITS next iteration: site calls
    advance once per live replica per pump, in replica-list order."""
    stepping = [r for r in fleet.replicas
                if r.state in (ReplicaState.HEALTHY, ReplicaState.DRAINING)]
    pos = stepping.index(target) + 1
    calls = injector.calls.get("serving.fleet.replica_step", 0)
    injector.add_plan("serving.fleet.replica_step", "fatal",
                      at=calls + pos)


@pytest.mark.slow
def test_fleet_double_failover_token_exact(injector):
    """Kill the replica serving a request, then kill the replica its
    replay landed on: the twice-failed-over stream is still
    token-identical to generate() with exactly-once delivery — the
    second replay dedupes against the union high-water mark."""
    eng = fleet_engine(replicas=3)
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    reqs, sinks = submit_wave(fleet, WAVE)
    fleet.pump()
    fleet.pump()                          # tokens flowing on all replicas
    target = next(f for f in reqs if f.status is None)
    first = target.replica
    _kill_on_next_step(fleet, injector, first)
    fleet.pump()                          # death + failover in one round
    assert first.state is ReplicaState.DEAD
    assert target.failovers == 1 and target.replica is not first
    fleet.pump()                          # the replay makes progress
    assert target.status is None, "kill window closed too fast"
    second = target.replica
    _kill_on_next_step(fleet, injector, second)
    fleet.pump()
    assert second.state is ReplicaState.DEAD
    assert target.failovers == 2
    fleet.run()
    assert fleet.fleet_counts["dead_replicas"] == 2
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, WAVE, reqs, sinks)
    # the double failover kept the ORIGINAL fold-in key end to end
    assert tuple(target.engine_req.prng_key) == target.prng_key
    assert target.replica.replica_id not in (first.replica_id,
                                             second.replica_id)
