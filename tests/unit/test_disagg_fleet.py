"""Disaggregated prefill/decode fleet suite (ISSUE 16): the KV fabric
(prefill workers publish finished chains into the shared host tier,
decode replicas claim-and-promote them), the router's class-aware
two-leg placement with token-exact handoff, and the SLO-driven
autoscaler that closes the burn-rate loop.

Pinned here:

  * fabric semantics — crc-verified claim, publish faults mutate
    nothing, fatal claim faults quarantine the entry, orphan reaping is
    publisher-scoped, and published entries never violate the host
    tier's slot/disjointness invariants;
  * placement — fabric-resident coverage is credited at the promote
    discount (satellite: host warmth beats cold, loses to equal device
    warmth), and pre-split replica handles still route;
  * autoscaler policy on a synthetic clock — burn-rate ramp scales up
    BEFORE the SLO breach lands in a histogram, quiet tails scale down
    behind the cooldown, the chip budget denies (not defers), the last
    healthy replica of a class is never drained, and an alert storm
    collapses to one bounded action per cooldown window;
  * end to end — a disaggregated fleet streams token-identical to
    sequential ``generate()`` through the handoff, degrades to
    decode-side recompute under publish/claim faults (never a wrong
    token, never a stall), and leaves zero orphaned fabric entries
    after a prefill worker dies or drains.

The ``chaos``-marked scenario also runs under the ``run_tests.sh``
disagg chaos matrix (transient ``serving.fabric.publish``, fatal
``serving.fabric.claim``, fatal ``serving.fleet.scale`` plans via
``DSTPU_FAULTS``).  docs/serving.md "Disaggregated fleet &
autoscaling" describes the semantics.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.config import FleetConfig
from deepspeed_tpu.inference.serving import (FleetAutoscaler, FleetRouter,
                                             HostTierCache, ReplicaHandle,
                                             ReplicaState, RequestStatus,
                                             StreamCollector,
                                             placement_score)
from deepspeed_tpu.inference.serving.engine import ServingEngine
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability.slo import (KIND_ITL, KIND_TTFT, SloAlert,
                                             SloMonitor)
from deepspeed_tpu.runtime.resilience import (FaultInjector,
                                              install_fault_injector)
from deepspeed_tpu.runtime.resilience.errors import TransientIOError

pytestmark = [pytest.mark.inference, pytest.mark.disagg]


@pytest.fixture
def injector():
    """A fresh empty injector tests add plans to; restored after."""
    fi = install_fault_injector(FaultInjector())
    yield fi
    install_fault_injector(FaultInjector())


@pytest.fixture
def env_injector():
    """Injector built from DSTPU_FAULTS (empty when unset) so the
    run_tests.sh disagg chaos matrix steers the scenario."""
    fi = install_fault_injector(FaultInjector.from_env())
    yield fi
    install_fault_injector(FaultInjector())


# ---------------------------------------------------------------------------
# fast units: the KV fabric over HostTierCache
# ---------------------------------------------------------------------------
def _cache(dram_slots=4, entry=64):
    return HostTierCache(entry_nbytes=entry, dram_slots=dram_slots)


def _payload(seed, entry=64):
    return (np.arange(entry, dtype=np.uint8) + seed) % 251


def test_fabric_publish_claim_roundtrip():
    hc = _cache()
    pay = _payload(1)
    hc.publish(b"d1", pay, publisher="p0")
    assert hc.published_total == 1
    assert hc.published_entries() == 1
    assert hc.published_entries("p0") == 1 and hc.published_entries("px") == 0
    got = hc.claim(b"d1")
    assert got is not None and np.array_equal(got, pay)
    # the claim consumed the published record and the entry itself
    assert hc.published_entries() == 0 and not hc.contains(b"d1")
    assert hc.corrupt_dropped_total == 0
    hc.assert_consistent()


def test_fabric_claim_drops_corrupt_payload():
    hc = _cache()
    hc.publish(b"d1", _payload(1), publisher="p0")
    # flip the stored bytes behind the crc's back (a torn fabric write)
    tier = hc._tiers[0]
    slot = tier.lru[b"d1"]
    tier.store.write_slot(slot, _payload(99))
    assert hc.claim(b"d1") is None       # dropped, reads as a cold miss
    assert hc.corrupt_dropped_total == 1
    assert not hc.contains(b"d1") and hc.published_entries() == 0
    hc.assert_consistent()


def test_fabric_publish_fault_mutates_nothing(injector):
    injector.add_plan("serving.fabric.publish", "fail", at=1)
    hc = _cache()
    with pytest.raises(TransientIOError):
        hc.publish(b"d1", _payload(1), publisher="p0")
    # the site fires BEFORE any state change: the fabric is untouched
    assert hc.published_total == 0 and hc.published_entries() == 0
    assert not hc.contains(b"d1")
    hc.assert_consistent()
    # the retry (call 2, past the plan) lands normally
    hc.publish(b"d1", _payload(1), publisher="p0")
    assert hc.published_entries() == 1


def test_fabric_claim_fault_semantics(injector):
    hc = _cache()
    hc.publish(b"d1", _payload(1), publisher="p0")
    # transient: miss, entry stays resident — a later claim may succeed
    injector.add_plan("serving.fabric.claim", "fail", at=1)
    assert hc.claim(b"d1") is None
    assert hc.claim_faults_total == 1 and hc.contains(b"d1")
    # fatal: miss AND the suspect entry is quarantined (discarded)
    injector.add_plan("serving.fabric.claim", "fatal", at=2)
    assert hc.claim(b"d1") is None
    assert hc.claim_faults_total == 2 and not hc.contains(b"d1")
    assert hc.published_entries() == 0
    hc.assert_consistent()


def test_fabric_reap_orphans_is_publisher_scoped():
    hc = _cache()
    hc.publish(b"a", _payload(1), publisher="p0")
    hc.publish(b"b", _payload(2), publisher="p0")
    hc.publish(b"c", _payload(3), publisher="p1")
    assert hc.reap_orphans("p0") == 2
    assert hc.orphans_reaped_total == 2
    assert hc.published_entries() == 1 and hc.contains(b"c")
    # fabric-wide sweep takes the rest
    assert hc.reap_orphans() == 1
    assert hc.published_entries() == 0
    hc.assert_consistent()


def test_fabric_eviction_untracks_published_digest():
    hc = _cache(dram_slots=2)
    hc.publish(b"a", _payload(1), publisher="p0")
    hc.publish(b"b", _payload(2), publisher="p0")
    hc.publish(b"c", _payload(3), publisher="p0")  # evicts LRU "a"
    assert hc.evictions_total == 1
    assert hc.published_entries() == 2 and not hc.contains(b"a")
    # no dangling published record survived the eviction
    hc.assert_consistent()


def test_fabric_published_exempt_from_device_cross_check():
    hc = _cache()
    hc.publish(b"pub", _payload(1), publisher="p0")
    hc.put(b"spill", _payload(2))
    # a published digest may coexist with a device copy on ANOTHER
    # replica (content-addressed transport) — no violation
    hc.assert_consistent(device_digests={b"pub"})
    # a plain spilled digest must NOT: spill/promote disjointness holds
    with pytest.raises(AssertionError, match="device radix"):
        hc.assert_consistent(device_digests={b"spill"})
    # a published record with no resident entry is a dangling tracker
    hc._published[b"ghost"] = (None, 0)
    with pytest.raises(AssertionError, match="not.*resident"):
        hc.assert_consistent()


# ---------------------------------------------------------------------------
# fast units: placement credits fabric coverage at the promote discount
# ---------------------------------------------------------------------------
def test_placement_score_discounts_fabric_coverage():
    """Satellite pin: host/fabric-resident chains count toward affinity,
    discounted by the promote cost — warm-but-remote beats cold, loses
    to equally warm device residency."""
    assert placement_score(0, 0, host_covered_tokens=64) == 32.0
    assert placement_score(64, 0) \
        > placement_score(0, 0, host_covered_tokens=64) \
        > placement_score(0, 0)
    # the discount knob: 0 ignores fabric warmth entirely
    assert placement_score(0, 0, host_covered_tokens=64,
                           promote_discount=0.0) == 0.0
    # fabric warmth can justify joining a shallow queue
    assert placement_score(0, 1, host_covered_tokens=128) \
        > placement_score(0, 0)


class _SplitStub:
    """Duck-typed replica with split (device, host) coverage."""

    def __init__(self, rid, dev=0, host=0, depth=0, role="mixed"):
        self.replica_id, self.role = rid, role
        self.state = ReplicaState.HEALTHY
        self.dev, self.host, self.depth = dev, host, depth
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

    def prefix_coverage(self, toks, split=False):
        return (self.dev, self.host) if split else self.dev + self.host

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
        req = types.SimpleNamespace(prng_key=(7, 9), retry_after_s=None,
                                    error=None)
        if spec.on_submitted is not None:
            spec.on_submitted(req)
        return req


class _LegacyStub(_SplitStub):
    """Pre-split handle: positional-only coverage (the router must fall
    back to treating everything as device-resident)."""

    def prefix_coverage(self, toks):
        return self.dev


def test_router_credits_fabric_coverage_discounted():
    warm = _SplitStub("warm", dev=0, host=100, depth=1)
    cold = _SplitStub("cold")
    fleet = FleetRouter([warm, cold])
    # 0.5 * 100 - 32 = 18 > 0: fabric warmth wins the placement
    assert fleet.submit([1, 2, 3, 4]).replica is warm
    # a steep promote cost flips the same decision
    fleet2 = FleetRouter([_SplitStub("warm", host=100, depth=1),
                          _SplitStub("cold")], promote_discount=0.1)
    assert fleet2.submit([1, 2, 3, 4]).replica.replica_id == "cold"


def test_router_handles_presplit_coverage_handles():
    warm = _LegacyStub("warm", dev=100, depth=1)
    cold = _LegacyStub("cold")
    fleet = FleetRouter([warm, cold])
    assert fleet.submit([1, 2, 3, 4]).replica is warm


def test_fleet_config_disagg_validation():
    cfg = FleetConfig()
    assert cfg.prefill_replicas == 0 and cfg.promote_discount == 0.5
    with pytest.raises(ValueError):
        # a fleet of pure publishers can never stream a token
        FleetConfig(replicas=2, prefill_replicas=2)
    with pytest.raises(ValueError):
        FleetConfig(prefill_replicas=-1)
    with pytest.raises(ValueError):
        FleetConfig(promote_discount=1.5)
    with pytest.raises(ValueError):
        FleetConfig(chip_budget=0)
    with pytest.raises(ValueError):
        FleetConfig(scale_up_cooldown_s=0.0)
    with pytest.raises(ValueError):
        FleetConfig(queue_low=4.0, queue_high=2.0)
    with pytest.raises(ValueError):
        FleetConfig(quiet_s=-1.0)


# ---------------------------------------------------------------------------
# fast units: autoscaler policy on a synthetic clock (stub fleet)
# ---------------------------------------------------------------------------
class _ScaleReplica:
    def __init__(self, rid, role="mixed", depth=0):
        self.replica_id, self.role = rid, role
        self.state = ReplicaState.HEALTHY
        self.depth = depth

    @property
    def alive(self):
        return self.state in (ReplicaState.STARTING, ReplicaState.HEALTHY,
                              ReplicaState.DRAINING)

    @property
    def queue_depth(self):
        return self.depth

    def has_work(self):
        return self.depth > 0

    def join(self):
        self.state = ReplicaState.HEALTHY

    def begin_drain(self):
        if self.state is ReplicaState.HEALTHY:
            self.state = ReplicaState.DRAINING

    def retire(self):
        self.state = ReplicaState.RETIRED


class _StubFleet:
    """The router surface the autoscaler actually touches."""

    def __init__(self, replicas):
        self.replicas = list(replicas)
        self.fleet_counts = {"drains": 0}
        self._m_drains = types.SimpleNamespace(inc=lambda: None)
        self.reaped = []

    def join(self, handle):
        handle.join()
        self.replicas.append(handle)
        return handle

    def drain(self, replica, pump=True):
        assert pump is False, "autoscaler drains must not block the loop"
        replica.begin_drain()
        return replica

    def _reap_publisher(self, r):
        self.reaped.append(r.replica_id)
        return 0


def _spawner(spawned):
    def spawn(role):
        h = _ScaleReplica(f"as-{role}-{len(spawned)}", role)
        h.state = ReplicaState.STARTING
        spawned.append(h)
        return h
    return spawn


def _firing(kind, at=0.0):
    return SloAlert(tenant="t0", kind=kind, state="firing", burn_fast=4.0,
                    burn_slow=4.0, target_s=1.0, at=at)


def test_autoscaler_burn_ramp_scales_up_before_breach():
    """Satellite pin: the burn-rate alert (which by construction fires
    while bad requests are still in flight, before a p99 histogram
    shows the breach) turns into a prefill scale-up the same tick."""
    t = [0.0]
    mon = SloMonitor(objective=0.9, fast_window_s=10.0, slow_window_s=10.0,
                     burn_threshold=2.0, min_samples=3,
                     time_fn=lambda: t[0])
    fleet = _StubFleet([_ScaleReplica("p0", "prefill"),
                        _ScaleReplica("d0", "decode")])
    spawned = []
    auto = FleetAutoscaler(fleet, _spawner(spawned), slo_monitor=mon,
                           clock=lambda: t[0], chip_budget=8,
                           scale_up_cooldown_s=5.0)
    # healthy traffic: no alert, no action
    for _ in range(5):
        t[0] += 0.5
        mon.observe("t0", KIND_TTFT, 0.1, 1.0)
    assert auto.tick() == []
    # TTFT latency ramp: burn fires -> +1 prefill replica, routable now
    for _ in range(6):
        t[0] += 0.5
        mon.observe("t0", KIND_TTFT, 5.0, 1.0)
    events = auto.tick()
    assert [e["action"] for e in events] == ["up"]
    assert events[0]["role"] == "prefill"
    assert "alert" in events[0]["reason"]
    assert spawned[0] in fleet.replicas
    assert spawned[0].state is ReplicaState.HEALTHY
    assert auto.counts["scale_ups"] == 1
    # ITL pain maps to the decode class (and the now-quiet, now-doubled
    # prefill class is eligible for its first scale-down)
    t[0] += 20.0
    for _ in range(6):
        t[0] += 0.5
        mon.observe("t0", KIND_ITL, 5.0, 1.0)
    events = auto.tick()
    assert [(e["action"], e["role"]) for e in events] == \
        [("up", "decode"), ("down", "prefill")]
    assert auto.counts["scale_ups"] == 2


def test_autoscaler_quiet_tail_scales_down_behind_cooldown():
    t = [0.0]
    reps = [_ScaleReplica(f"d{i}", "decode") for i in range(3)]
    fleet = _StubFleet(reps)
    auto = FleetAutoscaler(fleet, _spawner([]), clock=lambda: t[0],
                           quiet_s=10.0, scale_down_cooldown_s=30.0,
                           queue_high=8.0, queue_low=1.0)
    reps[0].depth = 5                     # busy epoch
    auto.tick()
    reps[0].depth = 0
    t[0] = 5.0
    assert auto.tick() == []              # quiet, but < quiet_s
    t[0] = 12.0
    events = auto.tick()                  # quiet_s elapsed: one drain
    assert [e["action"] for e in events] == ["down"]
    victim = next(r for r in reps if r.state is ReplicaState.DRAINING)
    t[0] = 13.0
    # down-cooldown gates a second action; the idle drain retires
    assert auto.tick() == []
    assert victim.state is ReplicaState.RETIRED
    assert fleet.fleet_counts["drains"] == 1
    assert victim.replica_id in fleet.reaped
    t[0] = 45.0                           # cooldown expired, still quiet
    assert [e["action"] for e in auto.tick()] == ["down"]
    assert auto.counts["scale_downs"] == 2


def test_autoscaler_chip_budget_denies_scale_up():
    t = [0.0]
    fleet = _StubFleet([_ScaleReplica("p0", "prefill"),
                        _ScaleReplica("d0", "decode")])
    spawned = []
    auto = FleetAutoscaler(fleet, _spawner(spawned), clock=lambda: t[0],
                           chip_budget=2, chips_per_replica=1)
    auto._on_alert(_firing(KIND_TTFT))
    assert auto.tick() == []              # at the ceiling: denied
    assert auto.counts["budget_denials"] == 1 and not spawned


def test_autoscaler_never_drains_last_replica_of_a_class():
    t = [0.0]
    lone = _ScaleReplica("d0", "decode")
    fleet = _StubFleet([lone])
    auto = FleetAutoscaler(fleet, _spawner([]), clock=lambda: t[0],
                           quiet_s=1.0, scale_down_cooldown_s=1.0)
    lone.depth = 3
    auto.tick()
    lone.depth = 0
    for step in range(1, 20):             # hours of quiet: still refuses
        t[0] = float(step * 10)
        assert auto.tick() == []
    assert auto.counts["scale_downs"] == 0
    assert lone.state is ReplicaState.HEALTHY


def test_autoscaler_alert_storm_one_action_per_window():
    t = [0.0]
    fleet = _StubFleet([_ScaleReplica("p0", "prefill"),
                        _ScaleReplica("d0", "decode")])
    spawned = []
    auto = FleetAutoscaler(fleet, _spawner(spawned), clock=lambda: t[0],
                           chip_budget=16, scale_up_cooldown_s=5.0)
    for _ in range(10):                   # storm before the first tick
        auto._on_alert(_firing(KIND_TTFT))
    assert len(auto.tick()) == 1
    for tick_t in (1.0, 2.0, 4.0):        # storm keeps raging in-window
        t[0] = tick_t
        auto._on_alert(_firing(KIND_TTFT))
        assert auto.tick() == []
    t[0] = 6.0                            # window over: one more action
    auto._on_alert(_firing(KIND_TTFT))
    assert len(auto.tick()) == 1
    assert auto.counts["scale_ups"] == 2 and len(spawned) == 2


def test_autoscaler_actuator_fault_semantics(injector):
    t = [0.0]
    fleet = _StubFleet([_ScaleReplica("p0", "prefill"),
                        _ScaleReplica("d0", "decode")])
    spawned = []
    auto = FleetAutoscaler(fleet, _spawner(spawned), clock=lambda: t[0],
                           chip_budget=16, scale_up_cooldown_s=5.0)
    # transient: the action is skipped WITHOUT charging the cooldown —
    # the same decision retries the very next tick and succeeds
    injector.add_plan("serving.fleet.scale", "fail", at=1)
    auto._on_alert(_firing(KIND_TTFT))
    assert auto.tick() == [] and not spawned
    t[0] = 1.0
    auto._on_alert(_firing(KIND_TTFT))
    assert len(auto.tick()) == 1 and len(spawned) == 1
    # fatal: abandoned, counted, and the cooldown IS charged so a
    # broken actuator cannot spin the spawner at tick rate
    injector.add_plan("serving.fleet.scale", "fatal", at=3)
    t[0] = 10.0
    auto._on_alert(_firing(KIND_TTFT))
    assert auto.tick() == []
    assert auto.counts["actuator_failures"] == 1
    t[0] = 12.0                           # inside the charged cooldown
    auto._on_alert(_firing(KIND_TTFT))
    assert auto.tick() == []
    t[0] = 16.0
    auto._on_alert(_firing(KIND_TTFT))
    assert len(auto.tick()) == 1
    assert auto.counts["scale_ups"] == 2 and len(spawned) == 2


# ---------------------------------------------------------------------------
# engine-backed end-to-ends (slow): handoff parity, fault degradation,
# orphan hygiene, chaos
# ---------------------------------------------------------------------------
def disagg_engine(replicas=3, prefill_replicas=1, slots=3, num_kv_blocks=32,
                  max_queue_depth=16, **fleet_kw):
    cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=64, dtype=jnp.float32)
    serving = {"enabled": True, "kv_block_size": 4,
               "num_kv_blocks": num_kv_blocks,
               "max_batch_slots": slots,
               "prefill_chunk_tokens": 8,
               "max_preemptions": 4,
               "max_queue_depth": max_queue_depth,
               "fleet": {"enabled": True, "replicas": replicas,
                         "prefill_replicas": prefill_replicas,
                         **fleet_kw},
               # wire_bits 0 keeps the fabric LOSSLESS: handoff streams
               # must stay token-exact whatever tier carried the KV
               "host_cache": {"enabled": True,
                              "dram_budget_bytes": 1 << 20,
                              "wire_bits": 0}}
    return ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 48, "temperature": 0.0,
        "replace_with_kernel_inject": False, "serving": serving})


def _generate(eng, prompt, n, seed=None, **samp):
    rng = jax.random.PRNGKey(seed) if seed is not None else None
    return np.asarray(eng.generate(np.asarray(prompt, np.int32)[None],
                                   max_new_tokens=n, rng=rng, **samp))[0]


# every prompt holds >= 1 full 4-token block, so the prefill leg has
# something publishable; mixed greedy + seeded sampling
DISAGG_WAVE = [([1, 2, 3, 4, 5, 6, 7, 8, 9], dict(temperature=0.0)),
               ([10, 11, 12, 13, 14], dict(temperature=0.0)),
               ([15, 16, 17, 18, 19, 20, 21], dict(temperature=0.0)),
               ([22, 23, 24, 25, 26], dict(temperature=0.8, seed=7)),
               ([27, 28, 29, 30, 31, 32], dict(temperature=0.6, top_k=12,
                                               seed=9))]


def submit_wave(fleet, wave, n=8):
    sinks, reqs = [], []
    for prompt, samp in wave:
        sink = StreamCollector()
        sinks.append(sink)
        reqs.append(fleet.submit(prompt, max_new_tokens=n,
                                 on_token=sink, **samp))
    return reqs, sinks


def assert_wave_exact(eng, fleet, wave, reqs, sinks, n=8):
    """Every OK stream token-identical to its (seeded) generate() twin,
    delivered exactly once; every surviving replica's pool and the
    shared fabric are invariant-clean afterwards."""
    assert all(f.done for f in reqs), "in-flight after run"
    for (prompt, samp), freq, sink in zip(wave, reqs, sinks):
        if freq.status is not RequestStatus.OK:
            continue
        ref = _generate(eng, prompt, n, **samp)
        assert np.array_equal(freq.output, ref), \
            f"{freq.req_id}: fleet {freq.output} != generate {list(ref)}"
        assert sink.tokens == freq.output
        toks = [e for e in sink.events if e.token is not None]
        assert [e.index for e in toks] == list(range(len(freq.output)))
        assert sink.finished
    device_digests = set()
    for r in fleet.replicas:
        if r.state is ReplicaState.DEAD:
            continue
        assert r.srv.decode_builds in (0, 2), \
            f"{r.replica_id}: the step's two shapes built once a replica"
        r.srv.allocator.assert_consistent()
        assert r.srv.allocator.num_used == 0
        device_digests |= set(r.srv.allocator._hash_to_block)
    if fleet.shared_host_cache is not None:
        fleet.shared_host_cache.assert_consistent(
            device_digests=device_digests)


@pytest.mark.slow
def test_disagg_handoff_token_exact():
    """Tentpole baseline: prefill workers publish, decode replicas
    claim-and-promote, and the two-leg handoff is invisible to the
    stream — token-identical to sequential generate()."""
    eng = disagg_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    assert [(r.replica_id, r.role) for r in fleet.replicas] == \
        [("p0", "prefill"), ("d0", "decode"), ("d1", "decode")]
    reqs, sinks = submit_wave(fleet, DISAGG_WAVE)
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    # every request took the two-leg plan and landed on the decode class
    assert fleet.fleet_counts["handoffs"] == len(DISAGG_WAVE)
    assert all(f.leg == "decode" for f in reqs)
    assert {f.replica.role for f in reqs} == {"decode"}
    assert_wave_exact(eng, fleet, DISAGG_WAVE, reqs, sinks)
    p0 = fleet.replica("p0")
    assert p0.srv.decode_builds == 2     # the one step, both its shapes
    assert p0.srv.fabric_counts["prefill_only_completed"] == \
        len(DISAGG_WAVE)
    assert p0.srv.fabric_counts["published_blocks"] >= len(DISAGG_WAVE)
    assert p0.srv.fabric_counts["publish_failures"] == 0
    # the decode side actually consumed the fabric (claims, not spills)
    hc = fleet.shared_host_cache
    assert sum(hc.hits_total.values()) >= 1
    # nothing left stranded: the handoff accounting closes to zero
    fleet.reap_orphans()
    assert hc.published_entries() == 0
    hc.assert_consistent()
    # a re-submitted warm prompt skips the prefill leg (direct plan)
    sink = StreamCollector()
    freq = fleet.submit(DISAGG_WAVE[0][0], max_new_tokens=8, on_token=sink)
    fleet.run()
    assert freq.leg in ("direct", "decode")
    assert freq.status is RequestStatus.OK
    assert np.array_equal(freq.output,
                          _generate(eng, DISAGG_WAVE[0][0], 8,
                                    temperature=0.0))


@pytest.mark.slow
def test_disagg_publish_faults_degrade_to_recompute(injector):
    """Every publish fails: the prefill leg still completes, the handoff
    still happens, and the decode side recomputes from a cold fabric —
    never a wrong token, never a stall."""
    injector.add_plan("serving.fabric.publish", "fail", at=1, count=-1)
    eng = disagg_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    wave = DISAGG_WAVE[:3]
    reqs, sinks = submit_wave(fleet, wave)
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, wave, reqs, sinks)
    p0 = fleet.replica("p0")
    assert p0.srv.fabric_counts["publish_failures"] >= len(wave)
    assert p0.srv.fabric_counts["published_blocks"] == 0
    hc = fleet.shared_host_cache
    assert hc.published_total == 0 and hc.published_entries() == 0
    assert fleet.fleet_counts["handoffs"] == len(wave)


@pytest.mark.slow
def test_disagg_claim_fatal_quarantines_and_recomputes(injector):
    """A fatal claim fault drops the suspect fabric entry; the decode
    replica pays a recompute and the stream stays exact."""
    injector.add_plan("serving.fabric.claim", "fatal", at=1, count=1)
    eng = disagg_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    wave = DISAGG_WAVE[:3]
    reqs, sinks = submit_wave(fleet, wave)
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, wave, reqs, sinks)
    assert fleet.shared_host_cache.claim_faults_total == 1
    fleet.reap_orphans()
    assert fleet.shared_host_cache.published_entries() == 0


@pytest.mark.slow
def test_disagg_drain_and_death_leave_no_orphans(injector):
    """Acceptance pin: a prefill worker leaving (drain here, injected
    death below) leaves ZERO orphaned fabric entries — its unclaimed
    publishes are reaped, and the decode legs that wanted them see a
    cold miss and recompute, still token-exact."""
    eng = disagg_engine(slots=2, max_queue_depth=8)
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    hc = fleet.shared_host_cache
    p0 = fleet.replica("p0")
    # saturate the decode class so handoffs QUEUE (their claims can't
    # land yet), then let the prefill leg publish into the window
    busy, busy_sinks = submit_wave(
        fleet, [([40 + i, 41 + i, 42 + i], dict(temperature=0.0))
                for i in range(4)], n=12)
    target_wave = DISAGG_WAVE[:2]
    reqs, sinks = submit_wave(fleet, target_wave)
    for _ in range(64):
        if hc.published_entries(p0.srv.publisher_id) > 0:
            break
        fleet.pump()
    assert hc.published_entries(p0.srv.publisher_id) > 0, \
        "prefill leg never published into the decode backlog window"
    # the prefill worker leaves while its publishes sit unclaimed
    fleet.drain(p0)
    assert p0.state is ReplicaState.RETIRED
    assert hc.published_entries(p0.srv.publisher_id) == 0
    assert fleet.fleet_counts["orphans_reaped"] >= 1
    assert hc.orphans_reaped_total >= 1
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in busy + reqs)
    for (prompt, samp), f, sink in zip(target_wave, reqs, sinks):
        ref = _generate(eng, prompt, 8, **samp)
        assert np.array_equal(f.output, ref)
        assert sink.tokens == list(ref)
    device_digests = set()
    for r in fleet.replicas:
        r.srv.allocator.assert_consistent()
        assert r.srv.allocator.num_used == 0
        device_digests |= set(r.srv.allocator._hash_to_block)
    assert hc.published_entries() == 0
    hc.assert_consistent(device_digests=device_digests)


@pytest.mark.slow
def test_disagg_prefill_death_degrades_to_direct(injector):
    """The only prefill worker dies mid-wave: its in-flight prefill
    legs fail over, the planner finds no prefill class and degrades to
    the single-leg direct path — every stream still OK and exact."""
    eng = disagg_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    # p0 steps first each pump: site call 1 is its first iteration
    injector.add_plan("serving.fleet.replica_step", "fatal", at=1)
    reqs, sinks = submit_wave(fleet, DISAGG_WAVE)
    fleet.run()
    p0 = fleet.replica("p0")
    assert p0.state is ReplicaState.DEAD
    assert fleet.fleet_counts["dead_replicas"] == 1
    assert all(f.status is RequestStatus.OK for f in reqs)
    # the two-leg plan was abandoned, not stalled
    assert all(f.leg in ("direct", "decode") for f in reqs)
    assert_wave_exact(eng, fleet, DISAGG_WAVE, reqs, sinks)
    fleet.reap_orphans()
    assert fleet.shared_host_cache.published_entries() == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_disagg_chaos_wave(env_injector):
    """The matrix scenario (run_tests.sh replays it under transient
    ``serving.fabric.publish``, fatal ``serving.fabric.claim`` and
    fatal ``serving.fleet.scale`` plans): a disaggregated wave with a
    live autoscaler in the loop — whatever the fault schedule, every
    stream is token-exact, the fabric closes to zero orphans, and a
    broken scale actuator degrades to a statically-sized fleet."""
    eng = disagg_engine()
    fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
    t = [0.0]

    def spawn(role):
        srv = ServingEngine(eng, rng=jax.random.PRNGKey(1),
                            shared_host_cache=fleet.shared_host_cache,
                            role=role)
        srv.publisher_id = f"as-{role}"
        return ReplicaHandle(f"as-{role}", srv, role=role)

    auto = FleetAutoscaler(fleet, spawn, clock=lambda: t[0],
                           chip_budget=4, scale_up_cooldown_s=1.0)
    reqs, sinks = submit_wave(fleet, DISAGG_WAVE[:3])
    fleet.pump()
    # decode-side pressure alert while the wave is in flight: the
    # actuator path runs mid-traffic (the serving.fleet.scale site)
    auto._on_alert(SloAlert(tenant="t0", kind=KIND_ITL, state="firing",
                            burn_fast=4.0, burn_slow=4.0, target_s=0.1,
                            at=t[0]))
    auto.tick()
    late_reqs, late_sinks = submit_wave(fleet, DISAGG_WAVE[3:])
    reqs, sinks = reqs + late_reqs, sinks + late_sinks
    fleet.run()
    assert all(f.status is RequestStatus.OK for f in reqs)
    assert_wave_exact(eng, fleet, DISAGG_WAVE, reqs, sinks)
    # the autoscaler either grew the decode class or (fatal actuator
    # plan) abandoned exactly one bounded action — never both, never a
    # stall
    assert auto.counts["scale_ups"] + auto.counts["actuator_failures"] == 1
    if auto.counts["scale_ups"]:
        joined = fleet.replica("as-decode")
        assert joined.routable and joined.srv.decode_builds in (0, 2)
    fleet.reap_orphans()
    assert fleet.shared_host_cache.published_entries() == 0
    fleet.shared_host_cache.assert_consistent()
