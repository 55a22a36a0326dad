"""SLO-grade multi-tenant front-end suite (inference/serving/frontend/,
docs/serving.md "Sampling, streaming & multi-tenant SLOs").

Coverage model:
  * in-program sampling: temperature-0 serving streams token-identical
    to ``generate()``; SEEDED sampled streams (per-request temperature /
    top-k / top-p / seed, mixed in ONE batch) token-identical to the
    same prompt through seeded ``generate()`` — the shared
    ``inference/sampling.py`` fold_in schedule — with
    ``decode_builds == 2`` across every sampling mix (params are step
    inputs, never shapes);
  * token streaming: per-token events at iteration boundaries carrying
    lifecycle status, a final tokenless terminal event for requests
    that never streamed, and callback-exception isolation;
  * mesh-shape determinism: the same seeded workload on a (1,1) and a
    (2,2) (data, model) mesh emits identical tokens, one compiled
    program each;
  * speculative decoding: with a draft model armed, emitted streams are
    TOKEN-EXACT vs the non-speculative engine under the same keys
    (exactness by construction: target samples at every draft position
    with that position's own fold_in key), acceptance counters move,
    and the step still traces once a shape;
  * weighted-fair multi-tenancy: virtual-token-counter unit math
    (charge / idle-lift / share), the admission policy's priority +
    at-risk + VTC ordering, the starvation bound under a bursty hog
    tenant, and the shed policy victimizing the queue hog instead of
    the incoming request.
"""
import re
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import (Request, RequestStatus,
                                             ServingFrontend,
                                             StreamCollector,
                                             TenantRegistry, TenantSpec)
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.slo import (KIND_ITL, KIND_TTFT,
                                             SloMonitor)

pytestmark = [pytest.mark.inference, pytest.mark.frontend]


def build_engine(max_slots=4, mesh=None, params=None, vocab=64,
                 d_model=32, heads=4, layers=2, spec_k=None):
    cfg = gpt2_config("125m", num_layers=layers, d_model=d_model,
                      num_heads=heads, vocab_size=vocab, max_seq_len=128,
                      dtype=jnp.float32)
    serving = {"enabled": True, "kv_block_size": 8, "num_kv_blocks": 64,
               "max_batch_slots": max_slots, "prefill_chunk_tokens": 16}
    if spec_k is not None:
        serving["spec_k"] = spec_k
    if mesh is not None:
        serving["mesh"] = {"data": mesh[0], "model": mesh[1]}
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 128, "temperature": 0.0,
        "replace_with_kernel_inject": False, "serving": serving})
    if params is not None:
        eng.params = params
    return eng


def seeded_generate(eng, prompt, n, seed, **samp):
    return np.asarray(eng.generate(
        jnp.asarray([prompt]), max_new_tokens=n,
        rng=jax.random.PRNGKey(seed), **samp))[0]


@pytest.fixture(scope="module")
def shared():
    """One engine + frontend shared by the single-device tests; the
    cumulative ``decode_builds == 2`` assertions across them prove that
    no sampling mix, stream, or tenant behavior ever retraces."""
    eng = build_engine()
    srv = eng.serving_engine()
    fe = ServingFrontend(srv)
    return eng, srv, fe


PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14, 15, 16, 17]]


# ---------------------------------------------------------------------------
# in-program sampling + streaming
# ---------------------------------------------------------------------------
def test_greedy_stream_matches_generate(shared):
    eng, srv, _fe = shared
    cols = [StreamCollector() for _ in PROMPTS]
    reqs = [srv.submit(p, max_new_tokens=8, on_token=c)
            for p, c in zip(PROMPTS, cols)]
    srv.run()
    for p, r, c in zip(PROMPTS, reqs, cols):
        gen = np.asarray(eng.generate(jnp.asarray([p]), max_new_tokens=8,
                                      temperature=0.0))[0]
        assert r.status is RequestStatus.OK
        np.testing.assert_array_equal(np.asarray(r.output), gen)
        # the stream saw every token in order, and ended final with the
        # terminal status attached to the LAST token event
        assert c.tokens == r.output
        assert c.finished
        assert c.events[-1].status is RequestStatus.OK
        assert [e.index for e in c.events] == list(range(8))
    assert srv.decode_builds == 2


def test_mixed_seeded_sampling_matches_generate_one_trace(shared):
    """Three sampling configs — greedy, temperature+top-k, nucleus — in
    the SAME batch: each stream matches its seeded generate() twin, and
    the mix rides the one already-compiled program (sampling params are
    data)."""
    eng, srv, _fe = shared
    samp = [dict(temperature=0.0, top_k=0, top_p=1.0),
            dict(temperature=0.9, top_k=16, top_p=1.0),
            dict(temperature=0.7, top_k=0, top_p=0.9)]
    reqs = [srv.submit(p, max_new_tokens=8, seed=100 + i, **samp[i])
            for i, p in enumerate(PROMPTS)]
    srv.run()
    for i, (p, r) in enumerate(zip(PROMPTS, reqs)):
        gen = seeded_generate(eng, p, 8, 100 + i, **samp[i])
        assert r.output == list(gen), (i, r.output, list(gen))
    assert srv.decode_builds == 2, "sampling mix retraced the step"


def test_terminal_events_and_callback_isolation(shared):
    eng, srv, _fe = shared
    # a request shed... is hard to force on the shared engine; use a
    # backdated deadline instead: it never streams a token, so its
    # stream must close with a single tokenless terminal event
    dead_col = StreamCollector()
    dead = srv.submit(PROMPTS[0], max_new_tokens=8, deadline_s=1.0,
                      on_token=dead_col)
    dead.submit_time -= 50.0

    # a broken callback: raises on the 3rd token — its stream dies,
    # the REQUEST keeps generating and stays token-exact
    class Boom:
        def __init__(self):
            self.seen = []

        def __call__(self, ev):
            if len(self.seen) == 2:
                raise RuntimeError("consumer bug")
            self.seen.append(ev.token)

    boom = Boom()
    noisy = srv.submit(PROMPTS[1], max_new_tokens=8, on_token=boom)
    srv.run()
    assert dead.status is RequestStatus.TIMED_OUT
    assert dead_col.tokens == []
    assert dead_col.finished
    assert dead_col.events[-1].token is None
    assert dead_col.events[-1].status is RequestStatus.TIMED_OUT
    assert noisy.status is RequestStatus.OK
    assert len(noisy.output) == 8
    assert boom.seen == noisy.output[:2], "stream died at the raise"
    assert noisy.on_token is None, "broken callback must be disabled"
    gen = np.asarray(eng.generate(jnp.asarray([PROMPTS[1]]),
                                  max_new_tokens=8, temperature=0.0))[0]
    np.testing.assert_array_equal(np.asarray(noisy.output), gen)
    assert srv.decode_builds == 2


# ---------------------------------------------------------------------------
# weighted-fair multi-tenancy
# ---------------------------------------------------------------------------
def test_vtc_unit_math():
    reg = TenantRegistry([TenantSpec("a", weight=1.0),
                          TenantSpec("b", weight=4.0)])
    reg.charge("a", 10)
    reg.charge("b", 10)
    assert reg.vtc["a"] == pytest.approx(10.0)
    assert reg.vtc["b"] == pytest.approx(2.5)   # 4x weight, 1/4 charge
    # idle->active lift: c enters at the ACTIVE minimum, not at 0
    reg.lift("c", ["a", "b", "c"])
    assert reg.vtc["c"] == pytest.approx(2.5)
    assert reg.fair_share("b", ["a", "b"]) == pytest.approx(0.8)
    with pytest.raises(ValueError):
        TenantSpec("bad", weight=0.0)
    with pytest.raises(ValueError):
        TenantSpec("bad", max_queue_share=1.5)


def test_admission_order_priority_risk_vtc():
    """Policy unit check on bare Requests: priority tier first, then
    TTFT-at-risk, then smallest virtual counter, then FCFS."""
    from collections import deque
    fe = ServingFrontend.__new__(ServingFrontend)   # policy-only, no engine
    fe.slo = None
    fe.tenants = TenantRegistry([
        TenantSpec("hog", weight=1.0),
        TenantSpec("fair", weight=1.0),
        TenantSpec("slo", weight=1.0, ttft_slo_s=10.0),
        TenantSpec("vip", weight=1.0, priority=5)])
    fe.tenants.vtc.update({"hog": 100.0, "fair": 1.0, "slo": 50.0})
    now = time.perf_counter()

    def mk(tenant, age=0.0):
        r = Request(prompt=[1], max_new_tokens=1, tenant=tenant)
        r.submit_time = now - age
        return r

    hog, fair = mk("hog"), mk("fair")
    at_risk = mk("slo", age=9.0)        # > 70% of its 10s TTFT budget
    calm = mk("slo", age=1.0)
    vip = mk("vip")
    q = deque([hog, calm, fair, at_risk, vip])
    fe._order_admissions(q)
    assert list(q) == [vip, at_risk, fair, calm, hog]


def test_fair_queue_starvation_bound(shared):
    """A hog floods the queue, then a premium tenant (4x weight)
    submits: under VTC admission the premium requests are served before
    the hog's TAIL — the bound is that a tenant's wait is its fair
    share of the backlog, not the whole backlog."""
    eng, srv, fe = shared
    fe.register(TenantSpec("hog", weight=1.0))
    fe.register(TenantSpec("premium", weight=4.0))
    order = []
    hook = lambda ev: order.append(ev.request) \
        if ev.index == 0 and ev.token is not None else None
    srv.token_hooks.append(hook)
    try:
        hogs = [fe.submit([3 + i, 4, 5], tenant="hog", max_new_tokens=6)
                for i in range(6)]
        srv.step()              # hog occupies all 4 slots, earns VTC
        prem = [fe.submit([40 + i, 2], tenant="premium",
                          max_new_tokens=6) for i in range(2)]
        srv.run()
    finally:
        srv.token_hooks.remove(hook)
    assert all(r.status is RequestStatus.OK for r in hogs + prem)
    first_tok = {id(r): i for i, r in enumerate(order)}
    # every premium request beats the hog's last request to its first
    # token: the hog's tail, not the premium tenant, absorbs the wait
    worst_hog = max(first_tok[id(r)] for r in hogs)
    for r in prem:
        assert first_tok[id(r)] < worst_hog, \
            "premium starved behind the hog's backlog"
    assert srv.decode_builds == 2


def test_shed_policy_victimizes_queue_hog(shared):
    """Under a full bounded queue the overload victim is the NEWEST
    waiting request of the over-share tenant, not the incoming request
    of the underrepresented one."""
    eng, srv, fe = shared
    fe.register(TenantSpec("hog", weight=1.0))
    fe.register(TenantSpec("premium", weight=4.0))
    running = [fe.submit([9, 9, 9 + i], tenant="hog", max_new_tokens=4)
               for i in range(4)]
    srv.step()                  # hog fills every slot
    srv.scheduler.max_queue_depth = 2
    try:
        waiting_before = [fe.submit([9, 9, 20 + i], tenant="hog",
                                    max_new_tokens=4) for i in range(2)]
        assert all(r.status is None for r in waiting_before)
        prem = fe.submit([50, 51], tenant="premium", max_new_tokens=4)
        # the hog's newest waiting request was shed in premium's favor
        assert prem.status is None, "incoming premium must not be shed"
        assert waiting_before[-1].status is RequestStatus.SHED
        assert waiting_before[0].status is None, \
            "only the NEWEST hog request is victimized"
    finally:
        srv.scheduler.max_queue_depth = 0
    srv.run()
    assert prem.status is RequestStatus.OK
    assert all(r.status is RequestStatus.OK
               for r in running + waiting_before[:1])
    assert srv.decode_builds == 2


# ---------------------------------------------------------------------------
# SLO burn-rate integration (observability/slo.py)
# ---------------------------------------------------------------------------
def _policy_frontend(tenants, slo=None):
    """Policy-only frontend: no engine, just the attrs the scheduler
    policy hooks and accounting hooks read."""
    fe = ServingFrontend.__new__(ServingFrontend)
    fe.tenants = TenantRegistry(tenants)
    fe.slo = slo
    fe._metrics = {}
    return fe


def _firing_monitor(tenant, kind=KIND_TTFT):
    """A real SloMonitor driven into the firing state for ``tenant``."""
    clock = [100.0]
    mon = SloMonitor(objective=0.5, fast_window_s=10.0,
                     slow_window_s=100.0, burn_threshold=1.0,
                     min_samples=1, registry=MetricsRegistry(),
                     time_fn=lambda: clock[0])
    for _ in range(4):
        mon.observe(tenant, kind, 2.0, 0.5)    # every sample bad
    assert mon.firing(tenant, kind)
    return mon


def test_firing_slo_alert_boosts_whole_tenant():
    """A firing TTFT burn-rate alert marks EVERY queued request of the
    tenant at-risk in admission ordering — not just the ones near their
    individual deadline."""
    from collections import deque
    mon = _firing_monitor("burning")
    fe = _policy_frontend([TenantSpec("calm"), TenantSpec("burning")],
                          slo=mon)
    now = time.perf_counter()

    def mk(tenant, age):
        r = Request(prompt=[1], max_new_tokens=1, tenant=tenant)
        r.submit_time = now - age
        return r

    calm = mk("calm", age=5.0)              # older — FCFS would win
    burning = mk("burning", age=0.1)        # fresh, no per-req risk
    q = deque([calm, burning])
    fe._order_admissions(q)
    assert list(q) == [burning, calm]
    # without the monitor, FCFS order holds
    fe.slo = None
    q = deque([calm, burning])
    fe._order_admissions(q)
    assert list(q) == [calm, burning]


def test_shed_policy_spares_firing_tenant():
    """When two tenants are over their queue-share cap, the one with a
    firing SLO alert is spared: shedding piles onto a tenant that is
    already losing.  With every over-cap tenant firing, the policy
    falls through to normal worst-offender selection."""
    tenants = [TenantSpec("loud", max_queue_share=0.3),
               TenantSpec("burning", max_queue_share=0.2),
               TenantSpec("fresh")]

    def waiting():
        reqs = []
        for tenant, n in (("loud", 2), ("burning", 3)):
            for i in range(n):
                reqs.append(Request(prompt=[1], max_new_tokens=1,
                                    tenant=tenant))
        return reqs

    incoming = Request(prompt=[1], max_new_tokens=1, tenant="fresh")
    # baseline, no monitor: burning is furthest over cap -> victim
    fe = _policy_frontend(tenants, slo=None)
    victim = fe._pick_shed_victim(incoming, waiting())
    assert victim is not None and victim.tenant == "burning"
    # burning's alert is firing: loud absorbs the shed instead
    fe = _policy_frontend(tenants, slo=_firing_monitor("burning"))
    w = waiting()
    victim = fe._pick_shed_victim(incoming, w)
    assert victim is not None and victim.tenant == "loud"
    assert victim is w[1], "newest waiting request of the victim tenant"
    # ALL over-cap tenants firing: fall through to the worst offender
    mon = _firing_monitor("burning")
    for _ in range(4):
        mon.observe("loud", KIND_TTFT, 2.0, 0.5)
    assert mon.firing_any("loud")
    fe = _policy_frontend(tenants, slo=mon)
    victim = fe._pick_shed_victim(incoming, waiting())
    assert victim is not None and victim.tenant == "burning"


def test_hostile_tenant_name_metrics(monkeypatch):
    """Caller-supplied tenant names cannot smuggle label syntax or
    newlines into the Prometheus textfile, and two hostile names that
    sanitize alike stay distinct series (crc disambiguation)."""
    reg = MetricsRegistry()
    reg.enabled = True
    monkeypatch.setattr(
        "deepspeed_tpu.inference.serving.frontend.frontend.get_registry",
        lambda: reg)
    fe = _policy_frontend([])
    hostile = 'evil{label="x"}\n# HELP bogus fake'
    tm = fe._tenant_metrics(hostile)
    tm["tokens"].inc()
    for m in tm.values():
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", m.name), m.name
    # names differing only in punctuation stay distinct series
    ta, tb = fe._tenant_metrics("a b"), fe._tenant_metrics("a.b")
    assert ta["tokens"].name != tb["tokens"].name
    text = reg.to_prometheus()
    assert 'label="x"' not in text
    assert "HELP bogus" not in text
    for line in text.splitlines():
        assert line.startswith(("#", "dstpu_")), line


def test_on_token_feeds_slo_and_exemplars(monkeypatch):
    """The token hook forwards TTFT / ITL samples to the burn-rate
    monitor against the tenant's SLO targets and attaches the request's
    trace id as a histogram exemplar."""
    reg = MetricsRegistry()
    reg.enabled = True
    monkeypatch.setattr(
        "deepspeed_tpu.inference.serving.frontend.frontend.get_registry",
        lambda: reg)
    mon = SloMonitor(objective=0.9, fast_window_s=10.0,
                     slow_window_s=100.0, min_samples=1,
                     registry=MetricsRegistry())
    fe = _policy_frontend(
        [TenantSpec("t", ttft_slo_s=0.5, itl_slo_s=0.1)], slo=mon)
    req = SimpleNamespace(prompt=[1, 2], submit_time=10.0,
                          trace_id="r0-000001")
    fe._on_token(SimpleNamespace(token=7, index=0, tenant="t",
                                 request=req, time_s=11.0,
                                 prev_time_s=None))
    fe._on_token(SimpleNamespace(token=8, index=1, tenant="t",
                                 request=req, time_s=11.3,
                                 prev_time_s=11.0))
    snap = mon.snapshot()
    assert snap[f"t/{KIND_TTFT}"]["samples"] == 1
    assert snap[f"t/{KIND_ITL}"]["samples"] == 1
    tm = fe._tenant_metrics("t")
    assert [x[0] for x in tm["ttft"].exemplars().values()] \
        == ["r0-000001"]
    assert 'trace_id="r0-000001"' in reg.to_prometheus()


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------
def make_draft(vocab=64, d_model=32, heads=4):
    cfg = gpt2_config("125m", num_layers=1, d_model=d_model,
                      num_heads=heads, vocab_size=vocab, max_seq_len=128,
                      dtype=jnp.float32)
    draft = TransformerLM(cfg)
    return draft, draft.init(jax.random.PRNGKey(1))


@pytest.mark.slow
def test_spec_streams_token_exact_vs_plain():
    """The acceptance pin: with an (untrained) draft armed, every
    emitted stream — mixed greedy and sampled — is byte-identical to
    the plain engine's on the same weights and seeds, acceptance
    counters move, and the three-lane step still compiles ONCE."""
    # spec_k=1 keeps the compiled draft loop short enough for tier-1;
    # the slow-marked mesh test below runs the default depth
    draft, dparams = make_draft(vocab=32, d_model=16, heads=2)
    spec_eng = build_engine(max_slots=2, vocab=32, d_model=16, heads=2,
                            layers=1, spec_k=1)
    spec_srv = spec_eng.serving_engine(draft_model=draft,
                                       draft_params=dparams)
    plain_eng = build_engine(max_slots=2, vocab=32, d_model=16, heads=2,
                             layers=1, params=spec_eng.params)
    plain_srv = plain_eng.serving_engine()
    samp = [dict(temperature=0.0), dict(temperature=0.8, seed=7),
            dict(temperature=0.6, top_k=12, seed=9)]
    outs = []
    for srv in (spec_srv, plain_srv):
        reqs = [srv.submit(p, max_new_tokens=8, **samp[i])
                for i, p in enumerate(PROMPTS)]
        srv.run()
        assert all(r.status is RequestStatus.OK for r in reqs)
        assert srv.decode_builds == 2
        outs.append([r.output for r in reqs])
    assert outs[0] == outs[1], "speculative lane changed the tokens"
    assert spec_srv.spec_counts["proposed"] > 0
    assert 0 <= spec_srv.spec_counts["accepted"] \
        <= spec_srv.spec_counts["proposed"]
    # with spec the engine must finish in FEWER dispatches than plain
    # whenever anything was accepted; at minimum it never does worse
    assert plain_srv.spec_counts["proposed"] == 0


# ---------------------------------------------------------------------------
# mesh-shape determinism
# ---------------------------------------------------------------------------
def _mesh_run(mesh, params, draft=None, dparams=None):
    eng = build_engine(mesh=mesh, params=params)
    srv = eng.serving_engine(draft_model=draft, draft_params=dparams)
    reqs = [srv.submit(p, max_new_tokens=6, temperature=0.8, top_k=16,
                       seed=200 + i) for i, p in enumerate(PROMPTS)]
    srv.run()
    assert srv.decode_builds == 2, (mesh, srv.decode_builds)
    assert all(r.status is RequestStatus.OK for r in reqs)
    return eng.params, [r.output for r in reqs]


@pytest.mark.slow
def test_mesh_shape_determinism_sampled():
    """The same seeded sampled workload on (1,1) and (2,2) meshes emits
    token-identical streams — the fold_in keys and the partitionable
    threefry draw are placement-independent."""
    params, single = _mesh_run((1, 1), None)
    _, sharded = _mesh_run((2, 2), params)
    assert single == sharded


@pytest.mark.slow
def test_mesh_shape_determinism_sampled_spec():
    """Full-feature acceptance: sampling AND the speculative lane on,
    (1,1) vs (2,2) token-identical, the step built once a shape on each."""
    draft, dparams = make_draft()
    params, single = _mesh_run((1, 1), None, draft, dparams)
    _, sharded = _mesh_run((2, 2), params, draft, dparams)
    assert single == sharded
