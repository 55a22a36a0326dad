"""Serving chaos suite (ISSUE 6 acceptance): injected faults, cancels,
deadline expiries, a poisoned slot, and forced KV-pressure preemption
interleaved over one continuous-batching engine — the drain must end
with the pool leak-check clean (``assert_consistent`` + zero
sequence-held blocks), ``decode_builds == 2`` (no retrace, whatever
failed), and every request that finished ``OK`` streaming
token-identically to sequential ``generate()``.

Runs standalone AND under the ``run_tests.sh`` serving-chaos stage,
which replays it across a ``DSTPU_FAULTS`` env matrix (transient-only
plans on the scheduling sites, transient AND fatal plans on the tiered
host-cache sites ``serving.spill`` / ``serving.promote``, whose fatal
handling is defined to degrade — eviction instead of spill, recompute
instead of promote — never to fail a request): the fixture builds the
injector FROM the environment, so each matrix entry is the same
workload under a different fault schedule.  docs/serving.md "Failure
handling & overload" describes the semantics being pinned.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import RequestState, RequestStatus
from deepspeed_tpu.models import TransformerLM, gpt2_config
from deepspeed_tpu.runtime.resilience import (FaultInjector,
                                              install_fault_injector)

pytestmark = [pytest.mark.inference, pytest.mark.chaos, pytest.mark.slow]


@pytest.fixture
def env_injector():
    """Install the injector built from DSTPU_FAULTS (empty when unset),
    so the run_tests.sh fault matrix steers the suite; restored to an
    empty injector afterwards."""
    fi = install_fault_injector(FaultInjector.from_env())
    yield fi
    install_fault_injector(FaultInjector())


def chaos_engine(num_kv_blocks=16, slots=3, max_queue_depth=16,
                 kv_cache_bits=0, spec_k=None, draft=False,
                 host_tier=True):
    cfg = gpt2_config("125m", num_layers=2, d_model=32, num_heads=4,
                      vocab_size=64, max_seq_len=64, dtype=jnp.float32)
    serving = {"enabled": True, "kv_block_size": 4,
               "num_kv_blocks": num_kv_blocks,
               "max_batch_slots": slots,
               "prefill_chunk_tokens": 8,
               "max_preemptions": 4,
               "max_queue_depth": max_queue_depth,
               "kv_cache_bits": kv_cache_bits,
               # host tier ON under chaos so the serving.spill /
               # serving.promote matrix entries bite; wire_bits 0 keeps
               # the raw-f32 pool's spill/promote LOSSLESS — OK streams
               # must stay token-exact whatever the fault schedule
               "host_cache": {"enabled": host_tier,
                              "dram_budget_bytes": 1 << 20,
                              "wire_bits": 0}}
    if spec_k is not None:
        serving["spec_k"] = spec_k
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 48, "temperature": 0.0,
        "replace_with_kernel_inject": False, "serving": serving})
    if draft:
        dm = TransformerLM(gpt2_config(
            "125m", num_layers=1, d_model=32, num_heads=4,
            vocab_size=64, max_seq_len=64, dtype=jnp.float32))
        return eng, eng.serving_engine(
            draft_model=dm, draft_params=dm.init(jax.random.PRNGKey(3)))
    return eng, eng.serving_engine()


def poison_slot_kv(srv, req):
    """NaN-poison the request's first KV block — through the SCALE
    plane when the pool is quantized (an int8 pool cannot hold NaN;
    NaN scales are exactly what dequant spreads over the block)."""
    blocks = srv.allocator.block_table(req.req_id)
    if srv.kv_bits:
        srv._pool_ks = srv._pool_ks.at[:, blocks[0]].set(jnp.nan)
    else:
        srv._pool_k = srv._pool_k.at[:, blocks[0]].set(jnp.nan)


def _generate(eng, prompt, n):
    return np.asarray(eng.generate(np.asarray(prompt, np.int32)[None],
                                   max_new_tokens=n, temperature=0.0))[0]


def assert_drained_clean(srv, reqs, finished):
    """The chaos invariants every scenario must satisfy."""
    assert len(finished) == len(reqs)
    assert all(r.status is not None for r in reqs), "in-flight after drain"
    # the acceptance pin: the step built once a shape across every failure mode
    assert srv.decode_builds == 2
    srv.allocator.assert_consistent()
    assert srv.allocator.num_used == 0, "sequence-held blocks after drain"
    assert srv.scheduler.queue_depth == 0
    assert srv.scheduler.active_slots == 0
    # lifecycle counters agree with the terminal statuses
    by = {s: sum(1 for r in reqs if r.status is s) for s in RequestStatus}
    lc = srv.lifecycle_counts
    assert lc["cancelled"] == by[RequestStatus.CANCELLED]
    assert lc["timed_out"] == by[RequestStatus.TIMED_OUT]
    assert lc["shed"] == by[RequestStatus.SHED]
    assert lc["failed"] == by[RequestStatus.FAILED]
    for r in reqs:
        if r.status is RequestStatus.SHED:
            assert r.output == [], "shed request must never stream"
        if r.status is not RequestStatus.OK:
            assert r in finished


@pytest.mark.parametrize("kv_cache_bits", [0, 8])
def test_chaos_staged_faults_cancels_deadlines(env_injector,
                                               kv_cache_bits):
    """The scripted scenario: staggered waves under KV pressure, one
    deadline expiry, one mid-flight cancel, one poisoned (NaN) slot —
    plus whatever DSTPU_FAULTS adds.  Runs at bf16 AND int8 KV: the
    quantized pool must satisfy the identical invariants — a
    quarantine discard drops the block (scales ride the block id, so
    they are recycled with it and overwritten at the next scatter),
    prefix-cache hits reuse scales, and OK streams at 8-bit stay
    token-exact against the bf16-cache generate() on the toy model."""
    eng, srv = chaos_engine(kv_cache_bits=kv_cache_bits)
    rs = np.random.RandomState(1009)
    new = 8
    prompts = [rs.randint(0, 64, (n,)).tolist()
               for n in (5, 9, 12, 7, 3, 10, 6, 8)]
    reqs = [srv.submit(p, max_new_tokens=new) for p in prompts[:4]]
    # deterministic deadline expiry: backdate the clock instead of
    # racing wall time
    reqs[3].deadline_s = 1.0
    reqs[3].submit_time -= 50.0
    srv.step()
    srv.step()
    cancel_target = next((r for r in reqs
                          if r.state is RequestState.RUNNING
                          and r.status is None), None)
    if cancel_target is not None:
        assert srv.cancel(cancel_target)
    reqs += [srv.submit(p, max_new_tokens=new) for p in prompts[4:]]
    srv.step()
    # poison one healthy decoding slot's first KV block with NaN: the
    # in-program finite flag must quarantine it (or, if it gets
    # preempted and its suspect blocks evicted first, it recomputes
    # clean and must then stream correctly — both outcomes are legal,
    # corruption of OTHER streams is not)
    poison = next((r for r in reqs
                   if r.state is RequestState.RUNNING and r.status is None
                   and not r.prefilling and len(r.output) < new - 2), None)
    if poison is not None:
        poison_slot_kv(srv, poison)
    finished = srv.run()

    assert_drained_clean(srv, reqs, finished)
    assert reqs[3].status is RequestStatus.TIMED_OUT
    if cancel_target is not None:
        assert cancel_target.status is RequestStatus.CANCELLED
    affected = sum(1 for r in reqs if r.status is not RequestStatus.OK)
    assert affected >= 2, "chaos exercised nothing"
    assert affected < len(reqs), "no unaffected streams left to check"
    for p, r in zip(prompts, reqs):
        if r.status is RequestStatus.OK:
            np.testing.assert_array_equal(
                np.asarray(r.output), _generate(eng, p, new),
                err_msg=f"prompt {p} (status {r.status})")


def test_chaos_sampled_spec_staged_faults(env_injector):
    """The front-end stack under the same staged chaos: seeded SAMPLED
    requests (mixed greedy / temperature / top-k, per-request seeds)
    over a DRAFT-ARMED engine — deadline expiry, mid-flight cancel and
    a NaN-poisoned slot land while the speculative lane is live.  The
    drain must satisfy the standard invariants (one compiled program,
    clean pool, coherent lifecycle counters), the speculative counters
    must have moved, and every OK stream must be token-exact against
    seeded sequential ``generate()`` with the same sampling config —
    the fold_in(key, j) schedule makes the stream independent of
    batching, preemption AND how many tokens each verified round
    emitted."""
    eng, srv = chaos_engine(spec_k=2, draft=True)
    rs = np.random.RandomState(2027)
    new = 8
    prompts = [rs.randint(0, 64, (n,)).tolist()
               for n in (5, 9, 12, 7, 3, 10, 6, 8)]
    samp = [{"temperature": 0.0} if i % 3 == 0 else
            {"temperature": 0.8, "top_k": 12, "seed": 500 + i}
            for i in range(len(prompts))]
    reqs = [srv.submit(p, max_new_tokens=new, **s)
            for p, s in zip(prompts[:4], samp[:4])]
    reqs[3].deadline_s = 1.0
    reqs[3].submit_time -= 50.0
    srv.step()
    srv.step()
    cancel_target = next((r for r in reqs
                          if r.state is RequestState.RUNNING
                          and r.status is None), None)
    if cancel_target is not None:
        assert srv.cancel(cancel_target)
    reqs += [srv.submit(p, max_new_tokens=new, **s)
             for p, s in zip(prompts[4:], samp[4:])]
    srv.step()
    poison = next((r for r in reqs
                   if r.state is RequestState.RUNNING and r.status is None
                   and not r.prefilling and len(r.output) < new - 2), None)
    if poison is not None:
        poison_slot_kv(srv, poison)
    finished = srv.run()

    assert_drained_clean(srv, reqs, finished)
    assert reqs[3].status is RequestStatus.TIMED_OUT
    assert srv.spec_counts["proposed"] > 0, "draft lane never ran"
    affected = sum(1 for r in reqs if r.status is not RequestStatus.OK)
    assert affected >= 2, "chaos exercised nothing"
    assert affected < len(reqs), "no unaffected streams left to check"
    for p, r, s in zip(prompts, reqs, samp):
        if r.status is not RequestStatus.OK:
            continue
        kw = dict(s)
        rng = jax.random.PRNGKey(kw.pop("seed")) if "seed" in kw else None
        ref = np.asarray(eng.generate(
            np.asarray(p, np.int32)[None], max_new_tokens=new,
            rng=rng, **kw))[0]
        np.testing.assert_array_equal(np.asarray(r.output), ref,
                                      err_msg=f"prompt {p} samp {s}")


def test_chaos_randomized_interleaving(env_injector):
    """Randomized (seeded) interleaving of submit / step / cancel /
    deadline ops over an undersized pool, on top of the env fault
    schedule: whatever order the chaos lands in, the drain is clean and
    OK streams are exact."""
    eng, srv = chaos_engine(num_kv_blocks=14, slots=3, max_queue_depth=6)
    rs = np.random.RandomState(4242)
    new = 6
    reqs, prompts = [], []
    for i in range(40):
        op = rs.choice(["submit", "step", "cancel", "step", "submit"])
        if op == "submit" and len(reqs) < 12:
            p = rs.randint(0, 64, (int(rs.randint(3, 14)),)).tolist()
            r = srv.submit(p, max_new_tokens=new)
            prompts.append(p)
            reqs.append(r)
            if rs.random_sample() < 0.2:       # some requests carry a
                r.deadline_s = 1.0             # TTL that already expired
                r.submit_time -= 50.0
        elif op == "cancel" and reqs:
            srv.cancel(reqs[int(rs.randint(len(reqs)))])
        else:
            srv.step()
    finished = srv.run()

    assert_drained_clean(srv, reqs, finished)
    assert sum(1 for r in reqs
               if r.status is RequestStatus.OK) >= 1, "nothing survived"
    for p, r in zip(prompts, reqs):
        if r.status is RequestStatus.OK:
            np.testing.assert_array_equal(
                np.asarray(r.output), _generate(eng, p, new),
                err_msg=f"prompt {p}")


@pytest.mark.parametrize("host_tier", [False, True],
                         ids=["ahead", "with_promotions"])
def test_chaos_news_arrives_with_a_dispatch_in_flight(env_injector,
                                                      host_tier):
    """The loop keeps an iteration in flight (ISSUE 37), so every piece
    of news below lands one dispatch late: an eos in mid-stream, a
    cancel, an expired deadline, a poisoned slot, KV pressure over an
    undersized pool — in a seeded random order, on top of the env fault
    schedule.  Void rows are counted and nothing of them is committed:
    the drain is clean, the step was built once a shape, every OK stream
    is ``generate()``'s (cut at its eos).  Without the host tier every
    dispatch but those after a drain runs ahead."""
    eng, srv = chaos_engine(num_kv_blocks=14, slots=3, max_queue_depth=6,
                            host_tier=host_tier)
    rs = np.random.RandomState(3737)
    new = 8
    reqs, prompts, eos = [], [], []
    poisoned = None
    for i in range(60):
        op = rs.choice(["submit", "step", "cancel", "step", "submit",
                        "poison", "step"])
        if op == "submit" and len(reqs) < 14:
            p = rs.randint(0, 64, (int(rs.randint(3, 14)),)).tolist()
            want = _generate(eng, p, new).tolist()
            # every other request ends on a token of its own stream
            stop = want[int(rs.randint(2, new - 1))] \
                if rs.random_sample() < 0.5 else None
            r = srv.submit(p, max_new_tokens=new, eos_token_id=stop)
            prompts.append(p)
            reqs.append(r)
            eos.append(stop)
            if rs.random_sample() < 0.15:
                r.deadline_s = 1.0
                r.submit_time -= 50.0
        elif op == "cancel" and reqs:
            srv.cancel(reqs[int(rs.randint(len(reqs)))])
        elif op == "poison" and poisoned is None:
            running = [r for r in reqs if r.state is RequestState.RUNNING
                       and r.cached_tokens > 0]
            if running:
                poisoned = running[0]
                poison_slot_kv(srv, poisoned)
        else:
            srv.step()
    finished = srv.run()

    assert_drained_clean(srv, reqs, finished)
    assert not srv._flight
    counts = srv.flight_counts
    assert counts["ahead_dispatches"] > 0
    assert counts["void_rows"] > 0, "no news met a dispatch in flight"
    assert sum(1 for r in reqs
               if r.status is RequestStatus.OK) >= 3, "nothing survived"
    for p, stop, r in zip(prompts, eos, reqs):
        if r.status is RequestStatus.OK:
            want = _generate(eng, p, new).tolist()
            if stop is not None:
                want = want[:want.index(stop) + 1]
            assert r.output == want, f"prompt {p} eos {stop}"


def test_flight_recorder_dumps_on_serving_error(tmp_path):
    """Black-box flight recorder end-to-end (docs/observability.md
    "Flight recorder"): with the recorder + tracing armed, a fatal
    fault at the dispatch site raises :class:`ServingError` and
    ``step()`` seals a post-mortem bundle FIRST — reason, snapshot
    ring, terminals, metrics textfile, and the Chrome trace carrying
    the per-request waterfall tracks, all manifest-verifiable.

    The ``run_tests.sh`` flight-recorder stage replays exactly this
    test with ``DSTPU_FLIGHT_TEST_DIR`` pointing at a scratch dir it
    inspects afterwards."""
    import json
    import os

    from deepspeed_tpu.inference.serving import ServingError
    from deepspeed_tpu.observability import (get_flight_recorder,
                                             get_request_tracer,
                                             get_tracer)
    from deepspeed_tpu.observability.request_trace import \
        REQUEST_TRACK_PID_OFFSET
    from deepspeed_tpu.runtime.resilience.integrity import verify_manifest

    out_dir = os.environ.get("DSTPU_FLIGHT_TEST_DIR") or str(tmp_path)
    fr, rt, tracer = (get_flight_recorder(), get_request_tracer(),
                      get_tracer())
    fi = install_fault_injector(FaultInjector())
    # the first step() enqueues three dispatches (two chunks, then the
    # iteration it plans ahead); the fault meets the second call's
    fi.add_plan("serving.dispatch", "fatal", at=4)
    try:
        fr.configure(enabled=True, capacity=32, output_dir=out_dir)
        fr.reset()
        rt.configure(enabled=True, rank=0)
        rt.reset()
        tracer.configure(enabled=True, output_dir=out_dir, rank=0)
        tracer.set_event_source("request_trace", rt.chrome_events)

        eng, srv = chaos_engine(num_kv_blocks=16, slots=2)
        reqs = [srv.submit([3 + i, 4, 5], max_new_tokens=6)
                for i in range(3)]
        with pytest.raises(ServingError):
            while srv.step():
                pass
        bundle = fr.last_bundle
        assert bundle is not None and bundle.startswith(out_dir)

        ok, problems = verify_manifest(bundle)
        assert ok, problems
        reason = json.load(open(os.path.join(bundle, "reason.json")))
        assert reason["reason"] == "serving_error"
        assert "fatal fault at serving dispatch" in reason["detail"]
        assert "queue_depth" in reason["extra"]["diagnose"]
        snaps = json.load(open(os.path.join(bundle, "snapshots.json")))
        assert snaps["count"] >= 1 and len(snaps["snapshots"]) \
            == snaps["count"]
        for key in ("queue_depth", "active_slots", "pool_used",
                    "lifecycle", "decode_builds"):
            assert key in snaps["snapshots"][-1]
        assert os.path.exists(os.path.join(bundle, "metrics.prom"))
        # the bundled trace carries the per-request waterfall tracks
        trace = json.load(open(os.path.join(bundle, "trace.json")))
        ev = trace["traceEvents"] if isinstance(trace, dict) else trace
        req_ev = [e for e in ev
                  if e.get("pid") == REQUEST_TRACK_PID_OFFSET]
        assert req_ev, "no request-track events in bundled trace"
        names = {e["name"] for e in req_ev if e.get("ph") == "X"}
        assert "queued" in names
        ids = {r.trace_id for r in reqs}
        assert len(ids) == 3 and None not in ids
    finally:
        install_fault_injector(FaultInjector())
        tracer.set_event_source("request_trace", None)
        tracer.configure(enabled=False)
        rt.configure(enabled=False)
        fr.configure(enabled=False)
