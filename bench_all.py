"""Multi-config benchmark suite (BASELINE.json tracked configs).

Prints one JSON line per config. `bench.py` stays the headline
single-line contract; this script covers the wider matrix: 125M ZeRO-0,
350M ZeRO-2/3, decode latency.  Runs on a TPU only: without one it exits
non-zero and prints no metric.
"""
from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

from bench import require_tpu


def _bench_artifact_dir() -> str:
    """Where serving benches drop their merged fleet trace artifacts:
    ``$DSTPU_BENCH_ARTIFACTS``, else the fixed (git-ignored)
    ``chiprun_out/bench_all`` beside this script."""
    d = os.environ.get("DSTPU_BENCH_ARTIFACTS") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out",
        "bench_all")
    os.makedirs(d, exist_ok=True)
    return d


def _obs_block(art_dir: str) -> dict:
    """Observability block the serving benches share: tracing +
    request waterfalls + metrics + the host/device overlap profiler."""
    return {"tracing": {"enabled": True, "output_dir": art_dir},
            "request_tracing": {"enabled": True},
            "metrics": {"enabled": True},
            "overlap": {"enabled": True}}


def _overlap_columns(kind: str = "serving") -> dict:
    """Host/device overlap summary for the bench JSON line, read from
    the overlap profiler's registry histograms."""
    from deepspeed_tpu.observability import get_registry
    reg = get_registry()
    h_plan = reg.histogram(f"dstpu_{kind}_host_plan_seconds")
    h_wait = reg.histogram(f"dstpu_{kind}_device_wait_seconds")
    return {"host_plan_ms_p50": round(h_plan.quantile(0.5) * 1e3, 3),
            "device_wait_ms_p50": round(h_wait.quantile(0.5) * 1e3, 3),
            "iterations": h_wait.count}


def train_bench(size: str, micro: int, seq: int, zero_stage: int,
                iters: int = 10, **cfg_kw):
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.profiling.flops_profiler import chip_peak_flops

    cfg = gpt2_config(size, max_seq_len=seq, remat="full",
                      attn_impl="flash", loss_chunk=256, **cfg_kw)
    model = TransformerLM(cfg)
    engine, _, _, _ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
        "gradient_clipping": 1.0, "steps_per_print": 0})
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (micro, seq),
                                     dtype=np.int32)}
    m = engine.train_step(batch)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        m = engine.train_step(batch)
    float(m["loss"])
    dt = time.perf_counter() - t0
    tok = micro * seq * iters / dt
    n = engine.num_parameters()
    fpt = 6 * n + 12 * cfg.num_layers * cfg.d_model * seq
    mfu = tok * fpt / chip_peak_flops(jax.devices()[0])
    print(json.dumps({
        "metric": f"gpt2_{size}_zero{zero_stage}_tokens_per_sec_per_chip",
        "value": round(tok, 1), "unit": "tokens/s",
        "mfu": round(mfu, 4), "vs_baseline": round(mfu / 0.45, 4)}),
        flush=True)


def train_3d_bench(size: str = "125m", seq: int = 128,
                   micro_batches: int = 4, micro: int = 2, iters: int = 3,
                   shapes=((1, 1, 8), (2, 2, 2), (4, 2, 1)), **cfg_kw):
    """3D-parallel train sweep over (pp, tp, dp) mesh shapes on one chip
    budget (docs/training_perf.md "3D parallelism"). Per shape:

      - tokens/s/chip — the comparable throughput number;
      - measured bubble fraction — the pipeline engine's two-point slope
        fit over the compiled schedule (pp >= 2 only; the 1F1B number
        should sit well under gpipe's (S-1)/(M+S-1));
      - per-chip param+optimizer resident bytes — summed from the placed
        arrays' actual shard shapes, i.e. what the (pipe, model) param
        split x ZeRO data sharding really left on one chip;
      - stage-boundary ppermute volume per step per chip — analytic:
        every schedule tick rotates one [micro_local, seq, d_model]
        activation (1F1B also rotates the cotangent), so
        volume = transfers/step x micro_local x seq x d_model x 2B.
    """
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    ndev = jax.device_count()

    def _shard_bytes(tree):
        tot = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            sh = getattr(leaf, "sharding", None)
            if sh is None or not hasattr(leaf, "shape"):
                continue
            tot += int(np.prod(sh.shard_shape(leaf.shape))) * \
                leaf.dtype.itemsize
        return tot

    for pp, tp, dp in shapes:
        name = f"train3d_{size}_pp{pp}_tp{tp}_dp{dp}"
        if pp * tp * dp != ndev:
            print(json.dumps({
                "metric": name, "skipped":
                f"shape needs {pp * tp * dp} devices, have {ndev}"}),
                flush=True)
            continue
        cfg = gpt2_config(size, max_seq_len=seq, **cfg_kw)
        model = TransformerLM(cfg)
        m_count = micro_batches if pp > 1 else 1
        tb = micro * m_count * dp
        engine, _, _, _ = ds.initialize(model=model, config={
            "train_batch_size": tb,
            "gradient_accumulation_steps": m_count,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 6e-4, "weight_decay": 0.1}},
            "zero_optimization": {"stage": 1 if dp > 1 else 0},
            "mesh": {"pipe": pp, "model": tp, "data": dp},
            "gradient_clipping": 1.0, "steps_per_print": 0},
            rng=jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        batch = {"input_ids": rs.randint(0, cfg.vocab_size, (tb, seq),
                                         dtype=np.int32)}
        mt = engine.train_step(batch)
        float(mt["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            mt = engine.train_step(batch)
        float(mt["loss"])
        dt = (time.perf_counter() - t0) / iters
        row = {"metric": name,
               "value": round(tb * seq / dt / ndev, 1),
               "unit": "tokens/s/chip",
               "loss": round(float(mt["loss"]), 4),
               "per_chip_state_bytes":
               _shard_bytes(engine.state.get("params"))
               + _shard_bytes(engine.state.get("opt"))}
        if pp > 1:
            probe = engine.measure_bubble_fraction(repeats=1)
            row["bubble_frac"] = round(probe["bubble_frac"], 4)
            row["schedule"] = probe["schedule"]
            act_bytes = np.dtype(engine.compute_dtype).itemsize
            transfers = (4 * (m_count + pp - 1)
                         if engine.schedule == "1f1b"
                         else 2 * (m_count + pp - 1))
            row["ppermute_bytes_per_step"] = int(
                transfers * micro * seq * cfg.d_model * act_bytes)
        print(json.dumps(row), flush=True)


def decode_bench(size: str = "125m", batch: int = 4, prompt: int = 64,
                 new: int = 64):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    cfg = gpt2_config(size, max_seq_len=prompt + new, attn_impl="flash",
                      dtype=jnp.bfloat16)
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "bfloat16", "max_out_tokens": prompt + new})
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (batch, prompt), dtype=np.int32)
    for _ in range(3):
        eng.generate(ids, max_new_tokens=new, temperature=0.0)
    stats = eng.latency_stats()
    print(json.dumps({
        "metric": f"gpt2_{size}_decode_p50_ms_per_token",
        "value": round(stats["p50_ms"], 3), "unit": "ms",
        "p90_ms": round(stats["p90_ms"], 3),
        # decode-only since PR 4 (prefill now reported as TTFT instead
        # of being amortized into the per-token number)
        "ttft_p50_ms": round(stats["ttft_p50_ms"], 3),
        "decode_tokens_per_sec": round(stats["tokens_per_sec"], 1)}),
        flush=True)


def serving_decode_bench(size: str = "125m", slots: int = 8,
                         prompt: int = 128, new: int = 128):
    """Continuous-batching serving throughput (inference/serving/):
    `slots` concurrent streams through the single-trace batched decode
    step + paged KV pool, vs the single-stream decode baseline the
    `gpt2_*_decode_p50_ms_per_token` metric tracks."""
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    cfg = gpt2_config(size, max_seq_len=prompt + new, attn_impl="flash",
                      dtype=jnp.bfloat16)
    block = 32
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "bfloat16", "max_out_tokens": prompt + new,
        "temperature": 0.0,
        "serving": {"enabled": True, "kv_block_size": block,
                    "num_kv_blocks":
                        slots * ((prompt + new) // block + 1) + 8,
                    "max_batch_slots": slots}})
    srv = eng.serving_engine()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (prompt,)).tolist()
               for _ in range(2 * slots)]
    # warm the compiled programs (prefill bucket + decode step)
    srv.submit(prompts[0], max_new_tokens=4)
    srv.run(max_steps=50)
    itl = srv._m_itl            # decode-iteration wall-time histogram
    warm_sum, warm_n = itl.sum, itl.count   # exclude warmup+compile iters
    t0 = time.perf_counter()
    reqs = [srv.submit(p, max_new_tokens=new) for p in prompts]
    srv.run(max_steps=100 * len(prompts) * new)
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in reqs)
    iter_ms = ((itl.sum - warm_sum) / max(itl.count - warm_n, 1)) * 1e3
    n_req = len(prompts) + 1                 # incl. the warmup request
    lc = srv.lifecycle_counts
    print(json.dumps({
        "metric": "decode_batched_tokens_per_sec",
        "value": round(toks / dt, 1), "unit": "tokens/s",
        "slots": slots, "requests": len(prompts),
        "prompt": prompt, "new": new,
        "decode_iter_mean_ms": round(iter_ms, 3),
        "preemptions": srv.scheduler.preemption_count,
        # lifecycle rates (docs/serving.md "Failure handling &
        # overload") — the acceptance instrument for SLO work: a bench
        # run that sheds/expires/quarantines is overloaded or broken,
        # and these make it visible next to the throughput number
        "shed_rate": round(lc["shed"] / n_req, 3),
        "timeout_rate": round(lc["timed_out"] / n_req, 3),
        "quarantine_rate": round(lc["quarantined"] / n_req, 3),
        "cancelled": lc["cancelled"], "failed": lc["failed"],
        "decode_builds": srv.decode_builds}), flush=True)


def prefix_cache_bench(size: str = "125m", slots: int = 8,
                       n_req: int = 8, system: int = 384, user: int = 32,
                       new: int = 32):
    """Shared-prefix serving (the 'millions of users behind one system
    prompt' shape): ``n_req`` requests share a ``system``-token prompt
    and differ only in a short user tail.  Round 1 (cold) prefills the
    shared prefix from scratch; round 2 (warm) hits the committed
    blocks parked in the allocator's LRU — warm TTFT must sit
    measurably below cold, and the hit-rate counter proves WHY."""
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    total = system + user + new
    cfg = gpt2_config(size, max_seq_len=total, attn_impl="flash",
                      dtype=jnp.bfloat16)
    block = 32
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "bfloat16", "max_out_tokens": total, "temperature": 0.0,
        "serving": {"enabled": True, "kv_block_size": block,
                    # concurrent footprint + headroom so the shared
                    # blocks survive the LRU between rounds
                    "num_kv_blocks":
                        slots * ((total + 1) // block + 2)
                        + system // block + 8,
                    "max_batch_slots": slots,
                    "prefill_chunk_tokens": 256}})
    srv = eng.serving_engine()
    rs = np.random.RandomState(0)
    shared = rs.randint(0, cfg.vocab_size, (system,)).tolist()
    # compile the mixed program off the clock (distinct prompt so its
    # blocks neither pollute the cache rounds nor hit them)
    srv.submit(rs.randint(0, cfg.vocab_size, (8,)).tolist(),
               max_new_tokens=2)
    srv.run(max_steps=500)

    def one_round():
        reqs = [srv.submit(
            shared + rs.randint(0, cfg.vocab_size, (user,)).tolist(),
            max_new_tokens=new) for _ in range(n_req)]
        srv.run(max_steps=200 * n_req * new)
        ttfts = [r.first_token_time - r.submit_time for r in reqs]
        hits = sum(r.cache_hit_tokens for r in reqs)
        return float(np.percentile(ttfts, 50) * 1e3), hits

    cold_p50, cold_hits = one_round()
    warm_p50, warm_hits = one_round()
    prompt_tokens = n_req * (system + user)
    print(json.dumps({
        "metric": "serving_prefix_cache_warm_ttft_p50_ms",
        "value": round(warm_p50, 2), "unit": "ms",
        "ttft_p50_cold_ms": round(cold_p50, 2),
        "warm_vs_cold": round(warm_p50 / max(cold_p50, 1e-9), 3),
        "prefix_cache_hit_rate": round(warm_hits / prompt_tokens, 3),
        "cold_round_hit_rate": round(cold_hits / prompt_tokens, 3),
        "shared_tokens": system, "requests": n_req,
        "evictions": srv.allocator.evictions_total,
        "shed_rate": round(srv.lifecycle_counts["shed"] / (2 * n_req + 1),
                           3),
        "timeout_rate": round(
            srv.lifecycle_counts["timed_out"] / (2 * n_req + 1), 3),
        "quarantine_rate": round(
            srv.lifecycle_counts["quarantined"] / (2 * n_req + 1), 3),
        "decode_builds": srv.decode_builds}), flush=True)


def tiered_prefix_cache_bench(size: str = "125m", slots: int = 8,
                              n_req: int = 8, system: int = 384,
                              user: int = 32, new: int = 32,
                              block: int = 32,
                              dram_budget: int = 1 << 28, **cfg_kw):
    """Tiered prefix cache under memory pressure: the same shared-prefix
    shape as ``prefix_cache_bench``, but after the HBM-warm round a
    flood of distinct filler prompts cycles the paged pool's LRU so the
    shared chain is *demoted* to the host tier (int8 at rest).  Round 3
    then hits host, holds in PROMOTING while blocks scatter back, and
    its TTFT answers the tentpole question: is promote-from-DRAM
    measurably cheaper than recompute?  Target: host-warm p50 < 0.5x
    cold p50."""
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    total = system + user + new
    cfg_kw.setdefault("dtype", jnp.bfloat16)
    cfg_kw.setdefault("attn_impl", "flash")
    cfg = gpt2_config(size, max_seq_len=total, **cfg_kw)
    # same headroom math as prefix_cache_bench: shared blocks survive
    # rounds 1->2 in the LRU; the filler flood is sized off this pool
    # so eviction pressure is explicit, not accidental
    nb = (slots * ((total + 1) // block + 2) + system // block + 8)
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "bfloat16" if cfg_kw["dtype"] == jnp.bfloat16
                 else "float32",
        "max_out_tokens": total, "temperature": 0.0,
        "serving": {"enabled": True, "kv_block_size": block,
                    "num_kv_blocks": nb,
                    "max_batch_slots": slots,
                    "prefill_chunk_tokens": 256,
                    # int8 pool => byte-exact at rest, so the host
                    # round trip costs zero extra fidelity
                    "kv_cache_bits": 8,
                    "host_cache": {"enabled": True,
                                   "dram_budget_bytes": dram_budget}}})
    srv = eng.serving_engine()
    rs = np.random.RandomState(0)
    shared = rs.randint(0, cfg.vocab_size, (system,)).tolist()
    srv.submit(rs.randint(0, cfg.vocab_size, (8,)).tolist(),
               max_new_tokens=2)
    srv.run(max_steps=500)

    def one_round():
        reqs = [srv.submit(
            shared + rs.randint(0, cfg.vocab_size, (user,)).tolist(),
            max_new_tokens=new) for _ in range(n_req)]
        srv.run(max_steps=400 * n_req * new)
        ttfts = [r.first_token_time - r.submit_time for r in reqs]
        hbm = sum(r.cache_hit_tokens for r in reqs)
        return float(np.percentile(ttfts, 50) * 1e3), hbm

    cold_p50, _ = one_round()
    hbm_p50, hbm_hits = one_round()

    # flood: enough distinct `system`-length prompts to cycle every LRU
    # slot at least twice -> the shared chain demotes to the host tier
    fillers = 2 * (nb // max(1, system // block)) + slots
    for _ in range(fillers):
        srv.submit(rs.randint(0, cfg.vocab_size, (system,)).tolist(),
                   max_new_tokens=2)
        srv.run(max_steps=40 * system)
    spills = srv.host_cache.spills_total

    host_tok0 = srv.allocator.host_hit_tokens_total
    promo0, psec0 = srv.host_counts["promoted_blocks"], srv.promote_seconds
    host_p50, host_round_hits = one_round()
    promoted = srv.host_counts["promoted_blocks"] - promo0
    psec = srv.promote_seconds - psec0
    host_hit_tok = srv.allocator.host_hit_tokens_total - host_tok0

    prompt_tokens = n_req * (system + user)
    print(json.dumps({
        "metric": "serving_tiered_prefix_cache_host_warm_ttft_p50_ms",
        "value": round(host_p50, 2), "unit": "ms",
        "ttft_p50_cold_ms": round(cold_p50, 2),
        "ttft_p50_hbm_warm_ms": round(hbm_p50, 2),
        "host_warm_vs_cold": round(host_p50 / max(cold_p50, 1e-9), 3),
        "target_host_warm_vs_cold": 0.5,
        "hbm_hit_rate": round(hbm_hits / prompt_tokens, 3),
        "host_hit_rate": round(host_hit_tok / prompt_tokens, 3),
        # total hit tokens in round 3 (HBM residue + host-claimed)
        "host_round_total_hit_rate": round(
            host_round_hits / prompt_tokens, 3),
        "tier_hits": dict(srv.host_cache.hits_total),
        "spills": spills, "filler_requests": fillers,
        "promoted_blocks": promoted,
        "promote_mb_s": round(
            promoted * srv.host_cache.entry_nbytes / max(psec, 1e-9)
            / 1e6, 2),
        "host_entry_bytes": srv.host_cache.entry_nbytes,
        "promote_failures": srv.host_counts["promote_failures"],
        "spill_failures": srv.host_counts["spill_failures"],
        "decode_builds": srv.decode_builds}), flush=True)


def paged_decode_attention_bench(slots: int = 8, heads: int = 16,
                                 d: int = 128, cache: int = 16384,
                                 block: int = 256, iters: int = 20):
    """Batched paged decode-attention kernel at serving shapes: `slots`
    ragged sequences (cache/2 .. cache tokens) through one kernel
    dispatch. Achieved GB/s counts only the VALID kv bytes each slot
    actually attends — the block tables mean padding is never read."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        paged_decode_attention)

    rs = np.random.RandomState(0)
    pages = cache // block
    nb = slots * pages + 1
    lens = np.linspace(cache // 2, cache, slots).astype(np.int32)
    bt = np.zeros((slots, pages), np.int32)
    free = 1
    for i, ln in enumerate(lens):
        n = -(-int(ln) // block)
        bt[i, :n] = np.arange(free, free + n)
        free += n
    q = jnp.asarray(rs.randn(slots, heads, d), jnp.bfloat16)
    pk = jnp.asarray(rs.randn(nb, block, heads * d), jnp.bfloat16)
    pv = jnp.asarray(rs.randn(nb, block, heads * d), jnp.bfloat16)
    lens_j = jnp.asarray(lens)
    bt_j = jnp.asarray(bt)
    # pools ride as ARGUMENTS (closing over them would bake ~GiB of pool
    # data into the executable as constants)
    f = jax.jit(lambda q, pk, pv: paged_decode_attention(q, pk, pv,
                                                         lens_j, bt_j))
    o = f(q, pk, pv)
    o.block_until_ready()
    qq = q
    t0 = time.perf_counter()
    for _ in range(iters):
        qq = jnp.roll(qq, 1, axis=1)           # a new input per dispatch
        o = f(qq, pk, pv)
    o.block_until_ready()
    ms = (time.perf_counter() - t0) / iters * 1000
    valid_gb = float(lens.sum()) * heads * d * 2 * 2 / 2**30
    print(json.dumps({
        "metric": "decode_attention_batched_gbps",
        "value": round(valid_gb / (ms / 1000), 1), "unit": "GB/s",
        "ms": round(ms, 3), "slots": slots,
        "valid_kv_gib": round(valid_gb, 2),
        "cache_tokens": [int(x) for x in lens]}), flush=True)


def hbm_ceiling_probe() -> float:
    """Measured HBM bandwidth ceiling (bf16 elementwise chain, best of
    8 — same discipline as bench.py measure_roofline): the denominator
    of every roofline_frac this file emits."""
    import jax
    import jax.numpy as jnp
    a = jnp.asarray(np.random.default_rng(0).standard_normal(
        1 << 26, dtype=np.float32), jnp.bfloat16)

    @jax.jit
    def ew_chain(a):
        return jax.lax.fori_loop(
            0, 20, lambda i, a: a * 1.0000001 + 0.0000001, a)

    y = ew_chain(a)
    y.block_until_ready()
    best = float("inf")
    for _ in range(8):
        t0 = time.perf_counter()
        y = ew_chain(y)
        y.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2 * a.nbytes * 20 / best / 2**30


def paged_decode_roofline_sweep(hbm_gbps: float, slots: int = 8,
                                heads: int = 16, d: int = 128,
                                cache: int = 16384, iters: int = 16):
    """ISSUE 8 roofline sweep: the paged decode kernel across pages-
    per-program (double-buffer group width) x block size x kv bits.
    Each point reports the bytes that ACTUALLY cross HBM (compressed
    values + scales at 8/4-bit) and its fraction of the probed
    ceiling; ``kv_blocks_capacity_effective`` records how many pool
    blocks the bf16 pool's HBM budget admits at each width — the
    concurrency side of the quantization win."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving.block_allocator import (
        blocks_for_budget, kv_block_bytes)
    from deepspeed_tpu.ops.quantizer import kv_quantize
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        paged_decode_attention)

    rs = np.random.RandomState(0)
    best = None
    for block in (128, 256):   # a quantized pool needs block % 128 == 0
        pages = cache // block
        nb = slots * pages + 1
        lens = np.linspace(cache // 2, cache, slots).astype(np.int32)
        bt = np.zeros((slots, pages), np.int32)
        free = 1
        for i, ln in enumerate(lens):
            n = -(-int(ln) // block)
            bt[i, :n] = np.arange(free, free + n)
            free += n
        q = jnp.asarray(rs.randn(slots, heads, d), jnp.bfloat16)
        pk16 = jnp.asarray(rs.randn(nb, block, heads, d), jnp.bfloat16)
        pv16 = jnp.asarray(rs.randn(nb, block, heads, d), jnp.bfloat16)
        lens_j, bt_j = jnp.asarray(lens), jnp.asarray(bt)

        def pool(rows):        # [nb, block, heads, De] -> kernel layout
            return rows.reshape(nb, block, -1)
        for bits in (0, 8, 4):
            if bits:
                # scales [nb, block, heads] -> [nb, heads, 1, block]
                (pk, ks), (pv, vs) = (kv_quantize(x, bits)
                                      for x in (pk16, pv16))
                ks, vs = (x.transpose(0, 2, 1)[:, :, None]
                          for x in (ks, vs))
            else:
                pk, pv, ks, vs = pk16, pv16, None, None
            pk, pv = pool(pk), pool(pv)
            # bytes one dispatch actually reads: each slot's valid rows,
            # values + scales, k and v — kv_block_bytes at block_size 1
            # IS the per-row rule (pinned against init_paged_cache)
            gb = float(lens.sum()) * kv_block_bytes(1, heads, d,
                                                    bits) / 2**30
            for pp in (1, 2, None):   # None: the kernel's own VMEM budget
                if pp is not None and pp > pages:
                    continue
                # pools AND scales ride as arguments (closing over them
                # would bake them into the executable as constants)
                kern = functools.partial(paged_decode_attention,
                                         kv_bits=bits,
                                         pages_per_program=pp)
                f = jax.jit(lambda q, pk, pv, ks, vs, kern=kern:
                            kern(q, pk, pv, lens_j, bt_j,
                                 k_scale=ks, v_scale=vs))
                qq = q
                o = f(qq, pk, pv, ks, vs)
                o.block_until_ready()
                t0 = time.perf_counter()
                for _ in range(iters):
                    qq = jnp.roll(qq, 1, axis=1)   # genuinely new input
                    o = f(qq, pk, pv, ks, vs)
                o.block_until_ready()
                ms = (time.perf_counter() - t0) / iters * 1000
                gbps = gb / (ms / 1000)
                point = {
                    "metric": "paged_decode_roofline_point",
                    "block": block, "pages_per_program": pp,
                    "kv_bits": bits, "ms": round(ms, 3),
                    "hbm_gib_moved": round(gb, 3),
                    "achieved_gbps": round(gbps, 1),
                    "roofline_frac": round(gbps / hbm_gbps, 3)
                    if hbm_gbps else None}
                print(json.dumps(point), flush=True)
                if bits == 0 and (best is None
                                  or ms < best["ms"]):
                    best = point
    budget = 512 * kv_block_bytes(16, heads, d)
    print(json.dumps({
        "metric": "kv_blocks_capacity_effective",
        "unit": "blocks@same_hbm_budget",
        "budget_bf16_blocks": 512,
        "value": {str(b): blocks_for_budget(budget, 16, heads, d, b)
                  for b in (0, 8, 4)},
        "best_bf16_point": best}), flush=True)


def blocksparse_bench(seq: int = 8192, heads: int = 8, d: int = 128,
                      iters: int = 8):
    """Block-sparse flash vs dense flash at long sequence — the nnz win
    (VERDICT r2 #10). Sliding-window layout, fwd+bwd timed."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.sparse_attention import (
        LocalSlidingWindowSparsityConfig, blocksparse_attention_bthd)
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_bthd)

    # block 512 / window 3 measured fastest on v5e (128-blocks are grid-
    # overhead-bound); the nnz win grows with seq as dense goes quadratic
    scfg = LocalSlidingWindowSparsityConfig(
        num_heads=heads, block=512, num_sliding_window_blocks=3)

    def run(f, q, k, v):
        # a new input per dispatch (roll the query), timed to the
        # readiness of the last result
        loss = jax.jit(jax.grad(lambda q: jnp.sum(f(q, k, v) ** 2)))
        jax.block_until_ready(loss(q))
        qq = q
        t0 = time.perf_counter()
        for _ in range(iters):
            qq = jnp.roll(qq, 1, axis=1)
            g = loss(qq)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / iters * 1000

    res = {}
    for s in (seq, 2 * seq):
        rng = np.random.RandomState(0)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.randn(1, s, heads, d), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        res[s] = (
            run(lambda q, k, v: blocksparse_attention_bthd(q, k, v, scfg),
                q, k, v),
            run(lambda q, k, v: flash_attention_bthd(q, k, v), q, k, v))
    bs_ms, fl_ms = res[2 * seq]
    print(json.dumps({
        "metric": "blocksparse_attn_fwdbwd_ms_seq16k",
        "value": round(bs_ms, 2), "unit": "ms",
        "flash_dense_ms": round(fl_ms, 2),
        "speedup_vs_flash": round(fl_ms / bs_ms, 2),
        "seq8k_ms": round(res[seq][0], 2),
        "seq8k_flash_ms": round(res[seq][1], 2),
        "layout_density": round(2 / (2 * seq // 512), 3)}), flush=True)


def diffusion_bench(iters: int = 4):
    """SD-v1.5-geometry UNet denoising step latency (BASELINE.md tracked
    config 'Stable-Diffusion inference with kernel injection'): full
    320/640/1280/1280 UNet at 64x64 latents with CFG (batch doubles),
    77-token text context, bf16."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.diffusion import (UNet2DCondition,
                                                UNetConfig)
    cfg = UNetConfig(dtype=jnp.bfloat16)
    unet = UNet2DCondition(cfg)
    params = jax.jit(unet.init)(jax.random.PRNGKey(0))
    step = jax.jit(unet.apply)
    lat = jnp.zeros((2, 64, 64, 4), jnp.bfloat16)      # CFG pair
    ctx = jnp.zeros((2, 77, 768), jnp.bfloat16)
    t = jnp.array([500, 500], jnp.int32)
    out = step(params, lat, t, ctx)
    np.asarray(jax.device_get(out[0, 0, 0]))           # sync barrier
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(params, out, t, ctx)
    np.asarray(jax.device_get(out[0, 0, 0]))
    ms = (time.perf_counter() - t0) / iters * 1e3
    print(json.dumps({
        "metric": "sd15_unet_step_latency", "value": round(ms, 1),
        "unit": "ms", "latents": "2x64x64x4 (cfg pair)",
        "steps_per_sec": round(1000.0 / ms, 2),
        "est_50step_image_s": round(ms * 50 / 1000.0, 1)}), flush=True)


def host_offload_bench(seq: int = 8192, iters: int = 2):
    """Host activation checkpointing ladder (reference cpu_checkpointing,
    `activation_checkpointing/checkpointing.py:485`): at a long sequence,
    find the largest micro-batch trainable under remat='full' (residual
    stash in HBM) vs remat='host_offload' (stash in pinned host DRAM) —
    the long-sequence memory lever Infinity doesn't cover."""
    import gc

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    # for THIS ladder, where the only varied quantity is memory, these
    # errors classify as OOM
    oom_markers = ("RESOURCE_EXHAUSTED", "Out of memory", "OOM",
                   "Ran out of memory")

    def try_step(remat, micro):
        # deep-narrow: the residual stash (L x d bytes/token) dominates
        # the per-layer recompute working set (~12 x d bytes/token), so
        # spilling the stash to host moves the trainable-batch ceiling —
        # the regime host activation checkpointing exists for
        cfg = gpt2_config("125m", max_seq_len=seq, remat=remat,
                          num_layers=48, d_model=512, num_heads=8,
                          attn_impl="flash", loss_chunk=256)
        conf = {"train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                "bf16": {"enabled": True}, "steps_per_print": 0}
        rs = np.random.RandomState(0)
        b = {"input_ids": rs.randint(0, cfg.vocab_size, (micro, seq),
                                     dtype=np.int32)}
        try:
            eng, _, _, _ = ds.initialize(model=TransformerLM(cfg),
                                         config=conf)
            fn = eng._build_train_step()
            ma = fn.lower(eng.state,
                          {"input_ids": b["input_ids"][None]}
                          ).compile().memory_analysis()
            mem = {"hbm_temp_gib": round(ma.temp_size_in_bytes / 2**30, 2),
                   "host_temp_gib": round(
                       getattr(ma, "host_temp_size_in_bytes", 0) / 2**30,
                       2)}
            m = eng.train_step(b)
            float(m["loss"])
            t0 = time.perf_counter()
            for _ in range(iters):
                m = eng.train_step(b)
            float(m["loss"])
            tput = micro * seq * iters / (time.perf_counter() - t0)
            del eng
            gc.collect()
            return tput, mem
        except Exception as e:
            if any(s in str(e) for s in oom_markers):
                gc.collect()
                return None, None
            raise

    results = {}
    for remat in ("full", "host_offload"):
        fit, tput, mem = 0, None, None
        for micro in (16, 32):
            t, ma = try_step(remat, micro)
            if t is None:
                break
            fit, tput, mem = micro, t, ma
        results[remat] = {"max_micro": fit,
                          "tokens_per_sec": round(tput or 0.0, 1),
                          "memory": mem}
    print(json.dumps({
        "metric": "host_act_ckpt_max_tokens",
        "value": results["host_offload"]["max_micro"] * seq,
        "unit": "tokens/batch", "seq": seq,
        "full_remat": results["full"],
        "host_offload": results["host_offload"]}), flush=True)


def wire_bench(mb: int = 32):
    """Measured host<->device wire roofline — the hard bound on every
    offload design on this machine; reported in-band so offload numbers
    can be judged against hardware reality (VERDICT r2 weak #1)."""
    import jax
    import jax.numpy as jnp
    x = np.ones((mb << 20,), np.uint8)
    jax.device_put(x[:1 << 20]).block_until_ready()   # warm the path
    t0 = time.perf_counter()
    d = jax.device_put(x)
    d.block_until_ready()
    h2d = mb / 1024 / (time.perf_counter() - t0)
    y = (jnp.asarray(d) + 1).block_until_ready()
    t0 = time.perf_counter()
    np.asarray(y)
    d2h = mb / 1024 / (time.perf_counter() - t0)
    print(json.dumps({"metric": "wire_bandwidth", "value": round(d2h, 4),
                      "unit": "GB/s_d2h", "h2d_gbps": round(h2d, 3),
                      "d2h_gbps": round(d2h, 4)}), flush=True)
    return h2d, d2h


def offload_bench(iters: int = 3):
    """ZeRO-Offload tier 1 (host-DRAM optimizer, pipelined sweep) vs the
    same model in-HBM. Model sized to the measured wire: the offload step
    moves 4 bytes/param f32 grads down + 2 bytes/param bf16 up."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    cfg = gpt2_config("125m", max_seq_len=256, num_layers=4, d_model=512,
                      num_heads=8, loss_chunk=256, attn_impl="flash")
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 256),
                                     dtype=np.int32)}

    def run(zero):
        conf = {"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": zero, "steps_per_print": 0}
        eng, _, _, _ = ds.initialize(model=TransformerLM(cfg), config=conf)
        m = eng.train_step(batch)
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            m = eng.train_step(batch)
        float(m["loss"])
        return 8 * 256 * iters / (time.perf_counter() - t0)

    base = run({"stage": 0})
    off = run({"stage": 0, "offload_optimizer": {"device": "cpu"}})
    # r5: the tier-1 grad wire rides the Infinity codec (offload_wire_bits)
    off1 = run({"stage": 0, "offload_optimizer": {"device": "cpu"},
                "offload_wire_bits": 1})
    print(json.dumps({
        "metric": "offload_tier1_tokens_per_sec",
        "value": round(off1, 1), "unit": "tokens/s",
        "in_hbm_tokens_per_sec": round(base, 1),
        "uncompressed_wire_tokens_per_sec": round(off, 1),
        "wire1bit_speedup": round(off1 / off, 2),
        "offload_vs_hbm": round(off1 / base, 4)}), flush=True)


def infinity_bench(h2d_gbps: float, d2h_gbps: float):
    """peak-params-per-chip: train the largest ladder config whose
    (wire-bound) step fits the time budget, with ZeRO-Infinity layer
    streaming. Also projects every larger config against host RAM and the
    measured wire so capability vs. wire-constraint is explicit."""
    import os

    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.models.transformer import GPT2_SIZES, TransformerConfig

    budget = float(os.environ.get("DSTPU_INFINITY_BUDGET_S", "900"))
    seq = 512
    try:
        avail = int(next(l for l in open("/proc/meminfo")
                         if "MemAvailable" in l).split()[1]) * 1024
    except Exception:
        avail = 64 << 30
    hbm = 16 << 30   # v5e

    ladder = ["350m", "760m", "1.3b", "2.7b", "6.7b", "13b"]
    wire_bits = 1                  # stochastic-sign D2H grad wire (16x)
    live_budget = int(4e9)         # device layer-cache params (8 GiB bf16)
    projections = {}
    chosen = None
    for name in ladder:
        c = TransformerConfig(**{"max_seq_len": seq, **GPT2_SIZES[name]})
        p = c.num_params()
        host = 14 * p               # 2 bf16 store + 12 opt state
        # step wire: fwd uploads every layer (2 bytes/param bf16); the
        # backward re-uses the device layer cache up to live_budget and
        # re-uploads the rest; grads cross D2H at wire_bits/8 bytes/param
        per_layer = p / max(c.num_layers, 1)
        cached = min(c.num_layers, int(live_budget // per_layer))
        h2d_bytes = 2 * p + 2 * p * (1 - cached / max(c.num_layers, 1))
        d2h_bytes = p * wire_bits / 8
        est = (d2h_bytes / (d2h_gbps * 2**30 + 1) +
               h2d_bytes / (h2d_gbps * 2**30 + 1) + 16 * p / (3 * 2**30))
        fits_ram = host < avail * 0.85
        projections[name] = {
            "params_b": round(p / 1e9, 2),
            "host_gib": round(host / 2**30, 1),
            "est_step_s": round(est, 1),
            "hbm_equiv": round(16 * p / hbm, 2),   # on-device Adam bytes
            "fits": bool(fits_ram and est < budget)}
        if fits_ram and est < budget:
            chosen = name
    if chosen is None:
        chosen = "350m"

    cfg = gpt2_config(chosen, max_seq_len=seq, loss_chunk=256,
                      attn_impl="flash")
    conf = {"train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 6e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {
                "stage": 3, "infinity_host_init": True,
                "offload_wire_bits": wire_bits,
                "max_live_parameters": live_budget,
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"device": "cpu"}},
            "steps_per_print": 0}
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (1, seq),
                                     dtype=np.int32)}
    eng, _, _, _ = ds.initialize(model=TransformerLM(cfg), config=conf)
    t0 = time.perf_counter()
    m = eng.train_step(batch)
    step1 = time.perf_counter() - t0
    steps, elapsed = 1, step1
    if elapsed + step1 < budget:      # a compile-free step fits too
        t0 = time.perf_counter()
        m = eng.train_step(batch)
        step_t = time.perf_counter() - t0
        steps += 1
    else:
        step_t = step1                # includes compile; flagged below
    p = eng.num_parameters()
    print(json.dumps({
        "metric": "peak_params_per_chip",
        "value": p, "unit": "params",
        "config": chosen,
        "tokens_per_sec": round(seq / step_t, 2),
        "step_seconds": round(step_t, 1),
        "includes_compile": steps == 1,
        "hbm_equivalent": round(16 * p / hbm, 2),
        "loss": round(float(m["loss"]), 3),
        "wire_d2h_gbps": round(d2h_gbps, 4),
        "wire_bits": wire_bits,
        "device_cache_layers": eng._infinity.max_live_layers,
        "projections": projections}), flush=True)


def multi_tenant_replay_bench(slots: int = 4, new: int = 16,
                              rounds: int = 60, spec_k: int = 1,
                              **model_kw):
    """Bursty 3-tenant replay through the SLO frontend (docs/serving.md
    "Sampling, streaming & multi-tenant SLOs"): an interactive tenant
    (4x weight, TTFT SLO) trickles short sampled prompts, a standard
    tenant submits steadily, and a batch tenant dumps two long-prompt
    bursts into a bounded queue — with the speculative lane armed.
    Reports per-tenant p50/p99 TTFT and inter-token latency, shed /
    timeout rates, and the draft acceptance rate: the fairness
    instrument — under the bursts the interactive percentiles should
    hold while the batch tenant absorbs the queueing and the sheds.

    An ``SloMonitor`` with bench-tight windows rides along: the SLO
    column reports how many burn-rate alerts fired, the time to the
    first alert, and the time the running p99 of the under-provisioned
    tenant's TTFT first showed the breach — the alert should win that
    race (docs/observability.md "SLO alerting")."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import (ServingFrontend,
                                                 SloMonitor, TenantSpec)
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    cfg = gpt2_config("125m", dtype=jnp.float32, **model_kw)
    art_dir = _bench_artifact_dir()
    eng = ds.init_inference(TransformerLM(cfg), config={
        "dtype": "float32", "max_out_tokens": 128, "temperature": 0.0,
        "replace_with_kernel_inject": False,
        "observability": _obs_block(art_dir),
        "serving": {"enabled": True, "kv_block_size": 8,
                    "num_kv_blocks": 64, "max_batch_slots": slots,
                    "prefill_chunk_tokens": 32, "max_queue_depth": 6,
                    "spec_k": spec_k}})
    draft = TransformerLM(gpt2_config(
        "125m", dtype=jnp.float32, **dict(model_kw, num_layers=1)))
    srv = eng.serving_engine(draft_model=draft,
                             draft_params=draft.init(jax.random.PRNGKey(1)))
    # bench-tight burn-rate windows so a breach inside a ~seconds run
    # is observable; threshold 1.0 = burning the error budget at all
    slo_mon = SloMonitor(objective=0.9, fast_window_s=2.0,
                         slow_window_s=8.0, burn_threshold=1.0,
                         min_samples=3)
    alerts = []
    slo_mon.subscribe(lambda a: alerts.append(
        (time.perf_counter(), a)))
    fe = ServingFrontend(srv, slo=slo_mon)
    fe.register(TenantSpec("interactive", weight=4.0, ttft_slo_s=0.5))
    fe.register(TenantSpec("standard", weight=1.0))
    # the under-provisioned tenant: unit weight, a TTFT target its own
    # bursts cannot meet behind the bounded queue — the burn-rate alert
    # should fire here, and before the p99 shows it
    fe.register(TenantSpec("batch", weight=1.0, max_queue_share=0.5,
                           ttft_slo_s=0.3))
    tenants = ("interactive", "standard", "batch")
    ttft = {t: [] for t in tenants}
    itl = {t: [] for t in tenants}
    p99_breach = {"at": None}

    def hook(ev):
        if ev.token is None or ev.tenant not in ttft:
            return
        if ev.index == 0:
            ttft[ev.tenant].append(ev.time_s - ev.request.submit_time)
            # the histogram's view of the breach: first wall time the
            # running p99 of the batch tenant's completed TTFTs
            # exceeds its target
            if (ev.tenant == "batch" and p99_breach["at"] is None
                    and len(ttft["batch"]) >= 3
                    and float(np.percentile(ttft["batch"], 99)) > 0.3):
                p99_breach["at"] = time.perf_counter()
        elif ev.prev_time_s is not None:
            itl[ev.tenant].append(ev.time_s - ev.prev_time_s)

    srv.token_hooks.append(hook)
    fe.submit([1, 2, 3], max_new_tokens=4)      # warm the compile
    srv.run()
    rs = np.random.RandomState(7)
    reqs = {t: [] for t in tenants}

    def sub(tenant, plen, **kw):
        p = rs.randint(0, cfg.vocab_size, (plen,)).tolist()
        reqs[tenant].append(fe.submit(p, tenant=tenant,
                                      max_new_tokens=new, **kw))

    t0 = time.perf_counter()
    for r in range(rounds):
        if r % 3 == 0:
            sub("interactive", int(rs.randint(4, 9)),
                temperature=0.7, top_k=16, seed=100 + r)
        if r % 5 == 0:
            sub("standard", int(rs.randint(10, 14)))
        if r in (2, rounds // 2):               # the bursts
            for _ in range(5):
                sub("batch", int(rs.randint(20, 25)))
        srv.step()
    srv.run()
    dt = time.perf_counter() - t0
    # quiet tail: the load is gone, the fast window drains, and the
    # firing alerts must RESOLVE (the hysteresis edge of the state
    # machine) — bounded at ~2.5x the fast window
    quiet_deadline = time.perf_counter() + 2.5 * slo_mon.fast_window_s
    while (any(v["state"] == "firing"
               for v in slo_mon.snapshot().values())
           and time.perf_counter() < quiet_deadline):
        time.sleep(0.1)
        slo_mon.evaluate()

    def pcts(xs):
        if not xs:
            return {"p50_ms": None, "p99_ms": None}
        return {"p50_ms": round(float(np.percentile(xs, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(xs, 99)) * 1e3, 2)}

    sc = srv.spec_counts
    per_tenant = {}
    for t in tenants:
        rs_t = reqs[t]
        shed = sum(r.status.value == "shed" for r in rs_t)
        timed = sum(r.status.value == "timed_out" for r in rs_t)
        per_tenant[t] = {
            "requests": len(rs_t),
            "ttft": pcts(ttft[t]), "inter_token": pcts(itl[t]),
            "shed_rate": round(shed / max(len(rs_t), 1), 3),
            "timeout_rate": round(timed / max(len(rs_t), 1), 3),
            "tokens": sum(len(r.output) for r in rs_t)}
    fired = [(at, a) for at, a in alerts if a.state == "firing"]
    first_alert_s = round(fired[0][0] - t0, 3) if fired else None
    breach_s = round(p99_breach["at"] - t0, 3) \
        if p99_breach["at"] is not None else None
    # one merged trace artifact per run: flush the tracer (request
    # waterfalls + overlap iteration track ride along) and assemble
    from deepspeed_tpu.observability import FleetTraceAssembler, get_tracer
    tracer = get_tracer()
    trace_path = FleetTraceAssembler() \
        .add_file(tracer.flush(), label=f"rank{tracer.rank}") \
        .write(os.path.join(art_dir, "multi_tenant_fleet_trace.json"))
    print(json.dumps({
        "metric": "multi_tenant_replay",
        "value": round(sum(pt["tokens"] for pt in per_tenant.values())
                       / dt, 1),
        "unit": "tokens/s", "slots": slots, "rounds": rounds,
        "tenants": per_tenant, "spec_k": spec_k,
        "spec_proposed": sc["proposed"], "spec_accepted": sc["accepted"],
        "spec_acceptance_rate": round(
            sc["accepted"] / max(sc["proposed"], 1), 3),
        "slo": {
            "alerts_fired": len(fired),
            "alerts_resolved": sum(
                a.state == "resolved" for _, a in alerts),
            "time_to_first_alert_s": first_alert_s,
            "p99_breach_at_s": breach_s,
            "alert_before_p99": (first_alert_s is not None
                                 and (breach_s is None
                                      or first_alert_s <= breach_s)),
            "firing_now": sorted(
                k for k, v in slo_mon.snapshot().items()
                if v["state"] == "firing")},
        "overlap": _overlap_columns("serving"),
        "fleet_trace": trace_path,
        "decode_builds": srv.decode_builds}), flush=True)


def fleet_failover_bench(replicas: int = 2, rounds: int = 12,
                         new: int = 12, kill_at: int = 9, **model_kw):
    """Price the fleet failover path (docs/serving.md "Fleet serving &
    failover"): the same two-tenant wave runs twice across the replica
    fleet — once clean, once with a fatal ``serving.fleet.replica_step``
    killing one replica at a fixed site-call index mid-wave.  Reports
    the failover detection latency (kill -> first replayed token
    delivered past the dedup high-water mark), the replayed-token
    overhead the dedup swallowed, per-tenant p99 TTFT with vs without
    the kill, and ``decode_builds`` (must stay 1 per surviving replica
    — failover replays ride the existing compiled step, never a
    retrace).  Absolute latencies are only meaningful on TPU."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import FleetRouter, ReplicaState
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.runtime.resilience import (FaultInjector,
                                                  install_fault_injector)

    cfg = gpt2_config("125m", dtype=jnp.float32, **model_kw)
    tenants = ("interactive", "batch")

    def run(kill: bool):
        eng = ds.init_inference(TransformerLM(cfg), config={
            "dtype": "float32", "max_out_tokens": 64,
            "temperature": 0.0, "replace_with_kernel_inject": False,
            "serving": {"enabled": True, "kv_block_size": 8,
                        "num_kv_blocks": 64, "max_batch_slots": 4,
                        "prefill_chunk_tokens": 32,
                        "max_queue_depth": 32,
                        "fleet": {"enabled": True,
                                  "replicas": replicas}}})
        fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
        # warm every replica's compile before the clock (and before the
        # injector: warmup steps must not consume the kill index)
        for _ in range(replicas):
            fleet.submit([1, 2, 3], max_new_tokens=4)
        fleet.run()
        t_kill = {}
        for r in fleet.replicas:
            orig = r.mark_dead
            def dead(reason, _orig=orig):
                t_kill.setdefault("t", time.perf_counter())
                _orig(reason)
            r.mark_dead = dead
        fi = FaultInjector()
        if kill:
            fi.add_plan("serving.fleet.replica_step", "fatal",
                        at=kill_at)
        install_fault_injector(fi)
        try:
            rs = np.random.RandomState(11)
            ttft = {t: [] for t in tenants}
            first_replay = {}

            def hook(freq):
                def _cb(ev):
                    if ev.token is None:
                        return
                    if ev.index == 0:
                        ttft[ev.tenant].append(
                            ev.time_s - freq.submit_time)
                    if "t" in t_kill and freq.failovers:
                        first_replay.setdefault(
                            freq.req_id, time.perf_counter())
                return _cb

            reqs = []
            t0 = time.perf_counter()
            for i in range(rounds):
                plen = int(rs.randint(4, 9)) if i % 2 == 0 \
                    else int(rs.randint(16, 21))
                tenant = tenants[i % 2]
                p = rs.randint(0, cfg.vocab_size, (plen,)).tolist()
                freq = fleet.submit(p, max_new_tokens=new,
                                    tenant=tenant)
                freq.on_token = hook(freq)
                reqs.append(freq)
                fleet.pump()
            fleet.run()
            dt = time.perf_counter() - t0
            assert all(r.status is not None and r.status.value == "ok"
                       for r in reqs), "a request did not survive"
            dead = [r.replica_id for r in fleet.replicas
                    if r.state is ReplicaState.DEAD]
            detect_ms = None
            if "t" in t_kill and first_replay:
                detect_ms = round(
                    (min(first_replay.values()) - t_kill["t"]) * 1e3, 2)
            return {
                "tokens_per_sec": round(
                    sum(len(r.output) for r in reqs) / dt, 1),
                "ttft_p99_ms": {
                    t: round(float(np.percentile(ttft[t], 99)) * 1e3, 2)
                    for t in tenants if ttft[t]},
                "dead_replicas": dead,
                "failovers": fleet.fleet_counts["failovers"],
                "replayed_tokens": fleet.fleet_counts["replayed_tokens"],
                "failover_detect_ms": detect_ms,
                "decode_builds": [r.srv.decode_builds
                                  for r in fleet.replicas]}
        finally:
            install_fault_injector(FaultInjector())

    base = run(kill=False)
    killed = run(kill=True)
    assert all(b == 1 for b in killed["decode_builds"]), \
        "failover replay retraced a surviving replica"
    print(json.dumps({
        "metric": "fleet_failover",
        "value": killed["failover_detect_ms"], "unit": "ms",
        "replicas": replicas, "kill_at": kill_at,
        "dead_replica": (killed["dead_replicas"] or [None])[0],
        "failovers": killed["failovers"],
        "replayed_tokens": killed["replayed_tokens"],
        "tokens_per_sec": {"baseline": base["tokens_per_sec"],
                           "kill": killed["tokens_per_sec"]},
        "ttft_p99_ms": {"baseline": base["ttft_p99_ms"],
                        "kill": killed["ttft_p99_ms"]},
        "decode_builds": killed["decode_builds"]}), flush=True)


def disaggregated_fleet_bench(rounds: int = 18, new: int = 10,
                              chips: int = 3, burst: int = 4,
                              **model_kw):
    """Price the disaggregated prefill/decode split (docs/serving.md
    "Disaggregated fleet & autoscaling"): the same bursty two-tenant
    trace runs twice on the SAME chip budget — once on a uniform
    ``chips``-replica fleet, once on a 1-prefill + 1-decode split with
    the SLO/queue-driven autoscaler allowed to grow the decode class up
    to the budget.  An interactive tenant streams short prompts every
    round while a batch tenant dumps long-prompt prefill bursts; in the
    uniform fleet those prefill chunks ride the decode iterations and
    inflate everyone's TTFT, in the split fleet they land on the
    prefill worker and arrive at the decode class as claimable fabric
    chains.  Reports per-tenant p99 TTFT and decode tokens/s for both
    shapes — aggregate AND per decode-class chip (every uniform replica
    is decode-class but spends iterations on prefill chunks; that
    dilution is the interference disaggregation removes, so the
    per-chip number is the one the split should win) — plus the
    autoscaler's scale events against the wall time the uniform run's
    running p99 first showed the breach (the scale-up should win that
    race), and ``decode_builds`` per replica (must stay 1 — the handoff
    rides the compiled mixed program, never a retrace).  Absolute
    latencies are only meaningful on TPU."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import (FleetAutoscaler,
                                                 FleetRouter)
    from deepspeed_tpu.inference.serving.engine import ServingEngine
    from deepspeed_tpu.inference.serving.fleet.replica import ReplicaHandle
    from deepspeed_tpu.models import TransformerLM, gpt2_config
    from deepspeed_tpu.observability.slo import KIND_TTFT, SloMonitor

    cfg = gpt2_config("125m", dtype=jnp.float32, **model_kw)
    tenants = ("interactive", "batch")
    targets = {"interactive": 0.5, "batch": 1.5}

    art_dir = _bench_artifact_dir()

    def build(replicas, prefill_replicas):
        eng = ds.init_inference(TransformerLM(cfg), config={
            "dtype": "float32", "max_out_tokens": 64,
            "temperature": 0.0, "replace_with_kernel_inject": False,
            "observability": _obs_block(art_dir),
            "serving": {"enabled": True, "kv_block_size": 8,
                        "num_kv_blocks": 64, "max_batch_slots": 4,
                        "prefill_chunk_tokens": 8,
                        "max_queue_depth": 32,
                        "fleet": {"enabled": True, "replicas": replicas,
                                  "prefill_replicas": prefill_replicas},
                        "host_cache": {"enabled": True,
                                       "dram_budget_bytes": 1 << 24,
                                       "wire_bits": 0}}})
        fleet = FleetRouter.from_engine(eng, rng=jax.random.PRNGKey(0))
        return eng, fleet

    def run(split: bool):
        eng, fleet = build(chips if not split else 2,
                           0 if not split else 1)
        # warm every replica's compile before the clock; the 12-token
        # prompt crosses a block boundary so the split fleet's warm-up
        # also runs the publish→claim→promote handoff once
        for _ in range(len(fleet.replicas)):
            fleet.submit(list(range(1, 13)), max_new_tokens=4)
        fleet.run()
        auto = None
        spawned = []
        if split:
            mon = SloMonitor(objective=0.9, fast_window_s=2.0,
                             slow_window_s=8.0, burn_threshold=1.0,
                             min_samples=3, time_fn=time.perf_counter)

            def spawn(role):
                srv = ServingEngine(
                    eng, rng=jax.random.PRNGKey(2 + len(spawned)),
                    shared_host_cache=fleet.shared_host_cache,
                    role=role)
                srv.publisher_id = f"as{len(spawned)}-{role}"
                h = ReplicaHandle(f"as{len(spawned)}-{role}", srv,
                                  role=role)
                spawned.append(h)
                return h

            auto = FleetAutoscaler(
                fleet, spawn, slo_monitor=mon, clock=time.perf_counter,
                chip_budget=chips, scale_up_cooldown_s=0.5,
                scale_down_cooldown_s=2.0, queue_high=3.0,
                queue_low=1.0, quiet_s=1.0)
        rs = np.random.RandomState(11)
        ttft = {t: [] for t in tenants}
        breach = {}

        def hook(freq, tenant):
            def _cb(ev):
                if ev.token is None or ev.index != 0:
                    return
                lat = ev.time_s - freq.submit_time
                ttft[tenant].append(lat)
                if split:
                    mon.observe(tenant, KIND_TTFT, lat, targets[tenant])
                elif (tenant not in breach and len(ttft[tenant]) >= 3
                      and float(np.percentile(ttft[tenant], 99))
                      > targets[tenant]):
                    # the uniform run's histogram view of the breach:
                    # the "would-be" timestamp the split fleet's
                    # scale-up must beat
                    breach[tenant] = time.perf_counter()
            return _cb

        reqs = []
        t0 = time.perf_counter()
        for i in range(rounds):
            p = rs.randint(0, cfg.vocab_size,
                           (int(rs.randint(4, 9)),)).tolist()
            freq = fleet.submit(p, max_new_tokens=new,
                                tenant="interactive")
            freq.on_token = hook(freq, "interactive")
            reqs.append(freq)
            if i in (2, rounds // 2):       # the prefill bursts
                for _ in range(burst):
                    p = rs.randint(0, cfg.vocab_size,
                                   (int(rs.randint(36, 45)),)).tolist()
                    freq = fleet.submit(p, max_new_tokens=6,
                                        tenant="batch")
                    freq.on_token = hook(freq, "batch")
                    reqs.append(freq)
            fleet.pump()
            if auto is not None:
                auto.tick()
        fleet.run()
        dt = time.perf_counter() - t0
        assert all(r.status is not None and r.status.value == "ok"
                   for r in reqs), "a request did not survive the trace"
        builds = [r.srv.decode_builds for r in fleet.replicas
                  if r.srv.decode_builds]
        assert all(b == 1 for b in builds), \
            "the disaggregated handoff retraced a replica"
        decode_chips = max(
            1, sum(r.role != "prefill" for r in fleet.replicas))
        tok_s = sum(len(r.output) for r in reqs) / dt
        # one merged fleet trace per run: every leg's waterfall under
        # its fleet trace id, flow arrows chaining the handoffs
        shape = "split" if split else "uniform"
        trace_path = fleet.export_fleet_trace(os.path.join(
            art_dir, f"disagg_fleet_trace_{shape}.json"))
        fleet.export_fleet_metrics(prometheus_path=os.path.join(
            art_dir, f"disagg_fleet_{shape}.prom"))
        out = {
            "replicas": [(r.replica_id, r.role) for r in fleet.replicas],
            "decode_tokens_per_sec": round(tok_s, 1),
            "decode_tokens_per_sec_per_decode_chip": round(
                tok_s / decode_chips, 1),
            "ttft_p99_ms": {
                t: round(float(np.percentile(ttft[t], 99)) * 1e3, 2)
                for t in tenants if ttft[t]},
            "overlap": _overlap_columns("serving"),
            "fleet_trace": trace_path,
            "decode_builds": builds}
        if split:
            out["handoffs"] = fleet.fleet_counts["handoffs"]
            out["fabric"] = {
                "published": fleet.shared_host_cache.published_total,
                "claim_hits": sum(
                    fleet.shared_host_cache.hits_total.values())}
            out["scale_events"] = [
                {"at_s": round(e["t"] - t0, 3), "action": e["action"],
                 "role": e["role"], "reason": e["reason"]}
                for e in (auto.events if auto else [])]
            # close the loop: quiet tail scale-down + orphan hygiene
            deadline = time.perf_counter() + 3.0
            while (auto and auto.counts["scale_ups"]
                   and not auto.counts["scale_downs"]
                   and time.perf_counter() < deadline):
                time.sleep(0.2)
                fleet.pump()
                auto.tick()
            fleet.reap_orphans()
            assert fleet.shared_host_cache.published_entries() == 0, \
                "orphaned fabric entries survived the drain"
            out["scale_downs"] = auto.counts["scale_downs"] if auto else 0
        else:
            out["p99_breach_at_s"] = {
                t: round(breach[t] - t0, 3) for t in breach}
        return out

    uniform = run(split=False)
    disagg = run(split=True)
    ups = [e for e in disagg["scale_events"] if e["action"] == "up"]
    first_up_s = ups[0]["at_s"] if ups else None
    breach_s = min(uniform["p99_breach_at_s"].values(), default=None) \
        if uniform["p99_breach_at_s"] else None
    print(json.dumps({
        "metric": "disaggregated_fleet",
        "value": disagg["ttft_p99_ms"].get("interactive"),
        "unit": "ms", "chips": chips, "rounds": rounds,
        "uniform": uniform, "disagg": disagg,
        "scale_up_before_breach": (
            first_up_s is not None
            and (breach_s is None or first_up_s <= breach_s)),
        "first_scale_up_s": first_up_s,
        "uniform_breach_s": breach_s,
        "disagg_wins_ttft": (
            uniform["ttft_p99_ms"].get("interactive", 0)
            > disagg["ttft_p99_ms"].get("interactive", float("inf"))),
        "disagg_wins_decode_throughput": (
            disagg["decode_tokens_per_sec_per_decode_chip"]
            > uniform["decode_tokens_per_sec_per_decode_chip"])}),
        flush=True)


def main():
    require_tpu("bench_all.py")
    train_bench("125m", 64, 1024, 0)
    train_bench("350m", 16, 1024, 2, iters=6)
    train_bench("350m", 16, 1024, 3, iters=6)
    train_3d_bench("350m", seq=1024, micro=8, iters=4)
    decode_bench()
    hbm = hbm_ceiling_probe()
    serving_decode_bench()
    multi_tenant_replay_bench(spec_k=3)
    fleet_failover_bench()
    disaggregated_fleet_bench()
    prefix_cache_bench()
    tiered_prefix_cache_bench()
    paged_decode_attention_bench()
    paged_decode_roofline_sweep(hbm)
    blocksparse_bench()
    diffusion_bench()
    host_offload_bench()
    h2d, d2h = wire_bench()
    offload_bench()
    infinity_bench(h2d, d2h)


if __name__ == "__main__":
    main()
