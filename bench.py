"""Headline benchmark: GPT-2 125M causal-LM training throughput on one chip.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is model FLOPs utilization (MFU) relative to the repo's
north-star target of 45% MFU (BASELINE.md) — >1.0 beats the target. The
reference's own single-device headline (BERT-large 64 TFLOPS on a 125-TFLOP
V100 = 51% MFU, `docs/_tutorials/bert-pretraining.md:392`) is the comparable
bar.

The headline is best-of-N independently timed windows of chained steps,
with every window's wall time emitted in-band (``window_times_s``), so a
host stall shows up as one bad window.  Per-phase ideals come from XLA's
own post-fusion cost analysis of each phase program (flops + bytes
accessed), the optimizer phase is timed directly (a jitted chained
_apply_grads loop) instead of by differencing, and the phase list
telescopes to the step exactly, so pct_of_step sums to 100 by
construction.

Runs on a TPU only: without one it exits non-zero and prints no metric.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def require_tpu(what: str):
    """The first TPU device, or exit non-zero with no metric printed: a
    number from another backend must never carry a chip metric's name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"{what}: needs a TPU; JAX reports {len(jax.devices())} x "
            f"{dev.platform} ({dev.device_kind}). No TPU, no metric.\n")
        raise SystemExit(2)
    return dev


def chip_peak_flops(device) -> float:
    from deepspeed_tpu.profiling.flops_profiler import (
        chip_peak_flops as _peak)
    return _peak(device)


def measure_roofline():
    """What the chip delivers on plain probes, in-band with the headline.

    Two chained probes (each dispatch consumes the previous output):
      - bf16 GEMM chain at the model's own [B*T, d] x [d, 4d] shapes
      - elementwise multiply-add chains (HBM bandwidth), bf16 AND f32;
        the ceiling is the best the memory system demonstrably does, so
        both are probed best-of-8 and the max is used for phase ideals.
    """
    import jax
    import jax.numpy as jnp

    m, d, f = 16384, 768, 3072
    inner = 40
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(m, d), jnp.bfloat16)
    w1 = jnp.asarray(rs.randn(d, f) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rs.randn(f, d) * 0.02, jnp.bfloat16)

    @jax.jit
    def gemm_chain(x):
        return jax.lax.fori_loop(0, inner, lambda i, a: (a @ w1) @ w2, x)

    x1 = gemm_chain(x)
    jax.block_until_ready(x1)
    # a ceiling is the BEST the silicon does: several chained-dispatch
    # batches (amortizing per-dispatch host latency), keep the fastest
    reps, best = 3, float("inf")
    for _ in range(8):
        t0 = time.perf_counter()
        for _ in range(reps):
            x1 = gemm_chain(x1)
        jax.block_until_ready(x1)
        best = min(best, time.perf_counter() - t0)
    gemm_tflops = 2 * 2 * m * d * f * inner * reps / best / 1e12

    def hbm_probe(dtype, n_elem):
        a = jnp.asarray(
            np.random.default_rng(0).standard_normal(n_elem,
                                                     dtype=np.float32),
            dtype)

        @jax.jit
        def ew_chain(a):
            return jax.lax.fori_loop(
                0, 20, lambda i, a: a * 1.0000001 + 0.0000001, a)

        y = ew_chain(a)
        jax.block_until_ready(y)
        best = float("inf")
        for _ in range(8):
            t0 = time.perf_counter()
            y = ew_chain(y)
            jax.block_until_ready(y)
            best = min(best, time.perf_counter() - t0)
        return 2 * a.nbytes * 20 / best / 2**30

    def hbm_probe_adam(n_elem):
        """Multi-stream probe matching the optimizer's access pattern
        (read p,m,v,g + write p,m,v — STREAM-triad-like): single-array
        scale chains understate what the memory system does for the
        phases that stream several arrays at once."""
        rng = np.random.default_rng(0)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.standard_normal(n_elem, dtype=np.float32))
        p, m, v, g = mk(), mk(), mk(), jnp.abs(mk()) + 1e-3

        @jax.jit
        def adam_chain(p, m, v):
            def body(i, c):
                p, m, v = c
                m = 0.9 * m + 0.1 * g
                v = 0.99 * v + 0.01 * (g * g)
                p = p - 1e-9 * m * jax.lax.rsqrt(v + 1e-8)
                return (p, m, v)
            return jax.lax.fori_loop(0, 10, body, (p, m, v))

        out = adam_chain(p, m, v)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(8):
            t0 = time.perf_counter()
            out = adam_chain(*out)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return 7 * p.nbytes * 10 / best / 2**30   # 4 reads + 3 writes

    hbm_f32 = hbm_probe(jnp.float32, 64 << 20)    # 256 MB resident
    hbm_bf16 = hbm_probe(jnp.bfloat16, 128 << 20)  # same footprint
    hbm_adam = hbm_probe_adam(32 << 20)            # 4 x 128 MB streams
    return (round(gemm_tflops, 1),
            round(max(hbm_f32, hbm_bf16, hbm_adam), 1),
            round(hbm_f32, 1), round(hbm_bf16, 1), round(hbm_adam, 1))


def phase_breakdown(engine, model, batch, seq, t_step, gemm_tf, hbm_gbps):
    """Per-phase roofline attribution — the shared engine in
    ``deepspeed_tpu/profiling/phase_bench.py`` (also consumed by the
    autotuner's experiment runner and the observability gauges); the
    bench keeps this thin wrapper so its output schema is pinned in one
    place."""
    from deepspeed_tpu.profiling.phase_bench import (
        phase_breakdown as _pb)
    return _pb(engine, model, batch, seq, t_step, gemm_tf, hbm_gbps)


def main():
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM, gpt2_config

    dev = require_tpu("bench.py")
    seq, micro = 1024, 64
    # remat=full + chunk 256: the setting the pre-round sweep kept
    cfg = gpt2_config("125m", max_seq_len=seq, remat="full",
                      attn_impl="flash", loss_chunk=256)
    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)

    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(
        0, cfg.vocab_size, (engine.train_batch_size, seq), dtype=np.int32)}

    # warmup (compile)
    jax.block_until_ready(engine.train_step(batch)["loss"])

    # N independently timed windows of chained steps, each ended by
    # block_until_ready on a loss that depends on every prior step's
    # params.  The headline is the best window and every window time is
    # emitted, so a stall is visible, not silently averaged.
    n_windows, wsteps = 6, 4
    window_times = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        for _ in range(wsteps):
            m = engine.train_step(batch)
        jax.block_until_ready(m["loss"])
        window_times.append(time.perf_counter() - t0)
    best_window = min(window_times)
    t_step = best_window / wsteps

    tokens = engine.train_batch_size * seq * wsteps
    tok_per_sec = tokens / best_window
    n_params = engine.num_parameters()
    # fwd+bwd FLOPs: 6 * N per token + attention term 12 * L * d * s
    flops_per_tok = 6 * n_params + 12 * cfg.num_layers * cfg.d_model * seq
    nominal_peak = chip_peak_flops(dev)
    mfu = tok_per_sec * flops_per_tok / nominal_peak

    out = {
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        # the contract number: MFU against the NOMINAL chip peak, over the
        # 45% north-star target
        "vs_baseline": round(mfu / 0.45, 4),
        "window_steps": wsteps,
        "window_times_s": [round(t, 3) for t in window_times],
    }
    # measured roofline, in-band: judge the train step against what plain
    # GEMM / elementwise probes reach on the same chip as well as against
    # the published peak
    gemm_tf, hbm_gbps, hbm_f32, hbm_bf16, hbm_adam = measure_roofline()
    achieved_tf = tok_per_sec * flops_per_tok / 1e12
    out.update({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "mfu_nominal": round(mfu, 4),
        "measured_gemm_tflops": gemm_tf,       # chain-GEMM ceiling
        "measured_hbm_gbps": hbm_gbps,
        "measured_hbm_gbps_f32": hbm_f32,
        "measured_hbm_gbps_bf16": hbm_bf16,
        "measured_hbm_gbps_adam": hbm_adam,
        "nominal_tflops": round(nominal_peak / 1e12, 1),
        "achieved_tflops": round(achieved_tf, 1),
        # achieved model FLOPs over the MEASURED GEMM ceiling
        "mfu_vs_measured_peak": round(
            achieved_tf / max(gemm_tf, 1e-9), 4),
        "vs_baseline_measured_peak": round(
            achieved_tf / max(gemm_tf, 1e-9) / 0.45, 4),
        # per-phase attribution of the gap to the measured ceiling
        "phases": phase_breakdown(engine, model, batch, seq, t_step,
                                  gemm_tf, hbm_gbps),
    })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
