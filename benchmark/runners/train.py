"""A training cell: ``ds.initialize`` -> ``engine.train_step`` on one seeded
batch, repeated.  There is no input pipeline in the repo to measure, so the
batch lives on the device and every step sees it again."""
from __future__ import annotations

import math
import time

import numpy as np

from ..lib import costs, device, model as model_lib, reference

#: the engine's loss (bf16 activations, flash, chunked fused head) against
#: the float32 reference on the same two sequences.  At a loss of about
#: ln(50304) = 10.8 bf16 rounding moved the mean over 2 x 1023 targets by
#: 6.5e-5 (my chip run, PR 23); the bound leaves 30x that and is still
#: under what 8-bit matmuls or a dropped term would move it by.
LOSS_ATOL = 2e-3
SPANS = ("train_step",)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM

    mix, chips = ctx.mix, ctx.cell["chips"]
    mc, ref_cfg = model_lib.build(ctx.config, ctx.tiny)
    micro = int(mix["micro_batch_per_chip"])
    seq = int(ctx.tiny["model"]["max_seq_len"]) if ctx.tiny else int(mix["seq_len"])
    engine, *_ = ds.initialize(
        model=TransformerLM(mc), rng=model_lib.seed_key(ctx.seed),
        config={"train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1, "steps_per_print": 0,
                "bf16": {"enabled": mix["bf16"]},
                "optimizer": mix["optimizer"],
                "gradient_clipping": mix["gradient_clipping"],
                "zero_optimization": {"stage": mix["zero_stage"]},
                "mesh": {"data": chips}})
    rng = np.random.default_rng([int(ctx.seed), 0xBA7C])
    rows = micro * chips
    batch = engine.shard_batch({"input_ids": rng.integers(
        0, mc.vocab_size, (rows, seq), dtype=np.int32)})

    # correct, part 1: the engine's loss on two seeded sequences (tiled to
    # fill the batch the engine is built for) against the reference's
    two = rng.integers(0, mc.vocab_size, (2, seq), dtype=np.int32)
    got = float(engine.eval_loss(
        {"input_ids": np.tile(two, (rows // 2, 1))}))
    replicated = jax.sharding.NamedSharding(
        engine.mesh, jax.sharding.PartitionSpec())
    want = float(jax.jit(lambda p, ids: reference.loss(
        p, ids, ref_cfg, lambda layer: jax.lax.with_sharding_constraint(
            layer, replicated)))(engine.state["params"], jnp.asarray(two)))
    loss_err = abs(got - want)

    def step():
        with jax.profiler.TraceAnnotation("train_step"):
            return float(jax.block_until_ready(
                engine.train_step(batch)["loss"]))

    loss_first = step()                       # compiles
    step()
    compiles_before = ctx.compile_log.compiles
    ends = np.zeros(1 << 16)
    losses = []
    tracing = False
    trace_at = ctx.seconds - float(mix["trace_seconds"])
    w0 = time.perf_counter()
    setup_s = device.process_age_s()
    ends[0] = w0
    n = 1
    while True:
        if ctx.trace and not tracing and ends[n - 1] - w0 >= trace_at:
            ctx.start_trace()
            tracing = True
        losses.append(step())
        ends[n] = time.perf_counter()
        n += 1
        if ends[n - 1] - w0 >= ctx.seconds:
            break
    w1 = w0 + ctx.seconds
    red = ctx.stop_trace(SPANS) if tracing else {}
    ends = ends[:n]

    tokens_per_step = rows * seq
    step_ms = np.diff(ends[ends <= w1]) * 1e3
    work = {}
    if red:
        # every layer's flash calls in the traced window: with remat="full"
        # the forward runs twice (once recomputed) and the backward once
        steps_traced = red["window_s"] / (np.median(step_ms) * 1e-3)
        per_chip = (micro, seq, mc.num_heads, mc.kv_heads, mc.hdim)
        fwd = costs.flash_attention_cost(*per_chip, backward=False)
        bwd = costs.flash_attention_cost(*per_chip, backward=True)
        n_fwd = 2 if mc.remat == "full" else 1
        flops = (n_fwd * fwd[0] + bwd[0]) * mc.num_layers * steps_traced
        nbytes = (n_fwd * fwd[1] + bwd[1]) * mc.num_layers * steps_traced
        least, bound = costs.roofline_seconds(flops, nbytes, ctx.peaks)
        work["flash"] = {"least_s": least, "bound": bound}
    ok = (loss_err <= LOSS_ATOL and all(map(math.isfinite, losses))
          and losses[-1] < loss_first
          and ctx.compile_log.compiles == compiles_before)
    return {
        "correct": bool(ok), "attempted": len(losses), "failed": 0,
        "window": (w0, w1), "memory": device.memory_peak(),
        "values": {
            "setup_s": setup_s,
            "flops_per_token": costs.transformer_flops_per_token(
                mc.num_params(), mc.num_layers, mc.d_model, seq),
        },
        "series": {"step_ms": step_ms},
        "steps": {"ends": ends, "work": tokens_per_step / chips},
        "trace": red, "work": work,
        "diag": {"loss_engine": got, "loss_reference": want,
                 "loss_abs_err": loss_err, "loss_first": loss_first,
                 "loss_last": losses[-1], "steps": len(losses),
                 "compiles_in_window":
                     ctx.compile_log.compiles - compiles_before,
                 "flash_bound": work.get("flash", {}).get("bound")},
    }
