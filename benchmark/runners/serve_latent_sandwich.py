"""A serving cell of the sandwich-norm latent block with leading dense
layers and a shared expert (``openpangu-ultra-moe``): the client, the two
loops, the weights and the routing values are ``runners/serve.py``'s and
``runners/serve_latent.py``'s; this file repeats only what must differ —
the build (another builder, another set of published keys), the two
checks against another reference
(``lib/reference_openpangu_ultra_moe.py``), the counts of a stack that is
one attention sublayer a layer and whose expert layers are fewer than its
layers, and ``run()``."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, device, model as model_lib,
                   reference_openpangu_ultra_moe as reference, stats,
                   traffic)
from .serve import (CHECK_REQUESTS, _closed_loop, _open_loop, _profile,
                    clock, stop_trace)
from .serve_latent import LatentClient, _routing_values, serving_weights

#: serving check: each token the engine chose greedily, through chunked
#: prefill and paged decode in bf16, must be within this of the float32
#: reference's best logit at that position.  Sound runs read up to 0.50
#: over 25 seeds (a bf16 activation flips an 8th-against-9th pick now and
#: then, so the tail is heavy); what only the logits can see reads 5.07
#: or more: the reference without the dense layer's FFN 5.07 / 6.10,
#: without the sandwich's output norms 8.41 / 9.52 (``PERF.md`` section
#: 4).  Float8 weights, the shared expert, the renormalisation and the
#: held experts are the second number's to refuse.
LOGIT_GAP_ATOL = 1.5
#: the expert layer's check (two prompts' logits cannot see 6 % of the
#: picks): the error of the first EXPERT layer's ``F_l`` (router, shared
#: expert, held experts through the grouped product) at the timed row
#: count, over the norm of the held experts' own part.  Sound runs read
#: 0.011-0.012, the reference in float8 0.51-0.54, the held experts left
#: out 1.000 (``PERF.md`` section 4).
EXPERT_REL_ERR_MAX = 0.05

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers",
             "first_k_dense_replace": "first_k_dense",
             "hidden_size": "d_model", "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_heads",
             "intermediate_size": "ff_dim",
             "moe_intermediate_size": "expert_d_ff",
             "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "v_head_dim": "v_head_dim",
             "num_experts_per_tok": "moe_topk",
             "n_shared_experts": "n_shared_experts",
             "norm_topk_prob": "norm_topk_prob",
             "routed_scaling_factor": "routed_scaling_factor",
             "rope_theta": "rope_theta", "rms_norm_eps": "layernorm_eps",
             "hidden_act": "activation", "attention_bias": "use_bias",
             "tie_word_embeddings": "tie_embeddings"}


def build(config: dict, tiny: dict | None = None):
    """``(model config, reference settings, experts held)``; the published
    sizes are checked against what the program built."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as T
    prog = config["program"]
    kwargs = dict(prog["kwargs"])
    if tiny:
        kwargs.update(tiny["model"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    kwargs["experts_held"] = tuple(kwargs["experts_held"])
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    ref = reference.settings(config)
    if tiny:
        ref.update(heads=mc.num_heads, kv_lora_rank=mc.kv_lora_rank,
                   qk_nope_head_dim=mc.qk_nope_head_dim,
                   qk_rope_head_dim=mc.qk_rope_head_dim,
                   v_head_dim=mc.v_head_dim, moe_topk=mc.moe_topk,
                   n_routed_experts=mc.n_routed_experts)
        return mc, ref, mc.held
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(n_routed_experts=mc.held[1] - mc.held[0])
    want = {k: config[k] for k in built}
    gate = (mc.router_scoring, mc.router_bias, mc.zero_expert_num)
    if built != want or gate != ("sigmoid", False, 0) \
            or not config["sandwich_norm"] or mc.n_routed_experts != \
            config["published"]["n_routed_experts"]:
        raise ValueError(f"the program built {built} with the gate {gate}, "
                         f"the configuration file says {want}")
    return mc, ref, mc.held


def _check_against_reference(srv, params, ref_cfg, held, vocab, seed, shrink,
                             leave_out=()):
    """Two seeded prompts through chunked prefill and paged decode; the
    reference's full forward over prompt + output, with the same share of
    the experts, judges every token: ``(worst gap to the reference's best
    logit, share of positions where the token is its argmax)``."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 0xC4EC])
    reqs = [srv.submit(rng.integers(0, vocab, max(2, p // shrink)),
                       max_new_tokens=n) for p, n in CHECK_REQUESTS]
    while srv.step():
        pass
    full = [list(r.prompt) + list(r.output) for r in reqs]
    ids = np.zeros((len(full), max(map(len, full))), np.int32)
    for row, seq in zip(ids, full):          # causal: padding is inert
        row[:len(seq)] = seq
    lg = np.asarray(jax.jit(lambda p, i: reference.logits(
        p, i, ref_cfg, held, leave_out))(params, jnp.asarray(ids)))
    worst, exact, n = 0.0, 0, 0
    for r, row in zip(reqs, lg):
        if len(r.output) != r.max_new_tokens:
            return math.inf, 0.0
        for j, tok in enumerate(r.output):
            at = row[len(r.prompt) + j - 1]
            worst = max(worst, float(at.max() - at[tok]))
            exact += int(at.argmax() == tok)
            n += 1
    return worst, exact / n


def _check_experts(model, params, ref_cfg, held, seed, rows, leave_out=()):
    """What ``_check_against_reference`` cannot see: the held experts and
    their grouped product.  The first EXPERT layer's ``F_l`` over ``rows``
    seeded rows (the mixed program's row count) on the timed weights — the
    program's ``expert_layer`` (its router, its shared expert, and
    ``expert_share`` whose kernel reads that layer's experts where they
    lie in the stack, as the mixed program does) — against the
    reference's shared expert and loop over experts: the norm of the
    difference over the norm of the held experts' own part of the
    reference."""
    import jax
    import jax.numpy as jnp
    blocks = params["blocks"]
    rest = {"moe": {"router": blocks["moe"]["router"]},
            "shared": blocks["shared"]}
    experts = blocks["moe"]["experts"]
    u = jax.random.normal(
        model_lib.seed_key(int(seed) + 0xE4),
        (rows, model.config.d_model)).astype(experts["w_up"].dtype)

    def layer0(rest):
        return jax.tree_util.tree_map(lambda a: a[0], rest)

    def program(rest, experts, u):
        return model.expert_layer(
            layer0(rest), u[None], stack=(experts, jnp.int32(0))
        )[0][0].astype(jnp.float32)

    def plain(rest, experts, u):
        def at(i):
            return {n: w[0, i] for n, w in experts.items()}
        layer = layer0(rest)
        with jax.default_matmul_precision("highest"):
            u32 = u.astype(jnp.float32)[None]
            want = reference.moe(
                layer, u32, dict(ref_cfg, float8="float8" in leave_out),
                held, leave_out, expert_at=at)
            own = reference.routed(layer["moe"], u32, ref_cfg, held, at)
        return want[0], own[0]
    got = jax.jit(program)(rest, experts, u)
    want, own = jax.jit(plain)(rest, experts, u)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(own))


def _expert_layer_values(overlap, mc, window) -> dict:
    """What the program counted of its expert layers beyond
    ``_routing_values``: rows a held expert, the share of held experts
    that received a row, the shared expert's share of the expert layers'
    operations, and the share of iterations that carried a chunk.  A
    program that keeps no such counters gives nothing."""
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "moe_rows_shared" not in names:
        return {}
    lo, hi = mc.held
    held = float(recs["moe_picks_held"].sum())
    shared = float(recs["moe_rows_shared"].sum()) * mc.n_shared_experts
    slots = float(recs["dispatches"].sum()) * mc.scan_length * (hi - lo)
    if slots <= 0 or shared + held <= 0:
        return {}
    return {
        "moe_rows_per_expert": held / slots,
        "moe_touched_share": 100.0 * float(
            recs["moe_experts_touched"].sum()) / slots,
        # every expert here, shared or routed, is the same three products
        "moe_shared_share": 100.0 * shared / (shared + held),
        "chunk_dispatch_share": 100.0 * float(
            (recs["chunk_rows"] > 0).sum()) / len(recs)}


def run(ctx) -> dict:
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.observability.overlap import get_overlap_profiler

    mix = ctx.mix
    mc, ref_cfg, held = build(ctx.config, ctx.tiny)
    model = build_model(mc)
    shrink = int(ctx.tiny["shrink"]) if ctx.tiny else 1
    eng_cfg = dict(mix["engine"])
    serving = dict(eng_cfg.pop("serving"), enabled=True,
                   mesh={"data": 1, "model": 1})
    if ctx.tiny:
        serving["num_kv_blocks"] = int(ctx.tiny["num_kv_blocks"])
        eng_cfg["max_out_tokens"] = int(ctx.tiny["model"]["max_seq_len"])
        eng_cfg["dtype"] = "float32"
    params = serving_weights(model, ctx.seed, jnp.dtype(eng_cfg["dtype"]))
    srv = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params).serving_engine()
    overlap = get_overlap_profiler()
    if ctx.trace:
        overlap.configure(enabled=True)

    # correct, part 1 (and the warm-up of the one mixed program)
    leave_out = tuple(mix.get("reference_leaves_out", ()))
    worst_gap, exact_share = _check_against_reference(
        srv, params, ref_cfg, held, mc.vocab_size, ctx.seed, shrink,
        leave_out)
    # correct, part 2: the expert layer, at the mixed program's row count
    expert_err = _check_experts(
        model, params, ref_cfg, held, ctx.seed,
        int(serving["max_batch_slots"]) + int(serving["prefill_chunk_tokens"]),
        leave_out)
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    work = traffic.requests(mix, ctx.seed, mc.vocab_size)
    if shrink > 1:
        work["max_new"] = np.maximum(2, work["max_new"] // shrink)
        work["prompts"] = [p[:max(2, len(p) // shrink)]
                           for p in work["prompts"]]
    open_loop = mix["loop"] == "open"
    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = LatentClient(srv, work, mc, ctx.trace, overlap)
    # one attention sublayer a layer here, over every layer of the stack
    client.sublayers = model.ATTN_SUBLAYERS * mc.num_layers
    gc_events = []

    def on_gc(phase, info):
        gc_events.append((clock(), phase, info["generation"]))
    gc.collect()
    gc.callbacks.append(on_gc)
    if open_loop:
        w0, w1, setup_s, tracing = _open_loop(ctx, client, shrink)
    else:
        w0, w1, setup_s, tracing = _closed_loop(ctx, client, slots)
    red = stop_trace(ctx) if tracing else {}
    compiles_in_window = ctx.compile_log.compiles - compiles_before

    c = client
    n_sub = c.n_sub
    judged = np.arange(n_sub)
    if open_loop:
        judged = judged[(c.due_t[:n_sub] >= w0) & (c.due_t[:n_sub] < w1)]
        limit = clock() + float(mix["drain_limit_s"])
        while c.live and clock() < limit and \
                any(c.first_t[i] == 0.0 for i in judged):
            c.iterate()
    else:
        judged = judged[(c.done_t[:n_sub] > w0) & (c.done_t[:n_sub] <= w1)]
    for i in list(c.live):           # in flight at the close: cancelled
        srv.cancel(c.reqs[i])
    while srv.step():
        pass
    gc.callbacks.remove(on_gc)

    its = slice(0, c.n_it)
    it_start, it_end = c.it_start[its], c.it_end[its]
    in_w = (it_end > w0) & (it_end <= w1)
    ttft = np.where(c.first_t[judged] > 0,
                    (c.first_t[judged] - c.due_t[judged]) * 1e3, math.inf)
    failed = int(np.sum(~c.ok_full[judged])) if not open_loop else int(
        np.sum(~np.isfinite(ttft))
        + np.sum((c.done_t[judged] > 0) & ~c.ok_full[judged]))
    gaps_in = c.gap_ms[:c.n_gap][(c.gap_end[:c.n_gap] > w0)
                                 & (c.gap_end[:c.n_gap] <= w1)]
    fifth = (w1 - w0) / 5
    queue = c.it_queue[its]
    first5 = queue[(it_end > w0) & (it_end <= w0 + fifth)]
    last5 = queue[(it_end > w1 - fifth) & (it_end <= w1)]
    ended = int(np.sum((c.done_t[:n_sub] > w0) & (c.done_t[:n_sub] <= w1)))
    routing, moe_work = ({}, None) if not ctx.trace else _routing_values(
        overlap, mc, (w0, w1), ctx.trace_started_at)
    if ctx.trace:
        routing.update(_expert_layer_values(overlap, mc, (w0, w1)))
    work_done = {}
    if red:
        traced = it_start >= ctx.trace_started_at
        flops, nbytes = c.it_flops[its][traced], c.it_bytes[its][traced]
        work_done["mla_paged_attention"] = {
            "least_s": sum(costs.roofline_seconds(f, b, ctx.peaks)[0]
                           for f, b in zip(flops, nbytes)),
            "bound": costs.roofline_seconds(flops.sum(), nbytes.sum(),
                                            ctx.peaks)[1]}
        if moe_work is not None:
            least, bound = costs.roofline_seconds(*moe_work, ctx.peaks)
            work_done["moe_grouped_matmul"] = {"least_s": least,
                                               "bound": bound}
    ok = (worst_gap <= LOGIT_GAP_ATOL and expert_err <= EXPERT_REL_ERR_MAX
          and failed == 0 and len(judged) > 0
          and srv.allocator.num_used == 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)
    values = {
        "setup_s": setup_s,
        "batch_occupancy": 100.0 * c.it_running[its][in_w].mean() / slots,
        "kv_pool_occupancy": 100.0 * c.it_blocks[its][in_w].mean() / blocks,
        "preemptions": float(srv.scheduler.preemption_count),
        "decode_builds": float(srv.decode_builds),
        "queue_depth_first_fifth": float(first5.mean()) if first5.size
        else 0.0,
        "queue_depth_last_fifth": float(last5.mean()) if last5.size else 0.0,
        "requests_per_s_completed": ended / (w1 - w0),
        "ttft_mean_ms": stats.finite_ms(float(ttft.mean())) if ttft.size
        else math.nan,
        **routing,
    }
    stamps = {"it_start": it_start - w0, "it_end": it_end - w0,
              "it_tokens": c.it_tokens[its], "it_running": c.it_running[its],
              "it_queue": queue, "it_blocks": c.it_blocks[its],
              "window_s": w1 - w0,
              "gc": [(t - w0, p, g) for t, p, g in gc_events],
              "submit_t": c.submit_t[:n_sub] - w0,
              "done_t": c.done_t[:n_sub] - w0,
              "first_t": c.first_t[:n_sub] - w0,
              "due_t": c.due_t[:n_sub] - w0}
    return {
        "correct": bool(ok), "attempted": int(len(judged)), "failed": failed,
        "window": (w0, w1), "memory": device.memory_peak(),
        "values": values,
        "series": {
            "step_ms": ((it_end - it_start) * 1e3)[in_w],
            "ttft_ms": np.array([stats.finite_ms(x) for x in ttft]),
            "itl_ms": gaps_in,
            "queue_wait_ms": np.array([
                (c.admit_t[i] - c.due_t[i]) * 1e3 if c.admit_t[i] > 0
                else stats.INF_MS for i in judged]),
            "gen_late_ms": (c.submit_t[judged] - c.due_t[judged]) * 1e3,
        },
        "steps": {"starts": it_start, "ends": it_end,
                  "work": c.it_tokens[its]},
        "trace": red, "work": work_done, "stamps": stamps,
        # the pool is [layers, blocks, kv_block_size, lanes of a row]:
        # what a pool-shaped copy would be shaped like
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {"logit_gap_worst": worst_gap, "argmax_share": exact_share,
                 "expert_rel_err": expert_err,
                 "blocks_used_max": float(c.it_blocks[its][in_w].max()),
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(srv.allocator.num_used),
                 "mla_bound": work_done.get("mla_paged_attention",
                                            {}).get("bound"),
                 "moe_bound": work_done.get("moe_grouped_matmul",
                                            {}).get("bound"),
                 "itl_ms": _profile(gaps_in), "ttft_ms": _profile(ttft),
                 **{k: values[k] for k in (
                     "queue_depth_first_fifth", "queue_depth_last_fifth",
                     "requests_per_s_completed", "ttft_mean_ms",
                     "batch_occupancy", "kv_pool_occupancy")},
                 **{k: v for k, v in routing.items()}},
    }
