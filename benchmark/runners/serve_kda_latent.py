"""A serving cell of the linear / latent hybrid over routed experts
(``kimi-linear-48b-a3b``): the client (a latent pool's work), the closed
loop with its lead-in and every stamp are ``runners/serve.py``'s,
``serve_latent.py``'s and ``serve_sparse_latent.py``'s, the weights' filler
(from the seed, in the served type, an element of a stack at a time)
``serve_hybrid.py``'s, the routing values ``serve_latent.py``'s and
``serve_latent_sandwich.py``'s, and the order of the run
``serve_ssd_hybrid.py``'s; this file repeats only what must differ — the
build (another builder, the configuration's own keys), the numbers of the
reference check (``lib/reference_kimi_linear.py``), the recurrence's work
(``lib/costs_kda.py``) and the values taken from the program's counters
and scopes."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, costs_kda, device, model as model_lib,
                   reference_kimi_linear as reference, stats, traffic)
from .serve import _profile, clock, stop_trace
from .serve_hybrid import serving_weights
from .serve_latent import LatentClient, _routing_values
from .serve_latent_sandwich import _expert_layer_values
from .serve_sparse_latent import _closed_loop

#: (a) every token the engine chose greedily, through chunked prefill and
#: paged decode in bfloat16, within this of the float32 reference's best
#: logit at its position (sound runs read 0.06-0.29: a bf16 activation
#: flips an 8th-against-9th pick of 256 now and then, in 26 layers, so the
#: tail is heavy, as openPangu's; every control but three reads 2.3 or
#: more, and those three — the 2.446, a rotation, a bfloat16 state — are
#: (d)'s, (c)'s and (b)'s to refuse); (b) the slot's KDA states read back
#: from the engine after the first check request, against the
#: reference's, norm of the difference over the norm: the FIRST layer's,
#: twice — against the float32 reference's (sound 0.0031-0.0032: the
#: rounding of what the layer was fed, bfloat16 activations; a bfloat16
#: state in the PROGRAM 0.0073, in the reference 0.0106-0.0110)
#: and against the state the reference makes from inputs rounded where
#: the served model keeps a tensor in bfloat16 (``reference.first_state``;
#: sound 0.0015-0.0018 and not the state path's own 6e-5, because XLA's
#: allowance for excess precision lets the program keep MORE than the
#: modelled roundings: with the allowance off the same run reads 6.2e-5; a
#: bfloat16 state in the PROGRAM 0.0067-0.0068 (``program_fault``), in the
#: reference 0.0103-0.0107, a reset at a chunk boundary 0.058-0.078) — and
#: all 20
#: together (sound 0.071-0.083; the 2.446 left out 0.21, a rotation 0.36);
#: (c) what the seven latent layers WROTE: their rows ``[c | k_r]`` of the
#: first check request, read back from the pool through the table it had
#: (sound 0.056-0.067; the 2.446 left out 0.17, a rotation 0.33); (d) the
#: first EXPERT layer's ``F_l`` at the timed row count over the norm of
#: the held experts' own part (two prompts' logits cannot see 6 % of the
#: picks; sound 0.0118-0.0126, the 2.446 left out 0.59).  Each limit lies
#: between the sound readings and the nearest control's, near their
#: geometric middle (my chip runs, PR 56; ``PERF.md`` section 4 has every
#: reading and its seeds).
LIMITS = {"logit_gap_worst": 1.5,
          "kda_state_rel_err": 0.0048,
          "kda_state_path_rel_err": 0.0035,
          "kda_states_rel_err": 0.13,
          "latent_rel_err": 0.11,
          "expert_rel_err": 0.05}
#: prompts across chunk boundaries and the blocked form's block
#: boundaries, not multiples of 16
CHECK_REQUESTS = ((1333, 24), (700, 24))
#: what the reference can be made to lack (``reference_leaves_out`` /
#: ``controls``, never a cell's; ``lib/reference_kimi_linear.py`` says what
#: each changes): every one reads ``correct`` false
CONTROLS = ("delta", "scalar_decay", "conv", "l2norm", "out_gate", "rotary",
            "renorm", "scale", "shared", "bf16_state", "state_carry",
            "float8")
#: faults put INTO the program (``program_fault``, never a cell's): the
#: recurrent state kept in bfloat16 between steps; every chunk started
#: from zero state
PROGRAM_FAULTS = ("bf16_state", "state_reset_at_chunk")

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers",
             "first_k_dense_replace": "first_k_dense",
             "hidden_size": "d_model", "num_attention_heads": "num_heads",
             "num_key_value_heads": "num_heads",
             "intermediate_size": "ff_dim",
             "moe_intermediate_size": "expert_d_ff",
             "vocab_size": "vocab_size", "model_max_length": "max_seq_len",
             "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "v_head_dim": "v_head_dim",
             "num_experts_per_token": "moe_topk",
             "num_shared_experts": "n_shared_experts",
             "moe_renormalize": "norm_topk_prob",
             "moe_router_activation_func": "router_scoring",
             "routed_scaling_factor": "routed_scaling_factor",
             "rms_norm_eps": "layernorm_eps", "hidden_act": "activation",
             "tie_word_embeddings": "tie_embeddings"}
#: the parameters at the published widths and 16 of 256 experts a layer
NUM_PARAMS = 4_956_660_608


def build(config: dict, tiny: dict | None = None):
    """``(model config, reference settings, experts held)``; the
    configuration file's sizes are checked against what the program
    built."""
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
        ref.update(layer_types=mc.layer_types, first_k_dense=mc.first_k_dense,
                   heads=mc.num_heads, kv_lora_rank=mc.kv_lora_rank,
                   qk_nope_head_dim=mc.qk_nope_head_dim,
                   qk_rope_head_dim=mc.qk_rope_head_dim,
                   v_head_dim=mc.v_head_dim, kda_heads=mc.kda_heads,
                   kda_head_dim=mc.kda_head_dim, moe_topk=mc.moe_topk,
                   n_routed_experts=mc.n_routed_experts)
        return mc, ref, mc.held
    lin = config["linear_attn_config"]
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(
        num_experts=mc.held[1] - mc.held[0],
        linear_attn_config={
            "full_attn_layers": [at + 1 for at, kind
                                 in enumerate(mc.layer_types)
                                 if kind == "mla"],
            "head_dim": mc.kda_head_dim,
            "kda_layers": [at + 1 for at, kind in enumerate(mc.layer_types)
                           if kind == "kda"],
            "num_heads": mc.kda_heads,
            "short_conv_kernel_size": mc.kda_conv})
    want = {k: config[k] for k in built}
    flags = (mc.mla_rotary, mc.router_bias, mc.zero_expert_num,
             mc.n_routed_experts)
    if built != want or mc.num_params() != NUM_PARAMS or flags != (
            not config["mla_use_nope"], True, 0,
            config["published"]["num_experts"]) or lin != \
            built["linear_attn_config"]:
        raise ValueError(f"the program built {built} with {flags} "
                         f"({mc.num_params():,} parameters), the "
                         f"configuration file says {want} ({NUM_PARAMS:,})")
    return mc, ref, mc.held


def _with_fault(model, fault: str) -> None:
    """Put ``fault`` into ``model``'s KDA mixer, for every program built
    from it afterwards: the control on the PROGRAM's side."""
    import jax
    if fault not in PROGRAM_FAULTS:
        raise ValueError(f"program_fault {fault!r} is none of "
                         f"{PROGRAM_FAULTS}")
    sound = model._kda_paged

    def bf16_state(p, h, conv_buf, state_buf, layer, st):
        out, conv_buf, state_buf = sound(p, h, conv_buf, state_buf, layer,
                                         st)
        at = layer * st.slots
        # (an explicit rounding: a pair of casts is dropped on the chip
        # under XLA's allowance for excess precision)
        low = jax.lax.reduce_precision(
            jax.lax.dynamic_slice_in_dim(state_buf, at, st.slots),
            exponent_bits=8, mantissa_bits=7)
        return out, conv_buf, jax.lax.dynamic_update_slice_in_dim(
            state_buf, low, at, 0)

    def state_reset_at_chunk(p, h, conv_buf, state_buf, layer, st):
        # every chunk is told it starts its prompt: zero state, zero tail
        return sound(p, h, conv_buf, state_buf, layer,
                     st._replace(chunk_start=st.chunk_start * 0))
    model._kda_paged = {"bf16_state": bf16_state,
                        "state_reset_at_chunk": state_reset_at_chunk}[fault]


def _serve_check_requests(srv, model, vocab, seed, shrink, stream=0):
    """Two seeded prompts through chunked prefill and paged decode on the
    engine the window uses, beside whatever else it is serving: ``(the
    finished requests, what the first one left in the engine, the fewest
    slots that were live meanwhile, these two among them)`` — its KDA
    states read back from its slot, and the latent layers' rows read back
    from the pool through the table it had, both in the iteration it
    finished in (the one in flight was planned before its pages were
    freed, so nothing has written to them yet).  Returns once both have
    finished; what else runs goes on running."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import RequestState
    mc = model.config
    rng = np.random.default_rng([int(seed), 0xC4EC + stream])
    reqs = [srv.submit(rng.integers(0, vocab, max(6, p // shrink)),
                       max_new_tokens=n) for p, n in CHECK_REQUESTS]
    first = reqs[0]
    width = mc.kv_lora_rank + mc.qk_rope_head_dim
    # (one gather: a slice of the pool alone would copy the whole pool)
    pages = jax.jit(lambda pool, table: pool[:, table])
    slot = table = left = None
    least = srv.num_slots
    while any(r.state is not RequestState.FINISHED for r in reqs):
        if not srv.step():
            break
        least = min(least, srv.scheduler.active_slots)
        for at, r in srv.scheduler.running.items():
            if r is first:
                slot, table = at, srv.allocator.block_table(first.req_id)
        if left is None and first.state is RequestState.FINISHED \
                and slot is not None:
            rows = len(first.prompt) + len(first.output) - 1
            pool = srv._pool_k
            left = {"states": model.slot_state(srv._pool_x, slot,
                                               srv.num_slots),
                    "latent": pages(pool, jnp.asarray(table)).reshape(
                        pool.shape[0], -1, pool.shape[-1]
                    )[:, :rows, :width].astype(jnp.float32)}
    return reqs, left, least


def _check_experts(model, params, ref_cfg, held, seed, rows):
    """What two prompts' logits cannot see: the held experts and their
    grouped product.  The first EXPERT layer's ``F_l`` over ``rows`` seeded
    rows (the mixed program's row count) on the timed weights — the
    program's ``expert_layer`` (its router, its shared expert, and
    ``expert_share`` whose kernel reads that layer's experts where they
    lie in the stack, as the mixed program does) — against the reference's
    shared expert and loop over experts: the norm of the difference over
    the norm of the held experts' own part of the reference."""
    import jax
    import jax.numpy as jnp
    stack = params["moe"]
    rest = {"moe": {k: v for k, v in stack["moe"].items()
                    if k != "experts"}, "shared": stack["shared"]}
    experts = stack["moe"]["experts"]
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
            u32 = u.astype(jnp.float32)
            want = reference.moe(layer, u32, ref_cfg, held, at)
            own = reference.routed(
                layer["moe"], u32, dict(ref_cfg, without=()), held, at)
        return want, own
    got = jax.jit(program)(rest, experts, u)
    want, own = jax.jit(plain)(rest, experts, u)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(own))


def _judge(reqs, left, params, ref_cfg, held, served) -> dict:
    """The reference's full forward over what the engine was fed judges
    every token the engine chose, and what it holds after the same tokens
    judges what the first request left: the worst gap to the reference's
    best logit, the share of positions where the token is its argmax, the
    first KDA layer's state error (and its error against the state the
    reference makes from inputs rounded to ``served``, the activations'
    type), all the layers', and the latent rows', errors as the norm of
    the difference over the reference's norm."""
    import jax
    import jax.numpy as jnp
    first_state = jax.jit(lambda p, ids: reference.first_state(
        p, ids, ref_cfg, served))

    def err(got, want):
        return float(jnp.linalg.norm(got - want)
                     / jnp.maximum(jnp.linalg.norm(want), 1e-30))
    out = {**{k: math.inf for k in LIMITS if k != "expert_rel_err"},
           "argmax_share": 0.0}
    if left is None or any(len(r.output) != r.max_new_tokens for r in reqs):
        return out
    worst, exact, n = 0.0, 0, 0
    for k, r in enumerate(reqs):
        # what the engine was fed: the prompt and all but the last token
        fed = jnp.asarray(list(r.prompt) + list(r.output)[:-1])[None]
        lg, states, latent = reference.logits(
            params, fed, ref_cfg, held, states=True, last=len(r.output))
        lg = np.asarray(lg[0])
        for j, tok in enumerate(r.output):
            worst = max(worst, float(lg[j].max() - lg[j][tok]))
            exact += int(lg[j].argmax() == tok)
            n += 1
        if k == 0:
            out.update(kda_state_rel_err=err(left["states"][0],
                                             states[0, 0]),
                       kda_state_path_rel_err=err(
                           left["states"][0], first_state(params, fed[0])),
                       kda_states_rel_err=err(left["states"], states[0]),
                       latent_rel_err=err(left["latent"], latent[0]))
        del lg, states, latent
    out.update(logit_gap_worst=worst, argmax_share=exact / n)
    return out


def _within_limits(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items()
               if k in numbers)


def _recurrence_values(overlap, red, mc, slots, window, since, peaks) -> dict:
    """What the program counted over the window's iterations, and — from
    the trace, joined to the program's scopes — the two lanes of the
    recurrence against their bounds over the iterations that began at or
    after ``since`` (the traced ones).  The lanes are the program's own
    names, ``kda_scan/decode`` and ``kda_scan/chunk``
    (``overlap.SCOPE_LANES``), whatever implements them.  A program that
    keeps no such counters or scopes gives nothing."""
    from deepspeed_tpu.observability.overlap import scope_key
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "kda_decode_rows" not in names:
        return {}
    span = window[1] - window[0]
    h, kd = mc.kda_heads, mc.kda_head_dim

    def decode_bytes(dispatches):
        # what the lane must move (every slot of the batch a layer, an
        # idle slot's too: the update passes over the layer's stretch)
        return costs_kda.kda_decode_update_cost(
            dispatches * slots * mc.kda_layers, h, kd, kd)[1]
    values = {
        "kda_decode_rows_per_s": float(recs["kda_decode_rows"].sum()) / span,
        "kda_chunk_rows_per_s": float(recs["kda_chunk_rows"].sum()) / span,
        "state_bytes_moved_per_s": decode_bytes(
            float(recs["dispatches"].sum())) / span,
        "state_slots_started": float(recs["state_slots_started"].sum())}
    traced = recs[recs["begin_s"] >= since]
    if not red or not len(traced):
        return values
    table = overlap.program_scopes(lanes=True)
    lane_s = {"kda_scan/decode": 0.0, "kda_scan/chunk": 0.0}
    scan_ops, unnamed_ops = [], []
    for name, seconds in red["op_s"].items():
        key = scope_key(name)
        scope = table.get(key, ("unnamed",))[0]
        if scope == "unnamed":
            unnamed_ops.append([key or name, seconds, red["op_calls"][name]])
        if scope in lane_s:
            lane_s[scope] += seconds
            scan_ops.append([key, seconds, red["op_calls"][name], scope])
    # the lanes' operations that took most of the traced window:
    # [instruction and result types, seconds, calls, lane]
    values["kda_scan_ops"] = sorted(scan_ops, key=lambda o: -o[1])[:10]
    # ... and what no declared scope names
    values["unnamed_ops"] = sorted(unnamed_ops, key=lambda o: -o[1])[:10]
    decode_s, chunk_s = lane_s["kda_scan/decode"], lane_s["kda_scan/chunk"]
    dispatches = float(traced["dispatches"].sum())
    if decode_s > 0:
        # at the chip's memory rate, over what the lane took
        values["kda_decode_bw_share"] = 100.0 * (
            decode_bytes(dispatches) / peaks["hbm_bytes_per_s"]) / decode_s
        values["kda_decode_update_us"] = 1e6 * decode_s / (
            dispatches * mc.kda_layers)
    chunk_rows = float(traced["kda_chunk_rows"].sum())
    if chunk_s > 0 and chunk_rows > 0:
        least, bound = costs.roofline_seconds(
            *costs_kda.kda_chunk_scan_cost(
                chunk_rows, float((traced["kda_chunk_rows"] > 0).sum())
                * mc.kda_layers, h, kd, kd), peaks)
        values["kda_chunk_roofline"] = 100.0 * least / chunk_s
        values["kda_chunk_bound"] = bound
        values["kda_chunk_time_share"] = 100.0 * chunk_s / red["busy_s"]
    return values


def _live_bytes() -> int:
    """Bytes of live buffers on the chip now (0 where the backend does not
    say)."""
    import jax
    return int((jax.devices()[0].memory_stats() or {}).get("bytes_in_use",
                                                           0))


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
    held_bytes = {"weights": _live_bytes()}
    eng = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params)
    model = eng.module               # (the engine may have rebuilt it)
    if mix.get("program_fault"):
        _with_fault(model, mix["program_fault"])
    srv = eng.serving_engine()
    held_bytes["engine"] = _live_bytes()
    overlap = get_overlap_profiler()
    if ctx.trace:
        overlap.configure(enabled=True)

    # correct on the quiet engine (and the warm-up of both step shapes)
    ref_cfg["without"] = tuple(mix.get("reference_leaves_out", ()))
    ref_cfg["chunk"] = int(serving["prefill_chunk_tokens"])
    checked, left, _ = _serve_check_requests(srv, model, mc.vocab_size,
                                             ctx.seed, shrink)
    while srv.step():
        pass
    served = jnp.dtype(eng_cfg["dtype"])
    rows = int(serving["max_batch_slots"]) \
        + int(serving["prefill_chunk_tokens"])
    # (the engine holds the latent layers' up-projections laid out for
    # the step; the reference reads the published ones, kept here)
    ref_params = params
    quiet = _judge(checked, left, ref_params, ref_cfg, held, served)
    quiet["expert_rel_err"] = _check_experts(model, ref_params, ref_cfg,
                                             held, ctx.seed, rows)
    # the limits' seating only: the same served tokens against a
    # reference that lacks one mechanism at a time
    controls = {}
    for name in mix.get("controls", ()):
        lacking = dict(ref_cfg, without=(name,))
        controls[name] = dict(
            _judge(checked, left, ref_params, lacking, held, served),
            expert_rel_err=_check_experts(model, ref_params, lacking, held,
                                          ctx.seed, rows))
        # what ``correct`` would read had the reference lacked it
        controls[name]["correct"] = _within_limits(controls[name])
    del checked, left
    held_bytes["checked"] = _live_bytes()
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    work = traffic.requests(mix, ctx.seed, mc.vocab_size)
    if shrink > 1:
        work["max_new"] = np.maximum(2, work["max_new"] // shrink)
        work["prompts"] = [p[:max(2, len(p) // shrink)]
                           for p in work["prompts"]]
    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = LatentClient(srv, work, mc, ctx.trace, overlap)
    # one attention sublayer a latent layer, over the latent layers alone
    client.sublayers = mc.mla_layers
    gc_events = []

    def on_gc(phase, info):
        gc_events.append((clock(), phase, info["generation"]))
    gc.collect()
    gc.callbacks.append(on_gc)
    w0, w1, setup_s, tracing = _closed_loop(ctx, client, slots)
    held_bytes["window"] = _live_bytes()
    red = stop_trace(ctx) if tracing else {}
    compiles_in_window = ctx.compile_log.compiles - compiles_before

    c = client
    n_sub = c.n_sub
    judged = np.arange(n_sub)
    judged = judged[(c.done_t[:n_sub] > w0) & (c.done_t[:n_sub] <= w1)]
    # correct again with the other slots live: the queue and the two
    # requests nearest their end make room, two more seeded prompts run
    # beside what the window left decoding (slots reused, every slot's
    # state at its stride, contexts as long as the window's)
    in_slots = {id(r) for r in srv.scheduler.running.values()}
    stay = sorted((i for i in c.live if id(c.reqs[i]) in in_slots),
                  key=lambda i: c.reqs[i].max_new_tokens
                  - len(c.reqs[i].output))
    for i in set(c.live) - set(stay[len(CHECK_REQUESTS):]):
        srv.cancel(c.reqs[i])
    checked, left, least = _serve_check_requests(
        srv, model, mc.vocab_size, ctx.seed, shrink, stream=1)
    for i in stay:                   # in flight at the close: cancelled
        srv.cancel(c.reqs[i])
    while srv.step():
        pass
    live = _judge(checked, left, ref_params, ref_cfg, held, served)
    live["slots_live_least"] = least
    del checked, left
    gc.callbacks.remove(on_gc)

    its = slice(0, c.n_it)
    it_start, it_end = c.it_start[its], c.it_end[its]
    in_w = (it_end > w0) & (it_end <= w1)
    ttft = np.where(c.first_t[judged] > 0,
                    (c.first_t[judged] - c.due_t[judged]) * 1e3, math.inf)
    failed = int(np.sum(~c.ok_full[judged]))
    gaps_in = c.gap_ms[:c.n_gap][(c.gap_end[:c.n_gap] > w0)
                                 & (c.gap_end[:c.n_gap] <= w1)]
    fifth = (w1 - w0) / 5
    queue = c.it_queue[its]
    first5 = queue[(it_end > w0) & (it_end <= w0 + fifth)]
    last5 = queue[(it_end > w1 - fifth) & (it_end <= w1)]
    ended = int(np.sum((c.done_t[:n_sub] > w0) & (c.done_t[:n_sub] <= w1)))
    counted, routing, moe_work = {}, {}, None
    if ctx.trace:
        counted = _recurrence_values(overlap, red, mc, slots, (w0, w1),
                                     ctx.trace_started_at, ctx.peaks)
        routing, moe_work = _routing_values(overlap, mc, (w0, w1),
                                            ctx.trace_started_at)
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
            least_s, bound = costs.roofline_seconds(*moe_work, ctx.peaks)
            work_done["moe_grouped_matmul"] = {"least_s": least_s,
                                               "bound": bound}

    # correct, the last part: nothing of any kind held after the drain
    held_after = srv.allocator.num_used_by_kind()
    ok = (_within_limits(quiet) and _within_limits(live)
          and not any(held_after.values())
          and failed == 0 and len(judged) > 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)

    running = c.it_running[its][in_w]
    act_bytes = jnp.dtype(eng_cfg["dtype"]).itemsize
    page_bytes = (int(serving["kv_block_size"]) * srv._pool_k.shape[-1]
                  * act_bytes * mc.mla_layers)
    one_state = costs_kda.state_bytes(
        mc.kda_layers, mc.kda_heads, mc.kda_head_dim, mc.kda_head_dim,
        mc.kda_conv, act_bytes)
    state_held = running.mean() * one_state
    pages_held = c.it_blocks[its][in_w].mean() * page_bytes
    hidden = ("kda_chunk_bound", "kda_scan_ops", "unnamed_ops")
    values = {
        "setup_s": setup_s,
        "batch_occupancy": 100.0 * running.mean() / slots,
        "kv_pool_occupancy": 100.0 * c.it_blocks[its][in_w].mean() / blocks,
        "kv_blocks_held_max": float(c.it_blocks[its].max()),
        "preemptions": float(srv.scheduler.preemption_count),
        "decode_builds": float(srv.decode_builds),
        "queue_depth_first_fifth": float(first5.mean()) if first5.size
        else 0.0,
        "queue_depth_last_fifth": float(last5.mean()) if last5.size else 0.0,
        "requests_per_s_completed": ended / (w1 - w0),
        "ttft_mean_ms": stats.finite_ms(float(ttft.mean())) if ttft.size
        else math.nan,
        "state_bytes_share": float(
            100.0 * state_held / (state_held + pages_held)),
        **routing,
        **{k: v for k, v in counted.items() if k not in hidden},
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
        # the pool is [latent layers, blocks, kv_block_size, lanes of a row]
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {**quiet, "live": live, "controls": controls,
                 "limits": LIMITS, "live_bytes_after": held_bytes,
                 "held_after_drain": held_after,
                 "kv_pool_bytes": int(srv.kv_pool_bytes),
                 "state_bytes_a_slot": one_state,
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
                     "batch_occupancy", "kv_pool_occupancy",
                     "kv_blocks_held_max", "state_bytes_share")},
                 **routing, **counted},
    }
