"""A serving cell of a Mamba-2 / attention hybrid (``granite-4.0-h-micro``):
the client, the closed loop with its lead-in and every stamp are
``runners/serve.py``'s and ``serve_sparse_latent.py``'s, the weights'
filler (from the seed, in the served type, an element of a stack at a
time) and the order of the run ``serve_hybrid.py``'s; this file repeats
only what must differ — the build (another builder, the configuration's
own keys), the numbers of the reference check
(``lib/reference_granite_hybrid.py``), the recurrence's work
(``lib/costs_ssd.py``) and the values taken from the program's counters
and scopes.  The benchmark's list of per-layer metrics is full
(128 of 128): what has no entry to be read by goes to ``diag``."""
from __future__ import annotations

import gc
import math
import re

import numpy as np

from ..lib import (costs, costs_ssd, device, model as model_lib,
                   reference_granite_hybrid as reference, stats, traffic)
from .serve import Client, _profile, clock, stop_trace
from .serve_hybrid import serving_weights
from .serve_sparse_latent import _closed_loop

#: (a) every token the engine chose greedily, through chunked prefill and
#: paged decode in bfloat16, within this of the float32 reference's best
#: logit at its position; (b) the slot's mamba states read back from the
#: engine after the first check request, against the reference's, norm of
#: the difference over the norm: the FIRST mamba layer's, twice — against
#: the float32 reference's (its input is the embedding, the same in both:
#: what it reads on a sound run is the rounding of what the layer was
#: fed, bfloat16 activations) and against the state the reference makes
#: from inputs in the served type (``reference.first_state``: what is
#: left is the state path's own arithmetic, float32 as the configuration
#: states it, so this is the number that refuses a state kept in
#: bfloat16, and the one that sees a reset at a chunk boundary on every
#: seed) — and all 36 together (the later layers' inputs carry the
#: bfloat16 activations' rounding: a coarse limit, for a state that is
#: wrong and not merely rounded); (c) what the four attention layers
#: WROTE: their keys and values of the first check request, read back from
#: the pool through the table it had, against the reference's (the later
#: layers' keys carry what the earlier layers' attention made: the scale
#: and a position signal show here first).  Each limit lies between the
#: sound readings and the nearest control's (my chip runs, PR 51;
#: ``PERF.md`` section 4 has every reading and its seeds).
LOGIT_GAP_ATOL = 0.1
STATE_REL_ERR_MAX = 0.008
STATE_PATH_REL_ERR_MAX = 3e-4
STATES_REL_ERR_MAX = 0.1
ATTN_KV_REL_ERR_MAX = 0.07
LIMITS = {"logit_gap_worst": LOGIT_GAP_ATOL,
          "ssm_state_rel_err": STATE_REL_ERR_MAX,
          "ssm_state_path_rel_err": STATE_PATH_REL_ERR_MAX,
          "ssm_states_rel_err": STATES_REL_ERR_MAX,
          "attn_kv_rel_err": ATTN_KV_REL_ERR_MAX}
#: prompts across chunk boundaries and the blocked form's block
#: boundaries, not multiples of 16
CHECK_REQUESTS = ((1333, 24), (700, 24))
#: faults put INTO the program (``program_fault``, never a cell's): the
#: recurrent state kept in bfloat16 between steps
PROGRAM_FAULTS = ("bf16_state",)

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "kv_heads",
             "intermediate_size": "ff_dim",
             "shared_intermediate_size": "ff_dim",
             "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "rms_norm_eps": "layernorm_eps",
             "tie_word_embeddings": "tie_embeddings",
             "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
             "mamba_d_state": "ssm_state", "mamba_d_conv": "ssm_conv",
             "attention_multiplier": "attn_softmax_scale",
             "embedding_multiplier": "embedding_multiplier",
             "residual_multiplier": "residual_multiplier",
             "logits_scaling": "logits_scaling"}
#: the published parameter count (the issue's own count, layer by layer)
NUM_PARAMS = 3_191_396_096


def build(config: dict, tiny: dict | None = None):
    """``(model config, reference settings)``; the configuration file's
    sizes are checked against what the program built."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as T
    prog = config["program"]
    kwargs = dict(prog["kwargs"])
    if tiny:
        kwargs.update(tiny["model"])
    kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    ref = reference.settings(config)
    if tiny:
        ref.update(layer_types=mc.layer_types, heads=mc.num_heads,
                   kv_heads=mc.kv_heads, ssm_heads=mc.ssm_heads,
                   ssm_head_dim=mc.ssm_head_dim, state=mc.ssm_state)
        return mc, ref
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(layer_types=list(mc.layer_types),
                 mamba_expand=mc.d_inner // mc.d_model)
    want = {k: config[k] for k in built}
    if built != want or mc.num_params() != NUM_PARAMS:
        raise ValueError(f"the program built {built} ({mc.num_params():,} "
                         f"parameters), the configuration file says {want} "
                         f"({NUM_PARAMS:,})")
    return mc, ref


def _with_fault(model, fault: str) -> None:
    """Put ``fault`` into ``model``'s mamba mixer, for every program built
    from it afterwards: the control on the PROGRAM's side."""
    import jax
    if fault not in PROGRAM_FAULTS:
        raise ValueError(f"program_fault {fault!r} is none of "
                         f"{PROGRAM_FAULTS}")
    sound = model._ssm_paged

    def faulty(p, h, conv_buf, ssm_buf, layer, st):
        out, conv_buf, ssm_buf = sound(p, h, conv_buf, ssm_buf, layer, st)
        at = layer * st.slots
        # (an explicit rounding: a pair of casts is dropped on the chip
        # under XLA's allowance for excess precision)
        low = jax.lax.reduce_precision(
            jax.lax.dynamic_slice_in_dim(ssm_buf, at, st.slots),
            exponent_bits=8, mantissa_bits=7)
        return out, conv_buf, jax.lax.dynamic_update_slice_in_dim(
            ssm_buf, low, at, 0)
    model._ssm_paged = faulty


def _serve_check_requests(srv, model, vocab, seed, shrink, stream=0):
    """Two seeded prompts through chunked prefill and paged decode on the
    engine the window uses, beside whatever else it is serving: ``(the
    finished requests, what the first one left in the engine, the fewest
    slots that were live meanwhile, these two among them)`` — its mamba
    states read back from its slot, and the attention layers' keys and
    values read back from the pool through the table it had, both in the
    iteration it finished in (the one in flight was planned before its
    pages were freed, so nothing has written to them yet).  Returns once
    both have finished; what else runs goes on running."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import RequestState
    rng = np.random.default_rng([int(seed), 0xC4EC + stream])
    reqs = [srv.submit(rng.integers(0, vocab, max(6, p // shrink)),
                       max_new_tokens=n) for p, n in CHECK_REQUESTS]
    first = reqs[0]
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
            at = jnp.asarray(table)
            left = {"states": model.slot_state(srv._pool_x, slot,
                                               srv.num_slots),
                    "kv": jnp.stack([
                        pages(pool, at).reshape(
                            pool.shape[0], -1, pool.shape[-1])[:, :rows]
                        for pool in (srv._pool_k, srv._pool_v)], axis=1
                    ).astype(jnp.float32)}
    return reqs, left, least


def _judge(reqs, left, params, ref_cfg, served) -> dict:
    """The reference's full forward over what the engine was fed judges
    every token the engine chose, and what it holds after the same tokens
    judges what the first request left: the worst gap to the reference's
    best logit, the share of positions where the token is its argmax, the
    first mamba layer's state error (and its error against the state the
    reference makes from inputs rounded to ``served``, the activations'
    type), all the layers', and the attention layers' keys' and values',
    errors as the norm of the difference over the reference's norm."""
    import jax
    import jax.numpy as jnp
    judge = jax.jit(lambda p, ids, n: reference.logits(
        p, ids, ref_cfg, states=True, last=n), static_argnums=2)
    first_state = jax.jit(lambda p, ids: reference.first_state(
        p, ids, ref_cfg, served))

    def err(got, want):
        return float(jnp.linalg.norm(got - want)
                     / jnp.maximum(jnp.linalg.norm(want), 1e-30))
    out = {**{k: math.inf for k in LIMITS}, "argmax_share": 0.0}
    if left is None or any(len(r.output) != r.max_new_tokens for r in reqs):
        return out
    worst, exact, n = 0.0, 0, 0
    for k, r in enumerate(reqs):
        # what the engine was fed: the prompt and all but the last token
        fed = jnp.asarray(list(r.prompt) + list(r.output)[:-1])[None]
        lg, states, kv = judge(params, fed, len(r.output))
        lg = np.asarray(lg[0])
        for j, tok in enumerate(r.output):
            worst = max(worst, float(lg[j].max() - lg[j][tok]))
            exact += int(lg[j].argmax() == tok)
            n += 1
        if k == 0:
            out.update(ssm_state_rel_err=err(left["states"][0], states[0, 0]),
                       ssm_state_path_rel_err=err(
                           left["states"][0], first_state(params, fed[0])),
                       ssm_states_rel_err=err(left["states"], states[0]),
                       attn_kv_rel_err=err(left["kv"], kv[0]))
    out.update(logit_gap_worst=worst, argmax_share=exact / n)
    return out


def _within_limits(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items())


class SSDHybridClient(Client):
    """``Client`` with the paged kernel's work counted over the layers
    that walk pages: the attention layers alone."""

    def __init__(self, srv, work, model_config, trace_on, overlap):
        super().__init__(srv, work, model_config, trace_on, overlap)
        self.layers = model_config.attention_layers_count


def _recurrence_values(overlap, red, mc, slots, window, since, peaks) -> dict:
    """What the program counted over the window's iterations, and — from
    the trace, joined to the program's scopes — the two lanes of the
    recurrence against their bounds over the iterations that began at or
    after ``since`` (the traced ones).  The decode lane is the scope's
    operations that give the whole state buffer (the update, in place,
    and the chunk's slot written back) or just a layer's slots' outputs
    (where XLA splits the update's reduction off, the state is read once
    more); the chunk lane is the rest of the scope.  A program that keeps
    no such counters or scopes gives nothing."""
    from deepspeed_tpu.observability.overlap import scope_key
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "ssm_decode_rows" not in names:
        return {}
    span = window[1] - window[0]
    h, p, n = mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state

    def state_moved(recs):
        # what the step's shape says the recurrence must read plus write
        return costs_ssd.state_bytes_moved(
            float(recs["dispatches"].sum()),
            float((recs["chunk_rows"] > 0).sum()), slots, mc.mamba_layers,
            h, p, n)
    values = {
        "kv_tokens_read_per_s": float(recs["kv_tokens_read_full"].sum())
        / span,
        "ssm_decode_rows_per_s": float(recs["ssm_decode_rows"].sum()) / span,
        "state_bytes_moved_per_s": state_moved(recs) / span,
        "chunk_dispatch_share": 100.0 * float(
            (recs["chunk_rows"] > 0).sum()) / len(recs),
        "state_slots_started": float(recs["state_slots_started"].sum())}
    table_of = getattr(overlap, "program_scopes", None)
    traced = recs[recs["begin_s"] >= since]
    if not red or table_of is None or not len(traced):
        return values
    table = table_of()
    buffer = f"f32[{mc.mamba_layers * slots},{h},{p},{n}]"
    rows_out = re.compile(rf" = f32\[{slots},{h},{p}\]$")
    scan_s = decode_s = io_s = 0.0
    scan_ops = []
    for name, seconds in red["op_s"].items():
        key = scope_key(name)
        scope = table.get(key, (None,))[0]
        if scope == "state_io":
            io_s += seconds
        if scope != "ssm_scan":
            continue
        scan_s += seconds
        scan_ops.append([key, seconds, red["op_calls"][name]])
        if buffer in key or rows_out.search(key):
            decode_s += seconds
    values["state_io_share"] = 100.0 * io_s / red["busy_s"]
    # the scope's operations that took most of the traced window:
    # [instruction and result types, seconds, calls]
    values["ssm_scan_ops"] = sorted(scan_ops, key=lambda o: -o[1])[:10]
    if decode_s > 0:
        # what the lane must move, at the chip's memory rate, over what
        # it took: a copy of a layer's slots out and back would halve it
        values["ssd_decode_bw_share"] = 100.0 * (
            state_moved(traced) / peaks["hbm_bytes_per_s"]) / decode_s
        values["ssd_decode_update_us"] = 1e6 * decode_s / (
            float(traced["dispatches"].sum()) * mc.mamba_layers)
    chunk_rows = float(traced["ssm_chunk_rows"].sum())
    if scan_s > decode_s and chunk_rows > 0:
        from deepspeed_tpu.ops.transformer.ssd_scan import BLOCK_ROWS
        least, bound = costs.roofline_seconds(
            *costs_ssd.ssd_chunk_scan_cost(
                chunk_rows, float((traced["ssm_chunk_rows"] > 0).sum())
                * mc.mamba_layers, h, p, n, BLOCK_ROWS), peaks)
        values["ssd_chunk_roofline"] = 100.0 * least / (scan_s - decode_s)
        values["ssd_chunk_bound"] = bound
        values["ssd_chunk_time_share"] = 100.0 * (scan_s - decode_s) \
            / red["busy_s"]
    return values


def run(ctx) -> dict:
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model
    from deepspeed_tpu.observability.overlap import get_overlap_profiler

    mix = ctx.mix
    mc, ref_cfg = build(ctx.config, ctx.tiny)
    model = build_model(mc)
    shrink = int(ctx.tiny["shrink"]) if ctx.tiny else 1
    eng_cfg = dict(mix["engine"])
    serving = dict(eng_cfg.pop("serving"), enabled=True,
                   mesh={"data": 1, "model": 1})
    if ctx.tiny:
        serving["num_kv_blocks"] = int(ctx.tiny["num_kv_blocks"])
        eng_cfg["max_out_tokens"] = int(ctx.tiny["model"]["max_seq_len"])
        eng_cfg["dtype"] = "float32"
    if mix.get("program_fault"):
        _with_fault(model, mix["program_fault"])
    params = serving_weights(model, ctx.seed, jnp.dtype(eng_cfg["dtype"]))
    srv = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params).serving_engine()
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
    quiet = _judge(checked, left, params, ref_cfg, served)
    # the README's limit seating only: the same served tokens against a
    # reference that lacks one mechanism at a time
    controls = {name: _judge(checked, left, params,
                             dict(ref_cfg, without=(name,)), served)
                for name in mix.get("controls", ())}
    del checked, left
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    work = traffic.requests(mix, ctx.seed, mc.vocab_size)
    if shrink > 1:
        work["max_new"] = np.maximum(2, work["max_new"] // shrink)
        work["prompts"] = [p[:max(2, len(p) // shrink)]
                           for p in work["prompts"]]
    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = SSDHybridClient(srv, work, mc, ctx.trace, overlap)
    gc_events = []

    def on_gc(phase, info):
        gc_events.append((clock(), phase, info["generation"]))
    gc.collect()
    gc.callbacks.append(on_gc)
    w0, w1, setup_s, tracing = _closed_loop(ctx, client, slots)
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
    live = _judge(checked, left, params, ref_cfg, served)
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
    counted = {} if not ctx.trace else _recurrence_values(
        overlap, red, mc, slots, (w0, w1), ctx.trace_started_at, ctx.peaks)
    work_done = {}
    if red:
        traced = it_start >= ctx.trace_started_at
        flops, nbytes = c.it_flops[its][traced], c.it_bytes[its][traced]
        work_done["paged_attention"] = {
            "least_s": sum(costs.roofline_seconds(f, b, ctx.peaks)[0]
                           for f, b in zip(flops, nbytes)),
            "bound": costs.roofline_seconds(flops.sum(), nbytes.sum(),
                                            ctx.peaks)[1]}

    # correct, part (d): nothing of any kind held after the drain
    held_after = srv.allocator.num_used_by_kind()
    ok = (_within_limits(quiet) and _within_limits(live)
          and not any(held_after.values())
          and failed == 0 and len(judged) > 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)

    running = c.it_running[its][in_w]
    act_bytes = jnp.dtype(eng_cfg["dtype"]).itemsize
    page_bytes = (int(serving["kv_block_size"]) * 2 * mc.kv_heads * mc.hdim
                  * act_bytes * mc.attention_layers_count)
    one_state = costs_ssd.state_bytes(
        mc.mamba_layers, mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state,
        mc.ssm_conv, act_bytes)
    state_held = running.mean() * one_state
    pages_held = c.it_blocks[its][in_w].mean() * page_bytes
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
        **{k: v for k, v in counted.items()
           if k not in ("ssd_chunk_bound", "ssm_scan_ops")},
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
        # the pool is [layers, blocks, kv_block_size, kv heads x dim]
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {**quiet, "live": live, "controls": controls,
                 "held_after_drain": held_after,
                 "kv_pool_bytes": int(srv.kv_pool_bytes),
                 "state_bytes_a_slot": one_state,
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(srv.allocator.num_used),
                 "paged_bound": work_done.get("paged_attention",
                                              {}).get("bound"),
                 "itl_ms": _profile(gaps_in), "ttft_ms": _profile(ttft),
                 **{k: values[k] for k in (
                     "queue_depth_first_fifth", "queue_depth_last_fifth",
                     "requests_per_s_completed", "ttft_mean_ms",
                     "batch_occupancy", "kv_pool_occupancy",
                     "kv_blocks_held_max", "state_bytes_share")},
                 **counted},
    }
