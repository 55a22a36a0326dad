"""A serving cell of a model whose stack mixes window-attention layers and
full-attention layers over routed experts (``trinity-mini``): the client,
the closed loop with its lead-in and every stamp are ``runners/serve.py``'s
and ``serve_sparse_latent.py``'s, the weights' filler (from the seed, in
the served type, an element of a stack at a time) ``serve_hybrid.py``'s;
this file repeats only what must differ — the build (another builder, the
configuration's own keys), the numbers of the reference check
(``lib/reference_afmoe.py``), the walks' work by kind of layer
(``lib/costs_window_moe.py``) and the values taken from the program's
counters and from its two lanes of ``attn_kernel``."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, costs_latent, costs_window_moe, device,
                   model as model_lib, reference_afmoe as reference, stats,
                   traffic)
from .serve import Client, _profile, clock, stop_trace
from .serve_block_diffusion import _live_bytes
from .serve_hybrid import serving_weights
from .serve_sparse_latent import _closed_loop

#: Two check requests through the ENGINE's own programs (chunked prefill, then
#: paged decode over both pools, in bfloat16) against the float32 reference's
#: full forward over what the engine was fed, on the quiet engine before the
#: window and again at its close beside 18 live slots. (a)
#: ``logit_gap_worst``: over the 16 tokens the engine chose greedily, the
#: reference's best logit minus its logit of the engine's token, as a share of
#: the best logit's size — sound 0.000-0.070 (24 readings over 12 seeds),
#: every layer full 0.209-0.285, no gate 0.363-0.568; the limit leaves the
#: sound tail 2.1x and is what a wrong page or slot beside live slots reads
#: far over.  (b) ``kv_rel_err``: the keys and values the first request LEFT
#: in the eight full layers' pool, read back through the table it had, over
#: the rows from the window's length on (whose window layers saw pages handed
#: back while the prompt prefilled; the window layers feed the full ones),
#: norm of the difference over the norm — sound 0.0473-0.0662 (the same 24
#: readings; 0.0505-0.0583 in the call the limit was seated on); ``float8``
#: 0.109-0.124, ``scale`` 0.201-0.221, ``window`` 0.311-0.323, ``gate``
#: 0.577-0.587, ``nope`` 1.000; NOT refused by it: ``bf16`` 0.068-0.078 and
#: ``bias`` 0.065-0.077, which (c) refuses.  (c) ``expert_rel_err``: the first
#: expert layer's ``f`` (router, bias, the held experts' grouped product out
#: of the stack, the shared expert) at the timed row count over rows both
#: sides are fed alike — sound 0.0027 on every seed, ``bias`` 0.0497-0.0743,
#: ``bf16`` 0.0508-0.0633, ``scale`` 0.22, ``float8`` 0.237-0.241.  (b) and
#: (c) sit at the geometric middle of the seating call's largest sound reading
#: and the nearest control's lowest. ``PERF.md`` section 4 has every reading
#: and its seeds (my chip runs, PR 62).
LIMITS = {"logit_gap_worst": 0.15, "kv_rel_err": 0.08,
          "expert_rel_err": 0.012}
#: one prompt of five chunks — the window's edge falls inside it, window
#: pages are handed back while it prefills — and one under the window
CHECK_REQUESTS = ((2560, 8), (384, 8))
#: what the reference can be made to lack (``reference_leaves_out`` /
#: ``controls``, never a cell's; ``lib/reference_afmoe.py`` says what each
#: changes)
CONTROLS = reference.CONTROLS

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "kv_heads", "head_dim": "hdim",
             "intermediate_size": "ff_dim",
             "moe_intermediate_size": "expert_d_ff",
             "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "num_experts_per_tok": "moe_topk",
             "num_shared_experts": "n_shared_experts",
             "num_dense_layers": "first_k_dense",
             "sliding_window": "sliding_window",
             "route_norm": "norm_topk_prob",
             "route_scale": "routed_scaling_factor",
             "score_func": "router_scoring",
             "rope_theta": "rotary_base", "rms_norm_eps": "layernorm_eps",
             "hidden_act": "activation",
             "tie_word_embeddings": "tie_embeddings"}
#: the parameters at the published widths and 16 of 128 experts a layer
NUM_PARAMS = 4_984_682_240


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
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    ref = reference.settings(config)
    if tiny:
        ref.update(heads=mc.num_heads, kv_heads=mc.kv_heads,
                   head_dim=mc.hdim, window=mc.sliding_window,
                   layer_types=mc.layer_types, dense=mc.first_k_dense,
                   experts=mc.n_routed_experts, topk=mc.moe_topk)
        return mc, ref, mc.held
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(num_experts=mc.held[1] - mc.held[0])
    want = {k: config[k] for k in built}
    flags = (mc.n_routed_experts, mc.layer_types, mc.rotary_interleaved,
             mc.norm_type, mc.router_bias)
    # (the block multiplies its embedding by sqrt(d) whatever the file says)
    if built != want or not config["mup_enabled"] \
            or mc.num_params() != NUM_PARAMS or flags != (
            config["published"]["num_experts"], ref["layer_types"], False,
            "rmsnorm", True):
        raise ValueError(f"the program built {built} with {flags} "
                         f"({mc.num_params():,} parameters), the "
                         f"configuration file says {want} ({NUM_PARAMS:,})")
    return mc, ref, mc.held


def _serve_check_requests(srv, vocab, seed, shrink, stream=0):
    """Two seeded prompts through chunked prefill and paged decode on the
    engine the window uses, beside whatever else it is serving: ``(the
    finished requests, what the first one left in the FULL layers' pool —
    every full layer's keys and values ``[2, full layers, rows, G D]``
    float32, read back through the table it had in the iteration it
    finished in (the one in flight was planned before its pages were
    freed, so nothing has written to them yet) — and the fewest slots that
    were live meanwhile)``.  Returns once both have finished; what else
    runs goes on running."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import RequestState
    rng = np.random.default_rng([int(seed), 0xC4EC + stream])
    reqs = [srv.submit(rng.integers(0, vocab, max(6, p // shrink)),
                       max_new_tokens=n) for p, n in CHECK_REQUESTS]
    first = reqs[0]
    # (one gather: a slice of the pool alone would copy the whole pool)
    pages = jax.jit(lambda pool, table: pool[:, table])
    table = left = None
    least = srv.num_slots
    while any(r.state is not RequestState.FINISHED for r in reqs):
        if not srv.step():
            break
        least = min(least, srv.scheduler.active_slots)
        if first in srv.scheduler.running.values():
            table = srv.allocator.block_table(first.req_id)
        if left is None and table is not None \
                and first.state is RequestState.FINISHED:
            rows = len(first.prompt) + len(first.output) - 1
            at = jnp.asarray(table)
            left = jnp.stack([
                pages(pool, at).reshape(pool.shape[0], -1,
                                        pool.shape[-1])[:, :rows]
                for pool in (srv._pool_k, srv._pool_v)]).astype(jnp.float32)
    return reqs, left, least


def _check_experts(model, params, ref_cfg, held, seed, rows):
    """What two prompts' logits cannot part from a pick flipped by a
    bfloat16 activation: the router's bias and scale, the held experts and
    their grouped product.  The first expert layer's ``f`` over ``rows``
    seeded rows (the mixed program's row count) on the timed weights — the
    program's ``expert_layer``, whose kernel reads that layer's experts
    where they lie in the stack, as the step does — against the
    reference's: the norm of the difference over the norm of the
    reference's."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import dropless
    rest, experts = dropless.split_experts(params["moe"])
    u = jax.random.normal(
        model_lib.seed_key(int(seed) + 0xE4),
        (rows, model.config.d_model)).astype(experts["w_up"].dtype)

    def layer0(tree):
        return jax.tree_util.tree_map(lambda a: a[0], tree)

    def program(rest, experts, u):
        return model.expert_layer(
            layer0(rest), u[None], stack=(experts, jnp.int32(0))
        )[0][0].astype(jnp.float32)

    def plain(rest, experts, u):
        p = layer0(rest)
        p = dict(p, moe=dict(p["moe"], experts=layer0(experts)))
        with jax.default_matmul_precision("highest"):
            return reference.expert_layer(
                p, u.astype(reference._dt(ref_cfg)), ref_cfg,
                held).astype(jnp.float32)
    got = jax.jit(program)(rest, experts, u)
    want = jax.jit(plain)(rest, experts, u)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _judge(reqs, left, params, ref_cfg, held) -> dict:
    """The reference's full forward over what the engine was fed judges
    every token the engine chose — the worst gap to the reference's best
    logit over that logit's size, the share of positions where the token
    is its argmax — and what the first request ``left`` in the full
    layers' pool, over the rows from the window's length on (those whose
    window layers saw pages handed back; every row of a request the
    window holds whole): the norm of the difference over the norm of the
    reference's."""
    import jax.numpy as jnp

    def err(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    out = {"logit_gap_worst": math.inf, "argmax_share": 0.0,
           "kv_rel_err": math.inf}
    if left is None or any(len(r.output) != r.max_new_tokens for r in reqs):
        return out
    worst, exact, n = 0.0, 0, 0
    for k, r in enumerate(reqs):
        # what the engine was fed: the prompt and all but the last token
        fed = jnp.asarray(list(r.prompt) + list(r.output)[:-1])[None]
        lg, kv = reference.logits(params, fed, ref_cfg, held,
                                  last=len(r.output), kv=True)
        lg = np.asarray(lg[0])
        for j, tok in enumerate(r.output):
            worst = max(worst, float((lg[j].max() - lg[j][tok])
                                     / abs(lg[j].max())))
            exact += int(lg[j].argmax() == tok)
            n += 1
        if k == 0:
            kv = kv[:, :, 0]
            past = ref_cfg["window"] if kv.shape[2] > ref_cfg["window"] else 0
            out["kv_rel_err"] = err(left[:, :, past:], kv[:, :, past:])
        del lg, kv
    out.update(logit_gap_worst=worst, argmax_share=exact / n)
    return out


def _within_limits(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items()
               if k in numbers)


class WindowClient(Client):
    """``Client`` with the paged kernel's work counted by kind of layer
    (``costs_window_moe.paged_walk_cost``: every new row of a request
    attends in both kinds), and the window pages held sampled an
    iteration."""

    def __init__(self, srv, work, model_config, trace_on, overlap, peaks):
        super().__init__(srv, work, model_config, trace_on, overlap)
        mc = model_config
        self.peaks = peaks
        self.walk = (mc.num_heads, mc.kv_heads, mc.hdim, mc.sliding_window)
        self.layers_of = {"full": mc.full_layers, "window": mc.window_layers}
        self.walked = np.zeros(len(work["prompts"]), np.int64)
        self.it_wblocks = np.zeros(self.it_blocks.shape)
        #: the least seconds of each kind's walks, an iteration
        self.it_kind_s = {k: np.zeros(self.it_blocks.shape)
                          for k in self.layers_of}

    def iterate(self) -> tuple:
        live = list(self.live)
        k = self.n_it
        out = super().iterate()
        flops = nbytes = 0.0
        for i in live:
            cached = self.reqs[i].cached_tokens
            rows = cached - self.walked[i]
            if rows > 0:
                for kind, layers in self.layers_of.items():
                    f, b = costs_window_moe.paged_walk_cost(
                        kind, cached, rows, *self.walk)
                    flops += f * layers
                    nbytes += b * layers
                    self.it_kind_s[kind][k] += layers * \
                        costs.roofline_seconds(f, b, self.peaks)[0]
            self.walked[i] = cached
        self.it_flops[k], self.it_bytes[k] = flops, nbytes
        self.it_wblocks[k] = self.srv.allocator.num_used_by_kind()["window"]
        return out


def _counter_values(overlap, mc, window, since) -> tuple:
    """What the program counted: the cell's values over the window's
    iterations, and the grouped product's operations and bytes over those
    that began at or after ``since`` (the traced ones).  A program that
    keeps no such counters gives nothing."""
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "kv_tokens_read_window" not in names:
        return {}, None
    span = window[1] - window[0]
    lo, hi = mc.held
    expert_layers = mc.num_layers - mc.first_k_dense
    picks = float(recs["moe_picks"].sum())
    held = float(recs["moe_picks_held"].sum())
    shared = float(recs["moe_rows_shared"].sum()) * mc.n_shared_experts
    layer_dispatches = float(recs["dispatches"].sum()) * expert_layers
    places = layer_dispatches * (hi - lo)
    full = float(recs["kv_tokens_read_full"].sum())
    win = float(recs["kv_tokens_read_window"].sum())
    values = {
        "kv_tokens_read_per_s": (full + win) / span,
        "chunk_dispatch_share": 100.0 * float(
            (recs["chunk_rows"] > 0).sum()) / len(recs),
        "window_blocks_freed": float(recs["window_blocks_freed"].sum())}
    if full + win > 0:
        values["window_tokens_read_share"] = 100.0 * win / (full + win)
    for kind in ("full", "window"):
        read = float(recs[f"kv_pages_read_{kind}"].sum())
        if read > 0:
            values[f"{kind}_pages_in_runs_share"] = 100.0 * float(
                recs[f"kv_pages_in_runs_{kind}"].sum()) / read
    if picks > 0 and held > 0 and places > 0:
        values.update(
            moe_held_share=100.0 * held / picks,
            # the fullest held expert's rows over the mean rows a held one
            moe_imbalance=(float(recs["moe_rows_max_expert"].sum())
                           / layer_dispatches) / (held / places),
            moe_rows_per_expert=held / places,
            moe_touched_share=100.0 * float(
                recs["moe_experts_touched"].sum()) / places,
            # every expert here, shared or routed, is the same three products
            moe_shared_share=100.0 * shared / (shared + held))
    traced = recs[recs["begin_s"] >= since]
    work = costs_latent.grouped_experts_cost(
        float(traced["moe_picks_held"].sum()),
        float(traced["moe_experts_touched"].sum()), mc.d_model,
        mc.expert_d_ff) if len(traced) else None
    return values, work


def _lane_values(overlap, red, client, traced) -> dict:
    """From the trace, joined to the program's scopes with their lanes
    (``attn_kernel/window`` and ``attn_kernel/full``, whatever implements
    them): each lane's share of the device's busy time, and its share of
    the roofline of the work the client counted for that kind of layer
    over the traced iterations.  A program that names no such lanes gives
    nothing."""
    from deepspeed_tpu.observability.overlap import scope_key
    table_of = getattr(overlap, "program_scopes", None)
    if not red or table_of is None:
        return {}
    table = table_of(lanes=True)
    lane_s = {"attn_kernel/window": 0.0, "attn_kernel/full": 0.0}
    for name, seconds in red["op_s"].items():
        scope = table.get(scope_key(name), ("unnamed",))[0]
        if scope in lane_s:
            lane_s[scope] += seconds
    values = {}
    for kind in ("window", "full"):
        spent = lane_s[f"attn_kernel/{kind}"]
        if spent > 0:
            values[f"{kind}_walk_time_share"] = 100.0 * spent / red["busy_s"]
            values[f"{kind}_walk_roofline"] = 100.0 * float(
                client.it_kind_s[kind][:client.n_it][traced].sum()) / spent
    return values


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
    block, chunk = (int(serving["kv_block_size"]),
                    int(serving["prefill_chunk_tokens"]))
    params = serving_weights(model, ctx.seed, jnp.dtype(eng_cfg["dtype"]))
    held_bytes = {"weights": _live_bytes()}
    eng = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params)
    model = eng.module               # (the engine may have rebuilt it)
    srv = eng.serving_engine()
    held_bytes["engine"] = _live_bytes()
    overlap = get_overlap_profiler()
    if ctx.trace:
        overlap.configure(enabled=True)

    # correct on the quiet engine (and the warm-up of both step shapes)
    ref_cfg["without"] = tuple(mix.get("reference_leaves_out", ()))
    checked, left, _ = _serve_check_requests(srv, mc.vocab_size, ctx.seed,
                                             shrink)
    while srv.step():
        pass
    rows = int(serving["max_batch_slots"]) + chunk
    quiet = _judge(checked, left, params, ref_cfg, held)
    quiet["expert_rel_err"] = _check_experts(model, params, ref_cfg, held,
                                             ctx.seed, rows)
    # the limits' seating only: the same served tokens and pool rows
    # against a reference that lacks one mechanism at a time
    controls = {}
    for name in mix.get("controls", ()):
        lacking = dict(ref_cfg, without=(name,))
        controls[name] = dict(
            _judge(checked, left, params, lacking, held),
            expert_rel_err=_check_experts(model, params, lacking, held,
                                          ctx.seed, rows))
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
    client = WindowClient(srv, work, mc, ctx.trace, overlap, ctx.peaks)
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
    # beside what the window left decoding (slots reused, window pages
    # recycled among them, contexts as long as the window's)
    in_slots = {id(r) for r in srv.scheduler.running.values()}
    stay = sorted((i for i in c.live if id(c.reqs[i]) in in_slots),
                  key=lambda i: c.reqs[i].max_new_tokens
                  - len(c.reqs[i].output))
    for i in set(c.live) - set(stay[len(CHECK_REQUESTS):]):
        srv.cancel(c.reqs[i])
    checked, left, least = _serve_check_requests(srv, mc.vocab_size,
                                                 ctx.seed, shrink, stream=1)
    for i in stay:                   # in flight at the close: cancelled
        srv.cancel(c.reqs[i])
    while srv.step():
        pass
    live = _judge(checked, left, params, ref_cfg, held)
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
    counted, moe_work = ({}, None) if not ctx.trace else _counter_values(
        overlap, mc, (w0, w1), ctx.trace_started_at)
    work_done = {}
    if red:
        traced = it_start >= ctx.trace_started_at
        flops, nbytes = c.it_flops[its][traced], c.it_bytes[its][traced]
        work_done["paged_attention"] = {
            "least_s": sum(costs.roofline_seconds(f, b, ctx.peaks)[0]
                           for f, b in zip(flops, nbytes)),
            "bound": costs.roofline_seconds(flops.sum(), nbytes.sum(),
                                            ctx.peaks)[1]}
        if moe_work is not None:
            least_s, bound = costs.roofline_seconds(*moe_work, ctx.peaks)
            work_done["moe_grouped_matmul"] = {"least_s": least_s,
                                               "bound": bound}
        counted.update(_lane_values(overlap, red, c, traced))

    # correct: what the slots held of each kind of page
    alloc = srv.allocator
    held_decoding, held_chunk = model.window_pages(block, chunk)
    held_after = alloc.num_used_by_kind()
    window_ok = (alloc.window_held_max["decode"] <= held_decoding
                 and alloc.window_held_max["chunk"] <= held_chunk
                 and not any(held_after.values()))
    ok = (_within_limits(quiet) and _within_limits(live) and window_ok
          and failed == 0 and len(judged) > 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)

    running = c.it_running[its][in_w]
    wblocks = c.it_wblocks[its][in_w]
    full_b, window_b = (c.it_blocks[its][in_w].mean() * mc.full_layers,
                        wblocks.mean() * mc.window_layers)
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
        "window_blocks_per_slot": float(
            (wblocks / np.maximum(running, 1)).mean()),
        # of the bytes the slots hold in pages, the window layers' share
        "window_bytes_share": float(100.0 * window_b
                                    / max(window_b + full_b, 1e-30)),
        **counted,
    }
    stamps = {"it_start": it_start - w0, "it_end": it_end - w0,
              "it_tokens": c.it_tokens[its], "it_running": c.it_running[its],
              "it_queue": queue, "it_blocks": c.it_blocks[its],
              "it_wblocks": c.it_wblocks[its], "window_s": w1 - w0,
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
        # both pools are [layers, blocks, kv_block_size, kv heads x dim]
        "shapes": {"kv_block_size": block, "kv_row_width": srv.kv_row_width},
        "diag": {**quiet, "live": live, "controls": controls,
                 "limits": LIMITS, "live_bytes_after": held_bytes,
                 "window_blocks_held": dict(alloc.window_held_max),
                 "window_blocks": int(srv.window_blocks),
                 "held_after_drain": held_after,
                 "kv_pool_bytes": int(srv.kv_pool_bytes),
                 "flight_counts": dict(srv.flight_counts),
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(alloc.num_used),
                 "paged_bound": work_done.get("paged_attention",
                                              {}).get("bound"),
                 "moe_bound": work_done.get("moe_grouped_matmul",
                                            {}).get("bound"),
                 "itl_ms": _profile(gaps_in), "ttft_ms": _profile(ttft),
                 **{k: values[k] for k in (
                     "queue_depth_first_fifth", "queue_depth_last_fifth",
                     "requests_per_s_completed", "ttft_mean_ms",
                     "batch_occupancy", "kv_pool_occupancy",
                     "kv_blocks_held_max", "window_blocks_per_slot",
                     "window_bytes_share")},
                 **counted},
    }
