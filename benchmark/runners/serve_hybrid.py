"""A serving cell of a hybrid state-space model
(``phi-4-mini-flash-reasoning``): the client, the closed loop with its
lead-in and every stamp are ``runners/serve.py``'s and
``serve_sparse_latent.py``'s; this file repeats only what must differ —
the build (another builder, the configuration's own keys), the weights
(made from the seed in the served type a pair of layers at a time: a
float32 copy of them does not fit the chip), the three numbers of the
reference check (``lib/reference_phi4_flash.py``), the work counts by
kind of layer (``lib/costs_hybrid.py``) and the values taken from the
program's counters."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, costs_hybrid, device, model as model_lib,
                   reference_phi4_flash as reference, stats, trace, traffic)
from .serve import Client, _profile, clock, stop_trace
from .serve_sparse_latent import _closed_loop

#: (a) every token the engine chose greedily, through chunked prefill and
#: paged decode in bfloat16, within this of the float32 reference's best
#: logit at its position; (b) the slot's state-space states read back
#: from the engine after the first check request, against the
#: reference's, norm of the difference over the norm: the FIRST
#: state-space layer's (its input is the embedding, the same in both, so
#: the number is the state path's own precision) and all nine together
#: (the later layers' inputs carry the bfloat16 activations' rounding,
#: which is more than a bfloat16 state would add: a coarse limit, for a
#: state that is wrong and not merely rounded).  (d) what layer 17 WROTE:
#: the full layer's keys and values of the first check request, read back
#: from the pool through the table it had, against the reference's.
#: (e) what the eight walks over those pages READ: the attention's output
#: (before the output projection) of the full layer and of each of the
#: seven cross layers for the judged rows, out of the model's own mixed
#: step (``_served_cross_reads``), against the reference's, the worst
#: layer's — at seeded weights attention over a thousand keys is close to
#: the values' mean, the cross layers add a hundredth to the residual,
#: and no logit can see whether they read the right pages, another
#: table's, a page short or zeros.  ``PERF.md`` section 4 has the
#: readings each limit lies between.
LOGIT_GAP_ATOL = 0.4
STATE_REL_ERR_MAX = 0.0058
STATES_REL_ERR_MAX = 0.055
FULL_KV_REL_ERR_MAX = 0.07
CROSS_READ_REL_ERR_MAX = 0.068
LIMITS = {"logit_gap_worst": LOGIT_GAP_ATOL,
          "ssm_state_rel_err": STATE_REL_ERR_MAX,
          "ssm_states_rel_err": STATES_REL_ERR_MAX,
          "full_kv_rel_err": FULL_KV_REL_ERR_MAX,
          "cross_read_rel_err": CROSS_READ_REL_ERR_MAX}
#: prompts past the window, across chunk boundaries, not multiples of 16
CHECK_REQUESTS = ((1333, 24), (700, 24))
#: the scan kernel among the trace's device operations (the pattern of
#: ``metrics/ssm_scan_roofline.json``): its share of the busy time goes to
#: ``diag``, the benchmark's list of per-layer metrics being full
SCAN_KERNEL = r"^%ssm_chunk_scan[.\d]* = "
#: faults put INTO the program (``program_fault``, never a cell's): the
#: cross layers walk the null block's table, or stop a page short
PROGRAM_FAULTS = ("cross_null_table", "cross_page_short")

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "kv_heads",
             "intermediate_size": "ff_dim", "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "sliding_window": "sliding_window",
             "layer_norm_eps": "layernorm_eps",
             "tie_word_embeddings": "tie_embeddings",
             "mamba_d_state": "ssm_state", "mamba_d_conv": "ssm_conv",
             "mamba_expand": "ssm_expand", "mamba_dt_rank": "dt_rank"}


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
        ref.update(heads=mc.num_heads, kv_heads=mc.kv_heads,
                   window=mc.sliding_window, state=mc.ssm_state,
                   dt_rank=mc.dt_rank)
        return mc, ref
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    want = {k: config[k] for k in built}
    if built != want or 2 * (mc.pairs_self + 1 + mc.pairs_cross) != \
            config["num_hidden_layers"]:
        raise ValueError(f"the program built {built}, the configuration "
                         f"file says {want}")
    return mc, ref


def serving_weights(model, seed: int, dtype):
    """The tree ``model.init`` gives for the seed, in the type it is
    served in, made on the device a pair of layers at a time into
    preallocated stacks: the random values are cast where they are drawn,
    so no float32 copy of more than a pair ever exists."""
    import jax
    import jax.numpy as jnp
    key = model_lib.seed_key(seed)

    def cast(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)
    params = jax.jit(lambda k: cast(model.init_resident(k)))(key)
    keys = model.pair_keys(key)
    for part, kinds in model.PARTS.items():
        def one(k, kinds=kinds):
            return cast(model.init_pair(kinds, k))
        if part == "mid":
            params[part] = jax.jit(one)(keys[part][0])
            continue
        n = keys[part].shape[0]
        shapes = jax.eval_shape(one, keys[part][0])
        stack = jax.jit(lambda shapes=shapes, n=n: jax.tree_util.tree_map(
            lambda s: jnp.zeros((n,) + s.shape, dtype), shapes))()
        put = jax.jit(lambda stack, k, at, one=one: jax.tree_util.tree_map(
            lambda s, a: jax.lax.dynamic_update_index_in_dim(s, a, at, 0),
            stack, one(k)), donate_argnums=0)
        for at in range(n):
            stack = put(stack, keys[part][at], at)
        params[part] = stack
    return params


def _with_fault(model, fault: str, block: int) -> None:
    """Put ``fault`` into ``model``'s cross mixer, for every program built
    from it afterwards: the control on the PROGRAM's side."""
    import jax.numpy as jnp
    if fault not in PROGRAM_FAULTS:
        raise ValueError(f"program_fault {fault!r} is none of "
                         f"{PROGRAM_FAULTS}")
    sound = model._cross_paged

    def faulty(p, h, pool_k, pool_v, st):
        if fault == "cross_null_table":
            st = st._replace(tables=jnp.zeros_like(st.tables))
        else:
            st = st._replace(
                lens=jnp.maximum(st.lens - block, 0),
                chunk_start=jnp.maximum(st.chunk_start - block, 0))
        return sound(p, h, pool_k, pool_v, st)
    model._cross_paged = faulty


def _serve_check_requests(srv, model, vocab, seed, shrink, stream=0):
    """Two seeded prompts through chunked prefill and paged decode on the
    engine the window uses, beside whatever else it is serving: ``(the
    finished requests, what the first one left in the engine, the fewest
    slots that were live meanwhile, these two among them)`` — its
    state-space states read back from its slot, and the full layer's keys
    and values read back from the pool through the table it had, both in
    the iteration it finished in (the one in flight was planned before
    its pages were freed, so nothing has written to them yet).  Returns
    once both have finished; what else runs goes on running."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import RequestState
    rng = np.random.default_rng([int(seed), 0xC4EC + stream])
    reqs = [srv.submit(rng.integers(0, vocab, max(6, p // shrink)),
                       max_new_tokens=n) for p, n in CHECK_REQUESTS]
    first = reqs[0]
    # (one gather: `pool[0]` alone would copy the whole pool)
    pages = jax.jit(lambda pool, table: pool[0, table])
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
                        pages(pool, at).reshape(-1, pool.shape[-1])[:rows]
                        for pool in (srv._pool_k, srv._pool_v)]
                    ).astype(jnp.float32)}
    return reqs, left, least


def _served_cross_reads(model, params, req, slots, chunk, block):
    """What the eight walks over the full layer's pages hand on.  The
    tokens the engine was fed for ``req`` go through the model's own mixed
    step — ``_apply_paged_mixed``, the function the engine's program is,
    at the engine's row counts, over a paged cache of its own whose tables
    are half consecutive blocks and half shuffled ones — the prompt chunk
    by chunk in a slot of the upper half, then a decode row a token;
    ``probe=True`` brings out the attention's output for the row that
    yields a token, of the full layer and of each cross layer.  Returns
    ``[1 + cross layers, tokens judged, heads x head_dim]`` float32: the
    rows ``reference.logits(.., last=len(req.output))`` gives."""
    import jax
    import jax.numpy as jnp
    fed = np.asarray(list(req.prompt) + list(req.output)[:-1], np.int32)
    plen, t = len(req.prompt), len(req.prompt) + len(req.output) - 1
    slot = slots // 2 + slots // 8
    pages = -(-(t + 1) // block)
    order = np.random.default_rng([t, 0x7AB]).permutation(
        np.arange(pages // 2, pages))
    table = 1 + np.concatenate([np.arange(pages // 2), order])
    tables = np.zeros((slots, 2 * (pages + 1)), np.int32)
    tables[slot, :pages] = table
    tables[slot, pages + 1:2 * pages + 1] = table[::-1]   # the window kind's
    dtype = params["embed"]["embedding"].dtype
    cache = model.init_paged_cache(pages + 1, block, dtype)
    cache.update(extra=model.init_paged_extra(slots, block, pages + 1, dtype),
                 block_tables=jnp.asarray(tables),
                 lens=jnp.zeros((slots,), jnp.int32))

    def step(params, cache, dec_tokens, dec_active, chunk_ids, start, rows):
        _, _, new = model._apply_paged_mixed(
            params, cache, dec_tokens, dec_active, chunk_ids,
            jnp.int32(slot), start, rows, probe=True)
        reads = new["probe"]["reads"]
        return ({k: new[k] for k in cache},
                jnp.where(rows > 0, reads[:, -1], reads[:, slot]))
    step = jax.jit(step, donate_argnums=1)
    idle = np.zeros((slots,), np.int32)
    none = np.zeros((chunk,), np.int32)
    out = []
    for start in range(0, plen, chunk):
        rows = min(chunk, plen - start)
        ids = none.copy()
        ids[:rows] = fed[start:start + rows]
        cache, read = step(params, cache, idle, idle, ids,
                           jnp.int32(start), jnp.int32(rows))
    out.append(read)                              # the prompt's last row
    live = idle.copy()
    live[slot] = 1
    for at in range(plen, t):
        tok = idle.copy()
        tok[slot] = fed[at]
        cache, read = step(params, cache, tok, live, none, jnp.int32(0),
                           jnp.int32(0))
        out.append(read)
    return jnp.stack(out, axis=1).astype(jnp.float32)


def _judge(reqs, left, params, ref_cfg) -> dict:
    """The reference's full forward over what the engine was fed judges
    every token the engine chose, and what it holds after the same tokens
    judges what the first request left: the worst gap to the reference's
    best logit, the share of positions where the token is its argmax, the
    first state-space layer's state error, all the layers', the full
    layer's keys' and values' and — where ``left`` has the walks'
    ``reads`` — the worst of the eight attentions' outputs', errors as
    the norm of the difference over the reference's norm (the attentions':
    over the larger of the two)."""
    import jax
    import jax.numpy as jnp
    judge = jax.jit(lambda p, ids, n: reference.logits(
        p, ids, ref_cfg, states=True, last=n), static_argnums=2)

    def err(got, want, larger=False):
        over = jnp.linalg.norm(want)
        if larger:      # the larger norm: a side that read zeros reads 1
            over = jnp.maximum(over, jnp.linalg.norm(got))
        return float(jnp.linalg.norm(got - want) / jnp.maximum(over, 1e-30))
    names = ["ssm_state_rel_err", "ssm_states_rel_err", "full_kv_rel_err"]
    if left is not None and "reads" in left:
        names.append("cross_read_rel_err")
    out = {"logit_gap_worst": math.inf, "argmax_share": 0.0,
           **{k: math.inf for k in names}}
    if left is None or any(len(r.output) != r.max_new_tokens for r in reqs):
        return out
    worst, exact, n = 0.0, 0, 0
    for k, r in enumerate(reqs):
        # what the engine was fed: the prompt and all but the last token
        fed = jnp.asarray(list(r.prompt) + list(r.output)[:-1])[None]
        lg, states, kv, reads = judge(params, fed, len(r.output))
        lg = np.asarray(lg[0])
        for j, tok in enumerate(r.output):
            worst = max(worst, float(lg[j].max() - lg[j][tok]))
            exact += int(lg[j].argmax() == tok)
            n += 1
        if k == 0:
            out.update(ssm_state_rel_err=err(left["states"][0], states[0, 0]),
                       ssm_states_rel_err=err(left["states"], states[0]),
                       full_kv_rel_err=err(left["kv"], kv[0]))
            if "reads" in left:
                by_layer = [err(g, w, larger=True)
                            for g, w in zip(left["reads"], reads[0])]
                out.update(cross_read_rel_err=max(by_layer),
                           cross_read_by_layer=by_layer)
    out.update(logit_gap_worst=worst, argmax_share=exact / n)
    return out


def _within_limits(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items()
               if k in numbers)


class HybridClient(Client):
    """``Client`` with the paged kernel's work counted by kind of layer
    (``costs_hybrid.paged_walk_cost``: the layers that walk the full
    layer's pages see one row a request that grew, the window layers all
    of its new rows), and the window pages and slots held sampled an
    iteration."""

    def __init__(self, srv, work, model_config, trace_on, overlap):
        super().__init__(srv, work, model_config, trace_on, overlap)
        mc = model_config
        self.walk = (mc.num_heads, mc.kv_heads, mc.hdim, mc.sliding_window)
        self.layers_of = {"full": 1 + mc.pairs_cross,
                          "window": mc.pairs_self}
        self.walked = np.zeros(len(work["prompts"]), np.int64)
        self.it_wblocks = np.zeros(self.it_blocks.shape)

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
                    f, b = costs_hybrid.paged_walk_cost(kind, cached, rows,
                                                        *self.walk)
                    flops += f * layers
                    nbytes += b * layers
            self.walked[i] = cached
        self.it_flops[k], self.it_bytes[k] = flops, nbytes
        self.it_wblocks[k] = self.srv.allocator.num_used_by_kind()["window"]
        return out


def _counter_values(overlap, mc, window, since) -> tuple:
    """What the program counted: the cell's values over the window's
    iterations, and the scan kernel's operations and bytes over those
    that began at or after ``since`` (the traced ones).  A program that
    keeps no such counters gives nothing."""
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "kv_tokens_read_full" not in names:
        return {}, None
    full = float(recs["kv_tokens_read_full"].sum())
    win = float(recs["kv_tokens_read_window"].sum())
    chunk_rows = float(recs["chunk_rows"].sum())
    values = {
        "kv_tokens_read_per_s": (full + win) / (window[1] - window[0]),
        "ssm_decode_rows_per_s": float(recs["ssm_decode_rows"].sum())
        / (window[1] - window[0]),
        "chunk_dispatch_share": 100.0 * float(
            (recs["chunk_rows"] > 0).sum()) / len(recs),
        "window_blocks_freed": float(recs["window_blocks_freed"].sum()),
        "state_slots_started": float(recs["state_slots_started"].sum())}
    if full + win > 0:
        values["shared_kv_read_share"] = 100.0 * full / (full + win)
    if chunk_rows > 0:
        values["cross_rows_spared_share"] = 100.0 * float(
            recs["cross_rows_spared"].sum()) / chunk_rows
    traced = recs[recs["begin_s"] >= since]
    work = None
    if len(traced) and traced["ssm_chunk_rows"].sum() > 0:
        work = costs_hybrid.ssm_chunk_scan_cost(
            float(traced["ssm_chunk_rows"].sum()),
            float((traced["ssm_chunk_rows"] > 0).sum()) * mc.ssm_layers,
            mc.d_inner, mc.ssm_state)
    return values, work


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
        _with_fault(model, mix["program_fault"],
                    int(serving["kv_block_size"]))
    params = serving_weights(model, ctx.seed, jnp.dtype(eng_cfg["dtype"]))
    srv = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params).serving_engine()
    overlap = get_overlap_profiler()
    if ctx.trace:
        overlap.configure(enabled=True)

    # correct, parts (a), (b), (d), (e) on the quiet engine (and the
    # warm-up of both step shapes)
    ref_cfg["without"] = tuple(mix.get("reference_leaves_out", ()))
    ref_cfg["chunk"] = int(serving["prefill_chunk_tokens"])
    checked, left, _ = _serve_check_requests(srv, model, mc.vocab_size,
                                             ctx.seed, shrink)
    while srv.step():
        pass
    if left is not None:
        left["reads"] = _served_cross_reads(
            model, params, checked[0], srv.num_slots, ref_cfg["chunk"],
            int(serving["kv_block_size"]))
    quiet = _judge(checked, left, params, ref_cfg)
    # the README's pool sizing and limit seating only: the same served
    # tokens against a reference that lacks one mechanism at a time
    controls = {name: _judge(checked, left, params,
                             dict(ref_cfg, without=(name,)))
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
    client = HybridClient(srv, work, mc, ctx.trace, overlap)
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
    # correct, parts (a), (b), (d) again with the other slots live: the
    # queue and the two requests nearest their end make room, two more
    # seeded prompts run beside what the window left decoding (slots
    # reused, window pages recycled among them, every slot's state at its
    # stride, contexts as long as the window's)
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
    live = _judge(checked, left, params, ref_cfg)
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
    counted, scan_work = ({}, None) if not ctx.trace else _counter_values(
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
        if scan_work is not None:
            least, bound = costs.roofline_seconds(*scan_work, ctx.peaks)
            work_done["ssm_chunk_scan"] = {"least_s": least, "bound": bound}
            counted["ssm_scan_time_share"] = 100.0 * trace.matching(
                red, SCAN_KERNEL) / red["busy_s"]

    # correct, part (c): what the slots held of each kind of state
    alloc = srv.allocator
    held_decoding, held_chunk = model.window_pages(
        int(serving["kv_block_size"]), int(serving["prefill_chunk_tokens"]))
    held_after = alloc.num_used_by_kind()
    window_ok = (alloc.window_held_max["decode"] <= held_decoding
                 and alloc.window_held_max["chunk"] <= held_chunk
                 and not any(held_after.values()))
    ok = (_within_limits(quiet) and _within_limits(live) and window_ok
          and failed == 0 and len(judged) > 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)

    running = c.it_running[its][in_w]
    page_bytes = (int(serving["kv_block_size"]) * 2 * mc.kv_heads * mc.hdim
                  * jnp.dtype(eng_cfg["dtype"]).itemsize)
    one_state = costs_hybrid.state_bytes(
        mc.ssm_layers, mc.d_inner, mc.ssm_state, mc.ssm_conv,
        jnp.dtype(eng_cfg["dtype"]).itemsize)
    state_held = running.mean() * one_state
    pages_held = (c.it_blocks[its][in_w].mean()
                  + c.it_wblocks[its][in_w].mean() * mc.pairs_self
                  ) * page_bytes
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
            (c.it_wblocks[its][in_w] / np.maximum(running, 1)).mean()),
        "state_bytes_share": float(
            100.0 * state_held / (state_held + pages_held)),
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
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {**quiet, "live": live, "controls": controls,
                 "window_blocks_held": dict(alloc.window_held_max),
                 "held_after_drain": held_after,
                 "kv_pool_bytes": int(srv.kv_pool_bytes),
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(alloc.num_used),
                 "paged_bound": work_done.get("paged_attention",
                                              {}).get("bound"),
                 "ssm_scan_bound": work_done.get("ssm_chunk_scan",
                                                 {}).get("bound"),
                 "itl_ms": _profile(gaps_in), "ttft_ms": _profile(ttft),
                 **{k: values[k] for k in (
                     "queue_depth_first_fifth", "queue_depth_last_fifth",
                     "requests_per_s_completed", "ttft_mean_ms",
                     "batch_occupancy", "kv_pool_occupancy",
                     "kv_blocks_held_max", "window_blocks_per_slot",
                     "state_bytes_share")},
                 **counted},
    }
