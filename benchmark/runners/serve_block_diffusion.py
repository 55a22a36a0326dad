"""A serving cell of a model generated by diffusion over blocks
(``sdar-30b-a3b-chat``): the closed loop with its lead-in and every stamp
are ``runners/serve.py``'s and ``serve_sparse_latent.py``'s, the weights'
filler (from the seed, in the served type, a layer at a time) and the
routing values ``serve_latent.py``'s; this file repeats only what must
differ — the build (another builder, the configuration's own keys), a
client that counts tokens by COMMITS (a block's tokens become visible at
once, at its commit; TTFT is the first commit) and the block lane's
attention work (``lib/costs_block_diffusion.py``), and the reference check
(``lib/reference_sdar.py``), which judges a TRAJECTORY of forwards, not a
left-to-right chain."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, costs_block_diffusion, device, model as model_lib,
                   reference_sdar as reference, stats, traffic)
from .serve import Client, _profile, clock, stop_trace
from .serve_latent import _routing_values, serving_weights
from .serve_sparse_latent import _closed_loop

#: Three check requests' recorded forwards, replayed by the float32
#: reference over what the engine was fed (``reference.replay``), on the
#: quiet engine before the window and again at its close beside 32-36 live
#: slots.  (a) ``logit_gap_worst``: over every filled row, the reference's
#: best logit minus its logit of the engine's token — sound 0.000-0.055
#: (90 readings over 30 seeds; a bfloat16 activation flips a near-tie now
#: and then); every control that moves it moves another number further
#: (the lowest: ``shift`` 0.113-0.589, ``renorm`` 0.000-0.141, the program
#: fault 0.116-0.760), so the limit sits between the sound tail and
#: ``qk_norm``'s lowest, 0.37.  (b) ``order_gap_worst``: over every
#: denoise forward, the reference's log confidence of the best masked row
#: the engine did NOT fill minus that of the worst it did (at most 0 where
#: they agree) — sound -0.010 to 0.044, ``shift`` 0.147 / 0.191 / 0.349.
#: (c) the kept k / v rows read back from the pool through the table the
#: request had, against the reference's over the FINAL tokens, norm of the
#: difference over the norm: the first request's first layer
#: (``kv_first_rel_err``: sound 0.00306-0.00307, ``float8`` 0.047,
#: ``commit`` 0.155-0.168) and all 48 (``kv_rel_err``: sound
#: 0.0153-0.0195, ``commit`` 0.054-0.056, ``renorm`` 0.072-0.074), and all
#: 48 of the SHORT request's (``kv_short_rel_err``: sound 0.0123-0.0203,
#: ``renorm`` 0.064, the program fault ``causal_block`` 0.215-0.291 — the
#: one number that parts it from a sound run on every seed — ``commit``
#: 0.60).  (d) ``expert_rel_err``: the first layer's expert sublayer at the
#: timed row count — sound 0.00347-0.00352, ``float8`` 0.304-0.309,
#: ``renorm`` 2.76-2.87.  Each limit lies between the sound readings and
#: the nearest control's, near their geometric middle.  NOT refused:
#: ``bf16_softmax`` (it reads as a sound run in every number: 0.014-0.019,
#: 0.007-0.024, 0.0031, 0.0176-0.0187, 0.0151, 0.0035).  ``PERF.md``
#: section 4 has every reading and its seeds (my chip runs, PR 58).
LIMITS = {"logit_gap_worst": 0.15,
          "order_gap_worst": 0.08,
          "kv_first_rel_err": 0.012,
          "kv_rel_err": 0.032,
          "kv_short_rel_err": 0.05,
          "expert_rel_err": 0.033}
#: prompts across a chunk boundary, not multiples of the block length — and
#: a SHORT one: over a context of 8-32 tokens a block's own rows are a
#: tenth to a half of what a row sees, so how they see each other shows in
#: what the request leaves (over a thousand tokens four rows are lost in
#: the rounding: the fault ``causal_block`` read as a sound run there)
CHECK_REQUESTS = ((1001, 24), (610, 24), (6, 24))
#: whose kept k / v are read back: the first's and the short one's
KEPT = (0, 2)
#: what the reference can be made to lack (``reference_leaves_out`` /
#: ``controls``, never a cell's; ``lib/reference_sdar.py`` says what each
#: changes): every one reads ``correct`` false
CONTROLS = reference.CONTROLS
#: a fault put INTO the program (``program_fault``, never a cell's): the
#: block lane run causally (a row sees its block only up to itself)
PROGRAM_FAULTS = ("causal_block",)

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "kv_heads", "head_dim": "hdim",
             "intermediate_size": "ff_dim",
             "moe_intermediate_size": "expert_d_ff",
             "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "num_experts_per_tok": "moe_topk",
             "norm_topk_prob": "norm_topk_prob",
             "rope_theta": "rotary_base", "rms_norm_eps": "layernorm_eps",
             "hidden_act": "activation", "attention_bias": "use_bias",
             "tie_word_embeddings": "tie_embeddings"}
#: the parameters at the published widths and 16 of 128 experts a layer
NUM_PARAMS = 5_164_972_032


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
                   head_dim=mc.hdim, experts=mc.n_routed_experts,
                   topk=mc.moe_topk, block_length=mc.block_length,
                   mask_token_id=mc.mask_token_id)
        return mc, ref, mc.held
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(num_experts=mc.held[1] - mc.held[0])
    want = {k: config[k] for k in built}
    gen = config["generation"]
    flags = (mc.n_routed_experts, mc.block_length, mc.mask_token_id,
             mc.rotary_interleaved, mc.norm_type)
    if built != want or mc.num_params() != NUM_PARAMS or flags != (
            config["published"]["num_experts"], gen["block_length"],
            gen["mask_token_id"], False, "rmsnorm"):
        raise ValueError(f"the program built {built} with {flags} "
                         f"({mc.num_params():,} parameters), the "
                         f"configuration file says {want} ({NUM_PARAMS:,})")
    return mc, ref, mc.held


def _with_fault(fault: str) -> None:
    """Put ``fault`` into the program, for every step built afterwards:
    the control on the PROGRAM's side.  ``causal_block``: the block lane's
    rows see their own block only up to themselves."""
    from deepspeed_tpu.ops.transformer import paged_decode_attention as pda
    if fault not in PROGRAM_FAULTS:
        raise ValueError(f"program_fault {fault!r} is none of "
                         f"{PROGRAM_FAULTS}")

    def causal_block(q, pool_k, pool_v, base, active, block_tables,
                     sm_scale=None, interpret=None, pages_per_program=None):
        import jax.numpy as jnp
        c = q.shape[1]
        base = jnp.asarray(base, jnp.int32).reshape(q.shape[0])
        total = jnp.where(jnp.asarray(active).reshape(q.shape[0]) > 0,
                          base + c, 0)
        return pda._paged_attention(
            q, pool_k, pool_v, base, total, block_tables, sm_scale=sm_scale,
            interpret=interpret, k_scale=None, v_scale=None, kv_bits=0,
            pages_per_program=pages_per_program,
            what="paged_block_attention").astype(q.dtype)
    pda.paged_block_attention = causal_block


def _serve_check_requests(srv, vocab, seed, shrink, stream=0):
    """Two seeded prompts through the block-causal chunk lane and the
    block lane of the engine the window uses, beside whatever else it is
    serving, every forward recorded: ``(the finished requests, the kept
    k / v of those ``KEPT`` names — every layer's rows read back from the
    pool through the table the request had, in the iteration it finished
    in: the one in flight was planned before its pages were freed, so
    nothing has written to them yet — the fewest slots that were live
    meanwhile)``.  Returns once
    both have finished; what else runs goes on running."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.serving import RequestState
    rng = np.random.default_rng([int(seed), 0xC4EC + stream])
    reqs = [srv.submit(rng.integers(0, vocab, max(6, p // shrink)),
                       max_new_tokens=n, record_blocks=True)
            for p, n in CHECK_REQUESTS]
    kept = {id(reqs[at]): at for at in KEPT}
    # (one gather: a slice of the pool alone would copy the whole pool)
    pages = jax.jit(lambda pool, table: pool[:, table])
    tables, left = {}, {}
    least = srv.num_slots
    while any(r.state is not RequestState.FINISHED for r in reqs):
        if srv.scheduler.active_slots:
            least = min(least, srv.scheduler.active_slots)
        more = srv.step()
        for r in srv.scheduler.running.values():
            if id(r) in kept:
                tables[kept[id(r)]] = srv.allocator.block_table(r.req_id)
        for at, table in tables.items():
            r = reqs[at]
            if at not in left and r.state is RequestState.FINISHED:
                left[at] = [pages(pool, jnp.asarray(table)).reshape(
                    pool.shape[0], -1, pool.shape[-1])[:, :r.cached_tokens]
                    for pool in (srv._pool_k, srv._pool_v)]
        if not more:
            break
    return reqs, left if len(left) == len(KEPT) else None, least


def _check_experts(model, params, ref_cfg, held, seed, rows):
    """What two prompts' logits cannot see: the held experts and their
    grouped product.  The first layer's expert sublayer over ``rows``
    seeded rows (the mixed program's row count) on the timed weights — the
    program's router and ``expert_share``, whose kernel reads that layer's
    experts where they lie in the stack, as the step does — against the
    reference's loop over experts: the norm of the difference over the
    norm of the reference's."""
    import jax
    import jax.numpy as jnp
    moe = params["blocks"]["moe"]
    experts = moe["experts"]
    router = {"router": moe["router"]}
    u = jax.random.normal(
        model_lib.seed_key(int(seed) + 0xE4),
        (rows, model.config.d_model)).astype(experts["w_up"].dtype)

    def layer0(tree):
        return jax.tree_util.tree_map(lambda a: a[0], tree)

    def program(router, experts, u):
        return model._moe_sublayer(
            layer0(router), u[None], stack=(experts, jnp.int32(0))
        )[0][0].astype(jnp.float32)

    def plain(router, experts, u):
        def at(i):
            return reference._weights({n: w[0, i]
                                       for n, w in experts.items()}, ref_cfg)
        with jax.default_matmul_precision("highest"):
            return reference.routed(
                reference._weights(layer0(router), ref_cfg),
                u.astype(jnp.float32), ref_cfg, held, at)
    got = jax.jit(program)(router, experts, u)
    want = jax.jit(plain)(router, experts, u)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _judge(reqs, left, params, ref_cfg, held, engine_cfg) -> dict:
    """The reference replays every forward the engine recorded for the
    check requests over what the engine was fed, and what it keeps over
    the same final tokens judges what the first request left in the
    pool."""
    import jax.numpy as jnp
    steps, rule, threshold = engine_cfg
    out = {k: math.inf for k in LIMITS if k != "expert_rel_err"}
    out.update(argmax_share=0.0, rows_agree=0.0, forwards=0)
    if left is None or any(len(r.output) != r.max_new_tokens for r in reqs):
        return out
    b = ref_cfg["block_length"]
    pad = max(-(-(len(r.prompt) + r.max_new_tokens) // b) * b for r in reqs)
    worst = {"logit_gap_worst": 0.0, "order_gap_worst": -math.inf}
    shares = []
    for k, r in enumerate(reqs):
        path = [(a, ph, list(toks)) for a, ph, toks in r.block_steps]
        got = reference.replay(params, r.prompt, path, ref_cfg, held, steps,
                               rule, threshold, pad_to=pad)
        final = list(r.prompt) + list(r.output)
        if got["final"][:len(final)] != final:
            return out
        for name in worst:
            worst[name] = max(worst[name], got[name])
        shares.append((got["argmax_share"], got["rows_agree"], len(path)))
        if k in KEPT:
            want = reference.kept_kv(params, r.prompt, path, ref_cfg, held)
            rows = left[k][0].shape[1]
            # (a layer at a time: nothing the size of the kept rows twice)
            diff, norm = np.zeros(want[0].shape[0]), np.zeros(
                want[0].shape[0])
            for got_pool, want_pool in zip(left[k], want):
                for at in range(want_pool.shape[0]):
                    w = want_pool[at, :rows]
                    diff[at] += float(jnp.sum(
                        (got_pool[at].astype(jnp.float32) - w) ** 2))
                    norm[at] += float(jnp.sum(w ** 2))
            every = math.sqrt(diff.sum() / max(norm.sum(), 1e-30))
            if k == 0:
                out.update(kv_first_rel_err=math.sqrt(
                    diff[0] / max(norm[0], 1e-30)), kv_rel_err=every)
            else:
                out.update(kv_short_rel_err=every)
            del want
    out.update(worst,
               argmax_share=float(np.mean([s[0] for s in shares])),
               rows_agree=float(np.mean([s[1] for s in shares])),
               forwards=int(sum(s[2] for s in shares)))
    return out


def _within_limits(numbers: dict) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items()
               if k in numbers)


class BlockClient(Client):
    """``Client`` for generation by diffusion over blocks.  A request's
    output grows by a block's tokens at its commit, which the inherited
    stamps take as they come (``it_tokens``: tokens made visible; the
    first growth is the first commit, TTFT); the attention work is counted
    here: every block-lane forward a request rode (``block_forwards``)
    over its context, and every prompt chunk, block-causal."""

    def __init__(self, srv, work, model_config, trace_on, overlap):
        super().__init__(srv, work, model_config, trace_on, overlap)
        self.block_rows = model_config.block_length
        self.seen_forwards = np.zeros(len(work["prompts"]), np.int64)
        self.walked = np.zeros(len(work["prompts"]), np.int64)

    def iterate(self) -> tuple:
        live = list(self.live)
        k = self.n_it
        out = super().iterate()
        b = self.block_rows
        flops = nbytes = 0.0
        for i in live:
            r = self.reqs[i]
            cached = r.cached_tokens
            forwards = r.block_forwards - self.seen_forwards[i]
            grown = cached - self.walked[i]
            if forwards > 0:
                # a commit moved the rows by a block: its context ends at
                # them; a denoise forward's a block past them
                commits = min(forwards, grown // b) if grown > 0 else 0
                prefilled = grown - commits * b
                for n, context in ((commits, cached),
                                   (forwards - commits, cached + b)):
                    if n:
                        f, by = costs_block_diffusion.block_forward_cost(
                            context, b, *self.attn)
                        flops += n * f
                        nbytes += n * by
            else:
                prefilled = grown
            if prefilled > 0:
                f, by = costs_block_diffusion.block_causal_chunk_cost(
                    cached, prefilled, b, *self.attn)
                flops += f
                nbytes += by
            self.seen_forwards[i] = r.block_forwards
            self.walked[i] = cached
        self.it_flops[k] = flops * self.layers
        self.it_bytes[k] = nbytes * self.layers
        return out


def _block_values(overlap, mc, window) -> dict:
    """What the program counted of its block lane and its expert layers
    over the window's iterations.  A program that keeps no such counters
    gives nothing."""
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "block_rows" not in names:
        return {}
    values = {"chunk_dispatch_share": 100.0 * float(
        (recs["chunk_rows"] > 0).sum()) / len(recs)}
    tokens = float(recs["block_tokens"].sum())
    if tokens > 0:
        values["block_rows_per_token"] = float(recs["block_rows"].sum()) \
            / tokens
        values["block_commit_share"] = 100.0 * mc.block_length * float(
            recs["block_commits"].sum()) / float(recs["block_rows"].sum())
    lo, hi = mc.held
    held = float(recs["moe_picks_held"].sum())
    places = float(recs["dispatches"].sum()) * mc.num_layers * (hi - lo)
    if places > 0 and held > 0:
        values.update(
            moe_rows_per_expert=held / places,
            moe_touched_share=100.0 * float(
                recs["moe_experts_touched"].sum()) / places)
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
    if mix.get("program_fault"):
        _with_fault(mix["program_fault"])
    model = build_model(mc)
    shrink = int(ctx.tiny["shrink"]) if ctx.tiny else 1
    eng_cfg = dict(mix["engine"])
    serving = dict(eng_cfg.pop("serving"), enabled=True,
                   mesh={"data": 1, "model": 1})
    if ctx.tiny:
        serving["num_kv_blocks"] = int(ctx.tiny["num_kv_blocks"])
        eng_cfg["max_out_tokens"] = int(ctx.tiny["model"]["max_seq_len"])
        eng_cfg["dtype"] = "float32"
    # the traffic draws prompt ids below the tokenizer's first special
    # token, so the mask token never stands in a prompt
    vocab = min(int(ctx.config["generation"]["prompt_ids_below"]),
                mc.vocab_size - 1 if ctx.tiny else mc.vocab_size)
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
    engine_cfg = (srv.denoising_steps, srv.unmask_rule,
                  srv.confidence_threshold)

    # correct on the quiet engine (and the warm-up of both step shapes)
    ref_cfg["without"] = tuple(mix.get("reference_leaves_out", ()))
    checked, left, _ = _serve_check_requests(srv, vocab, ctx.seed, shrink)
    while srv.step():
        pass
    rows = int(serving["max_batch_slots"]) * mc.block_length \
        + int(serving["prefill_chunk_tokens"])
    quiet = _judge(checked, left, params, ref_cfg, held, engine_cfg)
    quiet["expert_rel_err"] = _check_experts(model, params, ref_cfg, held,
                                             ctx.seed, rows)
    # the limits' seating only: the same served forwards against a
    # reference that lacks one mechanism at a time
    controls = {}
    for name in mix.get("controls", ()):
        lacking = dict(ref_cfg, without=(name,))
        controls[name] = dict(
            _judge(checked, left, params, lacking, held, engine_cfg),
            expert_rel_err=_check_experts(model, params, lacking, held,
                                          ctx.seed, rows))
        # what ``correct`` would read had the reference lacked it
        controls[name]["correct"] = _within_limits(controls[name])
    del checked, left
    held_bytes["checked"] = _live_bytes()
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    work = traffic.requests(mix, ctx.seed, vocab)
    if shrink > 1:
        work["max_new"] = np.maximum(2, work["max_new"] // shrink)
        work["prompts"] = [p[:max(2, len(p) // shrink)]
                           for p in work["prompts"]]
    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = BlockClient(srv, work, mc, ctx.trace, overlap)
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
    # beside what the window left generating
    in_slots = {id(r) for r in srv.scheduler.running.values()}
    stay = sorted((i for i in c.live if id(c.reqs[i]) in in_slots),
                  key=lambda i: c.reqs[i].max_new_tokens
                  - len(c.reqs[i].output))
    for i in set(c.live) - set(stay[len(CHECK_REQUESTS):]):
        srv.cancel(c.reqs[i])
    checked, left, least = _serve_check_requests(srv, vocab, ctx.seed,
                                                 shrink, stream=1)
    for i in stay:                   # in flight at the close: cancelled
        srv.cancel(c.reqs[i])
    while srv.step():
        pass
    live = _judge(checked, left, params, ref_cfg, held, engine_cfg)
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
        counted = _block_values(overlap, mc, (w0, w1))
        routing, moe_work = _routing_values(overlap, mc, (w0, w1),
                                            ctx.trace_started_at)
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

    ok = (_within_limits(quiet) and _within_limits(live)
          and srv.allocator.num_used == 0
          and failed == 0 and len(judged) > 0 and compiles_in_window == 0
          and srv.decode_builds == builds_before)

    values = {
        "setup_s": setup_s,
        "batch_occupancy": 100.0 * c.it_running[its][in_w].mean() / slots,
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
        **{k: v for k, v in routing.items()
           if k not in ("moe_zero_share", "latent_tokens_read_per_s")},
        **counted,
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
        # the pool is [layers, blocks, kv_block_size, kv_heads * head dim]
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {**quiet, "live": live, "controls": controls,
                 "limits": LIMITS, "live_bytes_after": held_bytes,
                 "kv_pool_bytes": int(srv.kv_pool_bytes),
                 "block_counts": dict(srv.block_counts),
                 "flight_counts": dict(srv.flight_counts),
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(srv.allocator.num_used),
                 "paged_bound": work_done.get("paged_attention",
                                              {}).get("bound"),
                 "moe_bound": work_done.get("moe_grouped_matmul",
                                            {}).get("bound"),
                 "itl_ms": _profile(gaps_in), "ttft_ms": _profile(ttft),
                 **{k: values[k] for k in (
                     "queue_depth_first_fifth", "queue_depth_last_fifth",
                     "requests_per_s_completed", "ttft_mean_ms",
                     "batch_occupancy", "kv_pool_occupancy",
                     "kv_blocks_held_max")},
                 **routing, **counted},
    }
