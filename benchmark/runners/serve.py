"""A serving cell: the benchmark's own client drives ``submit()`` /
``step()`` of the paged serving engine, closed loop or open loop, and takes
every time itself.  After each ``step()`` returns (the engine has pulled the
sampled ids to the host) the client stamps the clock once and records which
requests grew.  Nothing is read from the program's histograms.  The stamps
are explained in ``benchmark/README.md``."""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from deepspeed_tpu.observability.overlap import ITERATION_SPAN, PHASE_SPANS

from ..lib import (costs, device, model as model_lib, reference, stats,
                   traffic)

#: serving check: each token the engine chose greedily, through chunked
#: prefill and paged decode in bf16, must be within this of the float32
#: reference's best logit at that position.  Logits of the seeded weights
#: spread over about +-4; with bf16 rounding the engine's choice sat at most
#: 0.02 under the reference's best and was the same token in 90-100 % of
#: positions (my chip runs, PR 23); a wrong cache row or position moves the
#: choice to a token several units below the best, and 8-bit matmuls move
#: logits by tenths.
LOGIT_GAP_ATOL = 0.05
CHECK_REQUESTS = ((333, 24), (200, 24))      # (prompt, new) tokens
#: the client's own spans, which set the traced window, and every span an
#: idle gap can be booked to: the client's, and inside ``serve_step`` the
#: engine's iteration and its five phases
CLIENT_SPANS = ("serve_step", "plan_submit")
SPANS = CLIENT_SPANS + (ITERATION_SPAN,) + PHASE_SPANS


def stop_trace(ctx) -> dict:
    """The traced window's numbers, its bounds set by the client's spans."""
    return ctx.stop_trace(SPANS, CLIENT_SPANS)


CAP_IT = 1 << 16
CAP_GAPS = 1 << 21
clock = time.perf_counter


def _check_against_reference(srv, params, ref_cfg, vocab, seed, shrink):
    """Two seeded prompts through chunked prefill and paged decode; the
    reference's full forward over prompt + output judges every token.
    Returns the worst gap to the reference's best logit and the share of
    positions where the token is the reference's argmax."""
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
    lg = np.asarray(jax.jit(lambda p, i: reference.logits(p, i, ref_cfg))(
        params, jnp.asarray(ids)))
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


def _profile(series) -> dict:
    """A series' order statistics, for the record beside the judged ones."""
    if len(series) == 0:
        return {}
    out = {f"p{q}": stats.finite_ms(stats.order_stat(series, q / 100))
           for q in (50, 90, 95, 99, 99.9)}
    out["mean"] = stats.finite_ms(float(np.mean(series)))
    out["over_1.5x_median_share"] = float(np.mean(
        np.asarray(series) > 1.5 * np.median(series)))
    return out


class Client:
    """The load generator and its stamps.  Every buffer the window writes is
    allocated here, before it."""

    def __init__(self, srv, work, model_config, trace_on, overlap):
        from deepspeed_tpu.inference.serving import (RequestState,
                                                     RequestStatus)
        self.srv, self.work = srv, work
        self.waiting, self.finished = RequestState.WAITING, \
            RequestState.FINISHED
        self.ok_status = RequestStatus.OK
        self.trace_on, self.overlap = trace_on, overlap
        mc = model_config
        self.attn = (mc.num_heads, mc.kv_heads, mc.hdim)
        self.layers = mc.num_layers
        n = len(work["prompts"])
        self.total = n
        z = np.zeros
        self.it_start, self.it_end, self.it_tokens = z(CAP_IT), z(CAP_IT), \
            z(CAP_IT)
        self.it_running, self.it_queue, self.it_blocks = z(CAP_IT), \
            z(CAP_IT), z(CAP_IT)
        self.it_plan, self.it_total = z(CAP_IT), z(CAP_IT)
        self.it_flops, self.it_bytes = z(CAP_IT), z(CAP_IT)
        self.gap_end, self.gap_ms = z(CAP_GAPS), z(CAP_GAPS)
        self.due_t, self.submit_t, self.admit_t = z(n), z(n), z(n)
        self.first_t, self.done_t, self.last_tok_t = z(n), z(n), z(n)
        self.seen_out = z(n, np.int64)
        self.seen_cached = z(n, np.int64)
        self.ok_full = z(n, bool)
        self.reqs = [None] * n
        self.live = []
        self.n_it = self.n_gap = self.n_sub = self.n_first = 0

    def submit(self, due: float) -> None:
        """Submit the next request of the mix, due at ``due``."""
        i = self.n_sub
        if i == self.total:
            raise RuntimeError("the traffic file holds too few requests")
        self.due_t[i] = due
        self.reqs[i] = self.srv.submit(
            self.work["prompts"][i],
            max_new_tokens=int(self.work["max_new"][i]), eos_token_id=None)
        self.submit_t[i] = clock()
        self.live.append(i)
        self.n_sub += 1

    def iterate(self) -> tuple:
        """One engine iteration and its stamps: ``(end stamp, requests that
        finished in it)``."""
        import jax
        srv = self.srv
        ts = clock()
        with jax.profiler.TraceAnnotation("serve_step"):
            srv.step()
        te = clock()
        tokens = flops = nbytes = 0.0
        finished = 0
        for i in self.live:
            r = self.reqs[i]
            if self.admit_t[i] == 0.0 and r.state is not self.waiting:
                self.admit_t[i] = ts
            grown = len(r.output) - self.seen_out[i]
            if grown > 0:
                if self.seen_out[i] == 0:
                    self.first_t[i] = te
                    self.n_first += 1
                else:
                    self.gap_end[self.n_gap] = te
                    self.gap_ms[self.n_gap] = (te - self.last_tok_t[i]) * 1e3
                    self.n_gap += 1
                self.last_tok_t[i] = te
                self.seen_out[i] += grown
                tokens += grown
            rows = r.cached_tokens - self.seen_cached[i]
            if rows > 0:        # the attention this request needed just now
                f, b = costs.paged_attention_cost(r.cached_tokens, rows,
                                                  *self.attn)
                flops += f
                nbytes += b
            self.seen_cached[i] = r.cached_tokens
            if r.state is self.finished:
                self.done_t[i] = te
                self.ok_full[i] = (r.status is self.ok_status
                                   and len(r.output) == r.max_new_tokens)
                finished += 1
        if finished:
            self.live[:] = [i for i in self.live
                            if self.reqs[i].state is not self.finished]
        k = self.n_it
        self.it_start[k], self.it_end[k], self.it_tokens[k] = ts, te, tokens
        self.it_running[k] = srv.scheduler.active_slots
        self.it_queue[k] = srv.scheduler.queue_depth
        self.it_blocks[k] = srv.allocator.num_used
        self.it_flops[k] = flops * self.layers
        self.it_bytes[k] = nbytes * self.layers
        self.n_it += 1
        return te, finished


def _closed_loop(ctx, client, slots) -> tuple:
    """Every client submits its next request the moment its last completes;
    the window opens once every slot has been filled once and is decoding.
    Returns ``(w0, w1, setup_s, tracing)``."""
    import jax
    t_start = clock()
    for _ in range(int(ctx.mix["clients"])):
        client.submit(t_start)
    w0 = w1 = setup_s = None
    tracing = False
    trace_s = float(ctx.mix["trace_seconds"])
    while True:
        te, finished = client.iterate()
        if w0 is None and client.n_first >= slots:
            w0, w1 = te, te + ctx.seconds
            setup_s = device.process_age_s()
        if w0 is not None:
            if te >= w1:
                return w0, w1, setup_s, tracing
            if ctx.trace and not tracing and te >= w1 - trace_s:
                ctx.start_trace()
                tracing = True
        if finished:
            with jax.profiler.TraceAnnotation("plan_submit"):
                for _ in range(finished):
                    client.submit(te)


def _open_loop(ctx, client, shrink) -> tuple:
    """Arrivals on the mix's schedule, whatever the engine does; they start
    ``lead_in_s`` before the window, so that it opens on a system already in
    its steady state."""
    import jax
    t_start = clock()
    w0 = t_start + float(ctx.mix["lead_in_s"]) / shrink
    w1 = w0 + ctx.seconds
    due = np.append(client.work["due"] + t_start, math.inf)   # sentinel
    setup_s = None
    tracing = False
    trace_s = float(ctx.mix["trace_seconds"])
    while True:
        now = clock()
        if setup_s is None and now >= w0:
            setup_s = device.process_age_s() - (now - w0)
        if now >= w1:
            return w0, w1, setup_s, tracing
        if ctx.trace and not tracing and now >= w1 - trace_s:
            ctx.start_trace()
            tracing = True
        with jax.profiler.TraceAnnotation("plan_submit"):
            while due[client.n_sub] <= now and due[client.n_sub] < w1:
                client.submit(due[client.n_sub])
        if client.live:
            client.iterate()
        else:
            time.sleep(min(0.002, max(0.0, min(due[client.n_sub], w1) - now)))


def run(ctx) -> dict:
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.observability.overlap import get_overlap_profiler

    mix = ctx.mix
    mc, ref_cfg = model_lib.build(ctx.config, ctx.tiny)
    model = TransformerLM(mc)
    shrink = int(ctx.tiny["shrink"]) if ctx.tiny else 1
    eng_cfg = dict(mix["engine"])
    serving = dict(eng_cfg.pop("serving"), enabled=True,
                   mesh={"data": 1, "model": 1})
    if ctx.tiny:
        serving["num_kv_blocks"] = int(ctx.tiny["num_kv_blocks"])
        eng_cfg["max_out_tokens"] = int(ctx.tiny["model"]["max_seq_len"])
        eng_cfg["dtype"] = "float32"
    params = model_lib.serving_weights(model, ctx.seed,
                                       jnp.dtype(eng_cfg["dtype"]))
    srv = ds.init_inference(model, dict(eng_cfg, serving=serving),
                            params=params).serving_engine()
    overlap = get_overlap_profiler()
    if ctx.trace:
        overlap.configure(enabled=True)

    # correct, part 1 (and the warm-up of the one mixed program)
    worst_gap, exact_share = _check_against_reference(
        srv, params, ref_cfg, mc.vocab_size, ctx.seed, shrink)
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    work = traffic.requests(mix, ctx.seed, mc.vocab_size)
    if shrink > 1:
        work["max_new"] = np.maximum(2, work["max_new"] // shrink)
        work["prompts"] = [p[:max(2, len(p) // shrink)]
                           for p in work["prompts"]]
    open_loop = mix["loop"] == "open"
    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = Client(srv, work, mc, ctx.trace, overlap)
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
        # after the window nothing more is submitted; step until every
        # request due inside it has its first token, under a limit
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
    work_done = {}
    if red:
        traced = it_start >= ctx.trace_started_at
        flops, nbytes = c.it_flops[its][traced], c.it_bytes[its][traced]
        work_done["paged_attention"] = {
            "least_s": sum(costs.roofline_seconds(f, b, ctx.peaks)[0]
                           for f, b in zip(flops, nbytes)),
            "bound": costs.roofline_seconds(flops.sum(), nbytes.sum(),
                                            ctx.peaks)[1]}
    ok = (worst_gap <= LOGIT_GAP_ATOL and failed == 0 and len(judged) > 0
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
                   "kv_row_width": int(mc.kv_heads * mc.hdim)},
        "diag": {"logit_gap_worst": worst_gap, "argmax_share": exact_share,
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
                     "batch_occupancy", "kv_pool_occupancy")}},
    }
