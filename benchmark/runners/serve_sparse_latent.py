"""A serving cell of the latent block with a learned sparse selection
(``glm-5.2``) under document sessions: the client, its stamps, the
weights, the routing and expert-layer values are ``runners/serve.py``'s,
``serve_latent.py``'s and ``serve_latent_sandwich.py``'s; this file
repeats only what must differ — the build (another builder, another set
of published keys), the traffic (documents prefilled once during set-up
through the normal ``submit`` path, then requests = a document from the
prefix cache + a fresh question), the closed loop's lead-in, the checks
against another reference (``lib/reference_glm_dsa.py``), the counts of
the two new kernels (``lib/costs_dsa.py``) and ``run()``."""
from __future__ import annotations

import gc
import math

import numpy as np

from ..lib import (costs, costs_dsa, device, model as model_lib,
                   reference_glm_dsa as reference, stats, traffic)
from .serve import Client, _profile, clock, stop_trace
from .serve_latent import _routing_values, serving_weights
from .serve_latent_sandwich import _expert_layer_values

#: serving check: each token the engine chose greedily, through chunked
#: prefill and paged decode in bf16 at contexts past ``index_topk``, must
#: be within this of the float32 reference's best logit at that position
#: — for two seeded prompts and for the longer one again from the prefix
#: cache.  Sound runs read up to 0.67 over 30 seeds (a bf16 activation
#: flips an 8th-against-9th pick now and then: the tail is heavy, as
#: openPangu's); what only the logits can see reads 3.10 or more: the
#: reference that attends to every earlier token 4.11 / 5.00, without
#: the relu 3.71 / 3.10, every index head weighing 1 6.18 / 5.05,
#: without the shared expert 3.82 / 4.18 (``PERF.md`` section 4).
LOGIT_GAP_ATOL = 1.5
#: the expert layer's check, as ``serve_latent_sandwich.py``'s: the error
#: of the first EXPERT layer's ``F_l`` at the timed row count over the
#: norm of the held experts' own part.  Sound runs read 0.0116-0.0124,
#: the gate picking by score alone (no bias) 0.131 / 0.177, the
#: reference in float8 0.530 / 0.539, the held experts left out 1.000.
EXPERT_REL_ERR_MAX = 0.05
#: the selection's check (with seeded weights attention is near uniform
#: and the logits barely see WHICH tokens were read): every layer's sets
#: as the served step attends them (``_served_selection``: chunk rows'
#: masks and decode rows' pool rows out of the mixed step, a ``shared``
#: layer's as handed on) inside the reference's mask of the same layer,
#: the least over the layers.  Two limits, by which ``full`` layer made
#: the set.  The FIRST ``full`` layer's (the embedding is exact on both
#: sides) and the layers it is handed to: sound runs read 0.99815-0.99816
#: (bf16 scores swap neighbours at the 2,048th place), the reference in
#: float8 0.9799, without the relu 0.8394, every head weighing 1 0.548-
#: 0.549, and where the reference's ``shared`` layers select for
#: themselves 0.5386-0.5388 in those layers.  A LATER ``full`` layer
#: scores activations that are four bfloat16 layers from the float32
#: reference's: sound 0.9634-0.9645, float8 0.882, no relu 0.7245, no
#: ``w`` 0.539-0.545 (``PERF.md`` section 4).
INDEX_OVERLAP_MIN = 0.99
INDEX_OVERLAP_DEEP_MIN = 0.93
CHECK_REQUESTS = ((6000, 24), (3500, 24))      # (prompt, new) tokens
OVERLAP_CONTEXT = 8192

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
             "index_n_heads": "index_n_heads",
             "index_head_dim": "index_head_dim", "index_topk": "index_topk",
             "rms_norm_eps": "layernorm_eps", "hidden_act": "activation",
             "attention_bias": "use_bias",
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
    kwargs["indexer_types"] = tuple(kwargs["indexer_types"])
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    ref = reference.settings(config)
    if tiny:
        ref.update(heads=mc.num_heads, kv_lora_rank=mc.kv_lora_rank,
                   qk_nope_head_dim=mc.qk_nope_head_dim,
                   qk_rope_head_dim=mc.qk_rope_head_dim,
                   v_head_dim=mc.v_head_dim, moe_topk=mc.moe_topk,
                   n_routed_experts=mc.n_routed_experts,
                   index_heads=mc.index_n_heads,
                   index_head_dim=mc.index_head_dim,
                   index_topk=mc.index_topk, indexer_types=mc.layer_kinds,
                   block=16)
        return mc, ref, mc.held
    built = {k: getattr(mc, attr) for k, attr in PUBLISHED.items()}
    built.update(n_routed_experts=mc.held[1] - mc.held[0],
                 indexer_types=list(mc.layer_kinds),
                 mlp_layer_types=["dense"] * mc.first_k_dense
                 + ["sparse"] * mc.scan_length,
                 qk_head_dim=mc.qk_nope_head_dim + mc.qk_rope_head_dim)
    want = {k: config[k] for k in built}
    gate = (mc.router_scoring, mc.router_bias, mc.zero_expert_num)
    if built != want or gate != (config["scoring_func"], True, 0) \
            or config["topk_method"] != "noaux_tc" or mc.rope_theta != \
            config["rope_parameters"]["rope_theta"] or \
            mc.n_routed_experts != config["published"]["n_routed_experts"]:
        raise ValueError(f"the program built {built} with the gate {gate}, "
                         f"the configuration file says {want}")
    return mc, ref, mc.held


# ---------------------------------------------------------------------------
# the traffic: documents, and sessions over them
# ---------------------------------------------------------------------------
class _Sessions:
    """The requests' prompts, made when asked for: document ``doc[i]``
    followed by request ``i``'s own question."""

    def __init__(self, docs, doc, questions):
        self.docs, self.doc, self.questions = docs, doc, questions

    def __len__(self):
        return len(self.doc)

    def __getitem__(self, i):
        return np.concatenate([self.docs[self.doc[i]], self.questions[i]])


def requests(mix: dict, seed: int, vocab_size: int, shrink: int = 1) -> dict:
    """The documents and the requests over them.  A block holds every
    (document, question length, output length) combination once, in an
    order from the seed that gives every seed's window the same work:
    ``lib/traffic.stratified_pairs`` at a block of ONE of each (question,
    output) pair, so every run of that many requests holds each pair
    once, and a pair's occurrences in a block take the documents in an
    order from the seed.  (Drawn over the whole block, as it first was,
    the window's edges cut a block anywhere, and each seed's window held
    another share of long questions and short answers.)"""
    rng = np.random.default_rng([int(seed), 0xD0C5])
    doc_lens = [max(16, n // shrink) for n in mix["doc_lens"]]
    docs = [rng.integers(0, vocab_size, n, dtype=np.int32) for n in doc_lens]
    block, blocks = int(mix["block"]), int(mix["blocks"])
    run = len(mix["prompt_lens"]) * len(mix["output_lens"])
    if block != len(docs) * run:
        raise ValueError(f"block {block} is not documents x questions x "
                         f"outputs")
    pairs = traffic.stratified_pairs(mix["prompt_lens"], mix["output_lens"],
                                     run, blocks * len(docs), rng)
    doc = np.zeros(len(pairs), np.int64)
    for b in range(blocks):
        at = slice(b * block, (b + 1) * block)
        for pair in np.unique(pairs[at], axis=0):
            same = np.flatnonzero((pairs[at] == pair).all(axis=1))
            doc[at][same] = rng.permutation(len(docs))
    q_lens = np.maximum(2, pairs[:, 0] // shrink)
    flat = rng.integers(0, vocab_size, int(q_lens.sum()), dtype=np.int32)
    ends = np.cumsum(q_lens)
    questions = [flat[e - n:e] for e, n in zip(ends, q_lens)]
    return {"docs": docs, "doc": doc,
            "prompt_len": np.array(doc_lens)[doc] + q_lens,
            "max_new": np.maximum(2, pairs[:, 1] // shrink),
            "prompts": _Sessions(docs, doc, questions), "due": None}


def _fill(srv, docs) -> float:
    """Every document through the normal ``submit`` path once, one new
    token each: its blocks stay registered in the prefix cache.  Returns
    the seconds it took."""
    began = clock()
    reqs = [srv.submit(d, max_new_tokens=1) for d in docs]
    while srv.step():
        pass
    if any(len(r.output) != 1 for r in reqs):
        raise RuntimeError("a document's prefill did not complete")
    return clock() - began


class SparseClient(Client):
    """``Client`` with the new kernels' work counted a request that grew
    (``lib/costs_dsa.py``): the indexer's scores once a ``full`` layer,
    and attention over the selected tokens once a layer — a chunk's rows,
    which the Pallas sparse kernel serves, apart from a decode row, whose
    tokens are gathered by token."""

    def __init__(self, srv, work, model_config, trace_on, overlap):
        super().__init__(srv, work, model_config, trace_on, overlap)
        mc = model_config
        self.index = (mc.index_n_heads, mc.index_head_dim)
        self.selected = (mc.index_topk, mc.num_heads, mc.kv_lora_rank,
                         mc.qk_rope_head_dim)
        self.full_layers, self.all_layers = mc.full_layers, mc.num_layers
        self.walked = np.zeros(len(work["prompts"]), np.int64)
        z = np.zeros
        self.it_index = z((len(self.it_flops), 2))
        self.it_sparse = z((len(self.it_flops), 2))
        self.it_gather = z((len(self.it_flops), 2))

    def iterate(self) -> tuple:
        live = list(self.live)
        k = self.n_it
        out = super().iterate()
        index, sparse, gather = np.zeros(2), np.zeros(2), np.zeros(2)
        for i in live:
            r = self.reqs[i]
            cached = r.cached_tokens
            rows = cached - max(self.walked[i], r.cache_hit_tokens)
            if rows > 0:
                index += costs_dsa.index_scores_cost(cached, rows,
                                                     *self.index)
                lane = sparse if rows > 1 else gather
                lane += costs_dsa.selected_attention_cost(
                    cached, rows, *self.selected)
            self.walked[i] = cached
        self.it_index[k] = index * self.full_layers
        self.it_sparse[k] = sparse * self.all_layers
        self.it_gather[k] = gather * self.all_layers
        return out


def _closed_loop(ctx, client, slots) -> tuple:
    """``serve.py``'s closed loop with a lead-in: the window opens
    ``lead_in_s`` after every slot has been filled once and is decoding.
    Returns ``(w0, w1, setup_s, tracing)``."""
    import jax
    t_start = clock()
    for _ in range(int(ctx.mix["clients"])):
        client.submit(t_start)
    opens = w0 = w1 = setup_s = None
    tracing = False
    trace_s = float(ctx.mix["trace_seconds"])
    lead_in = float(ctx.mix["lead_in_s"]) / (
        int(ctx.tiny["shrink"]) if ctx.tiny else 1)
    while True:
        te, finished = client.iterate()
        if opens is None and client.n_first >= slots:
            opens = te + lead_in
        if w0 is None and opens is not None and te >= opens:
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


# ---------------------------------------------------------------------------
# correct
# ---------------------------------------------------------------------------
def _judge(reqs, logits_of) -> tuple:
    """``(worst gap to the reference's best logit, share of positions
    where the token is its argmax)`` over the requests' tokens."""
    worst, exact, n = 0.0, 0, 0
    for r in reqs:
        if len(r.output) != r.max_new_tokens:
            return math.inf, 0.0
        lg = logits_of(r)
        for j, tok in enumerate(r.output):
            at = lg[j]
            worst = max(worst, float(at.max() - at[tok]))
            exact += int(at.argmax() == tok)
            n += 1
    return worst, exact / n


def _check_against_reference(srv, params, ref_cfg, held, vocab, seed, shrink,
                             leave_out=()):
    """Two seeded prompts past ``index_topk`` through chunked prefill and
    paged decode, then the longer one again, now a prefix-cache hit; the
    reference's full forward over prompt + output, with the same share of
    the experts, judges every token of the three: ``(worst gap, argmax
    share, the resubmitted prompt's cache-hit tokens)``.  The reference
    runs one sequence at a time, padded to one length (causal: padding is
    inert), and gives the logits of the last positions only."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 0xC4EC])
    prompts = [rng.integers(0, vocab, max(2, p // shrink))
               for p, _ in CHECK_REQUESTS]
    new = CHECK_REQUESTS[0][1]
    reqs = [srv.submit(p, max_new_tokens=new) for p in prompts]
    while srv.step():
        pass
    reqs.append(srv.submit(prompts[0], max_new_tokens=new))
    while srv.step():
        pass
    width = max(len(p) for p in prompts) + new
    forward = jax.jit(lambda p, i: reference.logits(p, i, ref_cfg, held,
                                                    leave_out))

    def logits_of(r):
        seq = list(r.prompt) + list(r.output)
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        lg = forward(params, jnp.asarray(ids))[0]
        first = len(r.prompt) - 1
        return np.asarray(lg[first:first + len(r.output)])
    worst, exact = _judge(reqs, logits_of)
    return worst, exact, int(reqs[-1].cache_hit_tokens)


def _served_selection(model, params, seed, shrink, slots, chunk) -> dict:
    """WHICH tokens every layer of the served step attends.  A seeded
    sequence of ``OVERLAP_CONTEXT`` tokens goes through the model's own
    mixed step — ``_apply_paged_mixed``, the function the engine's
    program is, at the engine's row counts, over a paged cache of its own
    whose table is half consecutive blocks and half shuffled ones — chunk
    by chunk in slot 0; the last dispatch also carries a decode row in
    every other slot, at a block boundary of the same sequence behind the
    blocks slot 0 filled.  ``probe=True`` brings out what each layer's
    attention was handed: a decode row's pool rows and count, a chunk
    row's mask — in a ``shared`` layer the set as it was carried there,
    across the two scans.  Returns the sequence ``ids``, the chunk rows'
    sets as masks ``chunk [layers, t, t]``, the decode rows' positions
    ``at`` and their sets ``decode [layers, slots - 1, t]`` (pool rows
    read back to positions through each slot's own table; a row outside
    the slot's context counts in ``decode_size [layers]`` and is in no mask), and
    the last dispatch's ``counters`` by name."""
    import jax
    import jax.numpy as jnp
    mc = model.config
    t = OVERLAP_CONTEXT if shrink == 1 else 16 * mc.index_topk
    block = 16
    chunk = min(chunk, t // 4)
    pages = t // block
    ids = np.random.default_rng([int(seed), 0x1D]).integers(
        0, mc.vocab_size, t, dtype=np.int32)
    shuffled = np.random.default_rng([int(seed), 0x7AB]).permutation(
        np.arange(pages // 2, pages))
    table = 1 + np.concatenate([np.arange(pages // 2), shuffled])
    at = (np.linspace(mc.index_topk, t - chunk, slots - 1) // block
          * block).astype(np.int32)
    tables = np.zeros((slots, pages + 1), np.int32)
    tables[0, :pages] = table
    for b, p in enumerate(at, start=1):
        tables[b, :p // block] = table[:p // block]
        tables[b, p // block] = pages + b          # the row's own block
    cache = model.init_paged_cache(pages + slots + 1, block,
                                   params["embed"]["embedding"].dtype)
    cache.update(block_tables=jnp.asarray(tables),
                 lens=jnp.zeros((slots,), jnp.int32))

    def step(params, cache, dec_tokens, dec_active, chunk_ids, start):
        _, _, new = model._apply_paged_mixed(
            params, cache, dec_tokens, dec_active, chunk_ids,
            jnp.int32(0), start, jnp.int32(chunk), probe=True)
        seen = new["probe"]
        causal = jnp.arange(t)[None] <= (start + jnp.arange(chunk))[:, None]
        return (dict(cache, k=new["k"], v=new["v"], lens=new["lens"]),
                seen["chunk"][:, :, :t] & causal[None], seen["rows"],
                seen["count"], new["counters"])
    step = jax.jit(step, donate_argnums=1)
    idle = jnp.zeros((slots,), jnp.int32)
    masks = []
    for start in range(0, t, chunk):
        last = start + chunk == t
        if last:
            lens = np.zeros(slots, np.int32)
            lens[0], lens[1:] = start, at
            cache["lens"] = jnp.asarray(lens)
        cache, mask, rows, count, counters = step(
            params, cache,
            jnp.asarray(np.concatenate([[0], ids[at]])) if last else idle,
            jnp.asarray(np.arange(slots) > 0, jnp.int32) if last else idle,
            jnp.asarray(ids[start:start + chunk]), jnp.int32(start))
        masks.append(mask)
    rows, count = np.asarray(rows), np.asarray(count)
    decode = np.zeros((mc.num_layers, slots - 1, t), bool)
    for b, p in enumerate(at, start=1):
        where = np.full((pages + slots + 1) * block, -1)
        pos = np.arange(p + 1)
        where[tables[b, pos // block] * block + pos % block] = pos
        for layer in range(mc.num_layers):
            got = where[rows[layer, b, :count[layer, b]]]
            decode[layer, b - 1, got[got >= 0]] = True
    return {"ids": ids, "at": at, "chunk": jnp.concatenate(masks, axis=1),
            "decode": decode, "decode_size": count[:, 1:].sum(axis=1),
            "counters": dict(zip(model.PAGED_COUNTERS,
                                 map(int, np.asarray(counters))))}


def _index_overlap(got, params, ref_cfg, held, leave_out=()) -> list:
    """``index_overlap`` a layer: the share of the served step's sets
    (``_served_selection``) inside the reference's masks of the same
    layer, from its full forward over the same sequence — a ``shared``
    layer's against the mask the reference handed on."""
    import jax
    import jax.numpy as jnp
    want = jax.jit(lambda p, i: reference.logits(
        p, i, ref_cfg, held, leave_out, last=1, return_selection=True)[1]
    )(params, jnp.asarray(got["ids"])[None])[:, 0]     # [layers, t, t]
    hit = np.asarray(jnp.sum(got["chunk"] & want, axis=(1, 2))) + np.sum(
        got["decode"] & np.asarray(want[:, got["at"]]), axis=(1, 2))
    of = np.asarray(jnp.sum(got["chunk"], axis=(1, 2))) \
        + got["decode_size"]
    return [float(x) for x in hit / of]


def _check_experts(model, params, ref_cfg, held, seed, rows, leave_out=()):
    """``serve_latent_sandwich._check_experts`` against this family's
    reference (its gate picks by score + bias): the first EXPERT layer's
    ``F_l`` over ``rows`` seeded rows on the timed weights, the program's
    ``expert_layer`` against the reference's shared expert and loop over
    experts, as the norm of the difference over the norm of the held
    experts' own part of the reference."""
    import jax
    import jax.numpy as jnp
    blocks = params["blocks"]
    rest = {"moe": {k: v for k, v in blocks["moe"].items()
                    if k != "experts"}, "shared": blocks["shared"]}
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
        cfg = dict(ref_cfg, float8="float8" in leave_out)
        with jax.default_matmul_precision("highest"):
            u32 = u.astype(jnp.float32)[None]
            want = reference.moe(layer, u32, cfg, held, leave_out,
                                 expert_at=at)
            own = reference.routed(layer["moe"], u32,
                                   dict(ref_cfg, float8=False), held, at)
        return want[0], own[0]
    got = jax.jit(program)(rest, experts, u)
    want, own = jax.jit(plain)(rest, experts, u)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(own))


def _selection_values(overlap, mc, window) -> dict:
    """What the program counted of its selection over the window's
    iterations: selected tokens read over the tokens a dense walk of the
    same rows would have read, and the share of (row, layer) pairs that
    took a handed-on set.  A program that keeps no such counters gives
    nothing."""
    recs, complete = overlap.iterations(*window)
    recs = recs[recs["kind"] == "serving"]
    names = recs.dtype.names or ()
    if not complete or not len(recs) or "sparse_tokens_read" not in names:
        return {}
    ran = float(recs["index_rows"].sum())
    reused = float(recs["sparse_rows_reused"].sum())
    scored = float(recs["index_keys_scored"].sum())
    if ran <= 0 or scored <= 0:
        return {}
    dense = scored / mc.full_layers * mc.num_layers
    return {"select_density": 100.0 * float(
                recs["sparse_tokens_read"].sum()) / dense,
            "index_reuse_share": 100.0 * reused / (reused + ran),
            "index_keys_scored_per_s": scored / (window[1] - window[0])}


def _least(series, peaks) -> dict:
    """``work`` of one kernel from its per-iteration (operations, bytes)."""
    if not len(series) or series.sum() <= 0:
        return None
    return {"least_s": sum(costs.roofline_seconds(f, b, peaks)[0]
                           for f, b in series),
            "bound": costs.roofline_seconds(*series.sum(axis=0), peaks)[1]}


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

    # correct, parts 1 and 2 (and the warm-up of the step's two shapes)
    leave_out = tuple(mix.get("reference_leaves_out", ()))
    worst_gap, exact_share, rehit = _check_against_reference(
        srv, params, ref_cfg, held, mc.vocab_size, ctx.seed, shrink,
        leave_out)
    rehit_least = (max(2, CHECK_REQUESTS[0][0] // shrink) - 1) \
        // int(serving["kv_block_size"]) * int(serving["kv_block_size"])
    # part 3: the selection; part 4: the expert layer at the mixed
    # program's row count
    served = _served_selection(model, params, ctx.seed, shrink,
                               int(serving["max_batch_slots"]),
                               int(serving["prefill_chunk_tokens"]))
    overlap_by_layer = _index_overlap(served, params, ref_cfg, held,
                                      leave_out)
    # the layers the first ``full`` layer's set serves, and the rest
    deep = mc.layer_kinds.index("full", 1) if mc.full_layers > 1 \
        else mc.num_layers
    index_overlap = min(overlap_by_layer[:deep])
    index_overlap_deep = min(overlap_by_layer[deep:], default=1.0)
    overlap_counts = served.pop("counters")
    del served
    expert_err = _check_experts(
        model, params, ref_cfg, held, ctx.seed,
        int(serving["max_batch_slots"]) + int(serving["prefill_chunk_tokens"]),
        leave_out)

    work = requests(mix, ctx.seed, mc.vocab_size, shrink)
    fill_s = _fill(srv, work["docs"])
    hits_before = srv.allocator.hit_tokens_total
    evictions_before = srv.allocator.evictions_total
    compiles_before = ctx.compile_log.compiles
    builds_before = srv.decode_builds

    slots, blocks = srv.num_slots, srv.allocator.usable_blocks
    client = SparseClient(srv, work, mc, ctx.trace, overlap)
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
    hit = sum(c.reqs[i].cache_hit_tokens for i in judged)
    asked = sum(len(c.reqs[i].prompt) for i in judged)
    # a request that found less than its whole document in the prefix
    # cache: a document block was evicted (parked suffix blocks may be)
    doc_len = np.array([len(d) for d in work["docs"]])[work["doc"]]
    docs_missed = sum(c.reqs[i].cache_hit_tokens < doc_len[i]
                      for i in range(n_sub) if c.admit_t[i] > 0)
    evictions = srv.allocator.evictions_total - evictions_before
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
    failed = int(np.sum(~c.ok_full[judged]))
    gaps_in = c.gap_ms[:c.n_gap][(c.gap_end[:c.n_gap] > w0)
                                 & (c.gap_end[:c.n_gap] <= w1)]
    fifth = (w1 - w0) / 5
    queue = c.it_queue[its]
    first5 = queue[(it_end > w0) & (it_end <= w0 + fifth)]
    last5 = queue[(it_end > w1 - fifth) & (it_end <= w1)]
    ended = int(len(judged))
    routing, moe_work = ({}, None) if not ctx.trace else _routing_values(
        overlap, mc, (w0, w1), ctx.trace_started_at)
    if ctx.trace:
        routing.update(_expert_layer_values(overlap, mc, (w0, w1)))
        routing.update(_selection_values(overlap, mc, (w0, w1)))
    work_done = {}
    if red:
        traced = it_start >= ctx.trace_started_at
        for name, series in (("dsa_index_scores", c.it_index[its][traced]),
                             ("dsa_sparse_attention",
                              c.it_sparse[its][traced]),
                             ("gathered_latent_attention",
                              c.it_gather[its][traced])):
            least = _least(series, ctx.peaks)
            if least:
                work_done[name] = least
        if moe_work is not None:
            least, bound = costs.roofline_seconds(*moe_work, ctx.peaks)
            work_done["moe_grouped_matmul"] = {"least_s": least,
                                               "bound": bound}
    ok = (worst_gap <= LOGIT_GAP_ATOL and expert_err <= EXPERT_REL_ERR_MAX
          and index_overlap >= INDEX_OVERLAP_MIN
          and index_overlap_deep >= INDEX_OVERLAP_DEEP_MIN
          and rehit >= rehit_least
          and failed == 0 and len(judged) > 0 and docs_missed == 0
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
    if asked:
        values["prefix_hit_share"] = 100.0 * hit / asked
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
        # the latent pool is [layers, blocks, kv_block_size, lanes of a row]
        "shapes": {"kv_block_size": int(serving["kv_block_size"]),
                   "kv_row_width": srv.kv_row_width},
        "diag": {"logit_gap_worst": worst_gap, "argmax_share": exact_share,
                 "expert_rel_err": expert_err, "index_overlap": index_overlap,
                 "index_overlap_deep": index_overlap_deep,
                 "index_overlap_by_layer": overlap_by_layer,
                 "index_overlap_counters": overlap_counts,
                 "rehit_tokens": rehit, "fill_s": fill_s,
                 "num_params": mc.num_params(),
                 "kv_pool_bytes": srv.kv_pool_bytes,
                 "prefix_hit_tokens": int(srv.allocator.hit_tokens_total
                                          - hits_before),
                 "evictions_since_fill": int(evictions),
                 "documents_missed": int(docs_missed),
                 "blocks_used_max": float(c.it_blocks[its][in_w].max()),
                 "ttft_samples": int(ttft.size),
                 "itl_samples": int(gaps_in.size),
                 "iterations_in_window": int(in_w.sum()),
                 "requests_ended_in_window": ended, "submitted": n_sub,
                 "compiles_in_window": compiles_in_window,
                 "blocks_held_after_drain": int(srv.allocator.num_used),
                 "index_bound": work_done.get("dsa_index_scores",
                                              {}).get("bound"),
                 "sparse_bound": work_done.get("dsa_sparse_attention",
                                               {}).get("bound"),
                 "gather_bound": work_done.get("gathered_latent_attention",
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
