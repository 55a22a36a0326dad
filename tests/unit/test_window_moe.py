"""Window and full attention mixed in one stack over experts (the
``afmoe`` family: 3 window layers to a full one, a gate on the attention's
output, q / k norms, rotary on the window layers alone, four norms a
layer, a dense lead, sigmoid routing with a bias on the pick beside a
shared expert) against its plain reference, ``benchmark/lib/
reference_afmoe.py``: tiny sizes, CPU, float32, seeded weights.

  - ``apply`` (full sequences) and ``generate()``'s cache against the
    reference, and against one that lacks a mechanism;
  - chunked prefill then paged decode through ``ServingEngine`` and BOTH
    pools against the reference's full forward: contexts that end under,
    at and far past the window, a chunk that straddles the window's edge,
    a slot whose window pages were handed back;
  - the share ties to the model: all four shares of the routed experts
    and the shared expert once add up to the uncut layer;
  - the router's picks and weights against a NumPy transcription;
  - the allocator: window pages within ``window_pages``' two bounds under
    a host-only churn of the cell's mix, both kinds drained;
  - each counter against a known mix; each refusal's sentence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_afmoe as reference
from deepspeed_tpu.inference.serving.block_allocator import (
    PagedBlockAllocator)
from deepspeed_tpu.models import TransformerLM, afmoe_config, build_model
from deepspeed_tpu.models.transformer import find_layer_plan
from deepspeed_tpu.models.window_moe import WindowMoELM
from deepspeed_tpu.moe import dropless

#: the lead (two dense layers), one whole period and the boundary period;
#: a window of two pages
TINY = dict(num_layers=8, layer_types=("window", "window", "window",
                                       "full") * 2,
            first_k_dense=2, num_heads=4, num_kv_heads=2, head_dim=8,
            d_model=32, d_ff=64, vocab_size=128, max_seq_len=128,
            sliding_window=8, expert_d_ff=16, n_routed_experts=16,
            moe_topk=4, experts_held=(0, 4), dtype=jnp.float32)
REF = dict(heads=4, kv_heads=2, head_dim=8, eps=1e-5, theta=1e4, window=8,
           layer_types=TINY["layer_types"], dense=2, mup=True, experts=16,
           topk=4, scale=2.826, without=())
HELD = (0, 4)
SERVING = {"enabled": True, "kv_block_size": 4, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 128}
#: float32 on the CPU against the reference at precision ``highest``: the
#: two differ by the order of summation alone
ATOL = 1e-5


def build(**kw):
    """The tiny model with its vectors moved off their initial values
    (norms, the router's bias) and its matrices enlarged, so that every
    mechanism shows in the logits."""
    model = build_model(afmoe_config("trinity-mini", **{**TINY, **kw}))
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 512))

    def move(path, a):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "bias" in name:
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        return a * 3.0
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def built():
    return build()


def serving_engine(model, params, **serving):
    return ds.init_inference(
        model, {"dtype": "float32", "max_out_tokens": 128,
                "temperature": 0.0, "serving": {**SERVING, **serving}},
        params=params).serving_engine()


def worst_gap(params, req, held=HELD):
    """The largest gap of a chosen token to the reference's best logit."""
    fed = jnp.asarray(list(req.prompt) + list(req.output)[:-1])[None]
    lg = np.asarray(reference.logits(params, fed, REF, held,
                                     last=len(req.output)))[0]
    return max(float(lg[j].max() - lg[j][tok])
               for j, tok in enumerate(req.output))


def test_the_config_builds_its_own_model_class_and_counts_its_parameters(
        built):
    model, params = built
    assert type(model) is WindowMoELM
    with pytest.raises(TypeError, match="build_model"):
        TransformerLM(model.config)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == model.config.num_params()
    whole = afmoe_config("trinity-mini")
    assert whole.num_params() == 26_123_974_400          # the published 26B
    assert whole.attn_params() == 27_263_232
    held = afmoe_config("trinity-mini", experts_held=(0, 16))
    assert held.num_params() == 4_984_682_240
    assert (whole.window_layers, whole.full_layers) == (24, 8)
    assert afmoe_config("trinity-mini", num_layers=4, layer_types=[
        "window"] * 3 + ["full"]).layer_types == (
            "window", "window", "window", "full")
    with pytest.raises(ValueError, match="layer_types names"):
        build_model(afmoe_config("trinity-mini", num_layers=30))
    with pytest.raises(ValueError, match="experts_held"):
        build_model(afmoe_config("trinity-mini", **{
            **TINY, "experts_held": (8, 24)}))


@pytest.mark.parametrize("layer_types,dense,plan", [
    # published: the dense lead ends inside a period
    ((("window",) * 3 + ("full",)) * 8, 2, [(2, 1), (4, 7), (2, 1)]),
    # the tests': the lead, one whole period, the boundary period
    ((("window",) * 3 + ("full",)) * 2, 2, [(4, 1), (1, 3), (1, 1)]),
    # no dense lead: whole periods
    ((("window",) * 3 + ("full",)) * 3, 0, [(4, 3)]),
    # no repeat at all: one pass
    (("window", "full"), 1, [(2, 1)])])
def test_the_plan_finds_the_stretch_that_repeats(layer_types, dense, plan):
    c = afmoe_config("trinity-mini", num_layers=len(layer_types),
                     layer_types=layer_types, first_k_dense=dense)
    got = c.layer_plan
    assert [(len(sigs), passes) for sigs, passes in got] == plan
    flat = [sig for sigs, passes in got for sig in sigs * passes]
    assert flat == list(zip(c.layer_types, c.ffn_types))
    assert got == find_layer_plan(tuple(zip(c.layer_types, c.ffn_types)))


@pytest.mark.parametrize("without,moves", [
    ((), 0.0), (("window",), 0.3), (("gate",), 0.3), (("nope",), 0.2),
    (("bias",), 0.2), (("scale",), 0.2)])
def test_full_forward_matches_the_reference_and_not_one_that_lacks_a_part(
        built, without, moves):
    model, params = built
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, dict(REF, without=without), HELD)
    diff = float(jnp.abs(got - want).max())
    assert diff < ATOL if not without else diff > moves, diff


def test_generates_through_the_dense_cache_like_one_pass(built):
    """``generate()``'s prefill + one-token steps (every layer's k / v in
    ``init_cache``'s tree, the window a mask) are the full forward."""
    model, params = built
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 30), 0, 128)
    want = model.apply(params, ids)
    cache = model.init_cache(2, 30, jnp.float32)
    lg, cache = model.apply(params, ids[:, :19], cache=cache)
    outs = [lg]
    for t in range(19, 30):
        lg, cache = model.apply(params, ids[:, t:t + 1], cache=cache)
        outs.append(lg)
    assert float(jnp.abs(jnp.concatenate(outs, 1) - want).max()) < ATOL
    eng = ds.init_inference(model, {"dtype": "float32",
                                    "max_out_tokens": 64,
                                    "temperature": 0.0}, params=params)
    out = np.asarray(eng.generate(np.asarray(ids[:1, :12]),
                                  max_new_tokens=5))[0]
    full = jnp.concatenate([ids[0, :12], jnp.asarray(out[:-1])])[None]
    lg = np.asarray(model.apply(params, full))[0, 11:]
    assert [int(r.argmax()) for r in lg] == [int(t) for t in out]


#: (prompt, new tokens): a context that ends under the window (8), at it,
#: far past it (its window pages handed back while it prefills: 70 rows are
#: 18 pages, a slot holds 7 at most), a chunk (16 rows) that straddles the
#: window's edge, and one whose decode crosses it
REQUESTS = ((3, 4), (5, 3), (70, 8), (23, 12), (6, 6))


@pytest.fixture(scope="module")
def served(built):
    """One engine, the requests interleaved on 3 slots, then one more in a
    slot another has left."""
    model, params = built
    srv = serving_engine(model, params)
    rng = np.random.default_rng(0)
    reqs = [srv.submit(rng.integers(0, 128, n), max_new_tokens=m)
            for n, m in REQUESTS]
    srv.run()
    again = srv.submit(rng.integers(0, 128, 41), max_new_tokens=5)
    srv.run()
    return srv, reqs + [again]


@pytest.mark.parametrize("at", range(len(REQUESTS) + 1))
def test_chunked_prefill_then_decode_through_both_pools_is_the_reference(
        built, served, at):
    _, params = built
    _, reqs = served
    req = reqs[at]
    assert len(req.output) == req.max_new_tokens
    assert worst_gap(params, req) < ATOL


def test_the_engine_holds_two_kinds_of_page_and_no_state(built, served):
    model, _ = built
    srv, _ = served
    assert srv.allocator.kinds == ("full", "window")
    assert srv.table_kinds == ("full", "window") and srv._pool_x.keys() == {
        "wk", "wv"}
    assert srv._pool_k.shape[0] == 2 and srv._pool_x["wk"].shape[0] == 6
    held_decoding, held_chunk = model.window_pages(4, 16)
    assert (held_decoding, held_chunk) == (3, 7)
    assert srv.window_blocks == 2 * 3 + 7 + 1
    held = srv.allocator.window_held_max
    assert held["decode"] == held_decoding and 3 < held["chunk"] <= held_chunk
    assert srv.allocator.window_freed_total > 0
    assert srv.allocator.num_used_by_kind() == {"full": 0, "window": 0,
                                                "state": 0}
    assert srv.decode_builds == 2 and not srv._flight
    assert srv.prefix_cache is False


def test_a_preempted_request_prefills_again_through_both_kinds(built):
    """A pool too small for three long contexts: one is preempted, gives
    back the pages of BOTH kinds, and its recomputation is the reference
    again."""
    model, params = built
    srv = serving_engine(model, params, num_kv_blocks=24)
    rng = np.random.default_rng(3)
    reqs = [srv.submit(rng.integers(0, 128, 30), max_new_tokens=20)
            for _ in range(3)]
    srv.run()
    assert srv.scheduler.preemption_count > 0
    assert all(worst_gap(params, r) < ATOL for r in reqs)
    assert srv.allocator.num_used_by_kind() == {"full": 0, "window": 0,
                                                "state": 0}


def test_the_shares_of_the_routed_experts_add_up_to_the_uncut_layer(built):
    """One expert layer's ``f`` through the PROGRAM at each of the four
    shares of the 16 experts, the shared expert counted once, against the
    reference's uncut layer."""
    _, params = built
    whole_model, _ = build(experts_held=())
    moe = jax.tree_util.tree_map(lambda a: a[0], whole_model.init(
        jax.random.PRNGKey(0))["moe"])
    moe = jax.tree_util.tree_map(lambda a: a * 3.0, moe)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    want = reference.expert_layer(moe, u, REF)
    shared = whole_model._mlp(moe["shared"], u, scope="shared_expert")
    total, picks = shared, 0
    for lo in range(0, 16, 4):
        part, _ = build(experts_held=(lo, lo + 4))
        p = dict(moe, moe=dict(moe["moe"], experts={
            n: w[lo:lo + 4] for n, w in moe["moe"]["experts"].items()}))
        f, counts = part.expert_layer(p, u)
        total = total + (f - shared)
        picks += int(counts[dropless.COUNTERS.index("moe_picks_held")])
    assert picks == 24 * 4                    # every pick held by one share
    assert float(jnp.abs(total - want).max()) < ATOL
    uncut, _ = whole_model.expert_layer(moe, u)
    assert float(jnp.abs(uncut - want).max()) < ATOL


def test_the_router_is_the_numpy_transcription(built):
    """Bias on the pick only, ``1e-20`` in the sum, 2.826 on the weights,
    sigmoids in float32."""
    model, params = built
    p = jax.tree_util.tree_map(lambda a: a[1], params["moe"])["moe"]
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (50, 32)))
    s = 1.0 / (1.0 + np.exp(-(u.astype(np.float64)
                              @ np.asarray(p["router"]["kernel"],
                                           np.float64))))
    bias = np.asarray(p["bias"], np.float64)
    pick = np.argsort(-(s + bias), axis=-1)[:, :4]
    chosen = np.take_along_axis(s, pick, axis=-1)
    weight = 2.826 * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    c = model.config
    got = dropless.route(jnp.asarray(u), p["router"]["kernel"], p["bias"],
                         c.moe_topk, c.routed_scaling_factor,
                         scoring=c.router_scoring,
                         renormalize=c.norm_topk_prob)
    assert np.array_equal(np.sort(np.asarray(got.index), -1),
                          np.sort(pick, -1))
    order = np.argsort(np.asarray(got.index), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(got.weight), order, -1),
        np.take_along_axis(weight, np.argsort(pick, -1), -1), rtol=2e-5)
    # the bias moves picks, and never a weight
    no_bias = np.argsort(-s, axis=-1)[:, :4]
    assert not np.array_equal(np.sort(no_bias, -1), np.sort(pick, -1))
    picked, w = reference.router(p, jnp.asarray(u), REF)
    assert np.array_equal(np.sort(np.asarray(picked), -1), np.sort(pick, -1))


@pytest.mark.parametrize("seed", [0, 1])
def test_window_pages_stay_within_their_bounds_under_the_cells_churn(seed):
    """Host only: the cell's engine sizes (20 slots, pages of 16, chunks of
    512, a window of 2,048) and its mix of prompts and outputs through the
    allocator alone, as the engine drives it — reserve before a dispatch's
    rows, trim behind a chunk, free at the end.  No slot ever holds more
    than ``window_pages``' bounds, the pool sized from them never runs
    out, and both kinds drain to 0."""
    model = build_model(afmoe_config("trinity-mini", num_layers=4,
                                     layer_types=("window",) * 3 + ("full",),
                                     vocab_size=128, experts_held=(0, 16)))
    block, chunk, slots, window = 16, 512, 20, 2048
    held_decoding, held_chunk = model.window_pages(block, chunk)
    assert (held_decoding, held_chunk) == (129, 161)
    alloc = PagedBlockAllocator(6656, block, enable_prefix_cache=False)
    alloc.add_window_kind((slots - 1) * held_decoding + held_chunk + 1,
                          window)
    rng = np.random.default_rng(seed)
    mix = [(p, o) for p in (512, 2048, 4096, 8192)
           for o in (128, 256, 384, 512)]
    queue = [mix[i] for _ in range(4) for i in rng.permutation(len(mix))]
    live = {}                      # id -> [prompt, output, rows cached]
    n = 0
    while queue or live:
        while queue and len(live) < slots:
            p, o = queue.pop()
            alloc.allocate(f"r{n}", p + o)
            live[f"r{n}"] = [p, o, 0]
            n += 1
        # one dispatch: one chunk (the first request still prefilling) and
        # every decoding slot a row
        chunked = next((r for r, s in live.items() if s[2] < s[0]), None)
        for r, (p, o, at) in list(live.items()):
            if at >= p:
                alloc.window_reserve(r, at, at + 1)
                live[r][2] += 1
        if chunked is not None:
            p, o, at = live[chunked]
            rows = min(chunk, p - at)
            alloc.window_reserve(chunked, at, at + rows, "chunk")
            alloc.window_trim(chunked, at + rows)
            live[chunked][2] += rows
        for r, (p, o, at) in list(live.items()):
            if at >= p + o:
                alloc.free(r)
                del live[r]
        if len(live) > 1 and rng.random() < 0.01:     # a cancelled request
            gone = list(live)[int(rng.integers(len(live)))]
            alloc.free(gone)
            del live[gone]
    assert alloc.window_held_max["decode"] <= held_decoding
    assert alloc.window_held_max["chunk"] <= held_chunk
    assert alloc.window_held_max["decode"] == held_decoding
    assert alloc.num_used_by_kind() == {"full": 0, "window": 0, "state": 0}
    alloc.assert_consistent()


def test_the_step_counts_what_each_kind_of_walk_was_handed(built):
    """One dispatch through the model's own mixed step over a cache of the
    test's: two decoding slots (contexts of 5 and 21) and a chunk of 6
    rows from row 10 — the keys, pages and rows by their definitions."""
    model, params = built
    slots, pages, block = 3, 8, 4
    cache = model.init_paged_cache(1 + slots * pages, block, jnp.float32)
    cache["extra"] = model.init_paged_extra(slots, block, 1 + slots * pages,
                                            jnp.float32)
    table = 1 + np.arange(slots * pages, dtype=np.int32).reshape(slots, pages)
    cache["block_tables"] = jnp.asarray(np.concatenate([table, table], 1))
    cache["lens"] = jnp.asarray([4, 20, 10], jnp.int32)
    _, _, new = jax.jit(model._apply_paged_mixed)(
        params, cache, jnp.asarray([1, 2, 0]), jnp.asarray([1, 1, 0]),
        jnp.arange(8, dtype=jnp.int32), jnp.int32(2), jnp.int32(10),
        jnp.int32(6))
    got = dict(zip(model.PAGED_COUNTERS, np.asarray(new["counters"])))
    rows, full, window, expert = 2 + 6, 2, 6, 6
    assert got["moe_picks"] == rows * 4 * expert
    assert got["moe_rows_shared"] == rows * expert
    assert 0 < got["moe_picks_held"] < got["moe_picks"]
    # full layers: all of each context; window layers: its newest 8 keys,
    # the chunk's from its first row's window (row 10 sees 3 .. 10)
    assert got["kv_tokens_read_full"] == full * (5 + 21 + 16)
    assert got["kv_tokens_read_window"] == window * (5 + 8 + (16 - 3))
    # pages that hold attended keys: 2 + 6 + 4 in a full layer; in a window
    # layer 2, rows 13 .. 20 on pages 3 .. 5, rows 3 .. 15 on pages 0 .. 3
    assert got["kv_pages_read_full"] == full * (2 + 6 + 4)
    assert got["kv_pages_read_window"] == window * (2 + 3 + 4)
    assert got["kv_pages_in_runs_full"] <= got["kv_pages_read_full"]
    assert np.array_equal(np.asarray(new["lens"]), [5, 21, 16])


@pytest.mark.parametrize("how,says", [
    (dict(kv_bits=8), "scale rows take no first page"),
    (dict(spec=True), "speculative lane"),
    (dict(host_cache=True), "host tier"),
    (dict(mesh_model=2), "serves on one chip"),
    (dict(weight_quant=True), "int8 weight-only")])
def test_each_refusal_says_why(built, how, says):
    model, _ = built
    assert says in model.paged_refusal(**how)
    assert model.paged_refusal() is None


def test_training_and_the_prefix_cache_are_refused_with_their_reasons(built):
    model, params = built
    assert "takes no window" in model.training_refusal()
    assert "handed back" in model.prefix_cache_refusal()
    with pytest.raises(NotImplementedError, match="takes no window"):
        ds.initialize(model=model, config={
            "train_batch_size": 8, "optimizer": {
                "type": "AdamW", "params": {"lr": 1e-3}}})
