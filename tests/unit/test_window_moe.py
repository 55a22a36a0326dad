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
    a host-only churn of the cell's mix, both kinds drained; the window
    kind's groups of ``PAGE_RUN`` (where a page lies, when a group goes
    back, the one bound in groups, the walks' pages in runs);
  - each counter against a known mix; each refusal's sentence.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_afmoe as reference
from deepspeed_tpu.inference.serving.block_allocator import (
    BlockPoolError, PagedBlockAllocator, window_groups, window_pool_blocks)
from deepspeed_tpu.models import TransformerLM, afmoe_config, build_model
from deepspeed_tpu.models.transformer import find_layer_plan
from deepspeed_tpu.models.window_kind import WindowKind
from deepspeed_tpu.models.window_moe import WindowMoELM
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    PAGE_RUN, walk_pages)

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
    # (one program a shape: eleven one-token steps share theirs)
    step = jax.jit(lambda ids, cache: model.apply(params, ids, cache=cache))
    lg, cache = step(ids[:, :19], cache)
    outs = [lg]
    for t in range(19, 30):
        lg, cache = step(ids[:, t:t + 1], cache)
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
    # in groups of 8: 3 or 7 pages touch at most 2, and every slot's at once
    assert (window_groups(3), window_groups(7)) == (2, 2)
    assert srv.window_blocks == PAGE_RUN * (2 * 2 + 2) + 1
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


def cell_allocator(slots=20, block=16, chunk=512, window=2048, short=0):
    """The allocator at a cell's sizes, its window pool as the engine
    sizes it (``short`` groups fewer), and ``window_pages``' two bounds."""
    held = WindowKind.window_pages(SimpleNamespace(config=SimpleNamespace(
        sliding_window=window)), block, chunk)
    alloc = PagedBlockAllocator(6656, block, enable_prefix_cache=False)
    alloc.add_window_kind(window_pool_blocks(slots, *held)
                          - short * PAGE_RUN, window)
    return alloc, held


def prefill(alloc, seq, start, end, chunk):
    """Rows ``start .. end - 1`` of ``seq`` by chunks, as the engine drives
    the kind: reserve before the dispatch, trim behind it.  Returns the
    most groups the sequence held at a reserve."""
    most = 0
    for at in range(start, end, chunk):
        alloc.window_reserve(seq, at, min(at + chunk, end), "chunk")
        most = max(most, len(groups_held(alloc, seq)))
        alloc.window_trim(seq, min(at + chunk, end))
    return most


def groups_held(alloc, seq=None):
    """The groups ``seq`` (or every sequence) holds, each by its first
    block."""
    return [g for r, gs in alloc._wgroups.items() for g in gs
            if g and seq in (None, r)]


@pytest.mark.parametrize("seed", [0, 1])
def test_window_pages_stay_within_their_bounds_under_the_cells_churn(seed):
    """Host only: the cell's engine sizes (20 slots, pages of 16, chunks of
    512, a window of 2,048) and its mix of prompts and outputs through the
    allocator alone, as the engine drives it — reserve before a dispatch's
    rows, trim behind a chunk, free at the end.  No slot ever holds more
    than ``window_pages``' bounds, the pool sized from them in groups never
    runs out, the groups held and the groups free are all the groups at
    every dispatch, both kinds drain to 0 — and from the 500th dispatch on
    (the first turnover is long past) the pages the window walks are
    handed lie in runs, the decode rows' and the chunk's alike."""
    model = build_model(afmoe_config("trinity-mini", num_layers=4,
                                     layer_types=("window",) * 3 + ("full",),
                                     vocab_size=128, experts_held=(0, 16)))
    block, chunk, slots, window = 16, 512, 20, 2048
    alloc, (held_decoding, held_chunk) = cell_allocator()
    assert (held_decoding, held_chunk) == (129, 161) \
        == model.window_pages(block, chunk)
    assert alloc.window_blocks == 2753
    groups = (alloc.window_blocks - 1) // PAGE_RUN
    count = jax.jit(walk_pages, static_argnums=2)
    rng = np.random.default_rng(seed)
    mix = [(p, o) for p in (512, 2048, 4096, 8192)
           for o in (128, 256, 384, 512)]
    queue = [mix[i] for _ in range(4) for i in rng.permutation(len(mix))]
    live = {}                      # id -> [prompt, output, rows cached]
    n = dispatches = 0
    walked = {"decode": np.zeros(2, np.int64), "chunk": np.zeros(2, np.int64)}
    while queue or live:
        while queue and len(live) < slots:
            p, o = queue.pop()
            alloc.allocate(f"r{n}", p + o)
            live[f"r{n}"] = [p, o, 0]
            n += 1
        # one dispatch: one chunk (the first request still prefilling) and
        # every decoding slot a row
        chunked = next((r for r, s in live.items() if s[2] < s[0]), None)
        total = np.zeros(slots, np.int32)
        for at_slot, (r, (p, o, at)) in enumerate(list(live.items())):
            if at >= p:
                alloc.window_reserve(r, at, at + 1)
                live[r][2] += 1
                total[at_slot] = at + 1
        if chunked is not None:
            p, o, at = live[chunked]
            rows = min(chunk, p - at)
            alloc.window_reserve(chunked, at, at + rows, "chunk")
        assert len(groups_held(alloc)) + len(alloc._wfree) == groups
        if dispatches >= 500:
            # the tables as _window_operands lays them, the walks as
            # transformer.walk_counts hands them to walk_pages
            tables = np.zeros((slots, (8192 + 512) // block), np.int32)
            for at_slot, r in enumerate(live):
                first, held = alloc.window_pages_held(r)
                tables[at_slot, first:first + len(held)] = held
            walked["decode"] += count(tables, total, block,
                                      np.maximum(total - window, 0))
            if chunked is not None:
                row = list(live).index(chunked)
                walked["chunk"] += count(
                    tables[row][None], np.int32([at + rows]), block,
                    np.int32([max(at - (window - 1), 0)]))
        dispatches += 1
        if chunked is not None:
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
    assert len(alloc._wfree) == groups
    alloc.assert_consistent()
    assert dispatches > 1000
    for lane, (pages, in_runs) in walked.items():
        assert pages > 0 and in_runs >= 0.85 * pages, (lane, pages, in_runs)


def test_a_live_window_page_is_its_groups_block_at_the_tables_own_index():
    """Entry ``p`` of a window table is block ``p % PAGE_RUN`` of the group
    the sequence took at page ``p - p % PAGE_RUN``, whatever was handed
    back before: every run of the table is consecutive blocks."""
    alloc, _ = cell_allocator(slots=3, block=4, chunk=16, window=40)
    for seq in "ab":
        alloc.allocate(seq, 200)
    for at in range(0, 192, 16):        # chunks side by side: groups interleave
        for seq in "ab":
            alloc.window_reserve(seq, at, at + 16, "chunk")
            alloc.window_trim(seq, at + 16)
    for seq in "ab":
        first, held = alloc.window_pages_held(seq)
        assert first == (192 - 39) // 4 and len(held) == 48 - first
        groups = alloc._wgroups[seq]
        assert [g % PAGE_RUN for g in groups if g] == [1] * 2
        assert held == [groups[p // PAGE_RUN] + p % PAGE_RUN
                        for p in range(first, 48)]
    # the two sequences' groups alternate: consecutive runs of one table
    # are NOT neighbours in the pool, a run's pages are
    assert groups_held(alloc, "a")[1] != groups_held(alloc, "a")[0] + PAGE_RUN
    alloc.assert_consistent()


@pytest.mark.parametrize("how", ["trim", "free", "cancel"])
def test_a_window_group_goes_back_exactly_once(how):
    """A group goes back when its LAST page is handed back, not before;
    ``free`` gives back what is left — the partly dead first group, the
    partly filled last one — and nothing twice."""
    alloc, _ = cell_allocator(slots=3, block=4, chunk=16, window=9)
    every = (alloc.window_blocks - 1) // PAGE_RUN
    alloc.allocate("a", 200)
    alloc.window_reserve("a", 0, 44, "chunk")           # pages 0 .. 10
    assert len(groups_held(alloc, "a")) == 2 and len(alloc._wfree) == every - 2
    if how == "cancel":                 # gone before anything was trimmed
        alloc.free("a")
    else:
        # row 36 attends from row 28: pages 0 .. 6 go, the first group stays
        assert alloc.window_trim("a", 36) == 7
        assert len(groups_held(alloc, "a")) == 2
        first, last = groups_held(alloc, "a")
        assert alloc.window_trim("a", 40) == 1          # page 7, its last
        assert groups_held(alloc, "a") == [last]
        assert alloc._wfree[-1] == first and alloc._wfree.count(first) == 1
        assert alloc.window_trim("a", 40) == 0
        if how == "free":
            # a partly dead first group (page 8 gone) and a partly
            # filled last one
            alloc.window_reserve("a", 44, 70, "chunk")      # pages .. 17
            assert alloc.window_pages_held("a")[0] == 9
            assert len(groups_held(alloc, "a")) == 2
            alloc.free("a")
    if how != "trim":
        assert "a" not in alloc._wgroups and "a" not in alloc._wtables
    assert len(groups_held(alloc)) + len(alloc._wfree) == every
    assert len(set(alloc._wfree)) == len(alloc._wfree)
    alloc.assert_consistent()


@pytest.mark.parametrize("cell,slots,window,groups", [
    ("trinity-mini", 20, 2048, (17, 21)), ("phi-4-mini-flash", 64, 512, (5, 9))])
def test_a_slots_pages_never_touch_more_groups_than_the_one_bound(
        cell, slots, window, groups):
    """``n`` consecutive pages touch at most ``ceil((n - 1) / PAGE_RUN) +
    1`` groups: both cells' two page bounds in groups, and a sequence that
    prefills by chunks and then decodes, from every alignment of its
    window to its groups, never holds more."""
    alloc, held = cell_allocator(slots=slots, window=window)
    assert tuple(window_groups(n) for n in held) == groups
    assert all(window_groups(n) == -(-(n - 1) // PAGE_RUN) + 1 for n in held)
    assert alloc.window_blocks == 1 + PAGE_RUN * (
        (slots - 1) * groups[0] + groups[1])
    alloc.allocate("a", 9000)
    # chunks that start 5 rows into a page, one start a group's alignment
    most_chunk = max(prefill(alloc, "a", 0, 5, 512),
                     prefill(alloc, "a", 5, 5 + 512 * 2 * PAGE_RUN, 512))
    most_decode = 0
    for at in range(5 + 512 * 2 * PAGE_RUN, 8600):
        alloc.window_reserve("a", at, at + 1)
        most_decode = max(most_decode, len(groups_held(alloc, "a")))
    assert alloc.window_held_max == {"decode": held[0], "chunk": held[1]}
    assert (most_decode, most_chunk) == groups


@pytest.mark.parametrize("short", [0, 1])
def test_a_window_pool_one_group_short_runs_out(short):
    """Every slot at its bound at once fills the pool to its last group:
    one group fewer and the last slot's reserve raises."""
    alloc, (held_decoding, held_chunk) = cell_allocator(
        slots=4, block=4, chunk=16, window=40, short=short)
    assert (held_decoding, held_chunk) == (11, 15)
    # row 69's window starts on a group's LAST page (7) and ends in the
    # group after the next, and so does a chunk's from there: three groups
    # each, the bound of both
    for seq in "abcd":
        alloc.allocate(seq, 400)
    for seq in "abc":
        prefill(alloc, seq, 0, 69, 16)
        alloc.window_reserve(seq, 69, 70)
        assert len(groups_held(alloc, seq)) == window_groups(held_decoding)
    if short:
        with pytest.raises(BlockPoolError, match="window pool exhausted"):
            prefill(alloc, "d", 0, 85, 16)
    else:
        prefill(alloc, "d", 0, 69, 16)
        alloc.window_reserve("d", 69, 85, "chunk")
        assert len(groups_held(alloc, "d")) == window_groups(held_chunk)
        assert not alloc._wfree
        assert alloc.window_held_max == {"decode": 11, "chunk": 15}
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
