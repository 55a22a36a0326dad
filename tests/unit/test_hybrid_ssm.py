"""The hybrid state-space block (``phi4flash`` family: state-space layers,
window layers, one full layer re-read by a cross decoder with gated memory
units) against its plain reference, ``benchmark/lib/
reference_phi4_flash.py``: tiny sizes, CPU, float32, seeded weights.

  - ``apply`` (full sequences) and ``generate()``'s cache against the
    reference;
  - chunked prefill (prompts that cross the window and several chunks,
    requests interleaved) then paged decode through ``ServingEngine``
    against the reference's full forward: logits, not tokens;
  - the state carried across chunks equals one pass; a second request in
    a freed slot equals a fresh engine; preemption recomputes;
  - ``ssm_chunk_scan`` (interpret mode) against the loop, from zero and
    from a given state; the paged kernel with a window and 2 query heads
    a key-value head against its reference;
  - the allocator's kinds: window pages handed back, every kind empty
    after the drain;
  - each counter against a known mix, and against a walk that is handed
    less; the mixed step's ``probe`` (what the eight walks over the full
    layer's pages give) against the reference's; each refusal's sentence.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_phi4_flash as reference
from benchmark.runners import serve_hybrid
from deepspeed_tpu.inference.serving.block_allocator import (
    BlockPoolError, PagedBlockAllocator, window_groups, window_pool_blocks)
from deepspeed_tpu.models import (TransformerLM, build_model,
                                  phi4_flash_config)
from deepspeed_tpu.models.hybrid_ssm import HybridSSMLM
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer import ssm_scan
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    PAGE_RUN, page_runs, paged_attention_reference, paged_decode_attention,
    paged_prefill_attention, paged_prefill_reference)

#: 2 x (state space, window) + the middle pair + 2 x (memory unit, cross)
TINY = dict(num_layers=10, pairs_self=2, pairs_cross=2, num_heads=4,
            num_kv_heads=2, d_model=32, d_ff=64, vocab_size=128,
            max_seq_len=128, sliding_window=8, ssm_state=4,
            dtype=jnp.float32)
REF = dict(heads=4, kv_heads=2, window=8, eps=1e-5, state=4, dt_rank=2,
           without=())
SERVING = {"enabled": True, "kv_block_size": 4, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 128}
#: float32 on the CPU against the reference at precision ``highest``: the
#: two differ by the order of summation alone (the other blocks' 3e-7 /
#: 2e-5; here the recurrence sums over up to 60 positions more)
ATOL = 2e-5


def build(**kw):
    """The tiny model with its vectors moved off their initial values
    (biases, norms, ``D_skip``) and its matrices enlarged, so that every
    mechanism shows in the logits."""
    model = build_model(phi4_flash_config("mini", **{**TINY, **kw}))
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 512))

    def move(a):
        if a.ndim - (a.shape[0] in (2, 1) and a.ndim > 1) <= 1 \
                and a.shape[-1] != TINY["ssm_state"]:
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        return a * 3.0
    return model, jax.tree_util.tree_map(move, params)


@pytest.fixture(scope="module")
def built():
    return build()


def serving_engine(model, params, **serving):
    return ds.init_inference(
        model, {"dtype": "float32", "max_out_tokens": 128,
                "temperature": 0.0, "serving": {**SERVING, **serving}},
        params=params).serving_engine()


def worst_gap(params, req):
    """The largest gap of a chosen token to the reference's best logit."""
    full = jnp.asarray(list(req.prompt) + list(req.output))[None]
    lg = np.asarray(reference.logits(params, full, REF))[0]
    return max(float(lg[len(req.prompt) + j - 1].max()
                     - lg[len(req.prompt) + j - 1][tok])
               for j, tok in enumerate(req.output))


def test_the_config_builds_its_own_model_class_and_counts_its_parameters(
        built):
    model, params = built
    assert type(model) is HybridSSMLM
    with pytest.raises(TypeError, match="build_model"):
        TransformerLM(model.config)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == model.config.num_params()
    full = phi4_flash_config("mini")
    assert full.num_params() == 3_852_556_800
    part = full.layer_params()
    assert [round(part[k] / 1e6, 2) for k in ("ssm", "window", "gmu",
                                              "cross")] == [
        119.90, 98.32, 104.87, 91.77]
    assert full.layer_kinds[:4] == ("ssm", "window", "ssm", "window")
    assert full.layer_kinds[16:20] == ("ssm", "full", "gmu", "cross")
    with pytest.raises(ValueError, match="layers of the pattern"):
        build_model(phi4_flash_config("mini", num_layers=30))


@pytest.mark.parametrize("without,moves", [
    ((), 0.0), (("window",), 0.1), (("memory",), 0.1), (("cross_kv",), 0.03),
    (("state_carry",), 1e-3)])
def test_full_forward_matches_the_reference_and_not_one_that_lacks_a_part(
        built, without, moves):
    model, params = built
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, dict(REF, without=without,
                                              chunk=16))
    diff = float(jnp.abs(got - want).max())
    assert diff < ATOL if not without else diff > moves, diff


def test_generates_through_the_dense_cache_like_one_pass(built):
    """``generate()``'s prefill + one-token steps (convolution tail, state
    and k / v carried in ``init_cache``'s tree) are the full forward."""
    model, params = built
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 30), 0, 128)
    want = model.apply(params, ids)
    cache = model.init_cache(2, 30)
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
    toks = np.asarray(eng.generate(ids[:1, :21], max_new_tokens=5))[0]
    full = jnp.concatenate([ids[:1, :21], jnp.asarray(toks)[None]], 1)
    lg = np.asarray(reference.logits(params, full, REF))[0]
    assert all(lg[20 + j].max() - lg[20 + j][t] < ATOL
               for j, t in enumerate(toks))
    # the default prompt_bucket would pad 21 tokens to 64 and run the
    # padding through the state: this block's prompts are never padded
    assert eng.config.prompt_bucket and "recurrent state" in \
        model.padded_prompt_refusal()
    assert [k[1] for k in eng._gen_fns] == [21]


@pytest.fixture(scope="module")
def served(built):
    """Four requests through one engine of three slots — prompts past the
    window and over several chunks, one of them seated in a freed slot —
    with the profiler's records of every iteration."""
    model, params = built
    srv = serving_engine(model, params)
    enqueue, chunks = srv._enqueue, []

    def logged(dec, chunk, *args, **kw):
        if chunk is not None:
            chunks.append(chunk[2:])            # (first row, rows)
        return enqueue(dec, chunk, *args, **kw)
    srv._enqueue = logged
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        rng = np.random.default_rng(0)
        reqs = [srv.submit(rng.integers(0, 128, p), max_new_tokens=n)
                for p, n in ((37, 9), (21, 12), (50, 5), (5, 7))]
        seen, slots, working = [], {}, True
        while working:
            working = srv.step()
            seen.append(prof.last())    # the last call's record too
            for slot, r in srv.scheduler.running.items():
                slots[r.req_id] = slot
    finally:
        prof.configure(enabled=False)
    return srv, reqs, seen, slots, chunks


def test_chunked_prefill_then_paged_decode_match_the_reference_logits(
        built, served):
    _, params = built
    srv, reqs, seen, slots, _ = served
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        assert worst_gap(params, r) < ATOL
    # the fourth request sat in a slot another had left
    assert slots[reqs[3].req_id] in {slots[r.req_id] for r in reqs[:3]}
    assert srv.decode_builds == 2 and not srv._flight
    assert srv.prefix_cache is False


def test_every_kind_of_state_is_handed_back(served):
    srv = served[0]
    alloc = srv.allocator
    assert alloc.kinds == ("full", "window", "state")
    assert alloc.num_used_by_kind() == {"full": 0, "window": 0, "state": 0}
    alloc.assert_consistent()
    # window 8 over blocks of 4: 3 pages decoding, 7 with 16 rows in flight
    assert srv.model.window_pages(4, 16) == (3, 7)
    assert alloc.window_held_max == {"decode": 3, "chunk": 7}
    # the pool in groups of 8: a slot's 3 or 7 pages touch at most 2
    assert (window_groups(3), window_groups(7)) == (2, 2)
    assert srv.window_blocks == PAGE_RUN * (2 * 2 + 2) + 1
    assert alloc.window_freed_total > 0
    # the extra state is counted in the pool's bytes
    assert srv.kv_pool_bytes > srv._pool_k.nbytes + srv._pool_v.nbytes


def test_each_counter_against_the_known_mix(built, served):
    """What the program counted, summed over the run, against the same
    sums made here from the four requests' lengths."""
    model, _ = built
    c = model.config
    srv, reqs, seen, _, chunks = served
    total = {k: sum(int(rec[k]) for rec in seen)
             for k in HybridSSMLM.PAGED_COUNTERS + (
                 "window_blocks_freed", "chunk_rows", "decode_rows")}
    prompts = [len(r.prompt) for r in reqs]
    news = [r.max_new_tokens for r in reqs]
    w = c.sliding_window
    assert total["chunk_rows"] == sum(prompts) == sum(n for _, n in chunks)
    decoded = total["decode_rows"]
    assert decoded == sum(n - 1 for n in news)
    assert total["ssm_chunk_rows"] == sum(prompts) * c.ssm_layers
    assert total["ssm_decode_rows"] == sum(n - 1 for n in news) \
        * c.ssm_layers
    assert total["state_slots_started"] == len(reqs)
    # (a prompt's last chunk leaves budget, and the next prompt's first
    # chunk takes what is left of it: the chunks as they were enqueued)
    assert len(chunks) > sum(-(-p // 16) for p in prompts) - 2
    assert total["cross_rows_spared"] == sum(n - 1 for _, n in chunks)
    # a decode row at position t reads t + 1 tokens in each layer that
    # walks the full layer's pages; a chunk's last row everything so far
    full = sum(sum(range(p + 1, p + n)) for p, n in zip(prompts, news))
    full += sum(at + n for at, n in chunks)
    assert total["kv_tokens_read_full"] == full * (1 + c.pairs_cross)
    win = sum(sum(min(t, w) for t in range(p + 1, p + n))
              for p, n in zip(prompts, news))
    win += sum(at + n - max(0, at - (w - 1)) for at, n in chunks)
    assert total["kv_tokens_read_window"] == win * c.pairs_self
    assert total["window_blocks_freed"] > 0
    # the same walks in pages: all of a full walk's, a window walk's from
    # the page of its first attended position (a row at t reads from t -
    # w, a chunk from its first row's window)
    blk = srv.block_size
    walks = [(0, t) for p, n in zip(prompts, news)
             for t in range(p + 1, p + n)] + [(0, at + n) for at, n in chunks]
    wwalks = [(max(0, t - w), t) for _, t in walks[:decoded]] + [
        (max(0, at - (w - 1)), at + n) for at, n in chunks]
    pages = lambda ws: sum(-(-t // blk) - f // blk for f, t in ws)  # noqa: E731
    assert total["kv_pages_read"] == (pages(walks) * (1 + c.pairs_cross)
                                      + pages(wwalks) * c.pairs_self)
    assert 0 <= total["kv_pages_in_runs"] <= total["kv_pages_read"]


def test_the_counters_count_what_the_kernels_were_handed(built):
    """One dispatch — a decode row at position 9 and a chunk of 13 rows —
    through the sound step and through one whose cross layers stop a page
    short: the count of keys read falls by the page in the two cross
    layers' walks and stays in the full layer's own; the rows spared are
    the chunk's live rows but its last."""
    model, params = built
    short, _ = build()
    serve_hybrid._with_fault(short, "cross_page_short", 4)

    def counters(m):
        tables = np.zeros((3, 2 * 6), np.int32)
        tables[0, :3] = tables[0, 6:9] = (1, 2, 3)
        tables[1, :4] = tables[1, 6:10] = (4, 5, 6, 7)
        cache = m.init_paged_cache(8, 4, jnp.float32)
        cache.update(extra=m.init_paged_extra(3, 4, 8, jnp.float32),
                     block_tables=jnp.asarray(tables),
                     lens=jnp.asarray([9, 0, 0], jnp.int32))
        new = m._apply_paged_mixed(
            params, cache, jnp.asarray([5, 0, 0]), jnp.asarray([1, 0, 0]),
            jnp.arange(16), jnp.int32(1), jnp.int32(0), jnp.int32(13))[2]
        return dict(zip(m.PAGED_COUNTERS, np.asarray(new["counters"])))
    sound, less = counters(model), counters(short)
    assert sound["kv_tokens_read_full"] == (10 + 13) * 3
    assert less["kv_tokens_read_full"] == (10 + 13) + 2 * (6 + 13)
    assert sound["kv_tokens_read_window"] == (8 + 13) * 2
    assert sound["cross_rows_spared"] == less["cross_rows_spared"] == 12
    assert (sound["ssm_chunk_rows"], sound["ssm_decode_rows"],
            sound["state_slots_started"]) == (13 * 3, 1 * 3, 1)
    # pages of 4: 3 + 4 in each of the three full walks and, the window
    # of 8 starting inside the first page, in the two window walks; a
    # table of six pages holds no run of eight
    assert (sound["kv_pages_read"], sound["kv_pages_in_runs"]) == (7 * 5, 0)


@pytest.mark.parametrize("fault", (None,) + serve_hybrid.PROGRAM_FAULTS)
def test_the_walks_reads_are_the_references_and_a_misread_is_seen(
        built, fault):
    """The cell's fifth number on the CPU: the mixed step's ``probe``
    (the attention's output of the full layer and of each cross layer for
    the row that yields a token), driven by the cell's own
    ``_served_cross_reads`` over a 45-token prompt in chunks of 16 and 7
    decode rows, is the reference's at those positions; with a fault put
    into the cross mixer the cross layers' reads are not, and the full
    layer's own still is."""
    model, params = built
    if fault:
        model, _ = build()
        serve_hybrid._with_fault(model, fault, 4)
    rng = np.random.default_rng(11)
    req = types.SimpleNamespace(prompt=rng.integers(0, 128, 45),
                                output=rng.integers(0, 128, 8))
    got = serve_hybrid._served_cross_reads(model, params, req, 4, 16, 4)
    fed = jnp.asarray(list(req.prompt) + list(req.output)[:-1])[None]
    want = reference.logits(params, fed, REF, states=True, last=8)[3][0]
    assert got.shape == want.shape == (3, 8, 4 * 8)
    err = [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
           for g, w in zip(got, want)]
    assert err[0] < 1e-5
    if fault:       # (a page of 4 of these 52 tokens moves them by 0.03)
        assert min(err[1:]) > 0.02, err
    else:
        assert max(err) < 1e-5, err


def test_the_state_carried_across_chunks_equals_one_pass(built):
    """The same prompt through chunks of 16 and through one chunk of 64:
    the slot's states read back from the engine are the reference's
    states after the same tokens."""
    model, params = built
    prompt = np.random.default_rng(3).integers(0, 128, 45)
    states = []
    for chunk in (16, 64):
        srv = serving_engine(model, params, prefill_chunk_tokens=chunk)
        req = srv.submit(prompt, max_new_tokens=4)
        srv.run()
        states.append(model.slot_state(srv._pool_x, 0, srv.num_slots))
    fed = jnp.asarray(list(prompt) + list(req.output)[:-1])[None]
    _, want, _, _ = reference.logits(params, fed, REF, states=True)
    for got in states:
        assert got.shape == want[0].shape == (3, 64, 4)
        assert float(jnp.linalg.norm(got - want[0])
                     / jnp.linalg.norm(want[0])) < 1e-5


def test_preemption_recomputes_the_state_from_the_tokens(built):
    """A pool too small for both requests: one is preempted, recomputed
    from its tokens (a first chunk at row 0: zero state) and still chooses
    the reference's tokens."""
    model, params = built
    srv = serving_engine(model, params, num_kv_blocks=20,
                         max_batch_slots=2)
    rng = np.random.default_rng(5)
    reqs = [srv.submit(rng.integers(0, 128, 30), max_new_tokens=22)
            for _ in range(2)]
    srv.run()
    assert srv.scheduler.preemption_count >= 1
    for r in reqs:
        assert len(r.output) == 22 and worst_gap(params, r) < ATOL
    assert srv.allocator.num_used_by_kind() == {"full": 0, "window": 0,
                                                "state": 0}


@pytest.mark.parametrize("rows,d_inner,nstate,valid", [
    (32, 64, 4, None), (256, 2048, 16, 200)])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_the_scan_kernel_against_the_loop(rows, d_inner, nstate, valid,
                                          start):
    ks = jax.random.split(jax.random.PRNGKey(rows), 7)
    x = jax.random.normal(ks[0], (rows, d_inner))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, d_inner)) - 2)
    b = jax.random.normal(ks[2], (rows, nstate))
    c = jax.random.normal(ks[3], (rows, nstate))
    a = -jnp.exp(0.5 * jax.random.normal(ks[4], (d_inner, nstate)))
    d_skip = jax.random.normal(ks[5], (d_inner,))
    s0 = (jnp.zeros((d_inner, nstate)) if start == "zero"
          else jax.random.normal(ks[6], (d_inner, nstate)))
    want_y, want_s = ssm_scan.ssm_scan_reference(x, dt, b, c, a, d_skip, s0,
                                                 valid)
    y, s1 = ssm_scan.ssm_chunk_scan(x, dt, b, c, a, d_skip,
                                    ssm_scan.state_to_tiles(s0), valid,
                                    interpret=True)
    upto = rows if valid is None else valid
    assert float(jnp.abs(y[:upto] - want_y[:upto]).max()) < 2e-5
    assert float(jnp.abs(ssm_scan.state_from_tiles(s1) - want_s).max()) \
        < 5e-6
    # the decode lane's one-row update is the same recurrence
    yd, sd = ssm_scan.ssm_decode_update(
        x[:3], dt[:3], b[:3], c[:3], a, d_skip,
        jnp.stack([ssm_scan.state_to_tiles(s0)] * 3))
    one_y, one_s = ssm_scan.ssm_scan_reference(x[2:3], dt[2:3], b[2:3],
                                               c[2:3], a, d_skip, s0)
    assert float(jnp.abs(yd[2] - one_y[0]).max()) < 5e-6
    assert float(jnp.abs(ssm_scan.state_from_tiles(sd[2]) - one_s).max()) \
        < 5e-6


@pytest.fixture(scope="module")
def window_pool():
    """A pool of 4-token pages, 2 kv heads of 8, 4 query heads, five slots
    of 12 pages, and one block full of NaN for the pages a window has
    left."""
    rng = np.random.default_rng(0)
    nb, blk, lanes = 64, 4, 16
    pk = jnp.asarray(rng.normal(size=(nb, blk, lanes)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(nb, blk, lanes)), jnp.float32)
    tables = rng.permutation(np.arange(1, nb - 1))[:60].reshape(
        5, 12).astype(np.int32)
    return (pk.at[63].set(jnp.nan), pv.at[63].set(jnp.nan), tables,
            jnp.asarray(rng.normal(size=(16, 4, 8)), jnp.float32))


#: the window of the tables the allocator made (``own_window_pool``): 18
#: pages of 4, so a walk crosses whole runs of 8
OWN_WINDOW = 70


@pytest.fixture(scope="module")
def own_window_pool():
    """The allocator's OWN window tables after a churn, over a pool of
    their size: five slots decoding at contexts of 0 (no sequence), 3, 77,
    150 and 163, and one that prefilled 96 rows and has a chunk of 16 in
    flight; sequences that came and went beside them, so a table's
    consecutive runs are not neighbours in the pool.  Each walk has whole
    runs in its middle and broken ones at both ends.  Returns the pool
    (its last block NaN), the decode tables and lengths, the chunk's
    table, and the queries."""
    rng = np.random.default_rng(1)
    block, chunk, window, lens = 4, 16, OWN_WINDOW, [0, 3, 77, 150, 163]
    alloc = PagedBlockAllocator(512, block, enable_prefix_cache=False)
    alloc.add_window_kind(window_pool_blocks(6, *HybridSSMLM.window_pages(
        types.SimpleNamespace(config=types.SimpleNamespace(
            sliding_window=window)), block, chunk)), window)
    at = {}

    def rows(seq, n):
        """``n`` more rows of ``seq``, one a dispatch."""
        if seq not in at:
            alloc.allocate(seq, 200)
        for r in range(at.get(seq, 0), at.get(seq, 0) + n):
            alloc.window_reserve(seq, r, r + 1)
        at[seq] = at.get(seq, 0) + n

    for _ in range(10):                 # five sequences side by side ...
        for seq in "vwxyz":
            rows(seq, 9)
    for seq in "wy":                    # ... two go, three stay a while
        alloc.free(seq)
    for n in (3, 77, 150, 163):
        for step in range(0, n, 5):
            rows(f"s{n}", min(5, n - step))
            if n == 150 and step == 100:
                for seq in "vxz":
                    alloc.free(seq)
    alloc.allocate("chunk", 200)
    for start in range(0, 96, chunk):
        alloc.window_reserve("chunk", start, start + chunk, "chunk")
        alloc.window_trim("chunk", start + chunk)
    alloc.window_reserve("chunk", 96, 96 + chunk, "chunk")
    alloc.assert_consistent()
    nan = alloc.window_blocks

    def table(seq):
        """The table a dispatch is handed, the pages handed back NaN."""
        first, held = alloc.window_pages_held(seq)
        return [nan] * first + held + [0] * (48 - first - len(held))

    tables = np.asarray([[0] * 48] + [table(f"s{n}") for n in lens[1:]],
                        np.int32)
    lens = np.asarray(lens, np.int32)
    runs = np.asarray(page_runs(np.where(tables == nan, 0, tables), lens,
                                block))
    for b in (2, 3, 4):                 # broken, whole .., broken
        first, last = (lens[b] - window) // block // PAGE_RUN, \
            (lens[b] - 1) // block // PAGE_RUN
        assert runs[b, first + 1:last].all() and runs[b, first + 1:last].size
        assert not runs[b, last] and tables[b, first * PAGE_RUN] == nan
    pool = [jnp.asarray(rng.normal(size=(nan + 1, block, 16)), jnp.float32)
            .at[nan].set(jnp.nan) for _ in range(2)]
    return (*pool, tables, lens, np.asarray(table("chunk"), np.int32),
            jnp.asarray(rng.normal(size=(16, 4, 8)), jnp.float32))


@pytest.mark.parametrize("pages", [None, 1, 2, 4, "own"])
def test_the_paged_kernel_walks_a_window_from_its_first_page_decode(
        window_pool, own_window_pool, pages):
    """Grouped-query heads (2 a kv head) and a window of 9: the walk
    starts at the page that holds a slot's first attended position, and
    the pages before it — handed on, so NaN here — start no DMA.  ``own``:
    a window of 70 over the tables the allocator's window kind made, runs
    of 8 consecutive blocks fetched whole where the walk covers them."""
    if pages == "own":
        pk, pv, dead, lens, _, q = own_window_pool
        window, pages = OWN_WINDOW, PAGE_RUN
        tables = np.where(dead == pk.shape[0] - 1, 0, dead)
    else:
        pk, pv, tables, q = window_pool
        window, lens = 9, np.array([0, 3, 17, 40, 48], np.int32)
        dead = tables.copy()
        for b, n in enumerate(lens):
            dead[b, :max(0, n - 9) // 4] = 63
    got = paged_decode_attention(q[:5], pk, pv, lens, jnp.asarray(dead),
                                 interpret=True, window=window,
                                 pages_per_program=pages)
    want = paged_attention_reference(q[:5], pk, pv, lens,
                                     jnp.asarray(tables), window=window)
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert not np.asarray(got[0]).any()                 # the empty slot


@pytest.mark.parametrize("base,rows,tile_rows", [
    *((base, rows, tile_rows) for base, rows in ((0, 16), (16, 16), (32, 11),
                                                 (32, 3))
      for tile_rows in (None, 4, 8)), (96, 16, 8)])
def test_the_paged_kernel_walks_a_window_from_its_first_page_chunk(
        window_pool, own_window_pool, base, rows, tile_rows):
    """A chunk row sees its own window; cut into tiles, each walker
    starts at its own first page.  Without a window the tiles are the
    whole chunk's walk.  The chunk from row 96: a window of 70 over the
    table the allocator's window kind made for it."""
    if base == 96:
        pk, pv, _, _, dead, q = own_window_pool
        got = paged_prefill_attention(q, pk, pv, base, rows,
                                      jnp.asarray(dead), interpret=True,
                                      window=OWN_WINDOW, tile_rows=tile_rows)
        want = paged_prefill_reference(
            q, pk, pv, base, rows,
            jnp.asarray(np.where(dead == pk.shape[0] - 1, 0, dead)),
            window=OWN_WINDOW)
        assert float(jnp.abs(got[:rows] - want[:rows]).max()) < 1e-6
        return
    pk, pv, tables, q = window_pool
    dead = tables[4].copy()
    dead[:max(0, base - 8) // 4] = 63
    got = paged_prefill_attention(q, pk, pv, base, rows, jnp.asarray(dead),
                                  interpret=True, window=9,
                                  tile_rows=tile_rows)
    want = paged_prefill_reference(q, pk, pv, base, rows,
                                   jnp.asarray(tables[4]), window=9)
    assert float(jnp.abs(got[:rows] - want[:rows]).max()) < 1e-6
    got = paged_prefill_attention(q, pk, pv, base, rows,
                                  jnp.asarray(tables[4]), interpret=True,
                                  tile_rows=tile_rows)
    want = paged_prefill_reference(q, pk, pv, base, rows,
                                   jnp.asarray(tables[4]))
    assert float(jnp.abs(got[:rows] - want[:rows]).max()) < 1e-6


def test_the_allocators_window_kind_hands_pages_back():
    alloc = PagedBlockAllocator(64, 4, enable_prefix_cache=False)
    assert alloc.kinds == ("full",)
    alloc.add_window_kind(10, 9)
    alloc.add_state_kind(2)
    alloc.allocate("a", 40)
    alloc.attach_state("a", 1)
    assert alloc.window_reserve("a", 0, 16, "chunk") == 0
    assert alloc.window_pages_held("a") == (0, [1, 2, 3, 4])
    # rows 16 .. 29 attend from row 8: pages 0 and 1 are handed back
    assert alloc.window_reserve("a", 16, 30, "chunk") == 2
    assert alloc.window_pages_held("a")[0] == 2
    assert alloc.window_held_max["chunk"] == 6
    assert alloc.window_trim("a", 30) == 3
    assert alloc.window_reserve("a", 30, 31) == 0
    assert alloc.window_held_max["decode"] == 3
    assert alloc.num_used_by_kind() == {"full": 10, "window": 3, "state": 1}
    alloc.assert_consistent()
    with pytest.raises(BlockPoolError, match="is held"):
        alloc.allocate("b", 4)
        alloc.attach_state("b", 1)
    with pytest.raises(BlockPoolError, match="window pool exhausted"):
        alloc.window_reserve("b", 0, 40)
    alloc.free("a")
    alloc.free("b")
    assert alloc.num_used_by_kind() == {"full": 0, "window": 0, "state": 0}
    alloc.assert_consistent()


@pytest.mark.parametrize("how,sentence", [
    ("train", "no backward kernel"),
    ("spec", "roll the state-space layers' recurrent state back"),
    ("kv_bits", "scale rows take no first page"),
    ("host_cache", "recurrent state is not a page"),
    ("mesh", "serves on one chip"),
    ("quant", "do not dequantize a layer at a time"),
    ("prefix", "not snapshotted")])
def test_what_it_does_not_take_is_refused_with_its_reason(built, how,
                                                          sentence):
    model, params = built
    if how == "train":
        with pytest.raises(NotImplementedError, match=sentence):
            ds.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
        return
    if how == "prefix":
        # not a refusal to build: the engine runs with the cache off
        assert sentence in model.prefix_cache_refusal()
        srv = serving_engine(model, params, prefix_cache=True)
        assert srv.prefix_cache is False
        assert srv.allocator.enable_prefix_cache is False
        return
    kwargs = {"spec": dict(spec=True), "kv_bits": dict(kv_bits=8),
              "host_cache": dict(host_cache=True),
              "mesh": dict(mesh_model=2),
              "quant": dict(weight_quant=True)}[how]
    assert sentence in model.paged_refusal(**kwargs)
    if how == "kv_bits":
        with pytest.raises(NotImplementedError, match=sentence):
            serving_engine(model, params, kv_cache_bits=8)
    if how == "spec":
        with pytest.raises(NotImplementedError, match=sentence):
            ds.init_inference(
                model, {"dtype": "float32", "max_out_tokens": 128,
                        "serving": SERVING}, params=params
            ).serving_engine(draft_model=model, draft_params=params)
