"""The Mamba-2 / attention hybrid block (``granitemoehybrid`` family,
``models/ssd_hybrid.py``) and its recurrence (``ops/transformer/
ssd_scan.py``) against their plain references: tiny sizes, CPU, float32,
seeded weights.

  - ``ssd_chunk_scan`` (the blocked form) against the loop over rows:
    from zero and from a given state, a ``valid_rows`` inside a block, 1,
    2 and 3 blocks, two chunks chained equal to one pass;
    ``ssd_decode_update`` against one step of the loop;
  - ``apply`` (full sequences) and ``generate()``'s cache against
    ``benchmark/lib/reference_granite_hybrid.py`` (a pattern ``mamba,
    mamba, attention, mamba`` twice, 4 query heads a key-value head, all
    four multipliers away from 1), and not against a reference that
    lacks a mechanism;
  - chunked prefill (prompts across chunks and blocks, requests
    interleaved) then paged decode through ``ServingEngine`` against the
    reference's full forward: logits, not tokens; slot reuse; preemption
    and recompute; the states and the pages read back;
  - each counter against a known mix; every refusal's sentence; the
    published sizes' parameter count; another ``layer_types`` list.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_granite_hybrid as reference
from deepspeed_tpu.models import (TransformerLM, build_model,
                                  granite_hybrid_config)
from deepspeed_tpu.models.hybrid_ssm import PerSlotStateLM
from deepspeed_tpu.models.ssd_hybrid import SSDHybridLM
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer import ssd_scan

PATTERN = ("mamba", "mamba", "attention", "mamba") * 2
TINY = dict(num_layers=8, layer_types=PATTERN, num_heads=8, num_kv_heads=2,
            d_model=32, d_ff=64, vocab_size=128, max_seq_len=128,
            ssm_heads=4, ssm_head_dim=8, ssm_state=16,
            attn_softmax_scale=0.2, embedding_multiplier=3.0,
            residual_multiplier=0.5, logits_scaling=2.0, dtype=jnp.float32)
REF = dict(layer_types=PATTERN, heads=8, kv_heads=2, eps=1e-5, ssm_heads=4,
           ssm_head_dim=8, state=16, attention_multiplier=0.2,
           embedding_multiplier=3.0, residual_multiplier=0.5,
           logits_scaling=2.0, rope_theta=10000, without=())
SERVING = {"enabled": True, "kv_block_size": 4, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 128}
#: float32 on the CPU against the reference at precision ``highest``: the
#: two differ by the order of summation alone (the other blocks' 3e-7 to
#: 2e-5; the hybrid block's 2e-5 for a recurrence over 60 positions)
ATOL = 2e-5
#: the blocked form against the loop over rows, both float32: the blocked
#: form sums a block's rows in another order and takes the decay as the
#: exponential of a difference of running sums (1e-6 to 3e-5 read at up to
#: 96 rows, on values of a few units)
SCAN_ATOL = 1e-4


def build(**kw):
    """The tiny model with its vectors moved off their initial values
    (norms, ``D_skip``, the convolution's bias) and its matrices enlarged,
    so that every mechanism shows in the logits."""
    model = build_model(granite_hybrid_config("h-micro", **{**TINY, **kw}))
    params = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 512))

    def move(path, a):
        name = path[-1].key if path[-1].key != "kernel" else path[-2].key
        if name in ("a_log", "dt_bias", "conv_w", "embedding"):
            return a
        if name in ("scale", "d_skip", "conv_b"):
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


def worst_gap(params, req, ref=REF):
    """The largest gap of a chosen token to the reference's best logit."""
    full = jnp.asarray(list(req.prompt) + list(req.output))[None]
    lg = np.asarray(reference.logits(params, full, ref))[0]
    return max(float(lg[len(req.prompt) + j - 1].max()
                     - lg[len(req.prompt) + j - 1][tok])
               for j, tok in enumerate(req.output))


def rel_err(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- the recurrence ----------------------------------------------------------
def scan_inputs(t, seed=0, h=4, p=8, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (t, h)) - 2.0),
            jax.random.normal(ks[2], (t, n)), jax.random.normal(ks[3], (t, n)),
            -jnp.exp(jax.random.uniform(ks[4], (h,), maxval=2.7)),
            jax.random.normal(ks[5], (h,)),
            jax.random.normal(ks[6], (h, p, n)))


@pytest.mark.parametrize("rows,valid", [(32, None), (64, None), (96, None),
                                        (96, 50), (64, 0)])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_the_blocked_scan_against_the_loop(rows, valid, start):
    """1, 2 and 3 blocks of 32 rows, a ``valid_rows`` inside the second
    block and one of 0 (the state comes back as it went in)."""
    x, dt, b, c, a, d, s = scan_inputs(rows)
    s0 = s if start == "given" else jnp.zeros_like(s)
    with jax.default_matmul_precision("highest"):
        y, s1 = ssd_scan.ssd_chunk_scan(x, dt, b, c, a, d, s0, valid,
                                        block_rows=32)
    y_ref, s_ref = ssd_scan.ssd_scan_reference(x, dt, b, c, a, d, s0, valid)
    live = rows if valid is None else valid
    assert float(jnp.abs(s1 - s_ref).max()) < SCAN_ATOL
    if live:
        assert float(jnp.abs(y[:live] - y_ref[:live]).max()) < SCAN_ATOL
    else:
        assert bool(jnp.all(s1 == s0))


def test_two_chunks_chained_equal_one_pass():
    x, dt, b, c, a, d, s = scan_inputs(128, seed=3)
    with jax.default_matmul_precision("highest"):
        y, s1 = ssd_scan.ssd_chunk_scan(x, dt, b, c, a, d, s, block_rows=32)
        ya, sa = ssd_scan.ssd_chunk_scan(x[:64], dt[:64], b[:64], c[:64], a,
                                         d, s, block_rows=32)
        yb, sb = ssd_scan.ssd_chunk_scan(x[64:], dt[64:], b[64:], c[64:], a,
                                         d, sa, 40, block_rows=32)
    assert float(jnp.abs(jnp.concatenate([ya, yb[:40]]) - y[:104]).max()) \
        < SCAN_ATOL
    _, s_ref = ssd_scan.ssd_scan_reference(x, dt, b, c, a, d, s, 104)
    assert float(jnp.abs(sb - s_ref).max()) < SCAN_ATOL
    with pytest.raises(ValueError, match="whole blocks"):
        ssd_scan.ssd_chunk_scan(x[:40], dt[:40], b[:40], c[:40], a, d, s,
                                block_rows=32)
    with pytest.raises(ValueError, match="state must be"):
        ssd_scan.ssd_chunk_scan(x, dt, b, c, a, d, s[:2], block_rows=32)


def test_the_decode_update_is_one_step_of_the_loop():
    x, dt, b, c, a, d, s = scan_inputs(6, seed=5)
    states = s[None] * jnp.arange(1.0, 7.0)[:, None, None, None]
    active = jnp.arange(6) % 2 == 0
    y, new = ssd_scan.ssd_decode_update(x, dt, b, c, a, d, states, active)
    for i in range(6):
        y_ref, s_ref = ssd_scan.ssd_scan_reference(
            x[i:i + 1], dt[i:i + 1], b[i:i + 1], c[i:i + 1], a, d,
            states[i])
        if active[i]:
            assert float(jnp.abs(new[i] - s_ref).max()) < 1e-6
            assert float(jnp.abs(y[i] - y_ref[0]).max()) < 1e-5
        else:
            assert bool(jnp.all(new[i] == states[i]))


def decode_case(dtype, layers=3, slots=6, seed=7):
    """Rows for ``slots`` slots (``x``, ``B``, ``C`` in ``dtype``), every
    third slot idle, and a buffer of ``layers`` layers' states."""
    x, dt, b, c, a, d, s = scan_inputs(slots, seed=seed)
    buf = s[None] * jnp.linspace(-1.5, 2.0, layers * slots)[:, None, None,
                                                            None]
    return ((x.astype(dtype), dt, b.astype(dtype), c.astype(dtype), a, d),
            buf, jnp.arange(slots) % 3 != 1)


def assert_decode_matches_the_loop(rows, buf, active, first, y, new):
    """``new`` is ``buf`` with the rows ``first ..`` one step of the loop
    on (to 1e-5 of the largest), an idle slot's and every other layer's
    rows bit for bit; ``y`` the loop's for the live slots."""
    x, dt, b, c, a, d = rows
    slots = x.shape[0]
    want_y, want_s = jax.vmap(lambda *r: ssd_scan.ssd_scan_reference(
        *(t[None] for t in r[:4]), a, d, r[4]))(
            x, dt, b, c, buf[first:first + slots])
    live = np.asarray(active)
    got = np.asarray(new[first:first + slots])
    assert np.abs(got - want_s)[live].max() < 1e-5 * np.abs(want_s).max()
    assert np.abs(np.asarray(y) - want_y[:, 0])[live].max() \
        < 1e-5 * np.abs(want_y).max()
    assert (got[~live] == np.asarray(buf[first:first + slots])[~live]).all()
    others = np.ones(buf.shape[0], bool)
    others[first:first + slots] = False
    assert (np.asarray(new)[others] == np.asarray(buf)[others]).all()
    assert float(np.abs(got[live] - np.asarray(
        buf[first:first + slots])[live]).max()) > 1e-2     # and it moved


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("first", [0, 6, 12])
def test_the_decode_kernel_updates_one_layers_rows_where_they_lie(dtype,
                                                                   first):
    """The kernel is handed three layers' states and one layer's first
    row: that layer's live slots move one step of the loop, everything
    else comes back bit for bit."""
    rows, buf, active = decode_case(dtype)
    y, new = ssd_scan.ssd_decode_update(*rows, buf, active, first=first)
    assert new.shape == buf.shape and y.dtype == new.dtype == jnp.float32
    assert_decode_matches_the_loop(rows, buf, active, first, y, new)


@pytest.mark.parametrize("heads,slots", [(1, 6), (2, 16)])
def test_the_decode_kernel_under_jit_with_a_traced_first_row(monkeypatch,
                                                             heads, slots):
    """As the serving step calls it: the first row a traced scalar, the
    buffer donated, a slot's heads in several blocks; at 16 slots the
    rows arrive in two groups of eight slots."""
    monkeypatch.setattr(ssd_scan, "DECODE_HEADS", heads)
    rows, buf, active = decode_case(jnp.float32, slots=slots, seed=11)
    step = jax.jit(lambda buf, layer: ssd_scan.ssd_decode_update(
        *rows, buf, active, first=layer * slots), donate_argnums=0)
    y, new = step(buf + 0.0, jnp.int32(1))
    assert_decode_matches_the_loop(rows, buf, active, slots, y, new)


def test_the_decode_kernel_refuses_what_it_cannot_tile():
    rows, buf, active = decode_case(jnp.float32)
    with pytest.raises(ValueError, match="state must be"):
        ssd_scan.ssd_decode_update(*rows, buf[:4], active)
    with pytest.raises(ValueError, match="state must be"):
        ssd_scan.ssd_decode_update(*rows, buf[:, :2], active)
    with pytest.raises(ValueError, match="128-lane"):
        ssd_scan.ssd_decode_update(*rows, buf, active, interpret=False)


# -- the block ---------------------------------------------------------------
def test_the_config_builds_its_own_model_class_and_counts_its_parameters(
        built):
    model, params = built
    assert type(model) is SSDHybridLM and isinstance(model, PerSlotStateLM)
    with pytest.raises(TypeError, match="build_model"):
        TransformerLM(model.config)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == model.config.num_params()
    # the published sizes, from shapes alone (no weights)
    full = granite_hybrid_config("h-micro")
    shapes = jax.eval_shape(
        lambda: build_model(full).init(jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        shapes)) == full.num_params() == 3_191_396_096
    part = full.layer_params()
    assert (part["mamba"], part["attention"]) == (76_182_976, 60_821_504)
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert full.period == 10 and full.period_runs == [
        ("mamba", 5), ("attention", 1), ("mamba", 4)]
    # 36 x (64 x 64 x 128 x 4 B + 3 x 4,352 x 2 B): 76.4 MB a slot
    extra = jax.eval_shape(lambda: build_model(full).init_paged_extra(
        1, 16, 0, jnp.bfloat16))
    assert sum(a.size * a.dtype.itemsize for a in
               jax.tree_util.tree_leaves(extra)) == 76_437_504
    with pytest.raises(ValueError, match="layer_types names"):
        build_model(granite_hybrid_config("h-micro", num_layers=30))
    with pytest.raises(ValueError, match="RMSNorms"):
        build_model(granite_hybrid_config("h-micro", norm_type="layernorm"))


@pytest.mark.parametrize("scale", [0.2, 1 / 64])
def test_the_seeded_attention_logits_have_the_stated_spread(scale):
    """``W_q`` and ``W_k`` are drawn so that a logit between two normed
    rows has the standard deviation ``QK_LOGIT_STD`` whatever the widths
    and the softmax scale; ``W_v`` at 0.02 like every other matrix."""
    from deepspeed_tpu.models.ssd_hybrid import QK_LOGIT_STD
    model = build_model(granite_hybrid_config("h-micro", **{
        **TINY, "d_model": 128, "attn_softmax_scale": scale}))
    c = model.config
    qkv = model.init(jax.random.PRNGKey(3))["attention"]["mixer"]["qkv"][
        "kernel"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (64, c.d_model))
    h = h / jnp.sqrt(jnp.mean(h * h, axis=-1, keepdims=True))
    nq, nkv = c.num_heads * c.hdim, c.kv_heads * c.hdim
    q = (h @ qkv[:, :nq]).reshape(64, c.kv_heads, -1, c.hdim)
    k = (h @ qkv[:, nq:nq + nkv]).reshape(64, c.kv_heads, c.hdim)
    logits = jnp.einsum("tgjd,sgd->gjts", q, k) * scale
    assert abs(float(logits.std()) / QK_LOGIT_STD - 1) < 0.2
    assert abs(float(qkv[:, nq + nkv:].std()) / 0.02 - 1) < 0.1


@pytest.mark.parametrize("without,moves", [
    ((), 0.0), (("state_carry",), 1e-3), (("decay",), 1e-3),
    (("d_skip",), 1e-2), (("gate_order",), 1e-2), (("attn_scale",), 1e-3),
    (("rotary",), 1e-3), (("residual_multiplier",), 0.1),
    (("logits_scaling",), 0.1)])
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
    and k / v carried in its dense cache) choose the tokens one pass over
    the whole sequence would."""
    model, params = built
    eng = ds.init_inference(model, {"dtype": "float32",
                                    "max_out_tokens": 128,
                                    "temperature": 0.0}, params=params)
    prompt = np.random.default_rng(2).integers(0, 128, (2, 19))
    out = np.asarray(eng.generate(prompt, max_new_tokens=7))
    full = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(out)], axis=1)
    lg = np.asarray(reference.logits(params, full, REF))
    for b in range(2):
        for j in range(7):
            row = lg[b, 19 + j - 1]
            assert row.max() - row[out[b, j]] < ATOL


@pytest.fixture(scope="module")
def served(built):
    """Four requests through one engine of three slots — prompts across
    several chunks and pages, one of them seated in a freed slot — with
    the profiler's records of every iteration."""
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
    srv, reqs, _, slots, _ = served
    for r in reqs:
        assert len(r.output) == r.max_new_tokens
        assert worst_gap(params, r) < ATOL
    # the fourth request sat in a slot another had left: what the slot
    # held of the other's state did not reach it
    assert slots[reqs[3].req_id] in {slots[r.req_id] for r in reqs[:3]}
    assert srv.decode_builds == 2 and not srv._flight
    assert srv.prefix_cache is False
    alloc = srv.allocator
    assert alloc.kinds == ("full", "state")
    assert alloc.num_used_by_kind() == {"full": 0, "window": 0,
                                                "state": 0}
    alloc.assert_consistent()
    # the recurrent state is counted in the pool's bytes
    assert srv.kv_pool_bytes > srv._pool_k.nbytes + srv._pool_v.nbytes


def test_each_counter_against_the_known_mix(built, served):
    """What the program counted, summed over the run, against the same
    sums made here from the four requests' lengths."""
    model, _ = built
    c = model.config
    srv, reqs, seen, _, chunks = served
    total = {k: sum(int(rec[k]) for rec in seen)
             for k in SSDHybridLM.PAGED_COUNTERS + (
                 "chunk_rows", "decode_rows", "dispatches")}
    prompts = [len(r.prompt) for r in reqs]
    news = [r.max_new_tokens for r in reqs]
    assert total["chunk_rows"] == sum(prompts) == sum(n for _, n in chunks)
    assert total["decode_rows"] == sum(n - 1 for n in news)
    assert total["ssm_chunk_rows"] == sum(prompts) * c.mamba_layers
    assert total["ssm_decode_rows"] == sum(n - 1 for n in news) \
        * c.mamba_layers
    assert total["state_slots_started"] == len(reqs)
    # a decode row at position t reads t + 1 tokens in each attention
    # layer; a chunk everything up to its last row
    read = sum(sum(range(p + 1, p + n)) for p, n in zip(prompts, news))
    read += sum(at + n for at, n in chunks)
    assert total["kv_tokens_read_full"] == read * c.attention_layers_count
    blk = srv.block_size
    pages = sum(-(-t // blk) for p, n in zip(prompts, news)
                for t in range(p + 1, p + n))
    pages += sum(-(-(at + n) // blk) for at, n in chunks)
    assert total["kv_pages_read"] == pages * c.attention_layers_count
    assert 0 < total["kv_pages_in_runs"] <= total["kv_pages_read"]


def test_the_state_and_the_pages_read_back_are_the_references(built):
    """The same prompt through chunks of 16 and through one chunk of 64:
    the slot's states read back from the engine are the reference's
    states after the same tokens, and the pages hold its keys and
    values."""
    model, params = built
    prompt = np.random.default_rng(3).integers(0, 128, 45)
    for chunk in (16, 64):
        srv = serving_engine(model, params, prefill_chunk_tokens=chunk)
        req = srv.submit(prompt, max_new_tokens=4)
        table = None
        while srv.step():
            if req.req_id in {r.req_id
                              for r in srv.scheduler.running.values()}:
                table = srv.allocator.block_table(req.req_id)
        fed = jnp.asarray(list(prompt) + list(req.output)[:-1])[None]
        _, want, kv = reference.logits(params, fed, REF, states=True)
        got = model.slot_state(srv._pool_x, 0, srv.num_slots)
        assert got.shape == want[0].shape == (6, 4, 8, 16)
        assert rel_err(got, want[0]) < 1e-5
        # (a state kept in bfloat16 is another state: no logit of this
        # size sees it, the state's own number does)
        _, low, _ = reference.logits(
            params, fed, dict(REF, without=("bf16_state",)), states=True)
        assert rel_err(got, low[0]) > 1e-4
        rows = fed.shape[1]
        pages = jnp.stack([pool[:, jnp.asarray(table)].reshape(
            pool.shape[0], -1, pool.shape[-1])[:, :rows]
            for pool in (srv._pool_k, srv._pool_v)], axis=1)
        assert rel_err(pages, kv[0]) < 1e-5


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


def test_another_pattern_builds_and_runs():
    """``layer_types`` is data: a list with no period (one scan body), an
    attention layer first, runs of one and of three."""
    pattern = ("attention", "mamba", "mamba", "mamba", "attention", "mamba")
    model, params = build(num_layers=6, layer_types=pattern)
    assert model.config.period == 6
    srv = serving_engine(model, params)
    req = srv.submit(np.random.default_rng(1).integers(0, 128, 23),
                     max_new_tokens=6)
    srv.run()
    assert worst_gap(params, req, dict(REF, layer_types=pattern)) < ATOL


@pytest.mark.parametrize("how,sentence", [
    ("train", "has no backward of its own"),
    ("spec", "roll the state-space layers' recurrent state back"),
    ("kv_bits", "carries no scale planes"),
    ("host_cache", "recurrent state is not a page"),
    ("mesh", "serves on one chip"),
    ("quant", "do not dequantize a layer at a time"),
    ("prefix", "not snapshotted"),
    ("padded", "pads a prompt on the right")])
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
        return
    if how == "padded":
        assert sentence in model.padded_prompt_refusal()
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
