"""The latent-attention block with a learned sparse selection (GLM-5.2
family) against its plain reference, ``benchmark/lib/reference_glm_dsa.py``:
tiny sizes (``index_topk`` 8, contexts of 40-70), CPU, float32, seeded
weights.  Each tolerance is float32 rounding over five layers of width 64
(the two sides sum in different orders) unless it says otherwise.

  - ``apply`` (expanded form, the selection as a mask) against the
    reference; every mechanism the block adds is seen;
  - chunked prefill then paged decode PAST ``index_topk`` through both
    pools against the reference's full forward: logits;
  - a ``shared`` layer uses the set of the ``full`` layer before it: the
    masks handed on are the reference's, and a ``shared`` layer turned
    ``full`` changes the logits;
  - at contexts of at most ``index_topk`` the block is the same block with
    dense latent attention;
  - a request whose prompt is a prefix-cache hit gets the logits of the
    same request on a cold cache, with one dispatch in flight: the indexer
    keys of shared blocks are right;
  - the kernels and the sort-free selection against plain references;
  - the gate with sigmoid + bias + renormalisation; the shares add up; the
    counters of a known mix; what cannot serve it refuses with its reason;
  - the mixed step's own sets, layer by layer (``probe=True``), against
    the reference's masks, and against a reference whose ``shared`` layers
    select for themselves.
"""
import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_glm_dsa as reference
from deepspeed_tpu.inference.serving import latent_block_bytes
from deepspeed_tpu.models import build_model, glm_moe_dsa_config
from deepspeed_tpu.models.latent_moe import DenseLeadMoELM
from deepspeed_tpu.models.sandwich_moe import SandwichMoELM
from deepspeed_tpu.models.sparse_latent_moe import SparseLatentMoELM
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    mla_paged_reference)

KINDS = ("full", "shared", "shared", "shared", "full")
TINY = dict(num_layers=5, first_k_dense=1, num_heads=4, d_model=64,
            d_ff=128, head_dim=24, vocab_size=128, max_seq_len=128,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, expert_d_ff=32,
            n_routed_experts=8, moe_topk=3, index_n_heads=4,
            index_head_dim=16, index_topk=8, indexer_types=KINDS,
            dtype=jnp.float32)
REF = dict(heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           kv_lora_rank=32, eps=1e-5, rope_theta=8e6, index_heads=4,
           index_head_dim=16, index_topk=8, indexer_types=KINDS,
           n_routed_experts=8, moe_topk=3, scale=2.5, block=16)
SERVING = {"enabled": True, "kv_block_size": 8, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 64}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(**kw):
    model = build_model(glm_moe_dsa_config("5.2", **{**TINY, **kw}))
    return model, model.init(jax.random.PRNGKey(0))


def serving_engine(model, params, **serving):
    return ds.init_inference(
        model, {"dtype": "float32", "max_out_tokens": 128,
                "temperature": 0.0, "serving": {**SERVING, **serving}},
        params=params).serving_engine()


@functools.lru_cache(maxsize=None)
def shared():
    """ONE model, its weights and a serving engine over them for the
    tests that only submit and read (an engine is 20 s of tracing and
    compiling the step's two shapes with the kernels interpreted)."""
    model, params = build()
    return model, params, serving_engine(model, params)


def gaps(req, lg):
    """Each chosen token's distance to the best logit at its position."""
    return [float(lg[len(req.prompt) + j - 1].max()
                  - lg[len(req.prompt) + j - 1][tok])
            for j, tok in enumerate(req.output)]


def test_the_config_builds_its_own_model_class_and_counts_its_parameters():
    model, params = build()
    assert type(model) is SparseLatentMoELM
    # what the sandwich block and this one share lives once
    assert issubclass(SparseLatentMoELM, DenseLeadMoELM) \
        and issubclass(SandwichMoELM, DenseLeadMoELM)
    assert SparseLatentMoELM.expert_layer is SandwichMoELM.expert_layer
    # indexer weights only where ``full``, stacked by full-layer number
    assert params["indexer"]["wq"]["kernel"].shape == (2, 32, 4 * 16)
    assert params["indexer"]["k_norm"]["bias"].shape == (2, 16)
    assert "indexer" not in params["blocks"] \
        and "indexer" not in params["dense_blocks"]
    assert params["blocks"]["moe"]["bias"].shape == (4, 8)   # noaux_tc
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == model.config.num_params()
    full = glm_moe_dsa_config("5.2")
    # 78 layers, 21 of them ``full``, 256 experts: ~750B-A40B as
    # published, less the multi-token-prediction module
    assert full.layer_kinds.count("full") == 21 \
        and full.layer_kinds[:7] == ("full",) * 3 + ("shared",) * 3 + (
            "full",)
    assert round(full.num_params() / 1e9) == 743
    with pytest.raises(ValueError, match="the first 'full'"):
        build(indexer_types=("shared",) + KINDS[1:])
    with pytest.raises(ValueError, match="indexer_types must name 5"):
        build(indexer_types=KINDS[:3])


def test_full_forward_matches_the_reference_and_hands_its_selection_on():
    """Logits and every layer's mask: the ``shared`` layers' are the
    ``full`` layer's before them, in the program and the reference
    alike, and past ``index_topk`` a row keeps exactly 8 positions."""
    model, params = build()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 44), 0, 128)
    want, ref_masks = reference.logits(params, ids, REF,
                                       return_selection=True)
    assert float(jnp.abs(model.apply(params, ids) - want).max()) < 2e-5
    _, _, masks = model.hidden_states_and_aux(params, ids,
                                              return_selection=True)
    np.testing.assert_array_equal(masks, ref_masks)
    masks = np.asarray(masks)
    for at in (1, 2, 3):
        np.testing.assert_array_equal(masks[at], masks[0])
    assert (masks[4] != masks[0]).any()
    # (scores that tie with the 8th are all kept: at 4 index heads a
    # row's score is exactly 0 where every head's product is negative)
    kept, least = masks.sum(-1), np.minimum(np.arange(44) + 1, 8)
    assert (kept >= least).all() and (kept == least).mean() > 0.95


@functools.lru_cache(maxsize=None)
def share_case():
    """A share of the experts: the program's logits and the reference's,
    given the same share."""
    model, params = build(experts_held=(2, 6))
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 28), 0, 128)
    return (params, ids, model.apply(params, ids),
            reference.logits(params, ids, REF, experts_held=(2, 6)))


@pytest.mark.parametrize("leave_out", ["selection", "relu", "w", "shared",
                                       "experts", "shared_expert", "bias",
                                       "float8"])
def test_a_share_matches_the_reference_and_sees_each_mechanism(leave_out):
    """A share of the experts against the reference given the same; the
    reference with one mechanism left out is far from it: logits here
    spread over +-0.6."""
    params, ids, got, want = share_case()
    assert float(jnp.abs(got - want).max()) < 2e-5
    left_out = reference.logits(params, ids, REF, experts_held=(2, 6),
                                leave_out=(leave_out,))
    assert float(jnp.abs(got - left_out).max()) > 5e-3


def test_a_shared_layer_turned_full_changes_the_logits():
    """The same weights with layer 2 computing its own selection (the
    indexer stack grows by one; the others keep their weights)."""
    model, params = build()
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, 128)
    kinds = ("full", "shared", "full", "shared", "full")
    other = build_model(dataclasses.replace(model.config,
                                            indexer_types=kinds))
    grown = dict(params, indexer=jax.tree_util.tree_map(
        lambda a: a[jnp.asarray([0, 1, 1])], params["indexer"]))
    assert float(jnp.abs(other.apply(grown, ids)
                         - model.apply(params, ids)).max()) > 5e-3


def test_short_contexts_are_dense_latent_attention():
    """At 8 tokens or fewer every earlier token is selected: the block's
    logits are those of the reference with the selection left out, in
    ``apply`` and through the pools."""
    model, params, srv = shared()
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, 8), 0, 128)
    dense = reference.logits(params, ids, REF, leave_out=("selection",))
    assert float(jnp.abs(model.apply(params, ids) - dense).max()) < 2e-5
    req = srv.submit(np.asarray(ids[0, :5]), max_new_tokens=3)
    srv.run()
    full = jnp.asarray(list(req.prompt) + list(req.output))[None]
    lg = np.asarray(reference.logits(params, full, REF,
                                     leave_out=("selection",)))[0]
    assert max(gaps(req, lg)) < 1e-4


def test_chunked_prefill_then_paged_decode_match_the_reference_logits():
    """Three requests interleaved, prompts over several chunks, contexts
    of 43-59 (past ``index_topk`` 8 from the first chunk on): every token
    the engine chose is the reference's best at its position, by logits
    (1e-4: float32 through the absorbed form, the gathered rows and the
    online softmax); one program in two shapes, two pools in place, and
    the selection's counters on the result array."""
    model, params, srv = shared()
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        t0 = time.perf_counter()
        rng = np.random.default_rng(0)
        reqs = [srv.submit(rng.integers(0, 128, p), max_new_tokens=n)
                for p, n in ((37, 6), (21, 5), (50, 9))]
        srv.run()
        seen, complete = prof.iterations(t0, time.perf_counter())
    finally:
        prof.configure(enabled=False)
    assert complete
    read = 0
    for r in reqs:
        full = jnp.asarray(list(r.prompt) + list(r.output))[None]
        lg, masks = reference.logits(params, full, REF,
                                     return_selection=True)
        lg = np.asarray(lg)[0]
        assert len(r.output) == r.max_new_tokens
        assert max(gaps(r, lg)) < 1e-4
        # a chunk row's set is the reference's mask, ties at the 8th
        # score and all (with 4 index heads a score is exactly 0 now and
        # then); a decode row's is cut to 8 in position order
        sizes = np.asarray(masks[:, 0].sum(-1))          # [layers, rows]
        read += sizes[:, :len(r.prompt)].sum() + np.minimum(
            sizes[:, len(r.prompt):-1], 8).sum()
    assert srv.decode_builds == 2 and srv.allocator.num_used == 0
    # two kinds of row under one table: a latent row a layer, an indexer
    # key a ``full`` layer
    assert srv._pool_k.shape == (5, 64, 8, 128)
    assert srv._pool_v.shape == (2, 64, 8, 16)
    assert srv.kv_pool_bytes == 64 * latent_block_bytes(
        8, 32, 8, cache_itemsize=4, layers=5, index_width=16,
        index_layers=2)
    rows = int(seen["decode_rows"].sum() + seen["chunk_rows"].sum())
    assert rows == sum(len(r.prompt) + len(r.output) - 1 for r in reqs)
    assert seen["index_rows"].sum() == rows * 2
    assert seen["sparse_rows_reused"].sum() == rows * 3
    assert seen["moe_rows_shared"].sum() == rows * 4
    # every row reads its own set in each of 5 layers — the sets' sizes
    # as the program counted them, min(its context, 8) but for ties — and
    # scores its whole context in each of 2
    contexts = [at + 1 for r in reqs
                for at in range(len(r.prompt) + len(r.output) - 1)]
    assert seen["sparse_tokens_read"].sum() == read \
        >= 5 * sum(min(c, 8) for c in contexts)
    assert seen["index_keys_scored"].sum() == 2 * sum(contexts)
    assert 0 < seen["sparse_tokens_read"].sum() \
        < seen["latent_tokens_read"].sum()


def test_a_prefix_hit_request_gets_the_logits_of_a_cold_cache():
    """A document, then questions behind it while the loop keeps one
    dispatch in flight: the document's blocks — latent rows AND indexer
    keys — come from the prefix cache, only the question is computed, and
    the tokens are those of the same request on a cold cache and the
    reference's best by logits."""
    model, params, srv = shared()
    rng = np.random.default_rng(5)
    doc = rng.integers(0, 128, 40)                    # five whole blocks
    questions = [rng.integers(0, 128, n) for n in (5, 16)]
    cold_srv = serving_engine(model, params, prefix_cache=False)
    cold = [cold_srv.submit(np.concatenate([doc, q]), max_new_tokens=4)
            for q in questions]
    cold_srv.run()
    assert not any(r.cache_hit_tokens for r in cold)
    srv.submit(doc, max_new_tokens=1)
    srv.run()
    before = dict(srv.flight_counts)
    warm = [srv.submit(np.concatenate([doc, q]), max_new_tokens=4)
            for q in questions]
    srv.run()
    # the hits were served with a dispatch in flight
    assert srv.flight_counts["ahead_dispatches"] \
        - before["ahead_dispatches"] >= 3
    for w, c in zip(warm, cold):
        assert w.cache_hit_tokens == 40
        assert w.output == c.output
        full = jnp.asarray(list(w.prompt) + list(w.output))[None]
        lg = np.asarray(reference.logits(params, full, REF))[0]
        assert max(gaps(w, lg)) < 1e-4
    assert srv.decode_builds == 2 and not srv._flight


class TestKernelsAndSelection:
    """The two kernels (interpret mode) and the sort-free selection at
    the cell's row widths against plain references."""
    J, D, BLOCK = 32, 128, 16

    def pools(self, tokens, slots, width, seed=13):
        """A pool and a table a slot; slot 0's pages are consecutive pool
        blocks (its page groups are one DMA each), the others' scattered."""
        pages = -(-tokens // self.BLOCK) + 1
        nb = 1 + slots * pages
        pool = jax.random.normal(jax.random.PRNGKey(seed),
                                 (nb, self.BLOCK, width))
        rest = np.random.default_rng(1).permutation(
            np.arange(1 + pages, nb))
        order = np.concatenate([np.arange(1, 1 + pages), rest])
        return pool, jnp.asarray(order.reshape(slots, pages).astype(
            np.int32))

    def test_index_scores_of_decode_rows_and_of_a_chunk(self):
        ipool, tables = self.pools(70, 3, self.D)
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        total = jnp.asarray([70, 0, 33], jnp.int32)
        q = jax.random.normal(k1, (3, 1, self.J, self.D))
        w = jax.random.normal(k2, (3, 1, self.J))
        got = sla.dsa_index_scores(q, w, ipool, total - 1, total, tables,
                                   interpret=True, pages_per_program=2)
        want = sla.index_scores_reference(q, w, ipool, tables)
        for slot, n in enumerate((70, 0, 33)):
            np.testing.assert_allclose(got[slot, 0, :n], want[slot, 0, :n],
                                       rtol=2e-5, atol=2e-4)
        # a chunk of 24 rows (16 valid) behind 30 cached tokens
        q = jax.random.normal(k1, (1, 24, self.J, self.D))
        w = jax.random.normal(k2, (1, 24, self.J))
        got = sla.dsa_index_scores(q, w, ipool, jnp.asarray([30]),
                                   jnp.asarray([46]), tables[:1],
                                   interpret=True, pages_per_program=2)
        want = sla.index_scores_reference(q, w, ipool, tables[:1])
        for row in range(16):
            np.testing.assert_allclose(got[0, row, :31 + row],
                                       want[0, row, :31 + row], rtol=2e-5,
                                       atol=2e-4)

    @pytest.mark.parametrize("k", [8, 200])
    def test_the_selection_is_the_exact_top_k_in_position_order(self, k):
        rng = np.random.default_rng(k)
        scores = rng.standard_normal((5, 300)).astype(np.float32)
        scores[0, :50] = scores[0, 50:100]                   # ties
        seen = np.arange(300)[None] < np.array([300, 120, 7, 0, 201])[:, None]
        scores = np.where(seen, scores, np.nan)     # unseen: anything
        got, count = sla.select_positions(jnp.asarray(scores),
                                          jnp.asarray(seen), k)
        kth = sla.kth_largest(jnp.asarray(scores), jnp.asarray(seen), k)
        for row in range(5):
            n = int(seen[row].sum())
            vals = scores[row, :n]
            want = np.sort(np.argsort(-vals, kind="stable")[:k])
            assert int(count[row]) == min(n, k)
            if n > k:
                assert float(kth[row]) == np.sort(vals)[-k]
            else:
                assert float(kth[row]) == -np.inf
            picked = np.asarray(got[row, :min(n, k)])
            assert (np.diff(picked) > 0).all()
            # ties at the threshold are kept in position order: the values
            # picked are the k largest either way
            np.testing.assert_array_equal(np.sort(vals[picked]),
                                          np.sort(vals[want]))

    def test_pool_rows_of_reads_the_table(self):
        tables = jnp.asarray(np.random.default_rng(3).integers(
            0, 90000, (3, 37)).astype(np.int32))
        pos = jnp.asarray(np.random.default_rng(4).integers(
            0, 37 * 16, (3, 50)).astype(np.int32))
        got = sla.pool_rows_of(pos, tables, 16)
        want = np.take_along_axis(np.asarray(tables), np.asarray(pos) // 16,
                                  axis=1) * 16 + np.asarray(pos) % 16
        np.testing.assert_array_equal(got, want)

    def test_sparse_chunk_attention_and_gathered_decode_rows(self):
        """A chunk's rows under the selection as a mask, and decode rows
        over gathered tokens, against dense attention with the same mask
        (2e-4: float32, the online softmax's other order of sums)."""
        h, r, dr, lanes = 64, 512, 64, 640
        pool, tables = self.pools(60, 2, lanes)
        pool = pool.at[..., r + dr:].set(0.0)
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
        base, chunk, valid, k = 28, 32, 26, 8
        # whatever lies past the slot's 54 tokens must reach no product
        pool = pool.at[tables[0, 3], 6:].set(jnp.nan).at[tables[0, 4]].set(
            jnp.nan)
        ql = jax.random.normal(k1, (chunk, h, r)) * 0.1
        qr = jax.random.normal(k2, (chunk, h, dr)) * 0.1
        plane = jax.random.normal(k3, (chunk, 80))       # 5 pages of 16
        pos = jnp.arange(80)[None]
        seen = (pos <= base + jnp.arange(chunk)[:, None]) & (
            jnp.arange(chunk)[:, None] < valid)
        floor = sla.kth_largest(plane, seen, k)
        got = sla.dsa_sparse_prefill_attention(
            ql, qr, pool, plane, floor, base, valid, tables[0], 256 ** -0.5,
            interpret=True, pages_per_program=2)
        chosen = seen & (plane >= floor[:, None])
        assert (np.asarray(chosen.sum(1))[:valid] == k).all()
        rows = jnp.nan_to_num(pool[tables[0]].reshape(-1, lanes))
        s = (jnp.einsum("chr,sr->chs", ql, rows[:, :r])
             + jnp.einsum("chd,sd->chs", qr, rows[:, r:r + dr])) * 256 ** -0.5
        s = jnp.where(chosen[:, None], s, -1e30)
        want = jnp.einsum("chs,sr->chr", jax.nn.softmax(s, -1), rows[:, :r])
        np.testing.assert_allclose(got[:valid], want[:valid], rtol=2e-4,
                                   atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(got)))
        # decode rows: the same selection as positions, gathered by token
        picked, count = sla.select_positions(plane[:2], seen[:2], k)
        flat = sla.pool_rows_of(picked, jnp.stack([tables[0]] * 2), 16)
        got = sla.gathered_latent_attention(ql[:2], qr[:2], pool, flat,
                                            count, 256 ** -0.5)
        np.testing.assert_allclose(got, want[:2], rtol=2e-4, atol=2e-5)
        dead = sla.gathered_latent_attention(ql[:2], qr[:2], pool, flat,
                                             count * 0, 256 ** -0.5)
        assert not np.asarray(dead).any()
        # and the dense walk's answer where everything is chosen
        got = sla.dsa_sparse_prefill_attention(
            ql, qr, pool, plane, jnp.full((chunk,), -jnp.inf), base, valid,
            tables[0], 256 ** -0.5, interpret=True, pages_per_program=2)
        dense = mla_paged_reference(ql[None], qr[None], pool,
                                    np.array([base]),
                                    np.array([base + valid]), tables[:1],
                                    256 ** -0.5)[0]
        np.testing.assert_allclose(got[:valid], dense[:valid], rtol=2e-4,
                                   atol=2e-5)


def test_the_gate_with_sigmoid_bias_and_renormalisation():
    """``route`` in GLM's form — sigmoid scores, the picks by score +
    bias, weights of the scores alone renormalised and scaled — against
    the reference's gate: the same picks, weights to 1e-6."""
    u = jax.random.normal(jax.random.PRNGKey(5), (9, 16))
    w = jax.random.normal(jax.random.PRNGKey(4), (16, 12)) * 0.5
    bias = jax.random.normal(jax.random.PRNGKey(6), (12,)) * 0.3
    got = dropless.route(u, w, bias, 4, 2.5, scoring="sigmoid",
                         renormalize=True)
    with jax.default_matmul_precision("highest"):
        chosen, weight = reference.gate(
            {"router": {"kernel": w}, "bias": bias}, u,
            dict(REF, moe_topk=4, float8=False))
    np.testing.assert_array_equal(got.index, chosen)
    np.testing.assert_allclose(got.weight, weight, rtol=1e-6)
    unbiased = dropless.route(u, w, None, 4, 2.5, scoring="sigmoid",
                              renormalize=True)
    assert (np.asarray(unbiased.index) != np.asarray(got.index)).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 8 over 4 shares.  Every chip computes the shared expert (and
    the router, the indexer and attention) alike, so the four shares'
    ``F_l`` less three shared-expert outputs — the routed parts of all
    shares plus the shared expert counted once — are the reference's
    whole expert layer."""
    model, params = build()
    c = model.config
    layer = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 19, c.d_model))
    ref_cfg = dict(REF, float8=False)
    with jax.default_matmul_precision("highest"):
        want = reference.moe(layer, u, ref_cfg)
        shared = reference.ffn(layer["shared"], u, ref_cfg)
    total = jnp.zeros_like(want)
    for share in range(4):
        lo, hi = 2 * share, 2 * share + 2
        part = build_model(dataclasses.replace(c, experts_held=(lo, hi)))
        held = dict(layer, moe=dict(layer["moe"], experts={
            k: v[lo:hi] for k, v in layer["moe"]["experts"].items()}))
        got, _ = part.expert_layer(held, u)
        with jax.default_matmul_precision("highest"):
            ref_part = reference.moe(held, u, ref_cfg, experts_held=(lo, hi))
        assert float(jnp.abs(got - ref_part).max()) < 1e-5
        total = total + got
    assert float(jnp.abs(total - 3 * shared - want).max()) < 1e-5


class TestRefusals:
    def test_paged_refusals_with_their_reasons(self):
        model, params = build()
        for kw, reason in ((dict(spec=True), "speculative lane"),
                           (dict(kv_bits=8), "kv_cache_bits"),
                           (dict(mesh_model=2), "one chip"),
                           (dict(host_cache=True), "indexer keys"),
                           (dict(weight_quant=True), "weight-only")):
            assert reason in model.paged_refusal(**kw)
        assert model.paged_refusal() is None
        with pytest.raises(NotImplementedError, match="kv_cache_bits"):
            serving_engine(model, params, kv_cache_bits=8)
        with pytest.raises(NotImplementedError, match="one chip"):
            serving_engine(model, params, mesh={"data": 1, "model": 2},
                           max_batch_slots=4)
        with pytest.raises(NotImplementedError, match="host tier"):
            serving_engine(model, params, host_cache={
                "enabled": True, "dram_budget_bytes": 1 << 20})

    def test_training_and_the_dense_cache(self):
        model, _ = build()
        assert "no training kernel" in model.training_refusal()
        with pytest.raises(NotImplementedError, match="does not train"):
            ds.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
        with pytest.raises(NotImplementedError, match="paged serving path"):
            model.init_cache(1, 16)


def _cell_config():
    from benchmark.lib import model as model_lib
    return model_lib.load_config("benchmark/configs/glm-5.2.json")


def test_the_cells_configuration_file_is_what_the_program_builds():
    """``benchmark/configs/glm-5.2.json`` through the cell's own runner:
    the published widths, published layers 2..6 (one dense layer before
    four expert layers, ``full`` first and last), the chip's share of 16
    experts and 3.88 B held parameters (7.77 GB in bfloat16); every
    number of the catalog row beside the ``model-configs`` guide is in the
    file under its own key, and only the eight cuts differ."""
    from benchmark.runners import serve_sparse_latent as runner
    config = _cell_config()
    mc, ref, held = runner.build(config)
    assert type(build_model(mc)) is SparseLatentMoELM
    assert held == (0, 16) and ref["n_routed_experts"] == 256
    assert (mc.num_layers, mc.first_k_dense, mc.scan_length,
            mc.full_layers) == (5, 1, 4, 2)
    assert mc.layer_kinds == KINDS and ref["indexer_types"] == KINDS
    # the issue reckoned 3,882.6 M from the matrices; norms and biases add 0.1
    assert mc.num_params() == 3_882_696_704
    assert (mc.mla_params(), mc.indexer_params()) == (165_022_208, 9_371_904)
    assert (mc.d_model, mc.ff_dim, mc.expert_d_ff, mc.num_heads) == (
        6144, 12288, 2048, 64)
    assert set(config["changed"]) == set(config["published"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm-5.2")
    assert set(entry["reduced"]) == set(config["changed"])
    full = glm_moe_dsa_config("5.2")
    for key in ("num_hidden_layers", "first_k_dense_replace",
                "n_routed_experts", "vocab_size", "max_position_embeddings"):
        attr = runner.PUBLISHED.get(key, key)
        assert getattr(full, attr) == config["published"][key]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-5.2")
        assert config["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if config[k] != v}
        assert differs == set(entry["reduced"])
        assert list(full.layer_kinds) == row["config"]["indexer_types"]
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, index_topk=1024))
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, topk_method="greedy"))


def test_the_cells_work_counts_are_lower_bounds_from_shapes():
    """``lib/costs_dsa.py``: a decode row at 32 k scores 32 k tokens and
    attends 2,048; a 512-row chunk behind 16 k counts each row's own set
    and no more bytes than one set."""
    from benchmark.lib import costs_dsa
    ops, nbytes = costs_dsa.index_scores_cost(32768, 1, 32, 128)
    assert (ops, nbytes) == (2 * 32768 * 32 * 128, 32768 * 256)
    ops, nbytes = costs_dsa.selected_attention_cost(32768, 1, 2048, 64, 512,
                                                    64)
    assert (ops, nbytes) == (2 * 2048 * 64 * 1088, 2048 * 1152)
    ops, nbytes = costs_dsa.selected_attention_cost(16896, 512, 2048, 64,
                                                    512, 64)
    assert (ops, nbytes) == (2 * 512 * 2048 * 64 * 1088, 2048 * 1152)
    # a chunk that starts below the top-k: rows of 3, 4, 5 then 5, 5
    ops, _ = costs_dsa.selected_attention_cost(7, 5, 5, 1, 1, 0)
    assert ops == 2 * (3 + 4 + 5 + 5 + 5) * 2


def test_the_cells_values_from_a_known_mix_of_counters():
    from benchmark.runners import serve_sparse_latent as runner
    from deepspeed_tpu.observability.overlap import OverlapProfiler
    prof = OverlapProfiler(capacity=8)
    prof.configure(enabled=True)
    t0 = time.perf_counter()
    for rows, context in ((32, 40000), (544, 50000)):
        prof.begin()
        prof.mark(4)
        prof.count_dispatch(32, rows - 32, rows, index_rows=rows * 2,
                            index_keys_scored=context * 2,
                            sparse_tokens_read=rows * 2048 * 5,
                            sparse_rows_reused=rows * 3)
        prof.end()
    mc = runner.build(_cell_config())[0]
    got = runner._selection_values(prof, mc, (t0, time.perf_counter()))
    assert got["index_reuse_share"] == pytest.approx(60.0)
    assert got["select_density"] == pytest.approx(
        100 * 576 * 2048 / 90000)
    assert runner._selection_values(
        OverlapProfiler(capacity=2), mc, (t0, time.perf_counter())) == {}


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_the_cells_order_gives_every_stretch_the_same_work(seed):
    """The traffic file through the runner's ``requests``: a block of 72
    holds every (document, question, output) combination once; every run
    of 9 requests holds each (question, output) pair once, so a window's
    edges cut no more than a run; the order comes from the seed."""
    from benchmark.runners import serve_sparse_latent as runner
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-docqa-sat.json")) as f:
        mix = json.load(f)
    work = runner.requests(mix, seed, 512, shrink=16)
    q = work["prompt_len"] - np.array(mix["doc_lens"])[work["doc"]] // 16
    combos = np.stack([work["doc"], q, work["max_new"]], axis=1)
    assert len(combos) == mix["block"] * mix["blocks"]
    for b in range(mix["blocks"]):
        block = combos[b * 72:(b + 1) * 72]
        assert len(np.unique(block, axis=0)) == 72
        for r in range(8):
            assert len(np.unique(block[r * 9:(r + 1) * 9, 1:], axis=0)) == 9
    again = runner.requests(mix, seed, 512, shrink=16)
    other = runner.requests(mix, seed + 1, 512, shrink=16)
    assert (again["doc"] == work["doc"]).all()
    assert (again["max_new"] == work["max_new"]).all()
    assert (other["max_new"] != work["max_new"]).any()
    assert (other["doc"] != work["doc"]).any()


def test_the_cells_selection_check_reads_every_layers_set_from_the_step():
    """``index_overlap`` as the runner computes it: the mixed step's own
    sets (``probe=True``: chunk rows' masks, decode rows' pool rows read
    back through each slot's table), a layer at a time, against the
    reference's masks.  Sound: every layer's set is the reference's, the
    handed-on ones too.  A reference whose ``shared`` layers select for
    themselves — the control no logit sees — disagrees from the first of
    those layers on.  And the counters are the layers' own: a layer that ran the
    indexer says so in one branch, one that took the set in the other."""
    from benchmark.runners import serve_sparse_latent as runner
    model, params, _ = shared()
    got = runner._served_selection(model, params, 3, 256, 4, 32)
    rows = 32 + 3
    counts = got["counters"]
    assert counts["index_rows"] == rows * 2
    assert counts["sparse_rows_reused"] == rows * 3
    assert counts["sparse_tokens_read"] >= 5 * 8 * rows - 5 * 8   # row 0
    sound = runner._index_overlap(got, params, REF, None)
    assert min(sound) > 0.995, sound
    control = runner._index_overlap(got, params, REF, None, ("shared",))
    # (the last layer computes its own set, from activations the three
    # layers before it have already moved)
    assert control[0] == sound[0] and max(control[1:4]) < 0.9, control


def test_the_cell_rehearses_through_the_harness_at_a_tiny_size():
    """``glm-5.2.serve-docqa-sat`` through the harness's own ``run_cell``
    on the CPU: the runner's build, weights, four checks, the documents'
    fill, the sessions' closed loop with its lead-in and the result line,
    at a tiny size — documents of 64-256 tokens, 4 slots and 8 clients.
    A shape check, not a measurement."""
    from benchmark import run as harness
    from benchmark.lib import device
    tiny = {"model": dict({k: v for k, v in TINY.items() if k != "dtype"},
                          vocab_size=512, max_seq_len=512,
                          indexer_types=list(KINDS), experts_held=[0, 4],
                          dtype="float32"),
            "num_kv_blocks": 512, "shrink": 256}
    engine = {"dtype": "float32", "max_out_tokens": 512, "temperature": 0.0,
              "serving": {"kv_block_size": 16, "prefill_chunk_tokens": 32,
                          "max_batch_slots": 4, "num_kv_blocks": 512}}
    line, obs = harness.run_cell(
        harness.load_benchmark(), "glm-5.2.serve-docqa-sat",
        seed=2**31 + 7, seconds=5.0, trace_on=False,
        peaks={"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
        compile_log=device.CompileLog(), tiny=tiny,
        mix_overrides={"clients": 8, "engine": engine,
                       "prompt_lens": [2048, 4096, 8192],
                       "output_lens": [512, 1024, 1536]})
    diag = line["diag"]
    assert line["failed"] == 0 and line["attempted"] > 0, diag
    assert line["correct"] is True, diag
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert diag["logit_gap_worst"] < 1e-4 and diag["expert_rel_err"] < 1e-3
    assert diag["index_overlap"] > 0.99 and diag["index_overlap_deep"] > 0.99
    assert diag["rehit_tokens"] == 16          # 23 tokens: one whole block
    assert diag["documents_missed"] == 0
    assert obs["values"]["prefix_hit_share"] > 50
    assert diag["blocks_held_after_drain"] == 0
    assert obs["shapes"] == {"kv_block_size": 16, "kv_row_width": 128}
