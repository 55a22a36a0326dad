"""The sandwich-norm latent-attention block with a leading dense layer and
a shared expert (openPangu-Ultra-MoE family) against its plain reference,
``benchmark/lib/reference_openpangu_ultra_moe.py``: tiny sizes, CPU,
float32, seeded weights.  Each tolerance is float32 rounding over a few
layers of width 64 (the two sides sum in different orders) unless it says
otherwise.

  - ``apply`` (expanded form) against the reference, with all experts held
    and with a share of them; every mechanism the block adds is seen;
  - chunked prefill (a prompt over several chunks, two requests
    interleaved) then paged decode through ONE pool across the dense layer
    and the expert layers against the reference's full forward: logits;
  - the gate: ``route`` in its sigmoid form against a NumPy gate, and the
    softmax form byte-equal to the arithmetic it had before it took a form;
  - the shares add up: four shares of the routed experts, with the shared
    expert, the router and attention counted once, are the uncut layer;
  - the latent kernel at 128 heads (interpret mode) against
    ``mla_paged_reference`` for decode rows and chunk rows;
  - the counters of a known mix; what cannot serve it refuses with its
    reason; the cell's configuration file is what the program builds.
"""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_openpangu_ultra_moe as reference
from deepspeed_tpu.models import build_model, openpangu_ultra_moe_config
from deepspeed_tpu.models.latent_moe import LatentMoELM
from deepspeed_tpu.models.sandwich_moe import SandwichMoELM
from deepspeed_tpu.models.shortcut_moe import ShortcutMoELM
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    mla_paged_decode_attention, mla_paged_prefill_attention,
    mla_paged_reference)

TINY = dict(num_layers=3, first_k_dense=1, num_heads=4, d_model=64,
            d_ff=128, head_dim=24, vocab_size=128, max_seq_len=128,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, expert_d_ff=32,
            n_routed_experts=8, moe_topk=3, dtype=jnp.float32)
REF = dict(heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           kv_lora_rank=32, eps=1e-5, rope_theta=25.6e6, n_routed_experts=8,
           moe_topk=3, scale=2.5)
SERVING = {"enabled": True, "kv_block_size": 8, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 64}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(**kw):
    model = build_model(openpangu_ultra_moe_config("718b", **{**TINY, **kw}))
    return model, model.init(jax.random.PRNGKey(0))


def serving_engine(model, params, **serving):
    return ds.init_inference(
        model, {"dtype": "float32", "max_out_tokens": 128,
                "temperature": 0.0, "serving": {**SERVING, **serving}},
        params=params).serving_engine()


def test_the_config_builds_its_own_model_class_and_counts_its_parameters():
    model, params = build()
    assert type(model) is SandwichMoELM
    assert issubclass(SandwichMoELM, LatentMoELM) \
        and issubclass(ShortcutMoELM, LatentMoELM)
    assert (SandwichMoELM.ATTN_SUBLAYERS, ShortcutMoELM.ATTN_SUBLAYERS) \
        == (1, 2)
    # two kinds of layer in one stack: one dense layer, two expert layers
    assert params["dense_blocks"]["mlp"]["fc_in"]["kernel"].shape \
        == (1, 64, 128)
    assert params["blocks"]["moe"]["experts"]["w_up"].shape == (2, 8, 64, 32)
    assert "bias" not in params["blocks"]["moe"]          # no selection bias
    assert params["blocks"]["shared"]["fc_in"]["kernel"].shape == (2, 64, 32)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == model.config.num_params()
    full = openpangu_ultra_moe_config("718b")
    # 61 layers, 3 of them dense, 256 experts: 718B-A39B as published,
    # less the multi-token-prediction module
    assert round(full.num_params() / 1e9, 1) == 719.1
    with pytest.raises(ValueError, match="no expert layer"):
        build(first_k_dense=3)


def test_full_forward_matches_the_reference_with_all_experts_held():
    model, params = build()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, REF)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("leave_out", ["post_norms", "shared", "dense_ffn",
                                       "renorm", "experts", "float8"])
def test_a_share_matches_the_reference_and_sees_each_mechanism(leave_out):
    """A share of the experts against the reference given the same; the
    reference with one mechanism left out (or its weights rounded to
    float8) is far from it: logits here spread over +-0.6, and each
    mechanism moves them by a tenth of that or more."""
    model, params = build(experts_held=(2, 6))
    assert params["blocks"]["moe"]["experts"]["w_up"].shape[1] == 4
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, REF, experts_held=(2, 6))
    assert float(jnp.abs(got - want).max()) < 2e-5
    left_out = reference.logits(params, ids, REF, experts_held=(2, 6),
                                leave_out=(leave_out,))
    assert float(jnp.abs(got - left_out).max()) > 2e-2


def test_chunked_prefill_then_paged_decode_match_the_reference_logits():
    """Two requests interleaved, the longer prompt over three chunks:
    every token the engine chose is the reference's best at its position,
    by logits (1e-4: float32 through the absorbed form and the online
    softmax); one program, one pool across both kinds of layer, and the
    counters of the mix on the result array."""
    model, params = build(experts_held=(0, 6))
    srv = serving_engine(model, params)
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        rng = np.random.default_rng(0)
        reqs = [srv.submit(rng.integers(0, 128, p), max_new_tokens=n)
                for p, n in ((37, 6), (21, 5))]
        seen = []
        while srv.step():
            seen.append(prof.last())
    finally:
        prof.configure(enabled=False)
    for r in reqs:
        full = jnp.asarray(list(r.prompt) + list(r.output))[None]
        lg = np.asarray(reference.logits(params, full, REF,
                                         experts_held=(0, 6)))[0]
        assert len(r.output) == r.max_new_tokens
        for j, tok in enumerate(r.output):
            at = lg[len(r.prompt) + j - 1]
            assert at.max() - at[tok] < 1e-4
    assert srv.decode_builds == 2 and srv.allocator.num_used == 0
    # ONE buffer: a sublayer a layer, the dense layer's included
    from deepspeed_tpu.inference.serving import latent_block_bytes
    assert srv._pool_k.shape == (3, 64, 8, 128) and srv._pool_v is None
    assert srv.kv_pool_bytes == 3 * 64 * latent_block_bytes(
        8, 32, 8, cache_itemsize=4)
    rows = 0
    for rec in seen:
        assert rec["host_arrays_in"] == 2 * rec["dispatches"]
        assert rec["host_reads_out"] == rec["dispatches"]
        rows += rec["decode_rows"] + rec["chunk_rows"]
    # counted over the layers that have the thing counted: 2 expert
    # layers route, 3 layers read the pool
    picks = sum(rec["moe_picks"] for rec in seen)
    assert picks == rows * 3 * 2                   # rows x top-k x layers
    assert 0 < sum(rec["moe_picks_held"] for rec in seen) < picks
    assert sum(rec["moe_picks_zero"] for rec in seen) == 0
    assert sum(rec["moe_rows_shared"] for rec in seen) == rows * 2
    assert all(rec["latent_tokens_read"] % 3 == 0 for rec in seen)
    assert sum(rec["latent_tokens_read"] for rec in seen) > 3 * 58


def test_the_program_counts_the_pages_it_walked_and_those_in_runs():
    """A prompt of 70 tokens at 8 a page on a fresh pool: its first eight
    pages are one run of consecutive blocks from the chunk that carries
    the context past 64 tokens on, the ninth page opens the next; three
    sublayers walk them.  Pages and tokens are counted from the same
    lengths."""
    model, params = build(experts_held=(0, 6))
    srv = serving_engine(model, params)
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        req = srv.submit(np.arange(70) % 128, max_new_tokens=4)
        table, seen = None, []
        while srv.step():
            seen.append(prof.last())
            if req.req_id in srv.allocator._tables:
                table = srv.allocator.block_table(req.req_id)
    finally:
        prof.configure(enabled=False)
    assert table[:8] == list(range(1, 9)) and len(table) == 10
    live = [rec for rec in seen if rec["latent_tokens_read"]]
    for rec in live:
        tokens = rec["latent_tokens_read"] // 3
        assert rec["latent_pages_read"] == 3 * -(-tokens // 8)
        assert rec["latent_pages_in_runs"] == 3 * 8 * (tokens >= 64)
    assert [rec["latent_tokens_read"] // 3 for rec in live][:6] == [
        16, 32, 48, 64, 70, 71]


@pytest.mark.parametrize("block", ["sandwich", "shortcut"])
def test_tokens_alternate_between_the_two_shapes_of_the_step(block):
    """Prompts of 1, chunk, chunk + 1 and 3 x chunk tokens arriving while
    the others decode: a request's tokens come now from the mixed program
    and now from the decode-only one (no chunk lane, so no chunk rows in
    the latent kernel, the router or the experts' layout), and they are
    the tokens of a one-at-a-time run and the reference's best by logits.
    Both shapes built by the first dispatch; ``rows_computed`` is the rows
    of the program that ran."""
    if block == "shortcut":
        import test_shortcut_moe as other
        build_, ref, ref_cfg = other.build, other.reference, other.REF
    else:
        build_, ref, ref_cfg = build, reference, REF
    model, params = build_(experts_held=(0, 6))
    srv = serving_engine(model, params)
    slots, chunk = SERVING["max_batch_slots"], SERVING["prefill_chunk_tokens"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, n) for n in (1, chunk, chunk + 1,
                                                 3 * chunk)]
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        together, seen = [], []
        for p in prompts:
            together.append(srv.submit(p, max_new_tokens=5))
            for _ in range(2):       # its chunk, then a plain decode
                srv.step()
                seen.append(prof.last())
            assert srv.decode_builds == 2
        while srv.step():
            seen.append(prof.last())
    finally:
        prof.configure(enabled=False)
    shapes = {(rec["chunk_rows"] > 0, rec["rows_computed"])
              for rec in seen if rec["dispatches"] == 1}
    assert shapes == {(True, slots + chunk), (False, slots)}
    alone = []
    for p in prompts:
        alone.append(srv.submit(p, max_new_tokens=5))
        srv.run()
    for a, b in zip(together, alone):
        assert a.output == b.output and len(a.output) == 5
        full = jnp.asarray(list(a.prompt) + list(a.output))[None]
        lg = np.asarray(ref.logits(params, full, ref_cfg,
                                   experts_held=(0, 6)))[0]
        for j, tok in enumerate(a.output):
            at = lg[len(a.prompt) + j - 1]
            assert at.max() - at[tok] < 1e-4
    assert srv.decode_builds == 2 and srv.allocator.num_used == 0


@pytest.mark.parametrize("block", ["sandwich", "shortcut"])
def test_the_loop_keeps_a_dispatch_in_flight_for_the_latent_blocks(block):
    """Both latent families take the same loop (ISSUE 37): every dispatch
    but the first is enqueued before its predecessor's result is read,
    the counters the program counted still ride that result, a request's
    tokens are those of a one-at-a-time run, and an eos in mid-stream
    costs one void row and commits nothing of it."""
    if block == "shortcut":
        import test_shortcut_moe as other
        build_ = other.build
    else:
        build_ = build
    model, params = build_(experts_held=(0, 6))
    srv = serving_engine(model, params)
    chunk = SERVING["prefill_chunk_tokens"]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 128, n) for n in (5, chunk + 3, 2 * chunk)]
    alone = []
    for p in prompts:
        alone.append(srv.submit(p, max_new_tokens=9))
        srv.run()
    # a token of the middle stream that does not occur before its place
    full = alone[1].output
    at = next(j for j in range(2, 8) if full[j] not in full[:j])
    before = dict(srv.flight_counts)
    prof = get_overlap_profiler()
    prof.configure(enabled=True)
    try:
        t0 = time.perf_counter()
        together = [srv.submit(p, max_new_tokens=9,
                               eos_token_id=full[at] if k == 1 else None)
                    for k, p in enumerate(prompts)]
        srv.run()
        its, complete = prof.iterations(t0, time.perf_counter())
    finally:
        prof.configure(enabled=False)
    assert together[0].output == alone[0].output
    assert together[1].output == full[:at + 1]
    assert together[2].output == alone[2].output
    d = {k: srv.flight_counts[k] - before[k] for k in before}
    assert complete and its["ahead_dispatches"].sum() == d["ahead_dispatches"]
    # all but the idle engine's first iteration (the three prompts' tails
    # past their cached blocks share one chunk budget: three dispatches)
    assert d["ahead_dispatches"] == d["dispatches"] - its["dispatches"][0]
    assert d["void_rows"] == 1
    assert its["void_rows"].sum() == 1 and its["moe_picks"].sum() > 0
    again = srv.submit(prompts[1], max_new_tokens=9)
    srv.run()
    assert again.output == full
    assert srv.decode_builds == 2 and srv.allocator.num_used == 0


class TestGate:
    H, E, K = 16, 12, 4

    def case(self):
        u = jax.random.normal(jax.random.PRNGKey(5), (7, self.H))
        w = jax.random.normal(jax.random.PRNGKey(4), (self.H, self.E)) * 0.5
        return u, w

    def test_the_sigmoid_form_against_a_numpy_gate(self):
        """Sigmoid scores, the top k of them, renormalised over the picks
        and scaled: float32 against float64 NumPy, 1e-6 relative."""
        u, w = self.case()
        got = dropless.route(u, w, None, self.K, 2.5, scoring="sigmoid",
                             renormalize=True)
        s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64)
                                   @ np.asarray(w, np.float64))))
        index = np.argsort(-s, axis=1)[:, :self.K]
        np.testing.assert_array_equal(got.index, index)
        chosen = np.take_along_axis(s, index, axis=1)
        np.testing.assert_allclose(
            got.weight, 2.5 * chosen / (chosen.sum(1, keepdims=True)
                                        + 1e-20), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.weight).sum(1), 2.5,
                                   rtol=1e-6)
        plain = dropless.route(u, w, None, self.K, 2.5, scoring="sigmoid")
        np.testing.assert_allclose(plain.weight, 2.5 * chosen, rtol=1e-6)
        with pytest.raises(ValueError, match="scoring"):
            dropless.route(u, w, None, self.K, 1.0, scoring="tanh")

    def test_the_softmax_form_is_what_it_was_byte_for_byte(self):
        """The gate before it took a form — softmax, + bias for the
        choice, ``scale * score`` unrenormalised — written out here; the
        shortcut block's arguments give the same bits."""
        u, w = self.case()
        bias = jax.random.normal(jax.random.PRNGKey(6), (self.E,)) * 0.02
        got = dropless.route(u, w, bias, self.K, 6.0)
        logits = jnp.einsum("th,he->te", u, w.astype(u.dtype),
                            preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        _, index = jax.lax.top_k(p + bias.astype(jnp.float32), self.K)
        weight = 6.0 * jnp.take_along_axis(p, index, axis=-1)
        assert np.array_equal(np.asarray(got.index), np.asarray(index))
        assert np.asarray(got.weight).tobytes() \
            == np.asarray(weight).tobytes()


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 8 over 4 shares.  Every chip computes the shared expert (and
    the router and attention) alike, so the four shares' ``F_l`` less
    three shared-expert outputs — the routed parts of all shares plus the
    shared expert counted once — are the reference's whole expert
    layer."""
    model, params = build()
    c = model.config
    layer = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 19, c.d_model))
    want = reference.moe(layer, u, REF)
    shared = reference.ffn(layer["shared"], u, REF)
    total = jnp.zeros_like(want)
    for share in range(4):
        lo, hi = 2 * share, 2 * share + 2
        part = build_model(dataclasses.replace(c, experts_held=(lo, hi)))
        held = dict(layer, moe=dict(layer["moe"], experts={
            k: v[lo:hi] for k, v in layer["moe"]["experts"].items()}))
        got, _ = part.expert_layer(held, u)
        ref_part = reference.moe(held, u, REF, experts_held=(lo, hi))
        assert float(jnp.abs(got - ref_part).max()) < 1e-5
        total = total + got
    assert float(jnp.abs(total - 3 * shared - want).max()) < 1e-5


class TestLatentKernelAt128Heads:
    """The latent kernel (interpret mode) with the cell's heads and row
    ``[512 | 64 | 0]`` of 640 lanes against ``mla_paged_reference``: a
    decode walker is 128 rows, a chunk tile 8 positions.  2e-4: float32,
    the online softmax's other order of sums over up to 47 keys."""
    H, R, DR, LANES, BLOCK = 128, 512, 64, 640, 16

    def case(self, tokens, slots):
        keys = jax.random.split(jax.random.PRNGKey(13), 3)
        pages = -(-tokens // self.BLOCK) + 1
        nb = 1 + slots * pages
        pool = np.full((nb, self.BLOCK, self.LANES), np.nan, np.float32)
        rows = jax.random.normal(keys[0], (nb, self.BLOCK, self.R + self.DR))
        pool[..., :self.R + self.DR] = rows
        pool[..., self.R + self.DR:] = 0.0
        tables = jnp.asarray(np.random.default_rng(1).permutation(
            np.arange(1, nb)).reshape(slots, pages).astype(np.int32))
        return jnp.asarray(pool), tables, keys

    def test_decode_rows_over_slots_of_different_lengths(self):
        pool, tables, keys = self.case(47, 3)
        lengths = jnp.asarray([47, 0, 17], jnp.int32)
        ql = jax.random.normal(keys[1], (3, self.H, self.R)) * 0.1
        qr = jax.random.normal(keys[2], (3, self.H, self.DR)) * 0.1
        got = mla_paged_decode_attention(ql, qr, pool, lengths, tables,
                                         192 ** -0.5, interpret=True)
        want = mla_paged_reference(ql[:, None], qr[:, None], pool,
                                   lengths - 1, lengths, tables,
                                   192 ** -0.5)[:, 0]
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert not np.asarray(got[1]).any()

    def test_a_chunk_that_ends_mid_page_over_a_context_of_pages(self):
        pool, tables, keys = self.case(47, 1)
        base, chunk, valid = 21, 32, 26                    # 21 + 26 = 47
        ql = jax.random.normal(keys[1], (chunk, self.H, self.R)) * 0.1
        qr = jax.random.normal(keys[2], (chunk, self.H, self.DR)) * 0.1
        got = mla_paged_prefill_attention(ql, qr, pool, base, valid,
                                          tables[0], 192 ** -0.5,
                                          interpret=True)
        want = mla_paged_reference(ql[None], qr[None], pool,
                                   np.array([base]),
                                   np.array([base + valid]), tables,
                                   192 ** -0.5)[0]
        np.testing.assert_allclose(got[:valid], want[:valid], rtol=2e-4,
                                   atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(got)))


class TestLatentKernelFetchesRuns:
    """The latent kernel (interpret mode) over tables whose aligned runs
    of ``PAGE_RUN`` pages are consecutive pool blocks (one DMA a run),
    are not (one a page), or are some of each, the last run of a slot
    partly past its length — against ``mla_paged_reference``, with NaN in
    every pool block the walk must not read (block 0, every block no
    table names, every page past a slot's length: PR 6's pattern)."""
    H, R, DR, LANES, BLOCK, PAGES = 64, 16, 8, 32, 4, 24
    #: tokens a slot: two runs and 2.25 pages; dead; every page; one page
    LENGTHS = (73, 0, 96, 3)

    def case(self, kind, lengths):
        from deepspeed_tpu.ops.transformer.paged_decode_attention import (
            PAGE_RUN, page_runs)
        rng = np.random.default_rng(3)
        slots, nruns = len(lengths), self.PAGES // PAGE_RUN
        nb = 1 + slots * self.PAGES
        groups = rng.permutation(slots * nruns).reshape(slots, nruns)
        tables = 1 + groups[..., None] * PAGE_RUN + np.arange(PAGE_RUN)
        scattered = {"all_runs": np.zeros_like(groups, bool),
                     "no_runs": np.ones_like(groups, bool),
                     "mixed": np.broadcast_to(np.arange(nruns) % 2 == 1,
                                              groups.shape)}[kind]
        # a run read backwards holds no two consecutive blocks
        tables = np.where(scattered[..., None], tables[..., ::-1], tables)
        tables = tables.reshape(slots, self.PAGES).astype(np.int32)
        pool = np.full((nb, self.BLOCK, self.LANES), np.nan, np.float32)
        whole = 0
        for s, n in enumerate(lengths):
            live = tables[s, :-(-n // self.BLOCK)]
            pool[live] = 0.0
            pool[live, :, :self.R + self.DR] = rng.standard_normal(
                (len(live), self.BLOCK, self.R + self.DR))
            whole += int((~scattered[s, :n // (PAGE_RUN * self.BLOCK)]
                          ).sum())
        flags = page_runs(jnp.asarray(tables), jnp.asarray(lengths),
                          self.BLOCK)
        assert flags.shape == (slots, nruns) and int(flags.sum()) == whole
        return jnp.asarray(pool), jnp.asarray(tables)

    @pytest.mark.parametrize("kind", ["all_runs", "no_runs", "mixed"])
    def test_decode_rows(self, kind):
        lengths = jnp.asarray(self.LENGTHS, jnp.int32)
        pool, tables = self.case(kind, self.LENGTHS)
        keys = jax.random.split(jax.random.PRNGKey(5), 2)
        ql = jax.random.normal(keys[0], (4, self.H, self.R)) * 0.3
        qr = jax.random.normal(keys[1], (4, self.H, self.DR)) * 0.3
        got = mla_paged_decode_attention(ql, qr, pool, lengths, tables,
                                         0.2, interpret=True)
        want = mla_paged_reference(ql[:, None], qr[:, None], pool,
                                   lengths - 1, lengths, tables, 0.2)[:, 0]
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert not np.asarray(got[1]).any()

    @pytest.mark.parametrize("kind", ["all_runs", "no_runs", "mixed"])
    def test_a_chunk_in_two_tiles_that_ends_mid_run(self, kind):
        """32 positions x 64 heads = two walkers of 1,024 rows: the first
        stops inside a run the second reads whole."""
        base, chunk, valid = 41, 32, 29                    # ends at 70
        pool, tables = self.case(kind, (base + valid,))
        keys = jax.random.split(jax.random.PRNGKey(6), 2)
        ql = jax.random.normal(keys[0], (chunk, self.H, self.R)) * 0.3
        qr = jax.random.normal(keys[1], (chunk, self.H, self.DR)) * 0.3
        got = mla_paged_prefill_attention(ql, qr, pool, base, valid,
                                          tables[0], 0.2, interpret=True)
        want = mla_paged_reference(ql[None], qr[None], pool,
                                   np.array([base]),
                                   np.array([base + valid]), tables, 0.2)[0]
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got[:valid], want[:valid], rtol=2e-4,
                                   atol=2e-5)


class TestRefusals:
    """The refusals are the latent base's: the new block gives the same
    reasons as the shortcut block."""

    def test_paged_refusals_with_their_reasons(self):
        model, params = build()
        for kw, reason in ((dict(spec=True), "speculative lane"),
                           (dict(kv_bits=8), "kv_cache_bits"),
                           (dict(mesh_model=2), "one chip"),
                           (dict(host_cache=True), "host tier"),
                           (dict(weight_quant=True), "weight-only")):
            assert reason in model.paged_refusal(**kw)
        assert model.paged_refusal() is None
        with pytest.raises(NotImplementedError, match="kv_cache_bits"):
            serving_engine(model, params, kv_cache_bits=8)
        with pytest.raises(NotImplementedError, match="one chip"):
            serving_engine(model, params, mesh={"data": 1, "model": 2},
                           max_batch_slots=4)

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
    return model_lib.load_config("benchmark/configs/openpangu-ultra-moe.json")


def test_the_cells_configuration_file_is_what_the_program_builds():
    """``benchmark/configs/openpangu-ultra-moe.json`` through the cell's
    own runner: the published widths, one dense layer before four expert
    layers, the chip's share of 16 experts and 4.92 B held parameters
    (9.84 GB in bfloat16); only the six cuts differ from the published
    config, which the catalog row beside the ``model-configs`` guide
    gives."""
    from benchmark.runners import serve_latent_sandwich as runner
    config = _cell_config()
    mc, ref, held = runner.build(config)
    assert type(build_model(mc)) is SandwichMoELM
    assert held == (0, 16) and ref["n_routed_experts"] == 256
    assert (mc.num_layers, mc.first_k_dense, mc.scan_length) == (5, 1, 4)
    assert mc.num_params() == 4_919_139_840
    assert (mc.d_model, mc.ff_dim, mc.expert_d_ff, mc.num_heads) == (
        7680, 18432, 2048, 128)
    assert config["published"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600,
        "max_position_embeddings": 131072, "num_nextn_predict_layers": 1}
    assert set(config["changed"]) == set(config["published"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "openpangu-ultra-moe")
    assert set(entry["reduced"]) == set(config["changed"])
    full = openpangu_ultra_moe_config("718b")
    for key, value in config["published"].items():
        attr = runner.PUBLISHED.get(key)
        if attr:
            assert getattr(full, attr) == value
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, kv_lora_rank=256))
    with pytest.raises(ValueError, match="the program built"):
        runner.build(dict(config, norm_topk_prob=False))


@pytest.mark.parametrize("leave_out, low, high", [
    ((), 0.0, 1e-3), (("experts",), 0.99, 1.01), (("shared",), 0.5, 50.0),
    (("renorm",), 0.05, 50.0)])
def test_the_cells_expert_check_sees_the_expert_layer(leave_out, low, high):
    """``correct``'s second number (``_check_experts``): the program's
    ``F_l`` of the first expert layer against the reference's, over the
    norm of the held experts' own part — nothing at float32, 1 when the
    reference leaves the held experts out (so a grouped product that
    returned nothing would read 1 too), and far over the cell's 0.05
    without the shared expert or the renormalisation."""
    from benchmark.runners import serve_latent_sandwich as runner
    model, params = build(experts_held=(2, 6))
    err = runner._check_experts(model, params, REF, (2, 6), seed=7, rows=19,
                                leave_out=leave_out)
    assert low <= err <= high


def test_the_cells_values_from_a_known_mix_of_counters():
    """The four per-layer values the runner computes from the iteration
    records: 3 iterations of one dispatch each, 16 held experts, 4 expert
    layers, one of them with a chunk."""
    from benchmark.runners import serve_latent_sandwich as runner
    from deepspeed_tpu.observability.overlap import OverlapProfiler
    import time
    prof = OverlapProfiler(capacity=8)
    prof.configure(enabled=True)
    t0 = time.perf_counter()
    for decode, chunk, held, touched in ((128, 0, 256, 60), (128, 480, 1216,
                                                             64),
                                         (100, 0, 200, 56)):
        prof.begin()
        prof.mark(4)
        prof.count_dispatch(decode, chunk, 640, moe_picks_held=held,
                            moe_experts_touched=touched,
                            moe_rows_shared=(decode + chunk) * 4)
        prof.end()
    mc = runner.build(_cell_config())[0]
    got = runner._expert_layer_values(prof, mc, (t0, time.perf_counter()))
    slots = 3 * 4 * 16
    assert got["moe_rows_per_expert"] == pytest.approx(1672 / slots)
    assert got["moe_touched_share"] == pytest.approx(100 * 180 / slots)
    assert got["moe_shared_share"] == pytest.approx(
        100 * 836 * 4 / (836 * 4 + 1672))
    assert got["chunk_dispatch_share"] == pytest.approx(100 / 3)
    # a program that kept no records there gives nothing
    assert runner._expert_layer_values(
        OverlapProfiler(capacity=2), mc, (t0, time.perf_counter())) == {}


def test_the_cell_rehearses_through_the_harness_at_a_tiny_size():
    """``openpangu-ultra-moe.serve-reason-sat`` through the harness's own
    ``run_cell`` on the CPU (``benchmark/tests/rehearse.py`` has no sizes
    for this runner): the runner's build, weights, two checks, closed loop
    and result line at a tiny size, 8 slots and 16 clients.  A shape
    check, not a measurement."""
    from benchmark import run as harness
    from benchmark.lib import device
    tiny = {"model": dict({k: v for k, v in TINY.items() if k != "dtype"},
                          vocab_size=512, max_seq_len=256,
                          experts_held=[0, 4], dtype="float32"),
            "num_kv_blocks": 2048, "shrink": 16}
    engine = {"dtype": "float32", "max_out_tokens": 256, "temperature": 0.0,
              "serving": {"kv_block_size": 16, "prefill_chunk_tokens": 32,
                          "max_batch_slots": 8, "num_kv_blocks": 2048}}
    line, obs = harness.run_cell(
        harness.load_benchmark(), "openpangu-ultra-moe.serve-reason-sat",
        # a window of 6 s and outputs of 2-8 tokens after the shrink: an
        # idle CPU ends 26 requests a second, and the driver's run of six
        # workers once ended none in a window of 2 s (a stall as long as
        # the window), which is `attempted == 0` below
        seed=2**31 + 7, seconds=6.0, trace_on=False,
        peaks={"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10},
        compile_log=device.CompileLog(), tiny=tiny,
        mix_overrides={"clients": 16, "engine": engine,
                       "output_lens": [32, 64, 96, 128]})
    assert line["failed"] == 0 and line["attempted"] > 0, line["diag"]
    assert line["correct"] is True, line["diag"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["diag"]["logit_gap_worst"] < 1e-4
    assert line["diag"]["expert_rel_err"] < 1e-3
    assert line["diag"]["blocks_held_after_drain"] == 0
    assert obs["shapes"] == {"kv_block_size": 16, "kv_row_width": 128}
