"""The shortcut-connected latent-attention MoE block (LongCat-Flash
family) against its plain reference, ``benchmark/lib/
reference_longcat_flash.py``: tiny sizes, CPU, float32, seeded weights.

  - ``apply`` (expanded form, all experts held) against the reference;
  - chunked prefill (a prompt over several chunks, two requests
    interleaved) then paged decode through the latent pool against the
    reference's full forward: logits, not tokens;
  - the shares add up: the routed parts of four shares of the experts plus
    the identity part counted once are the reference's uncut layer;
  - the router: the bias moves a choice and not a weight, an identity pick
    returns ``w u``, twelve picks and none dropped under a batch routed
    wholly to one expert;
  - absorbed equals expanded: the latent kernel (interpret mode) against
    plain ``jnp`` at a context that spans pages and a chunk that ends
    mid-page;
  - what cannot serve it yet refuses at build with its reason.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_longcat_flash as reference
from deepspeed_tpu.models import (TransformerLM, build_model, gpt2_config,
                                  longcat_flash_config)
from deepspeed_tpu.models.shortcut_moe import ShortcutMoELM
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    mla_paged_decode_attention, mla_paged_prefill_attention,
    mla_paged_reference)

TINY = dict(num_layers=2, num_heads=4, d_model=64, d_ff=128, head_dim=24,
            vocab_size=128, max_seq_len=128, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            expert_d_ff=32, n_routed_experts=8, zero_expert_num=4,
            moe_topk=3, dtype=jnp.float32)
REF = dict(heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           q_lora_rank=32, kv_lora_rank=32, mla_scale_q_lora=True,
           mla_scale_kv_lora=True, eps=1e-5, rope_theta=1e7,
           n_routed_experts=8, moe_topk=3, scale=6.0)
SERVING = {"enabled": True, "kv_block_size": 8, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 64}


def build(**kw):
    model = build_model(longcat_flash_config("omni", **{**TINY, **kw}))
    return model, model.init(jax.random.PRNGKey(0))


def serving_engine(model, params, **serving):
    return ds.init_inference(
        model, {"dtype": "float32", "max_out_tokens": 128,
                "temperature": 0.0, "serving": {**SERVING, **serving}},
        params=params).serving_engine()


def test_the_config_builds_its_own_model_class_and_counts_its_parameters():
    model, params = build()
    assert type(model) is ShortcutMoELM
    assert type(build_model(gpt2_config("125m"))) is TransformerLM
    # the standard block never runs this configuration by accident
    with pytest.raises(TypeError, match="build_model"):
        TransformerLM(model.config)
    leaves = jax.tree_util.tree_leaves(params)
    assert sum(a.size for a in leaves) == model.config.num_params()
    full = longcat_flash_config("omni")
    per_layer = (full.num_params() - 2 * full.vocab_size * full.d_model
                 - full.d_model) / full.num_layers
    # 2 MLA (90.57 M) + 2 dense FFN (226.5 M) + router + 512 experts
    assert round(per_layer / 1e9, 2) == 19.97


def test_full_forward_matches_the_reference_with_all_experts_held():
    model, params = build()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, REF)
    assert float(jnp.abs(got - want).max()) < 2e-5


def test_a_share_of_the_experts_matches_the_reference_given_the_same():
    model, params = build(experts_held=(2, 5))
    assert params["blocks"]["moe"]["experts"]["w_up"].shape[1] == 3
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 20), 0, 128)
    got = model.apply(params, ids)
    want = reference.logits(params, ids, REF, experts_held=(2, 5))
    assert float(jnp.abs(got - want).max()) < 2e-5
    left_out = reference.logits(params, ids, REF, experts_held=(2, 5),
                                leave_out=("shortcut",))
    assert float(jnp.abs(got - left_out).max()) > 1e-2


def test_chunked_prefill_then_paged_decode_match_the_reference_logits():
    """Two requests interleaved, the longer prompt over three chunks:
    every token the engine chose is the reference's best at its position,
    by logits; one program, two host arrays in, one read out, and the
    routing counters on it."""
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
    # the pool's bytes are the planning mirror's: 2 L sublayers x blocks
    from deepspeed_tpu.inference.serving import latent_block_bytes
    assert srv.kv_pool_bytes == 2 * 2 * 64 * latent_block_bytes(
        8, 32, 8, cache_itemsize=4)
    assert srv.kv_row_width == 128 and srv._pool_v is None
    rows = 0
    for rec in seen:
        assert rec["host_arrays_in"] == 2 * rec["dispatches"]
        assert rec["host_reads_out"] == rec["dispatches"]
        rows += rec["decode_rows"] + rec["chunk_rows"]
    picks = sum(rec["moe_picks"] for rec in seen)
    assert picks == rows * 3 * 2                   # rows x top-k x layers
    assert 0 < sum(rec["moe_picks_held"] for rec in seen) < picks
    assert 0 < sum(rec["moe_picks_zero"] for rec in seen) < picks
    assert all(rec["latent_tokens_read"] % 4 == 0 for rec in seen)
    assert sum(rec["latent_tokens_read"] for rec in seen) > 4 * 58


def test_the_shares_add_up_to_the_uncut_layer():
    """E = 8 over 4 shares: every share computes the identity part for its
    own rows, so the four shares' outputs less three identity parts — the
    routed parts of all shares plus the identity part counted once — are
    the reference's whole MoE layer."""
    model, params = build()
    c = model.config
    layer = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])["moe"]
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 19, c.d_model))
    want = reference.moe(layer, u, REF)
    identity = reference.moe(layer, u, REF, experts_held=(0, 0))
    total = jnp.zeros_like(want)
    for share in range(4):
        lo, hi = 2 * share, 2 * share + 2
        part = dataclasses.replace(c, experts_held=(lo, hi))
        held = dict(layer, experts={k: v[lo:hi]
                                    for k, v in layer["experts"].items()})
        got, _ = build_model(part)._moe_sublayer(held, u)
        ref_part = reference.moe(held, u, REF, experts_held=(lo, hi))
        assert float(jnp.abs(got - ref_part).max()) < 1e-5
        total = total + got
    assert float(jnp.abs(total - 3 * identity - want).max()) < 1e-5


def test_the_cells_configuration_file_is_what_the_program_builds():
    """``benchmark/configs/longcat-flash-omni.json`` through the cell's
    own runner: the published widths, the chip's share of 16 experts and
    5.17 B held parameters (10.35 GB in bfloat16); only the four cuts
    differ from the published config."""
    from benchmark.runners import serve_latent
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "longcat-flash-omni.json")) as f:
        config = json.load(f)
    mc, ref, held = serve_latent.build(config)
    assert type(build_model(mc)) is ShortcutMoELM
    assert held == (0, 16) and ref["n_routed_experts"] == 512
    assert mc.num_params() == 5_172_749_312
    assert (mc.d_model, mc.ff_dim, mc.expert_d_ff, mc.num_heads) == (
        6144, 12288, 2048, 64)
    assert config["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072,
        "max_position_embeddings": 131072}
    assert set(config["changed"]) == set(config["published"])
    with pytest.raises(ValueError, match="the program built"):
        serve_latent.build(dict(config, kv_lora_rank=256))


@pytest.mark.parametrize("leave_out, low, high", [((), 0.0, 1e-3),
                                                  (("experts",), 0.99, 1.01)])
def test_the_cells_expert_check_sees_the_held_experts(leave_out, low, high):
    """``correct``'s second number (``serve_latent._check_experts``): the
    program's MoE sublayer against the reference's, over the norm of the
    held experts' own part — nothing at float32, 1 when the reference
    leaves the held experts out (so a grouped product that returned
    nothing would read 1 too)."""
    from benchmark.runners import serve_latent
    model, params = build(experts_held=(2, 6))
    err = serve_latent._check_experts(model, params, REF, (2, 6), seed=7,
                                      rows=19, leave_out=leave_out)
    assert low <= err <= high


class TestRouter:
    H, E, Z, K = 16, 6, 3, 3

    def weights(self):
        return jax.random.normal(jax.random.PRNGKey(4),
                                 (self.H, self.E + self.Z)) * 0.5

    def test_the_bias_changes_a_choice_and_not_a_weight(self):
        u = jax.random.normal(jax.random.PRNGKey(5), (5, self.H))
        w = self.weights()
        plain = dropless.route(u, w, jnp.zeros(self.E + self.Z), self.K,
                               6.0)
        p = jax.nn.softmax(u @ w, axis=-1)
        np.testing.assert_allclose(
            plain.weight, 6.0 * jnp.take_along_axis(p, plain.index, 1),
            rtol=1e-6)
        assert np.all(np.diff(np.asarray(plain.weight), axis=1) <= 0)
        never = int(jnp.argmin(p[0]))               # row 0 never picks it
        assert never not in np.asarray(plain.index[0])
        biased = dropless.route(
            u, w, jnp.zeros(self.E + self.Z).at[never].set(1.0), self.K,
            6.0)
        assert int(biased.index[0, 0]) == never     # the choice moved
        np.testing.assert_allclose(                 # its weight did not
            biased.weight[0, 0], 6.0 * p[0, never], rtol=1e-6)
        kept = np.isin(np.asarray(biased.index[0]), np.asarray(plain.index[0]))
        assert kept.sum() == self.K - 1

    def test_an_identity_pick_returns_w_u(self):
        u = jax.random.normal(jax.random.PRNGKey(6), (4, self.H))
        experts = dropless.init_experts(jax.random.PRNGKey(7), self.E,
                                        self.H, 8, 0.1, 0.1, jnp.float32)
        index = jnp.full((4, 1), self.E + 1, jnp.int32)    # a zero expert
        weight = jnp.asarray([[0.5], [1.0], [0.25], [2.0]])
        y, counts = dropless.expert_share(
            experts, u, dropless.Routing(index, weight), self.E,
            (0, self.E), pass_rows=32)
        np.testing.assert_allclose(y, weight * u, rtol=1e-6)
        assert dict(zip(dropless.COUNTERS, map(int, counts))) == {
            "moe_picks": 4, "moe_picks_held": 0, "moe_picks_zero": 4,
            "moe_rows_max_expert": 0, "moe_experts_touched": 0,
            "moe_rows_moved": 0}

    def test_twelve_picks_none_dropped_under_one_hot_routing(self):
        """Every row's first pick is expert 2, so it gets all 40 rows —
        more than one pass of the row buffer: all of them are computed."""
        t, e, k, h, f = 40, 16, 12, 16, 8
        u = jax.random.normal(jax.random.PRNGKey(8), (t, h))
        experts = dropless.init_experts(jax.random.PRNGKey(9), e, h, f,
                                        0.3, 0.3, jnp.float32)
        order = np.array([2] + [i for i in range(e) if i != 2])[:k]
        index = jnp.asarray(np.tile(order, (t, 1)), jnp.int32)
        weight = jax.random.uniform(jax.random.PRNGKey(10), (t, k)) + 0.1
        y, counts = dropless.expert_share(
            experts, u, dropless.Routing(index, weight), e, (0, e),
            pass_rows=64)
        want = jnp.zeros_like(u)
        for j in range(k):
            ex = int(order[j])
            out = (jax.nn.silu(u @ experts["w_gate"][ex])
                   * (u @ experts["w_up"][ex])) @ experts["w_down"][ex]
            want = want + weight[:, j:j + 1] * out
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
        counted = dict(zip(dropless.COUNTERS, map(int, counts)))
        assert counted["moe_picks"] == counted["moe_picks_held"] == t * k
        assert counted["moe_rows_max_expert"] == t
        assert counted["moe_experts_touched"] == k

    def test_rows_that_carry_no_token_are_routed_nowhere(self):
        u = jax.random.normal(jax.random.PRNGKey(11), (6, self.H))
        experts = dropless.init_experts(jax.random.PRNGKey(12), self.E,
                                        self.H, 8, 0.3, 0.3, jnp.float32)
        routing = dropless.route(u, self.weights(),
                                 jnp.zeros(self.E + self.Z), self.K, 6.0)
        valid = jnp.asarray([1, 0, 1, 1, 0, 0], bool)
        y, counts = dropless.expert_share(experts, u, routing, self.E,
                                          (0, self.E), row_valid=valid,
                                          pass_rows=32)
        assert int(counts[0]) == 3 * self.K
        assert not np.asarray(y)[~np.asarray(valid)].any()
        assert np.asarray(y)[np.asarray(valid)].any()


class TestAbsorbedEqualsExpanded:
    """The latent kernel (interpret mode) on ``q_lat = q_nope W_UK^T``
    against the EXPANDED form in plain ``jnp`` on the same latents."""
    H, R, DR, DN, DV, BLOCK = 4, 32, 8, 16, 16, 8

    def case(self, tokens):
        keys = jax.random.split(jax.random.PRNGKey(13), 6)
        lat = jax.random.normal(keys[0], (tokens, self.R))
        k_rope = jax.random.normal(keys[1], (tokens, self.DR))
        w_uk = jax.random.normal(keys[2], (self.R, self.H, self.DN)) * 0.3
        w_uv = jax.random.normal(keys[3], (self.R, self.H, self.DV)) * 0.3
        q_nope = jax.random.normal(keys[4], (tokens, self.H, self.DN))
        q_rope = jax.random.normal(keys[5], (tokens, self.H, self.DR))
        pages = -(-tokens // self.BLOCK) + 1
        # the sequence's pages lie scattered in a pool that starts as NaN:
        # whatever is read past the length must not reach the result
        table = np.random.default_rng(0).permutation(
            np.arange(1, 3 * pages))[:pages].astype(np.int32)
        pool = np.full((3 * pages, self.BLOCK, self.R + 16), np.nan,
                       np.float32)
        for t in range(tokens):
            pool[table[t // self.BLOCK], t % self.BLOCK] = np.concatenate(
                [lat[t], k_rope[t], np.zeros(16 - self.DR, np.float32)])
        return (lat, k_rope, w_uk, w_uv, q_nope, q_rope, jnp.asarray(table),
                jnp.asarray(pool))

    def expanded(self, lat, k_rope, w_uk, w_uv, q_nope, q_rope, rows):
        k_nope = jnp.einsum("sr,rhd->shd", lat, w_uk)
        v = jnp.einsum("sr,rhd->shd", lat, w_uv)
        s = (jnp.einsum("qhd,shd->hqs", q_nope[rows], k_nope)
             + jnp.einsum("qhd,sd->hqs", q_rope[rows], k_rope)
             ) / np.sqrt(self.DN + self.DR)
        seen = jnp.arange(lat.shape[0])[None, :] <= rows[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shd->qhd", p, v)

    @pytest.mark.parametrize("pages_per_program", [None, 1, 2])
    def test_a_chunk_that_ends_mid_page_over_a_context_of_pages(
            self, pages_per_program):
        tokens, base, chunk, valid = 43, 21, 32, 22   # 21 + 22 = 43
        (lat, k_rope, w_uk, w_uv, q_nope, q_rope, table,
         pool) = self.case(tokens)
        rows = jnp.arange(base, base + valid)
        pad = chunk - valid
        q_lat = jnp.einsum("qhd,rhd->qhr", q_nope[rows], w_uk)
        o_lat = mla_paged_prefill_attention(
            jnp.pad(q_lat, ((0, pad), (0, 0), (0, 0))),
            jnp.pad(q_rope[rows], ((0, pad), (0, 0), (0, 0))), pool, base,
            valid, table, 1 / np.sqrt(self.DN + self.DR),
            interpret=True, pages_per_program=pages_per_program)
        got = jnp.einsum("qhr,rhd->qhd", o_lat[:valid], w_uv)
        want = self.expanded(lat, k_rope, w_uk, w_uv, q_nope, q_rope, rows)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        assert bool(jnp.all(jnp.isfinite(o_lat)))

    def test_decode_rows_over_slots_of_different_lengths(self):
        tokens = 43
        (lat, k_rope, w_uk, w_uv, q_nope, q_rope, table,
         pool) = self.case(tokens)
        lengths = jnp.asarray([43, 0, 17, 8], jnp.int32)
        last = jnp.maximum(lengths - 1, 0)
        q_lat = jnp.einsum("qhd,rhd->qhr", q_nope[last], w_uk)
        o_lat = mla_paged_decode_attention(
            q_lat, q_rope[last], pool, lengths,
            jnp.tile(table[None], (4, 1)), 1 / np.sqrt(self.DN + self.DR),
            interpret=True, pages_per_program=2)
        got = jnp.einsum("qhr,rhd->qhd", o_lat, w_uv)
        for slot, n in enumerate(np.asarray(lengths)):
            if n == 0:
                assert not np.asarray(got[slot]).any()
                continue
            want = self.expanded(lat[:n], k_rope[:n], w_uk, w_uv, q_nope,
                                 q_rope, jnp.asarray([n - 1]))[0]
            np.testing.assert_allclose(got[slot], want, rtol=2e-4,
                                       atol=2e-5)
        ref = mla_paged_reference(
            q_lat[:, None], q_rope[last][:, None], pool, lengths - 1,
            lengths, jnp.tile(table[None], (4, 1)),
            1 / np.sqrt(self.DN + self.DR))[:, 0]
        np.testing.assert_allclose(o_lat, ref, rtol=2e-4, atol=2e-5)


class TestRefusals:
    """What cannot serve the block yet says so when the engine is built."""

    def test_the_speculative_lane(self):
        model, params = build()
        eng = ds.init_inference(
            model, {"dtype": "float32", "max_out_tokens": 128,
                    "serving": dict(SERVING, spec_k=2)}, params=params)
        draft = build_model(gpt2_config(
            "125m", num_layers=1, d_model=32, num_heads=2, vocab_size=128,
            max_seq_len=128, dtype=jnp.float32))
        with pytest.raises(NotImplementedError, match="speculative lane"):
            eng.serving_engine(draft_model=draft, draft_params=draft.init(
                jax.random.PRNGKey(1)))

    @pytest.mark.parametrize("bits", [8, 4])
    def test_a_quantized_pool(self, bits):
        model, params = build()
        with pytest.raises(NotImplementedError, match="kv_cache_bits"):
            serving_engine(model, params, kv_cache_bits=bits)
        with pytest.raises(NotImplementedError, match="kv_cache_bits"):
            model.init_paged_cache(8, 8, kv_bits=bits)

    @pytest.mark.parametrize("mesh", [{"data": 1, "model": 2},
                                      {"data": 2, "model": 1}])
    def test_the_tensor_parallel_step(self, mesh):
        model, params = build()
        with pytest.raises(NotImplementedError, match="one chip"):
            serving_engine(model, params, mesh=mesh, max_batch_slots=4)

    def test_the_host_tier_cache(self):
        model, params = build()
        with pytest.raises(NotImplementedError, match="host tier"):
            serving_engine(model, params, host_cache={"enabled": True,
                                             "dram_budget_bytes": 1 << 20})

    def test_int8_weight_only_serving(self):
        model, params = build()
        eng = ds.init_inference(
            model, {"dtype": "float32", "max_out_tokens": 128,
                    "quant": {"enabled": True}, "serving": SERVING},
            params=params)
        with pytest.raises(NotImplementedError, match="weight-only"):
            eng.serving_engine()

    def test_training(self):
        model, _ = build()
        with pytest.raises(NotImplementedError, match="does not train"):
            ds.initialize(model=model, config={
                "train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
        assert build_model(gpt2_config("125m")).training_refusal() is None

    def test_the_dense_cache_of_generate(self):
        model, _ = build()
        with pytest.raises(NotImplementedError, match="paged serving path"):
            model.init_cache(1, 16)
