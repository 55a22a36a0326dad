"""The serving step has ONE definition, ``TransformerLM._apply_paged_mixed``:
a block says what its scans carry, its layers, its walks and its counters
(``models/transformer.py`` "the serving step").  What the one skeleton
owes every block — the counters' width, the lengths a dispatch leaves, a
chunk of static length 0, the refusals — is held here once a block, at
tiny float32 sizes."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import TransformerLM, build_model, gpt2_config
from deepspeed_tpu.models.transformer import (
    MixedStep, afmoe_config, glm_moe_dsa_config, granite_hybrid_config,
    kimi_linear_config, longcat_flash_config, openpangu_ultra_moe_config,
    phi4_flash_config, pool_rows, sdar_moe_config, walk_counts, zaya_config)

F32 = dict(vocab_size=128, max_seq_len=128, dtype=jnp.float32)
LATENT = dict(num_heads=4, d_model=32, d_ff=64, head_dim=16, q_lora_rank=16,
              kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
              v_head_dim=8, expert_d_ff=16, n_routed_experts=8, moe_topk=2,
              **F32)
#: block -> its tiny configuration
BLOCKS = {
    "plain": lambda: gpt2_config(
        "125m", num_layers=2, d_model=32, num_heads=4, **F32),
    "shortcut": lambda: longcat_flash_config(
        "omni", num_layers=2, zero_expert_num=4, **LATENT),
    "sandwich": lambda: openpangu_ultra_moe_config(
        "718b", num_layers=3, first_k_dense=1, **LATENT),
    "sparse": lambda: glm_moe_dsa_config(
        "5.2", num_layers=3, first_k_dense=1, index_n_heads=4,
        index_head_dim=16, index_topk=8,
        indexer_types=("full", "shared", "full"), **LATENT),
    "hybrid": lambda: phi4_flash_config(
        "mini", num_layers=6, pairs_self=1, pairs_cross=1, num_heads=4,
        num_kv_heads=2, d_model=32, d_ff=64, sliding_window=8, ssm_state=4,
        **F32),
    "ssd-hybrid": lambda: granite_hybrid_config(
        "h-micro", num_layers=4,
        layer_types=("mamba", "mamba", "attention", "mamba"), num_heads=8,
        num_kv_heads=2, d_model=32, d_ff=64, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, **F32),
    "kda-latent": lambda: kimi_linear_config(
        "48b-a3b", num_layers=4, layer_types=("kda", "kda", "mla", "kda"),
        num_heads=4, d_model=32, d_ff=64, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kda_heads=2,
        kda_head_dim=8, expert_d_ff=16, n_routed_experts=8, moe_topk=2,
        **F32),
    "block-diffusion": lambda: sdar_moe_config(
        "30b-a3b", num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
        d_model=32, expert_d_ff=16, n_routed_experts=8, moe_topk=2,
        mask_token_id=96, block_length=4, **F32),
    "window-moe": lambda: afmoe_config(
        "trinity-mini", num_layers=4,
        layer_types=("window", "window", "window", "full"), first_k_dense=1,
        num_heads=4, num_kv_heads=2, head_dim=8, d_model=32, d_ff=64,
        sliding_window=8, expert_d_ff=16, n_routed_experts=8, moe_topk=2,
        **F32),
}
#: the blocks that refuse the draft lane and a quantized pool
REFUSING = [b for b in BLOCKS if b != "plain"]
SLOTS, PAGES, BLOCK, POOL = 3, 4, 8, 16


def build(block):
    model = build_model(BLOCKS[block]())
    return model, model.init(jax.random.PRNGKey(0))


def dispatch(model, params, chunk):
    """One dispatch over a cache of the test's own: slot 0 idle, slot 1
    riding the first lane at 11 rows of context, slot 2 the chunk's (5
    valid rows of ``chunk`` from row 8; nothing where ``chunk`` is 0)."""
    kinds = len(model.TABLE_KINDS)
    cache = dict(model.init_paged_cache(POOL, BLOCK, jnp.float32))
    extra = model.init_paged_extra(SLOTS, BLOCK, POOL, jnp.float32)
    if extra is not None:
        cache["extra"] = extra
    tables = np.zeros((SLOTS, PAGES), np.int32)
    tables[1, :2], tables[2, :2] = (3, 9), (5, 2)
    lens = np.array([0, 8 if model.block_rows else 11, 8], np.int32)
    cache.update(block_tables=jnp.asarray(np.tile(tables, (1, kinds))),
                 lens=jnp.asarray(lens))
    rows = (SLOTS, model.block_rows) if model.block_rows else (SLOTS,)
    out = jax.jit(model._apply_paged_mixed)(
        params, cache, jnp.ones(rows, jnp.int32),
        jnp.asarray([0, 1, 0], jnp.int32), jnp.arange(chunk, dtype=jnp.int32),
        jnp.int32(2), jnp.int32(8), jnp.int32(5 if chunk else 0))
    return lens, out


def test_the_step_is_defined_in_one_class():
    """Over every ``TransformerLM`` subclass ``models.build_model`` can
    build (each config builder's ``model_class`` and every base between
    it and ``TransformerLM``), ``_apply_paged_mixed`` is in exactly one
    class's ``__dict__``, nothing defines a step under another name, and
    a dispatch's rows have one record."""
    configs = [make() for make in BLOCKS.values()] + [zaya_config("8b")]
    classes = {k for c in configs for k in c.model_class().__mro__
               if issubclass(k, TransformerLM)}
    assert len(classes) >= 12, classes
    assert [k for k in classes if "_apply_paged_mixed" in vars(k)] \
        == [TransformerLM]
    assert not [(k.__name__, n) for k in classes for n in vars(k)
                if n.startswith("_apply_paged") and k is not TransformerLM]
    from deepspeed_tpu.models import hybrid_ssm, latent_moe
    assert not hasattr(hybrid_ssm, "HybridStep")
    assert latent_moe.MixedStep is MixedStep


@pytest.mark.parametrize("chunk", [8, 0], ids=["mixed", "decode_only"])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_what_the_skeleton_owes_every_block(block, chunk):
    """``counters`` as wide as ``PAGED_COUNTERS`` (absent for a block that
    counts nothing); ``lens`` as the host's arithmetic has them — a
    decoding slot a row on, the chunk's slot the chunk's valid rows on, an
    idle slot where it was; a block-diffusion model's left to the host —;
    logits finite and of the lane's shape; ``chunk_logits`` all zero at a
    chunk of static length 0 (and for the model nothing samples a chunk
    of)."""
    model, params = build(block)
    lens, (dec_logits, chunk_logits, new) = dispatch(model, params, chunk)
    if model.PAGED_COUNTERS:
        assert new["counters"].shape == (len(model.PAGED_COUNTERS),)
        assert new["counters"].dtype == jnp.int32
    else:
        assert "counters" not in new
    want = lens.copy()
    if not model.block_rows:
        want[1] += 1
        want[2] += 5 if chunk else 0
    np.testing.assert_array_equal(np.asarray(new["lens"]), want)
    lane = (SLOTS, model.block_rows) if model.block_rows else (SLOTS,)
    assert dec_logits.shape == lane + (128,)
    assert np.isfinite(np.asarray(dec_logits)).all()
    assert chunk_logits.shape == (128,)
    sampled = chunk and not model.block_rows
    assert bool(jnp.any(chunk_logits != 0)) == bool(sampled)
    for name in ("k", "v"):
        old = model.init_paged_cache(POOL, BLOCK, jnp.float32)[name]
        assert (new[name] is None) if old is None \
            else new[name].shape == old.shape


@pytest.mark.parametrize("how", ["spec", "kv_bits"])
@pytest.mark.parametrize("block", REFUSING)
def test_the_refusals_are_the_blocks_own_sentences(block, how):
    """A draft run, or a quantized pool, handed to a block that refuses it
    raises that block's own ``paged_refusal`` sentence, from the one
    place (before anything reads the weights: none are made)."""
    model, params = build_model(BLOCKS[block]()), None
    cache = dict(model.init_paged_cache(POOL, BLOCK, jnp.float32))
    cache.update(block_tables=jnp.zeros((SLOTS, PAGES), jnp.int32),
                 lens=jnp.zeros((SLOTS,), jnp.int32))
    idle = jnp.zeros((SLOTS,), jnp.int32)
    more = {}
    if how == "spec":
        sentence = model.paged_refusal(spec=True)
        more = dict(spec_tokens=jnp.zeros((SLOTS, 2), jnp.int32),
                    spec_active=idle)
    else:
        sentence = model.paged_refusal(kv_bits=8)
        cache["k_scale"] = jnp.zeros((1, POOL, 1, 1, BLOCK), jnp.float32)
    assert sentence
    with pytest.raises(NotImplementedError, match=re.escape(sentence)):
        model._apply_paged_mixed(params, cache, idle, idle,
                                 jnp.zeros((0,), jnp.int32), jnp.int32(0),
                                 jnp.int32(0), jnp.int32(0), **more)


@pytest.mark.parametrize("block", ["plain", "block-diffusion"])
def test_a_lane_of_the_wrong_width_is_refused(block):
    """``dec_tokens`` is ``[slots]`` for a model that decodes a token a
    slot and ``[slots, block_rows]`` for one that generates by diffusion
    over blocks: the other shape names the model and its rows."""
    model, params = build_model(BLOCKS[block]()), None
    wrong = (SLOTS,) if model.block_rows else (SLOTS, 4)
    cache = dict(model.init_paged_cache(POOL, BLOCK, jnp.float32),
                 block_tables=jnp.zeros((SLOTS, PAGES), jnp.int32),
                 lens=jnp.zeros((SLOTS,), jnp.int32))
    with pytest.raises(ValueError, match=type(model).__name__):
        model._apply_paged_mixed(
            params, cache, jnp.zeros(wrong, jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((0,), jnp.int32),
            jnp.int32(0), jnp.int32(0), jnp.int32(0))


def step_of(lens, act, rows=1, chunk=0, at=(0, 0, 0)):
    lens = jnp.asarray(lens, jnp.int32)
    slot, start, n = (jnp.int32(a) for a in at)
    return MixedStep(None, None, lens, jnp.asarray(act, bool), slot, start,
                     n, None, None, len(act), rows, chunk, 0, 0)


@pytest.mark.parametrize("window", [None, 8], ids=["whole", "window"])
@pytest.mark.parametrize("rows,chunk", [(1, 0), (1, 8), (4, 8)])
def test_the_one_walk_count_against_a_loop(rows, chunk, window):
    """``walk_counts`` — what every block's ``*_tokens_read`` /
    ``*_pages_read`` / ``*_pages_in_runs`` come from — against a loop over
    the walks: a riding slot's context with the rows it wrote, the chunk
    slot's up to the chunk's last row, from the window's first position;
    a table of consecutive blocks is one run of eight pages."""
    tables = np.zeros((3, 16), np.int32)
    tables[0, :12] = 1 + np.arange(12)           # consecutive: runs
    tables[1, :4] = (40, 30, 35, 20)
    tables[2, :4] = (50, 51, 60, 61)
    lens, act = [70, 20, 0], [1, 1, 0]
    st = step_of(lens, act, rows, chunk, (2, 16, 5))
    got = np.asarray(walk_counts(st, jnp.asarray(tables), 8, window))
    walks = [(n + rows, 0) for n, a in zip(lens, act) if a]
    if window is not None:
        walks = [(t, max(t - window, 0)) for t, _ in walks]
    if chunk:
        walks.append((16 + 5, 0 if window is None
                      else max(16 - (window - 1), 0)))
    assert got[0] == sum(t - f for t, f in walks)
    assert got[1] == sum(-(-t // 8) - f // 8 for t, f in walks)
    # slot 0's first eight pages are one run where the walk starts at 0
    assert got[2] == (8 if window is None else 0)


def test_the_one_row_address():
    """``pool_rows``: position ``p`` at ``table[p // block] * block + p %
    block``; a masked row at row 0 of the null block; a parked position
    past the table's edge still indexes the table."""
    tables = jnp.asarray([[7, 3], [5, 2]], jnp.int32)
    got = pool_rows(tables, jnp.asarray([[5], [11]]),
                    jnp.asarray([[True], [True]]), 8, 4)
    np.testing.assert_array_equal(got, [[7 * 8 + 5], [2 * 8 + 3]])
    at = jnp.asarray([[5, 6], [40, 41]])
    got = pool_rows(tables, at, jnp.asarray([[True], [False]]), 8, 4)
    np.testing.assert_array_equal(got, [[61, 62], [32, 32]])
    got = pool_rows(tables[1], 8 + jnp.arange(4), jnp.arange(4) < 3, 8, 0)
    np.testing.assert_array_equal(got, [16, 17, 18, 0])
