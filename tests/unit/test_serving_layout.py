"""The layout a model's serving step reads its weights in
(``model.serving_params``): made once by ``init_inference``, told by the
tree's keys everywhere else.

A latent block's ``q_b`` and ``kv_b`` are published with the split
INSIDE a head; the step reads ``q_sections`` / ``w_uk`` / ``w_uv`` (and
the sparse block's indexer ``wq_sections``).  The
paged mixed step on either tree gives the logits of the full-sequence
forward on the published one, the conversion is idempotent, and an
engine holds the serving tree alone.  The standard block reads its
weights as stored."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import (
    build_model, glm_moe_dsa_config, gpt2_config, longcat_flash_config,
    openpangu_ultra_moe_config)

SIZES = dict(num_heads=4, d_model=64, d_ff=128, head_dim=24, vocab_size=128,
             max_seq_len=128, q_lora_rank=32, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             expert_d_ff=32, n_routed_experts=8, moe_topk=3,
             dtype=jnp.float32)
#: block -> (config builder, size, what else it takes, the parameter
#: groups that hold a latent attention)
LATENT = {
    "shortcut": (longcat_flash_config, "omni",
                 dict(num_layers=2, zero_expert_num=4),
                 [("blocks", "attn0"), ("blocks", "attn1")]),
    "sandwich": (openpangu_ultra_moe_config, "718b",
                 dict(num_layers=3, first_k_dense=1),
                 [("dense_blocks", "attn"), ("blocks", "attn")]),
    "sparse": (glm_moe_dsa_config, "5.2",
               dict(num_layers=3, first_k_dense=1, index_n_heads=4,
                    index_head_dim=16, index_topk=8,
                    indexer_types=("full", "shared", "full")),
               [("dense_blocks", "attn"), ("blocks", "attn")]),
}
HEADS, NOPE, ROPE, VDIM, RANK = (SIZES[k] for k in (
    "num_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "kv_lora_rank"))


def build(block):
    config, size, own, groups = LATENT[block]
    model = build_model(config(size, **SIZES, **own))
    return model, model.init(jax.random.PRNGKey(0)), groups


def paged_logits(model, params, ids, chunk=16, block=8):
    """``ids [t]`` through ``model._apply_paged_mixed`` over a cache of
    its own: all but the last token as chunks of slot 0 (of two slots),
    then the last as a decode row.  Returns ``(the positions whose logits
    came out, the logits)``: every chunk's last row and the decode
    row."""
    prompt, pages = len(ids) - 1, -(-len(ids) // block)
    cache = model.init_paged_cache(pages + 2, block, jnp.float32)
    tables = np.zeros((2, pages), np.int32)
    tables[0] = 1 + np.arange(pages)
    cache.update(block_tables=jnp.asarray(tables),
                 lens=jnp.zeros((2,), jnp.int32))
    step = jax.jit(model._apply_paged_mixed)
    idle = jnp.zeros((2,), jnp.int32)
    at, out = [], []
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        row = np.zeros(chunk, np.int32)
        row[:n] = ids[start:start + n]
        _, logits, new = step(params, cache, idle, idle, jnp.asarray(row),
                              jnp.int32(0), jnp.int32(start), jnp.int32(n))
        cache = dict(cache, **{k: new[k] for k in ("k", "v", "lens")})
        at.append(start + n - 1)
        out.append(logits)
    logits, _, _ = step(params, cache, jnp.asarray([ids[-1], 0], jnp.int32),
                        jnp.asarray([1, 0], jnp.int32),
                        jnp.zeros((chunk,), jnp.int32), jnp.int32(0),
                        jnp.int32(0), jnp.int32(0))
    return at + [prompt], jnp.stack(out + [logits[0]])


@pytest.mark.parametrize("tree", ["serving", "published"])
@pytest.mark.parametrize("block", list(LATENT))
def test_the_paged_step_on_either_tree_gives_the_full_forwards_logits(
        block, tree):
    """37 tokens over three chunks and a decode row (past ``index_topk``
    for the sparse block): the mixed step on the tree an engine holds,
    and on the published tree as a check of the cell's brings it, against
    ``hidden_states_and_aux`` on the published tree — 1e-4, float32
    through the absorbed form and the online softmax, as the latent
    parity tests allow."""
    model, params, _ = build(block)
    ids = np.random.default_rng(3).integers(0, 128, 38)
    want = model.apply(params, jnp.asarray(ids)[None])[0]
    given = model.serving_params(params) if tree == "serving" else params
    at, got = paged_logits(model, given, ids)
    assert float(jnp.abs(got - want[np.asarray(at)]).max()) < 1e-4


@pytest.mark.parametrize("block", list(LATENT))
def test_the_layout_is_told_by_the_keys_and_made_once(block):
    model, params, groups = build(block)
    laid = model.serving_params(params)
    for group in groups:
        old, new = params, laid
        for key in group:
            old, new = old[key], new[key]
        layers = old["q_b"]["kernel"].shape[0]
        assert {"q_b", "kv_b"} & set(new) == set()
        assert set(new) - set(old) == {"q_sections", "w_uk", "w_uv"}
        assert new["q_sections"]["kernel"].shape \
            == old["q_b"]["kernel"].shape
        assert new["w_uk"].shape == (layers, HEADS, RANK, NOPE)
        assert new["w_uv"].shape == (layers, HEADS, RANK, VDIM)
        # the same numbers, each where the rule says: head h's columns
        q = np.asarray(old["q_b"]["kernel"]).reshape(
            layers, -1, HEADS, NOPE + ROPE)
        sections = np.asarray(new["q_sections"]["kernel"])
        assert np.array_equal(sections[..., :HEADS * NOPE],
                              q[..., :NOPE].reshape(layers, -1, HEADS * NOPE))
        assert np.array_equal(sections[..., HEADS * NOPE:],
                              q[..., NOPE:].reshape(layers, -1, HEADS * ROPE))
        kv = np.asarray(old["kv_b"]["kernel"]).reshape(
            layers, RANK, HEADS, NOPE + VDIM).transpose(0, 2, 1, 3)
        assert np.array_equal(np.asarray(new["w_uk"]), kv[..., :NOPE])
        assert np.array_equal(np.asarray(new["w_uv"]), kv[..., NOPE:])
        # what the layout leaves alone is the same array, not a copy
        assert new["out"]["kernel"] is old["out"]["kernel"]
    assert laid["embed"] is params["embed"]
    if block == "sparse":
        # the indexer's query projection: rotary part | rest, 4 heads of 16
        wq = np.asarray(params["indexer"]["wq"]["kernel"]).reshape(
            2, RANK, HEADS, 16)
        sections = np.asarray(laid["indexer"]["wq_sections"]["kernel"])
        assert "wq" not in laid["indexer"]
        assert np.array_equal(sections[..., :HEADS * ROPE],
                              wq[..., :ROPE].reshape(2, RANK, HEADS * ROPE))
        assert np.array_equal(sections[..., HEADS * ROPE:],
                              wq[..., ROPE:].reshape(2, RANK, HEADS * ROPE))
        assert laid["indexer"]["wk"] is params["indexer"]["wk"]
    # a serving tree passed again comes back as it is, never re-permuted
    assert model.serving_params(laid) is laid
    specs = model.partition_specs()
    assert jax.tree_util.tree_structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree_util.tree_structure(laid)


@pytest.mark.parametrize("block", list(LATENT))
def test_an_engine_holds_the_serving_tree_alone(block):
    """``init_inference`` on the published tree (a checkpoint's, the
    benchmark's): the engine's own tree is the serving one, with specs
    to match, and the caller's tree is as it was."""
    model, params, groups = build(block)
    eng = ds.init_inference(model, {"dtype": "float32"}, params=params)
    held = jax.tree_util.tree_leaves_with_path(eng.params)
    names = {str(getattr(p[-1], "key", "")) for p, _ in held} \
        | {str(getattr(p[-2], "key", "")) for p, _ in held}
    assert {"q_sections", "w_uk", "w_uv"} <= names
    assert not {"q_b", "kv_b"} & names
    assert "q_b" in params[groups[0][0]][groups[0][1]]
    assert jax.tree_util.tree_structure(eng.params) \
        == jax.tree_util.tree_structure(eng.param_specs, is_leaf=lambda s:
                                        isinstance(s, jax.sharding.PartitionSpec))
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 128, (1, 12)))
    assert float(jnp.abs(eng.forward(ids) - model.apply(params, ids)).max()) \
        < 2e-5


def test_the_standard_block_reads_its_weights_as_stored():
    model = build_model(gpt2_config(
        "125m", num_layers=2, d_model=64, num_heads=4, vocab_size=128,
        max_seq_len=64))
    params = model.init(jax.random.PRNGKey(0))
    assert model.serving_params(params) is params
    eng = ds.init_inference(model, {"dtype": "float32"}, params=params)
    assert jax.tree_util.tree_structure(eng.params) \
        == jax.tree_util.tree_structure(params)


def test_int8_weight_only_quantizes_the_serving_tree():
    """``quant.enabled`` comes after the layout: the int8 leaves are the
    serving tree's, dequantized a layer at a time into the one layout the
    sublayers read (the serving engine still refuses int8 weights for a
    latent block: ``paged_refusal(weight_quant=True)``)."""
    model, params, _ = build("shortcut")
    eng = ds.init_inference(
        model, {"dtype": "float32", "quant": {"enabled": True}},
        params=params)
    attn = eng.params["blocks"]["attn0"]
    assert {"q_sections", "w_uk", "w_uv"} <= set(attn) \
        and not {"q_b", "kv_b"} & set(attn)
    assert attn["w_uk"].dtype == jnp.int8
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 128, (1, 12)))
    want = model.apply(params, ids)
    assert float(jnp.abs(eng.forward(ids) - want).max()) \
        < 0.02 * float(jnp.abs(want).max())
