"""The CCA + top-1 expert block (``models/cca_moe.py``; ZAYA1 family)
against the benchmark's plain reference (``benchmark/lib/
reference_zaya.py``) at a tiny size in float32 on the CPU; the kernels
(flash, the grouped product and its two backward products) run in the
Pallas interpreter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_zaya as R
from deepspeed_tpu.models import build_model, zaya_config
from deepspeed_tpu.models import cca_moe
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.config import MeshConfig

TINY = dict(num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
            d_model=64, d_ff=32, expert_d_ff=32, vocab_size=128,
            max_seq_len=64, router_hidden=16, n_routed_experts=8,
            loss_chunk=16)
REF = dict(heads=4, kv_heads=2, head_dim=16, rotary_dim=8, rope_theta=5e6,
           eps=1e-5, bias_unit=cca_moe.ROUTER_BIAS_UNIT)


def build(held=(0, 4), seed=1, **kw):
    """A float32 model whose learned scalars have left their initial
    values (``gamma`` 0, ``tau`` 1, ``b`` 0, the convolutions' biases 0),
    so that every term of the layer shows, and whose router MLP's matrices
    are large enough that the pick depends on the token (at 16 wide and
    std 0.02 one expert wins every row)."""
    model = build_model(zaya_config(
        "8b", **{**TINY, "experts_held": held, "dtype": jnp.float32, **kw}))
    params = model.init(jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 8))
    blocks = params["blocks"]

    def moved(a, scale, around=0.0):
        return around + scale * jax.random.normal(next(keys), a.shape)
    router = blocks["moe"]["router"]
    router["gamma"] = moved(router["gamma"], 0.3)
    for name in ("fc1", "fc2", "fc3"):
        router[name]["kernel"] = 25.0 * router[name]["kernel"]
    blocks["attn"]["tau"] = moved(blocks["attn"]["tau"], 0.2, 1.0)
    blocks["moe"]["bias"] = moved(blocks["moe"]["bias"],
                                  0.03 / cca_moe.ROUTER_BIAS_UNIT)
    for conv in ("conv0", "conv1"):
        blocks["attn"][conv]["bias"] = moved(blocks["attn"][conv]["bias"],
                                             0.1)
    return model, params


def ids(seed=3, rows=2, seq=64):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq), 0, 128)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("impl,remat", [("xla", "none"), ("flash", "full")])
class TestAgainstTheReference:
    def test_hidden_states(self, impl, remat):
        model, params = build(attn_impl=impl, remat=remat)
        got, _ = model.hidden_states_and_aux(params, ids())
        want, _ = R.hidden(params, ids(), {**REF, "held": (0, 4)})
        assert rel(got, want) < 1e-5

    def test_loss_and_every_gradient(self, impl, remat):
        model, params = build(attn_impl=impl, remat=remat)
        batch = {"input_ids": ids()}
        (loss, counters), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        want, want_grads = jax.value_and_grad(R.loss)(
            params, ids(), {**REF, "held": (0, 4)})
        assert abs(float(loss) - float(want)) < 1e-5
        flat = jax.tree_util.tree_leaves_with_path(grads)
        for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
            assert float(jnp.linalg.norm(w)) > 0, path
            assert rel(g, w) < 1e-4, (jax.tree_util.keystr(path), rel(g, w))
        # the counters, against the reference's own picks
        picks = 2 * 64 * TINY["num_layers"]
        assert int(counters["moe_picks"]) == picks
        assert 0 < int(counters["moe_picks_held"]) < picks
        assert int(counters["moe_experts_touched"]) >= 9
        assert set(counters) == set(cca_moe.COUNTERS) | {
            "router_bias_abs_max"}
        assert float(counters["router_bias_abs_max"]) == pytest.approx(
            cca_moe.ROUTER_BIAS_UNIT * float(jnp.abs(params["blocks"]["moe"]["bias"]).max()))


def reference_picks(params, tokens, cfg):
    """Every layer's picks ``[L, B, T]`` by the reference's router."""
    x = params["embed"]["embedding"][tokens]
    r = jnp.zeros(x.shape[:2] + (TINY["router_hidden"],))
    out = []
    for at in range(TINY["num_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[at], params["blocks"])
        u = R._rms(p["ln2"], x + R.attention(
            p["attn"], R._rms(p["ln1"], x, cfg["eps"]), cfg), cfg["eps"])
        _, prob = R.router(p["moe"]["router"], u, r, cfg)
        out.append(jnp.argmax(prob + cfg["bias_unit"] * p["moe"]["bias"],
                              axis=-1))
        x, r, _ = R.layer(p, x, r, cfg)
    return jnp.stack(out)


def test_reference_takes_the_picks_it_is_handed():
    """``own_picks`` is what the reference chooses; handed back they change
    nothing, and another choice changes the loss (the weight stays the
    reference's own ``p_e``)."""
    _, params = build()
    cfg = {**REF, "held": (0, 4)}
    own = R.own_picks(params, ids(), cfg)
    with jax.default_matmul_precision("highest"):
        assert np.array_equal(own, reference_picks(params, ids(), cfg))
    assert float(R.loss(params, ids(), cfg, picks=own)) == float(
        R.loss(params, ids(), cfg))
    assert float(R.loss(params, ids(), cfg, picks=(own + 1) % 4)) != float(
        R.loss(params, ids(), cfg))


def test_held_and_absent_picks_add_up():
    model, params = build()
    cfg = {**REF, "held": (0, 4)}
    with jax.default_matmul_precision("highest"):
        picks = reference_picks(params, ids(), cfg)
    _, counters = model.loss(params, {"input_ids": ids()})
    absent = int((picks >= 4).sum())
    assert absent > 0
    assert int(counters["moe_picks_held"]) + absent == int(
        counters["moe_picks"])
    per_layer = [np.bincount(np.asarray(p).ravel(), minlength=8)[:4]
                 for p in picks]
    assert int(counters["moe_rows_max_expert"]) == sum(
        int(c.max()) for c in per_layer)
    assert int(counters["moe_experts_touched"]) == sum(
        int((c > 0).sum()) for c in per_layer)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0..3 on one chip and 4..7 on the other: the parts of the
    expert sublayer's output that the two programs compute add up to what
    the reference computes with every expert."""
    whole, params = build(held=(0, 8))
    u = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 64))
    r_prev = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (2, 64, 16))
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])
    with jax.default_matmul_precision("highest"):
        want, want_r, _ = R.experts(p, u, r_prev, {**REF, "held": (0, 8)})
    total = 0.0
    for lo, hi in ((0, 4), (4, 8)):
        share = build_model(zaya_config(
            "8b", **{**TINY, "experts_held": (lo, hi),
                     "dtype": jnp.float32}))
        mine = dict(p, experts={n: w[lo:hi]
                                for n, w in p["experts"].items()})
        y, r, _, counted, _ = share._moe(mine, u, r_prev)
        assert rel(r, want_r) < 1e-5
        assert 0 < int(counted[1]) < int(counted[0])
        total = total + y
    assert rel(total, want) < 1e-5
    assert float(jnp.linalg.norm(want)) > 0


def test_convolutions_and_shift_see_nothing_later():
    """Perturb the token at position 40: the attention sublayer's output
    (convolutions, value shift, attention) and the whole model's hidden
    states at positions before it are bit-equal."""
    model, params = build()
    a = ids()
    b = a.at[:, 40].set((a[:, 40] + 1) % 128)
    p = jax.tree_util.tree_map(lambda w: w[0], params["blocks"]["attn"])
    h_a = params["embed"]["embedding"][a] * 50.0
    h_b = params["embed"]["embedding"][b] * 50.0
    out_a, out_b = model._cca(p, h_a), model._cca(p, h_b)
    assert np.array_equal(out_a[:, :40], out_b[:, :40])
    assert not np.array_equal(out_a[:, 40], out_b[:, 40])
    # position 41 sees position 40 through both convolutions and the shift
    assert not np.array_equal(out_a[:, 41], out_b[:, 41])
    x_a = model.hidden_states(params, a)
    x_b = model.hidden_states(params, b)
    assert np.array_equal(x_a[:, :40], x_b[:, :40])
    assert not np.array_equal(x_a[:, 40:], x_b[:, 40:])


def test_bias_gradient_is_the_load_and_the_term_adds_nothing():
    model, params = build()
    batch = {"input_ids": ids()}
    cfg = {**REF, "held": (0, 4)}
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch)
    with jax.default_matmul_precision("highest"):
        picks = reference_picks(params, ids(), cfg)
    share = np.stack([np.bincount(np.asarray(p).ravel(), minlength=8)
                      / p.size for p in picks])
    np.testing.assert_allclose(np.asarray(grads["blocks"]["moe"]["bias"]),
                               share - 1.0 / 8, atol=1e-6)
    x, balance = model.hidden_states_and_aux(params, ids())
    assert float(balance) == 0.0
    labels, mask = model._targets(batch)
    assert float(loss) == float(model.nll_from_hidden(params, x, labels,
                                                      mask))


def test_engine_trains_it_and_hands_back_the_counters():
    model = build_model(zaya_config(
        "8b", **{**TINY, "num_layers": 2, "experts_held": (0, 4),
                 "attn_impl": "flash", "remat": "full"}))
    engine, *_ = ds.initialize(
        model=model, rng=jax.random.PRNGKey(0),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2, "steps_per_print": 0,
                "bf16": {"enabled": True}, "gradient_clipping": 1.0,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 2},
                "mesh": {"data": 1}},
        mesh=build_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    tokens = np.random.default_rng(0).integers(0, 128, (4, 64),
                                               dtype=np.int32)
    losses = []
    for _ in range(8):
        out = engine.train_step({"input_ids": tokens})
        losses.append(float(out["loss"]))
        # two microbatches of 2 x 64 tokens through 2 layers
        assert int(out["moe_picks"]) == 2 * 2 * 64 * 2
        assert 0 <= int(out["moe_picks_held"]) <= int(out["moe_picks"])
        assert set(cca_moe.COUNTERS) <= set(out)
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.5
    assert float(out["router_bias_abs_max"]) > 0      # b has moved
    assert np.isfinite(float(engine.eval_loss({"input_ids": tokens})))


def test_refusals():
    model, _ = build()
    assert model.training_refusal() is None
    assert "convolution" in model._paged_supported()
    with pytest.raises(NotImplementedError, match="shift"):
        model.init_cache(1, 16)
    with pytest.raises(ValueError, match="experts_held"):
        build_model(zaya_config("8b", **{**TINY, "experts_held": (4, 12)}))
    from deepspeed_tpu.models import TransformerLM
    with pytest.raises(TypeError, match="build_model"):
        TransformerLM(zaya_config("8b", **TINY))


def test_published_counts():
    """The layer equations reproduce the card's counts: 8.3 B outside the
    embedding, 0.75 B active."""
    c = zaya_config("8b")
    part = c.layer_params()
    assert part["projections"] == 5_242_880
    assert 320_000 < part["convolutions"] < 340_000
    assert 650_000 < part["router"] < 670_000
    assert part["expert"] == 12_582_912
    layer = sum(part.values()) + 15 * part["expert"]
    assert round(40 * layer / 1e9, 2) == 8.30
    active = 40 * (sum(part.values()))
    assert round(active / 1e9, 2) == 0.75
    cut = zaya_config("8b", num_layers=5, vocab_size=32896,
                      max_seq_len=8192, experts_held=(0, 8))
    assert round(cut.num_params() / 1e6, 1) == 601.9
    model = build_model(zaya_config("8b", **TINY))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert model.config.num_params() == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
