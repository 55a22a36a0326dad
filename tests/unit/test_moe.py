"""The expert layer (``moe/dropless.py``) by itself: the router in every
form ``route_logits`` spells and this chip's share of the experts against a
NumPy loop over experts, the grouped product's backward, and what refuses
the capacity-gated layer's old options.  The blocks that use the layer have
their own files (``test_shortcut_moe.py``, ``test_latent_moe.py``,
``test_sparse_latent_moe.py``, ``test_cca_moe.py``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import (TransformerConfig, TransformerLM,
                                  build_model, gpt2_config, zaya_config)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.config import DeepSpeedConfig, MeshConfig

# ---------------------------------------------------------------------------
# route + expert_share against a float64 loop over experts
# ---------------------------------------------------------------------------
T, H, F, E, Z, SCALE = 40, 16, 8, 8, 2, 2.5
HOT = 1                      # held by both shares below
SHARES = {"all": [(0, E)], "half": [(0, E // 2), (E // 2, E)]}
#: the published gates: LongCat's is softmax_bias, openPangu's
#: sigmoid_renorm, GLM's sigmoid_bias_renorm, ZAYA's softmax_bias at k = 1
FORMS = {f"{scoring}{'_bias' * biased}{'_renorm' * renorm}":
         (scoring, bool(biased), bool(renorm))
         for scoring in ("softmax", "sigmoid") for biased in (0, 1)
         for renorm in (0, 1)}
#: pinned already, through the block that uses it: ZAYA's gate over a half
#: share (test_cca_moe.py::test_the_two_shares_add_up_to_the_uncut_layer,
#: ::test_held_and_absent_picks_add_up)
ELSEWHERE = {("softmax_bias", 1, "half")}


def layer_case(load, seed=0):
    """Rows, a gate and all ``E`` experts' weights; under the ``one_expert``
    load every row's first pick is ``HOT`` (a feature all rows share and
    only ``HOT``'s gate column reads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    u = np.array(jax.random.normal(ks[0], (T, H)))
    kernel = np.array(0.5 * jax.random.normal(ks[1], (H, E + Z)))
    if load == "one_expert":
        u[:, 0], kernel[0], kernel[0, HOT] = 1.0, 0.0, 12.0
    bias = np.asarray(0.05 * jax.random.normal(ks[2], (E + Z,)))
    experts = dropless.init_experts(ks[3], E, H, F, 0.3, 0.3, jnp.float32)
    return jnp.asarray(u), jnp.asarray(kernel), jnp.asarray(bias), experts


def loop_over_experts(u, kernel, bias, k, scoring, renorm, experts, lo, hi):
    """``route`` then ``expert_share`` of experts ``lo .. hi - 1`` in
    float64, an expert at a time: ``(index, weight, y)``."""
    u, kernel = np.asarray(u, np.float64), np.asarray(kernel, np.float64)
    logits = u @ kernel
    if scoring == "softmax":
        p = np.exp(logits - logits.max(1, keepdims=True))
        p /= p.sum(1, keepdims=True)
    else:
        p = 1.0 / (1.0 + np.exp(-logits))
    chosen_by = p if bias is None else p + np.asarray(bias, np.float64)
    index = np.argsort(-chosen_by, axis=1, kind="stable")[:, :k]
    weight = np.take_along_axis(p, index, axis=1)
    if renorm:
        weight = weight / (weight.sum(1, keepdims=True) + 1e-20)
    weight = SCALE * weight
    return index, weight, share_of_the_loop(u, index, weight, experts, lo,
                                            hi)


def share_of_the_loop(u, index, weight, experts, lo, hi):
    """``expert_share`` of experts ``lo .. hi - 1`` (``experts`` holds all
    ``E``) in float64, an expert at a time, plus the identity experts'."""
    u = np.asarray(u, np.float64)
    w = {n: np.asarray(a, np.float64) for n, a in experts.items()}
    y = np.zeros_like(u)
    for e in range(lo, hi):
        gate = u @ w["w_gate"][e]
        out = (gate / (1.0 + np.exp(-gate)) * (u @ w["w_up"][e])) \
            @ w["w_down"][e]
        y += (weight * (index == e)).sum(1, keepdims=True) * out
    y += (weight * (index >= E)).sum(1, keepdims=True) * u
    return y


@pytest.mark.parametrize("form,k,share", [
    (form, k, share) for form in FORMS for k in (1, 2, 8)
    for share in SHARES if (form, k, share) not in ELSEWHERE])
def test_route_and_expert_share_against_a_loop_over_experts(form, k, share):
    """What the capacity-gated layer's tests held it to, in the form that
    is true of this one: every row has exactly ``k`` distinct picks and
    its weights are the gate's; NO row is dropped at any load (under
    ``one_expert`` all ``T`` rows reach ``HOT``, several passes of the row
    buffer); a row with no pick held here gets exactly nothing, and the
    two halves add up to the whole layer; the output is the loop's."""
    scoring, biased, renorm = FORMS[form]
    for load in ("balanced", "one_expert"):
        u, kernel, bias, experts = layer_case(load)
        bias = bias if biased else None
        routing = dropless.route(u, kernel, bias, k, SCALE, scoring, renorm)
        index = np.asarray(routing.index)
        assert index.shape == (T, k)
        assert ((0 <= index) & (index < E + Z)).all()
        assert all(len(set(row)) == k for row in index)
        total = np.zeros((T, H))
        for lo, hi in SHARES[share]:
            held = {n: a[lo:hi] for n, a in experts.items()}
            y, counted = dropless.expert_share(held, u, routing, E, (lo, hi),
                                               pass_rows=32)
            want_index, want_weight, want = loop_over_experts(
                u, kernel, bias, k, scoring, renorm, experts, lo, hi)
            np.testing.assert_array_equal(index, want_index)
            np.testing.assert_allclose(routing.weight, want_weight,
                                       rtol=2e-5)
            np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
            counted = dict(zip(dropless.COUNTERS, map(int, counted)))
            here = (index >= lo) & (index < hi)
            assert counted["moe_picks"] == T * k
            assert counted["moe_picks_held"] == here.sum()
            assert counted["moe_rows_max_expert"] == max(
                (index == e).sum() for e in range(lo, hi))
            if load == "one_expert" and lo <= HOT < hi:
                assert (index[:, 0] == HOT).all()
                assert counted["moe_rows_max_expert"] == T > 32
            nothing_here = ~(here | (index >= E)).any(1)
            assert not np.asarray(y)[nothing_here].any()
            total += np.asarray(y, np.float64)
        if share == "half":
            whole = loop_over_experts(u, kernel, bias, k, scoring, renorm,
                                      experts, 0, E)
            identity = (whole[1] * (index >= E)).sum(1, keepdims=True) \
                * np.asarray(u, np.float64)
            # each share adds the identity experts' part: counted once
            np.testing.assert_allclose(total - identity, whole[2],
                                       rtol=2e-4, atol=4e-5)


# ---------------------------------------------------------------------------
# a pass moves its live rows only: blocks of BLOCK_ROWS under loops whose
# trip count is the pass's live tiles
# ---------------------------------------------------------------------------
ROWS, HELD, PASS, BLOCK = 200, (0, 4), 256, 32
#: load -> the blocks each pass must walk of its ``PASS // BLOCK`` = 8
#: (None: whatever the drawn picks need, asserted to be in the middle)
LOADS = {"no_pick_held": [], "one_pick": [1], "balanced": None,
         "two_experts": [8, 3]}


def picks_under(load):
    """``index [ROWS, 2]`` over ``E`` routed + ``Z`` identity experts, of
    which ``HELD`` are here, and ``row_valid`` (every seventh row carries
    no token)."""
    rng = np.random.default_rng(3)
    valid = np.arange(ROWS) % 7 != 3
    absent = np.arange(HELD[1], E + Z)
    if load == "balanced":
        index = np.stack([rng.permutation(E + Z)[:2] for _ in range(ROWS)])
    else:
        index = np.stack([rng.permutation(absent)[:2] for _ in range(ROWS)])
    if load == "one_pick":
        index[5, 1] = 2
    if load == "two_experts":      # every first pick to one, second to one
        index[:] = HOT, 3
    return index.astype(np.int32), valid


@pytest.mark.parametrize("stacked", [False, True], ids=["held", "layer"])
@pytest.mark.parametrize("load", list(LOADS))
def test_a_pass_moves_its_live_blocks_only(monkeypatch, load, stacked):
    """The form with passes at 0, 1, a middle count and ALL blocks of a
    pass live (and a second pass after the full one), rows without a token
    masked, the experts read out of a ``[layers, held, ..]`` stack or
    handed over: the loop's output, no pick dropped, and
    ``moe_rows_moved`` = blocks walked x ``BLOCK_ROWS`` — the rows past a
    pass's last live block are neither gathered nor added."""
    monkeypatch.setattr(dropless, "BLOCK_ROWS", BLOCK)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    u = jax.random.normal(ks[0], (ROWS, H))
    index, valid = picks_under(load)
    weight = np.asarray(jax.random.uniform(ks[1], index.shape)) + 0.5
    layers = [dropless.init_experts(k, E, H, F, 0.3, 0.3, jnp.float32)
              for k in jax.random.split(ks[2], 3)]
    lo, hi = HELD
    if stacked:
        held = {n: jnp.stack([w[n][lo:hi] for w in layers])
                for n in layers[0]}
        kw = {"layer": jnp.int32(1)}
    else:
        held, kw = {n: a[lo:hi] for n, a in layers[1].items()}, {}
    y, counted = dropless.expert_share(
        held, u, dropless.Routing(jnp.asarray(index), jnp.asarray(weight)),
        E, HELD, row_valid=jnp.asarray(valid), pass_rows=PASS, **kw)
    want = share_of_the_loop(u, index, weight * valid[:, None], layers[1],
                             lo, hi)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert not np.asarray(y)[~valid].any()
    here = valid[:, None] & (index >= lo) & (index < hi)
    tiles = sum(-(-int((here & (index == e)).sum()) // dropless.TILE_ROWS)
                for e in range(lo, hi))
    a_pass = PASS // dropless.TILE_ROWS
    walked = [-(-min(tiles - at, a_pass) * dropless.TILE_ROWS // BLOCK)
              for at in range(0, tiles, a_pass)]
    if LOADS[load] is None:
        assert len(walked) == 1 and 1 < walked[0] < PASS // BLOCK, walked
    else:
        assert walked == LOADS[load]
    counted = dict(zip(dropless.COUNTERS, map(int, counted)))
    assert counted["moe_picks_held"] == here.sum()
    assert counted["moe_rows_moved"] == sum(walked) * BLOCK


# ---------------------------------------------------------------------------
# the layout: where every held pick sits in the row buffer, ONE kernel
# (``moe_layout``) against a loop over experts
# ---------------------------------------------------------------------------
#: (T, k, held, tile, the router's outputs, the rows ``expert_share`` asks
#: for): SDAR's decode-only dispatch, its mixed one, LongCat's top-12 and
#: the training call (top-1, 8 held, tiles of 256, ONE pass; 16,384 rows on
#: the chip, cut in ``T`` for the CPU)
LAYOUT_SHAPES = {"decode": (160, 8, 16, 16, 128, 2048),
                 "mixed": (672, 8, 16, 16, 128, 6144),
                 "top12": (48, 12, 16, 16, 64, 896),
                 "training": (1536, 1, 8, 256, 16, 3584)}
LAYOUT_LOADS = ("no_pick_held", "one_pick", "balanced", "one_expert",
                "every_pick_held", "rows_masked")


def layout_case(shape, load):
    """``local [T, k]`` as ``expert_share`` hands it over (``held`` for a
    pick that is absent, or whose row carries no token) and the weights."""
    t, k, held, tile, routed, rows = LAYOUT_SHAPES[shape]
    rng = np.random.default_rng(LAYOUT_LOADS.index(load))
    absent = np.arange(held, routed)
    index = np.stack([rng.permutation(absent)[:k] for _ in range(t)])
    if load == "one_pick":
        index[t // 3, k - 1] = 2
    elif load in ("balanced", "rows_masked"):
        index = np.stack([rng.permutation(routed)[:k] for _ in range(t)])
    elif load == "one_expert":          # a row picks an expert once
        index[:, k // 2] = 3
    elif load == "every_pick_held":
        index = np.stack([rng.permutation(held)[:k] for _ in range(t)])
    valid = np.ones((t, 1), bool)
    if load == "rows_masked":
        valid[np.arange(t) % 7 == 3] = False
    local = np.where(valid & (index < held), index, held).astype(np.int32)
    weight = (rng.random((t, k)) + 0.5).astype(np.float32) * valid
    return local, weight, held, rows, tile


def layout_by_a_loop(local, weight, held, rows, tile):
    """Experts in order, an expert's rows from a tile boundary on, picks in
    token order; ``T`` / 0.0 in every row without a pick.  Returns the
    ``_Layout`` fields and every pick's row (``rows`` for none)."""
    t, k = local.shape
    flat, w = local.reshape(-1), weight.reshape(-1)
    row_token = np.full(rows, t, np.int32)
    row_weight = np.zeros(rows, np.float32)
    tile_expert = np.full(rows // tile, held - 1, np.int32)
    counts = np.zeros(held, np.int32)
    dest = np.full(flat.shape, rows, np.int32)
    at = 0
    for e in range(held):
        mine = np.nonzero(flat == e)[0]
        counts[e] = len(mine)
        for r, i in enumerate(mine):
            dest[i] = at * tile + r
            row_token[dest[i]], row_weight[dest[i]] = i // k, w[i]
        tiles = -(-len(mine) // tile)
        tile_expert[at:at + tiles] = e
        at += tiles
    return dropless._Layout(row_token, row_weight, tile_expert,
                            np.int32(at), counts), dest


@functools.lru_cache(maxsize=None)
def compiled_layout(held, rows, tile):
    """One program a shape: the loads of a shape share it."""
    return jax.jit(lambda local, weight: dropless._layout(
        local, weight, held, rows, tile))


@pytest.mark.parametrize("load", LAYOUT_LOADS)
@pytest.mark.parametrize("shape", list(LAYOUT_SHAPES))
def test_layout_is_the_loops_element_for_element(shape, load):
    local, weight, held, rows, tile = layout_case(shape, load)
    want, _ = layout_by_a_loop(local, weight, held, rows, tile)
    got = compiled_layout(held, rows, tile)(local, weight)
    for name, a, b in zip(dropless._Layout._fields, got, want):
        a = np.asarray(a)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.array_equal(a, b), (name, np.nonzero(a != b)[0][:8])
    held_picks = int((local < held).sum())
    assert int(got.counts.sum()) == held_picks
    assert {"no_pick_held": held_picks == 0, "one_pick": held_picks == 1,
            "one_expert": int(got.counts[3]) == held_picks == len(local),
            "every_pick_held": held_picks == local.size}.get(load, True)


@pytest.mark.parametrize("shape", ["decode", "training"])
def test_layout_row_weight_differentiates_as_the_scatter_did(shape):
    """``row_weight`` is a gather of the picks' weights by the pick in each
    row; the form it replaced scattered them to their rows (``zeros.at[
    dest].set(weight)``): the same gradient in the router's weights, an
    absent pick's exactly zero."""
    local, weight, held, rows, tile = layout_case(shape, "balanced")
    _, dest = layout_by_a_loop(local, weight, held, rows, tile)
    g = np.random.default_rng(5).standard_normal(rows).astype(np.float32)

    def ours(weight):
        return jnp.sum(dropless._layout(local, weight, held, rows,
                                        tile).row_weight * g)

    def scattered(weight):
        return jnp.sum(jnp.zeros((rows,), jnp.float32).at[dest].set(
            weight.reshape(-1), mode="drop") * g)
    got, want = jax.grad(ours)(weight), jax.grad(scattered)(weight)
    assert np.array_equal(got, want)
    assert np.asarray(got)[local < held].all()
    assert not np.asarray(got)[local == held].any()


# ---------------------------------------------------------------------------
# what is gone says where its successor is
# ---------------------------------------------------------------------------
OLD_KEYS = ("moe_num_experts", "moe_freq", "moe_k", "moe_capacity_factor",
            "moe_eval_capacity_factor", "moe_min_capacity",
            "moe_use_residual", "moe_noisy_gate_policy", "moe_use_rts",
            "moe_aux_loss_coef", "moe_d_ff")
TRAIN = {"train_micro_batch_size_per_gpu": 1, "steps_per_print": 0,
         "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}


def tiny_lm():
    return TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=32, num_heads=2, vocab_size=64,
        max_seq_len=16, dtype=jnp.float32))


def standard_config_given(key):
    TransformerConfig(**{key: 2})


def block_config_given(key):
    zaya_config("8b", **{key: 2})


def pipeline_schedule(value):
    DeepSpeedConfig({**TRAIN, "pipeline": {"schedule": value}})


def expert_axis_under(engine):
    mesh = {"pipeline": {"pipe": 2, "data": 2, "expert": 2},
            "infinity": {"data": 4, "expert": 2}}[engine]
    zero = {"pipeline": {"stage": 1}, "infinity": {
        "stage": 3, "offload_param": {"device": "cpu"},
        "offload_optimizer": {"device": "cpu"}}}[engine]
    ds.initialize(model=tiny_lm(), mesh=build_mesh(MeshConfig(**mesh)),
                  config={**TRAIN, "bf16": {"enabled": True}, "mesh": mesh,
                          "zero_optimization": zero})


@pytest.mark.parametrize("refused,given", [
    *[(standard_config_given, key) for key in OLD_KEYS],
    (block_config_given, "moe_num_experts"),
    (pipeline_schedule, "gpipe"), (pipeline_schedule, "1f1b"),
    (expert_axis_under, "pipeline"), (expert_axis_under, "infinity")],
    ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_what_is_gone_names_the_dropless_layer_or_roadmap_b6(refused, given):
    """The capacity-gated layer's eleven options on the standard block's
    configuration (and on a block's own), the pipeline's second schedule
    and an ``expert`` mesh axis under the two engines that took one for
    that layer alone: each fails, and says where to go."""
    with pytest.raises((TypeError, ValueError, NotImplementedError),
                       match=r"moe/dropless\.py|ROADMAP B6"):
        refused(given)


# ---------------------------------------------------------------------------
# the block that trains, under every ZeRO stage over data = 8
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("stage", [1, 2, 3])
def test_cca_block_under_zero_matches_one_device(stage):
    """The CCA + top-1 expert block through ``ds.initialize`` at ZeRO
    ``stage`` on ``mesh {data: 8}`` walks one device's losses: stage 3's
    ``gather_layer`` meets a stacked ``[layers, experts, ..]`` leaf."""
    sizes = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                 d_model=64, d_ff=32, expert_d_ff=32, vocab_size=128,
                 max_seq_len=32, router_hidden=16, n_routed_experts=8,
                 experts_held=(0, 4), loss_chunk=16, dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 128, (8, 32),
                                               dtype=np.int32)

    def losses(devices, stage):
        mesh = {"data": devices}
        engine, *_ = ds.initialize(
            model=build_model(zaya_config("8b", **sizes)),
            rng=jax.random.PRNGKey(0),
            config={"train_batch_size": 8, "steps_per_print": 0,
                    "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                    "zero_optimization": {"stage": stage}, "mesh": mesh},
            mesh=build_mesh(MeshConfig(**mesh),
                            devices=jax.devices()[:devices]))
        return [float(engine.train_step({"input_ids": tokens})["loss"])
                for _ in range(3)]
    np.testing.assert_allclose(losses(8, stage), losses(1, 0), rtol=2e-5)


# ---------------------------------------------------------------------------
# the dropless grouped product's backward (moe/dropless.py): dx is the
# same kernel over the weights' other axis, dw the kernel
# ``moe_grouped_matmul_dw`` — against a loop of einsums, in the interpreter
# ---------------------------------------------------------------------------
def grouped_case(counts, tile, k_dim=32, n=48, dead=3, seed=0):
    """A row buffer laid out as ``dropless._layout`` lays it: expert ``e``'s
    ``counts[e]`` rows from a tile boundary on, padding rows zero, ``dead``
    tiles after the last live one."""
    tiles_of = [-(-c // tile) for c in counts]
    live = sum(tiles_of)
    rows = (live + dead) * tile
    te, real = [], np.zeros(rows, bool)
    at = 0
    for e, (c, t) in enumerate(zip(counts, tiles_of)):
        te += [e] * t
        real[at * tile:at * tile + c] = True
        at += t
    te += [len(counts) - 1] * dead
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (rows, k_dim)) * real[:, None]
    w = jax.random.normal(kw, (len(counts), k_dim, n))
    g = jax.random.normal(kg, (rows, n)) * real[:, None]
    return x, w, g, jnp.asarray(te, jnp.int32), live, real


class TestGroupedProductBackward:
    @pytest.mark.parametrize("tile", [16, 32])
    @pytest.mark.parametrize("counts", [
        [20, 20, 20, 20], [0, 0, 70, 0], [17, 0, 33, 5]],
        ids=["balanced", "one_expert", "an_expert_without_rows"])
    def test_dx_and_dw_against_a_loop_of_einsums(self, counts, tile):
        from deepspeed_tpu.moe import dropless
        x, w, g, te, live, real = grouped_case(counts, tile)
        row_expert = np.repeat(np.asarray(te), tile)

        def through_kernel(x, w):
            y = dropless.grouped_matmul(x, w, te, live)
            return jnp.sum(jnp.where(real[:, None], y, 0.0) * g)

        def through_einsums(x, w):
            y = jnp.zeros((x.shape[0], w.shape[2]))
            for e in range(w.shape[0]):
                y = y + jnp.where((row_expert == e)[:, None],
                                  jnp.einsum("mk,kn->mn", x, w[e]), 0.0)
            return jnp.sum(jnp.where(real[:, None], y, 0.0) * g)
        dx, dw = jax.grad(through_kernel, (0, 1))(x, w)
        want_dx, want_dw = jax.grad(through_einsums, (0, 1))(x, w)
        live_rows = np.arange(x.shape[0]) < live * tile
        np.testing.assert_allclose(np.asarray(dx)[live_rows],
                                   np.asarray(want_dx)[live_rows],
                                   atol=2e-5)
        np.testing.assert_allclose(dw, want_dw, atol=5e-5)
        for e, c in enumerate(counts):
            if not c:                      # exactly zero, not small
                assert not np.asarray(dw[e]).any()
            else:
                assert np.asarray(dw[e]).any()

    def test_expert_share_differentiates_in_one_pass(self):
        """``pass_rows=None``: one pass over the whole buffer, whose
        gradients (rows, weights, pick weights) equal a dense loop's; the
        load-dependent loop refuses reverse differentiation."""
        from deepspeed_tpu.moe import dropless
        t, h, f, held = 96, 32, 48, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        u = jax.random.normal(ks[0], (t, h))
        experts = dropless.init_experts(ks[1], held, h, f, 0.2, 0.2,
                                        jnp.float32)
        index = jax.random.randint(ks[2], (t, 1), 0, 8)     # 4..7 absent
        weight = jax.random.uniform(ks[3], (t, 1)) + 0.5

        def ours(u, experts, weight, **kw):
            y, _ = dropless.expert_share(
                experts, u, dropless.Routing(index, weight), 8, (0, held),
                **kw)
            return jnp.sum(y * y)

        def dense(u, experts, weight):
            y = 0.0
            for e in range(held):
                out = (jax.nn.silu(u @ experts["w_gate"][e])
                       * (u @ experts["w_up"][e])) @ experts["w_down"][e]
                y = y + jnp.where(index == e, weight, 0.0) * out
            return jnp.sum(y * y)
        want = jax.grad(dense, (0, 1, 2))(u, experts, weight)
        for tile in (16, 32):
            got = jax.grad(ours, (0, 1, 2))(u, experts, weight,
                                            pass_rows=None, tile_rows=tile)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        with pytest.raises(ValueError, match="[Rr]everse"):
            jax.jit(jax.grad(lambda u: ours(u, experts, weight,
                                            pass_rows=32)))(u)

    def test_route_is_route_logits_of_one_product(self):
        from deepspeed_tpu.moe import dropless
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        u = jax.random.normal(ks[0], (40, 32))
        kernel = jax.random.normal(ks[1], (32, 12))
        bias = 0.1 * jax.random.normal(ks[2], (12,))
        for scoring, renorm in (("softmax", False), ("sigmoid", True)):
            a = dropless.route(u, kernel, bias, 3, 2.5, scoring, renorm)
            b = dropless.route_logits(
                jnp.einsum("th,he->te", u, kernel,
                           preferred_element_type=jnp.float32),
                bias, 3, 2.5, scoring, renorm)
            assert np.array_equal(a.index, b.index)
            assert np.array_equal(a.weight, b.weight)
