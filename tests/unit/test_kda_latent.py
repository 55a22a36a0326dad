"""The linear / latent hybrid block (``kimi_linear`` family,
``models/kda_latent_moe.py``) and its recurrence (``ops/transformer/
kda_scan.py``) against their plain references: tiny sizes, CPU, float32,
seeded weights.

  - ``kda_chunk_scan`` (the blocked form) and ``kda_decode_update``
    against the loop over rows: across blocks and sub-blocks, from zero
    and from a given state, under a gate strong enough that ``1 /
    exp(G)`` over a block would overflow; ``valid_rows`` and idle slots
    leave the state alone; two chunks chained equal one pass;
  - ``apply`` (full sequences) against
    ``benchmark/lib/reference_kimi_linear.py``, and not against a
    reference that lacks a mechanism;
  - ONE engine: chunked prefill (prompts across chunks and blocks,
    requests interleaved) then paged decode through ``ServingEngine``
    against the reference's full forward — logits, not tokens — the
    states and the latent rows read back, a preempted request recomputed
    to the same tokens, every counter against the mix, the state kind
    and the latent pool in ``assert_consistent()``;
  - the shares add up; every refusal's sentence; the published sizes'
    parameter count and plan.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_kimi_linear as reference
from deepspeed_tpu.models import (TransformerLM, build_model,
                                  kimi_linear_config)
from deepspeed_tpu.models.kda_latent_moe import KDALatentMoELM
from deepspeed_tpu.observability.overlap import get_overlap_profiler
from deepspeed_tpu.ops.transformer import kda_scan

#: the published pattern in small: a leading (kda, dense) layer, a period
#: that repeats, a tail that ends on a latent layer
PATTERN = ("kda",) + ("kda", "mla", "kda") * 2 + ("kda", "mla")
TINY = dict(num_layers=9, layer_types=PATTERN, num_heads=4, d_model=32,
            d_ff=64, vocab_size=128, max_seq_len=128, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            kda_heads=2, kda_head_dim=8, expert_d_ff=16,
            n_routed_experts=8, moe_topk=2, dtype=jnp.float32)
REF = dict(layer_types=PATTERN, first_k_dense=1, heads=4,
           qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
           kv_lora_rank=16, kda_heads=2, kda_head_dim=8, eps=1e-5,
           rope_theta=1e4, n_routed_experts=8, moe_topk=2, scale=2.446,
           without=())
SERVING = {"enabled": True, "kv_block_size": 4, "prefill_chunk_tokens": 16,
           "max_batch_slots": 3, "num_kv_blocks": 128}
#: float32 on the CPU against the reference at precision ``highest``: the
#: two differ by the order of summation alone
ATOL = 5e-5
SCAN_ATOL = 2e-5
#: the decode update against float64, norm over norm: float32's own error
EXACT_REL = 5e-7


def build(**kw):
    """The tiny model with its vectors moved off their initial values and
    its matrices enlarged, so that every mechanism shows in the logits."""
    model = build_model(kimi_linear_config("48b-a3b", **{**TINY, **kw}))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)

    def move(path, a):
        name = path[-1].key if path[-1].key != "kernel" else path[-2].key
        if name in ("a_log", "dt_bias", "conv_w", "embedding"):
            return a
        if name == "scale":
            return a + 0.1 * rng.standard_normal(a.shape).astype(a.dtype)
        if name == "bias":
            return a * 8.0
        return a * 3.0
    return model, jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def built():
    return build()


def rel_err(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# -- the recurrence ----------------------------------------------------------
def scan_inputs(t, strong, seed=0, h=2, kd=8, vd=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (t, h, kd))) / kd ** 0.5,
            unit(jax.random.normal(ks[1], (t, h, kd))),
            jax.random.normal(ks[2], (t, h, vd)),
            -strong * jax.random.uniform(ks[3], (t, h, kd)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (t, h))),
            jax.random.normal(ks[5], (h, vd, kd)))


_loop = jax.jit(kda_scan.kda_scan_reference)
_blocked = jax.jit(kda_scan.kda_chunk_scan,
                   static_argnames=("block_rows", "sub_rows"))


@pytest.mark.parametrize("strong", [0.1, 6.0])
@pytest.mark.parametrize("rows,valid,block,sub,start", [
    (64, None, 64, 16, "zero"), (64, None, 32, 8, "given"),
    (96, 50, 32, 8, "given"), (64, 0, 32, 16, "given"),
    (48, None, 16, 4, "zero")])
def test_the_blocked_form_against_the_loop(strong, rows, valid, block, sub,
                                           start):
    """Across blocks and sub-blocks, from zero and from a given state, a
    ``valid_rows`` inside a block and one of 0 (the state comes back as it
    went in); at ``strong`` 6 the gate's running sum passes -190 inside a
    block of 64, where ``1 / exp(G)`` is past float32."""
    q, k, v, g, beta, s = scan_inputs(rows, strong)
    s0 = s if start == "given" else jnp.zeros_like(s)
    live = rows if valid is None else valid
    o, s1 = _blocked(q, k, v, g, beta, s0, jnp.int32(live),
                     block_rows=block, sub_rows=sub)
    o_ref, s_ref = _loop(q, k, v, g, beta, s0, jnp.int32(live))
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s1)))
    assert float(jnp.abs(s1 - s_ref).max()) < SCAN_ATOL
    if live:
        assert float(jnp.abs(o[:live] - o_ref[:live]).max()) < SCAN_ATOL
    else:
        assert bool(jnp.all(s1 == s0))


def test_two_chunks_chained_equal_one_pass():
    q, k, v, g, beta, s = scan_inputs(128, 1.0, seed=3)
    o_ref, s_ref = _loop(q, k, v, g, beta, s, jnp.int32(104))
    oa, sa = _blocked(q[:64], k[:64], v[:64], g[:64], beta[:64], s,
                      jnp.int32(64), block_rows=32, sub_rows=16)
    ob, sb = _blocked(q[64:], k[64:], v[64:], g[64:], beta[64:], sa,
                      jnp.int32(40), block_rows=32, sub_rows=16)
    assert float(jnp.abs(sb - s_ref).max()) < SCAN_ATOL
    got = jnp.concatenate([oa, ob[:40]])
    assert float(jnp.abs(got - o_ref[:104]).max()) < SCAN_ATOL


def flat(a):
    """``[S, H, K]`` -> the decode kernel's ``[S, H K]``."""
    return a.reshape(a.shape[0], -1)


def float64_step(q, k, v, g, beta, s):
    """One step of the recurrence in float64 (numpy), ``s [S, H, V, K]``
    value-major: ``(o, the new state)``."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, s))
    decayed = np.exp(g)[:, :, None, :] * s
    u = v - np.einsum("shvk,shk->shv", decayed, k)
    new = decayed + (beta[..., None] * u)[..., None] * k[:, :, None, :]
    return np.einsum("shvk,shk->shv", new, q), new


@pytest.mark.parametrize("slots,strong,size,steps,hd", [
    (5, 1.0, 1.0, 1, 8), (8, 1.0, 1.0, 1, 8), (8, 6.0, 1.0, 1, 8),
    (8, 1.0, 1e3, 1, 8), (8, 0.5, 1.0, 24, 128)])
def test_the_decode_update_against_one_step_of_the_loop(slots, strong, size,
                                                        steps, hd):
    """Every live slot's row is one step of the loop on ITS state, at a
    first row inside the buffer; an idle slot's state and every other
    row of the buffer come back bit for bit.  Against float64 the error
    is float32's own, under a strong gate and from a state of size 1,000
    too, and after 24 successive updates at 128 x 128 (PR 57: 4e-8 to
    8e-8 here; a product that lost its third bfloat16 piece reads 1.1e-6
    to 3.6e-6)."""
    update = jax.jit(kda_scan.kda_decode_update)
    if steps > 1:
        s = jnp.zeros((slots, 2, hd, hd))
        want = np.zeros(s.shape)
        for step in range(steps):
            q, k, v, g, beta, _ = scan_inputs(slots, strong, seed=step, kd=hd,
                                              vd=hd)
            o, s = update(flat(q), flat(k), flat(v), flat(g), beta, s)
            want_o, want = float64_step(q, k, v, g, beta, want)
        assert rel_err(s, want) < EXACT_REL
        assert rel_err(o, flat(want_o)) < EXACT_REL
        return
    q, k, v, g, beta, _ = scan_inputs(slots, strong, seed=1)
    buf = size * jax.random.normal(jax.random.PRNGKey(9), (3 * slots, 2, 8, 8))
    act = jnp.arange(slots) % 3 != 1
    o, new = update(flat(q), flat(k), flat(v), flat(g), beta, buf, act,
                    jnp.int32(slots))
    mine = buf[slots:2 * slots]
    o_ref, s_ref = jax.jit(jax.vmap(
        lambda *xs: kda_scan.kda_scan_reference(*(x[None] for x in xs[:5]),
                                                xs[5])))(q, k, v, g, beta,
                                                         mine)
    live = act[:, None, None, None]
    assert bool(jnp.all(jnp.where(live, True,
                                  new[slots:2 * slots] == mine)))
    assert float(jnp.abs(jnp.where(
        live, new[slots:2 * slots] - s_ref, 0.0)).max()) < SCAN_ATOL * size
    assert float(jnp.abs(jnp.where(
        act[:, None, None], o.reshape(o_ref[:, 0].shape) - o_ref[:, 0],
        0.0)).max()) < SCAN_ATOL * size
    assert bool(jnp.all(new[:slots] == buf[:slots]))
    assert bool(jnp.all(new[2 * slots:] == buf[2 * slots:]))
    want_o, want = float64_step(q, k, v, g, beta, mine)
    assert rel_err(new[slots:2 * slots][act], want[act]) < EXACT_REL
    assert rel_err(o[act], flat(want_o)[act]) < EXACT_REL


# -- full sequences ----------------------------------------------------------
def test_apply_against_the_reference_and_not_one_that_lacks_a_mechanism(
        built):
    model, params = built
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, 128)
    got = jax.jit(model.apply)(params, ids)
    want = reference.logits(params, ids, REF)
    assert float(jnp.abs(got - want).max()) < ATOL
    # (the other controls fail in benchmark/tests/test_serve_kda_latent.py)
    for name in ("delta", "scalar_decay", "rotary", "renorm"):
        lacking = reference.logits(params, ids[:1], dict(REF, without=(name,)))
        assert float(jnp.abs(got[:1] - lacking).max()) > 50 * ATOL, name


def test_the_shares_add_up(built):
    """Four chips' shares of the first expert layer: their routed parts
    and the shared expert ONCE equal the uncut reference layer, and the
    program's share is the reference's share."""
    _, params = built
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 32))
    bp = jax.tree_util.tree_map(lambda a: a[0], params["moe"])
    with jax.default_matmul_precision("highest"):
        want = reference.moe(bp, u[0], REF)
        shared = reference.ffn(bp["shared"], u[0], REF)
    total = shared
    for lo in range(0, 8, 2):
        model = build_model(kimi_linear_config(
            "48b-a3b", **{**TINY, "experts_held": (lo, lo + 2)}))
        share = dict(bp, moe=dict(bp["moe"], experts=jax.tree_util.tree_map(
            lambda a: a[lo:lo + 2], bp["moe"]["experts"])))
        got, _ = model.expert_layer(share, u)
        with jax.default_matmul_precision("highest"):
            ref = reference.moe(share, u[0], REF, (lo, lo + 2))
        assert float(jnp.abs(got[0] - ref).max()) < ATOL
        total = total + got[0] - shared
    assert float(jnp.abs(total - want).max()) < ATOL
    assert float(jnp.abs(want).max()) > 100 * ATOL


# -- the engine --------------------------------------------------------------
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


def test_the_engine_serves_the_block(built):
    """Chunked prefill then paged decode equal the reference's full
    forward; what a slot holds is the reference's states and latent rows;
    preemption recomputes to the same tokens; the counters follow the
    mix; both kinds of state are the allocator's."""
    from deepspeed_tpu.inference.serving import RequestState
    model, params = built
    overlap = get_overlap_profiler()
    overlap.configure(enabled=True)
    # (this test's iterations alone: an earlier test of the same worker
    # may have left records of its own in the ring)
    began = time.perf_counter()
    try:
        srv = serving_engine(model, params)
        assert srv.prefix_cache is False
        assert srv.allocator.kinds == ("full", "state")
        assert srv._pool_v is None
        assert srv._pool_k.shape == (2 + 1, 128, 4, 128)
        assert srv._pool_x["state"].shape == (6 * 3, 2, 8, 8)
        rng = np.random.default_rng(0)
        # prompts across chunks (16) and blocks (4), not multiples of 4
        reqs = [srv.submit(rng.integers(0, 128, n), max_new_tokens=m)
                for n, m in ((37, 6), (21, 9), (5, 4), (18, 5))]
        first, slot, left = reqs[0], None, None
        while any(r.state is not RequestState.FINISHED for r in reqs):
            if not srv.step():
                break
            for at, r in srv.scheduler.running.items():
                if r is first:
                    slot, table = at, srv.allocator.block_table(
                        first.req_id)
            if left is None and first.state is RequestState.FINISHED:
                rows = len(first.prompt) + len(first.output) - 1
                left = (model.slot_state(srv._pool_x, slot, srv.num_slots),
                        srv._pool_k[:, jnp.asarray(table)].reshape(
                            3, -1, 128)[:, :rows, :20])
            srv.allocator.assert_consistent()
        while srv.step():
            pass
        assert srv.decode_builds == 2
        for r in reqs:
            assert len(r.output) == r.max_new_tokens
            assert worst_gap(params, r) < ATOL
        fed = jnp.asarray(list(first.prompt) + list(first.output)[:-1])[None]
        _, states, latents = reference.logits(params, fed, REF, states=True)
        assert rel_err(left[0], states[0]) < 1e-4
        assert rel_err(left[1], latents[0]) < 1e-5
        assert not any(srv.allocator.num_used_by_kind().values())
        recs, _ = overlap.iterations(began, float("inf"))
        recs = recs[recs["kind"] == "serving"]
        tokens = sum(len(r.prompt) + len(r.output) - 1 for r in reqs)
        assert recs["kda_decode_rows"].sum() + recs["kda_chunk_rows"].sum() \
            == 6 * tokens
        assert recs["kda_chunk_rows"].sum() == 6 * sum(
            len(r.prompt) for r in reqs)
        assert recs["state_slots_started"].sum() == len(reqs)
        assert recs["moe_rows_shared"].sum() == 8 * tokens
        assert recs["moe_picks"].sum() == 8 * 2 * tokens
        assert recs["latent_tokens_read"].sum() > 0

        # a preempted request recomputes to the same tokens
        small = serving_engine(model, params, num_kv_blocks=16,
                               max_batch_slots=2)
        prompts = [rng.integers(0, 128, n) for n in (23, 19)]
        quiet = [srv.submit(p, max_new_tokens=16) for p in prompts]
        while srv.step():
            pass
        tight = [small.submit(p, max_new_tokens=16) for p in prompts]
        while small.step():
            pass
        assert small.scheduler.preemption_count > 0
        for a, b in zip(quiet, tight):
            assert list(a.output) == list(b.output)
        small.allocator.assert_consistent()
        assert not any(small.allocator.num_used_by_kind().values())
    finally:
        overlap.configure(enabled=False)


# -- refusals, sizes ---------------------------------------------------------
@pytest.mark.parametrize("how,says", [
    (dict(spec=True), ("speculative lane", "roll")),
    (dict(kv_bits=8), ("already the compressed cache", "no quantizer")),
    (dict(host_cache=True), ("latent rows", "not a page")),
    (dict(mesh_model=2), ("one chip", "indexed by slot")),
    (dict(weight_quant=True), ("grouped-product kernel", "dequantize")),
])
def test_the_refusals_of_both_kinds_of_block(how, says):
    model = build_model(kimi_linear_config("48b-a3b", **TINY))
    reason = model.paged_refusal(**how)
    for part in says:
        assert part in reason, reason
    assert model.paged_refusal() is None


def test_what_else_the_block_refuses():
    model = build_model(kimi_linear_config("48b-a3b", **TINY))
    assert "kda_scan.py" in model.training_refusal()
    assert "B18" in model.prefix_cache_refusal()
    assert model.padded_prompt_refusal() is not None
    with pytest.raises(NotImplementedError, match="no dense KV cache"):
        model.init_cache(1, 16)
    with pytest.raises(NotImplementedError, match="one chip"):
        model.tp_serving_view(2, "model", None)
    with pytest.raises(TypeError, match="KDALatentMoELM"):
        TransformerLM(kimi_linear_config("48b-a3b", **TINY))
    with pytest.raises(ValueError, match="layer_types"):
        build_model(kimi_linear_config("48b-a3b", **{**TINY,
                                                     "num_layers": 8}))
    assert isinstance(model, KDALatentMoELM)


def test_the_published_sizes():
    """27 layers, 20 KDA and 7 latent; 48 B whole, 4.96 B at a sixteenth
    of the experts; seven layer bodies traced, not 27; the tree ``init``
    makes is the count."""
    c = kimi_linear_config("48b-a3b")
    assert (c.kda_layers, c.mla_layers, c.scan_length) == (20, 7, 26)
    assert c.num_params() == 49_122_681_728
    held = kimi_linear_config("48b-a3b", experts_held=(0, 16))
    assert held.num_params() == 4_956_660_608
    plan = held.layer_plan
    assert [(len(s), n) for s, n in plan] == [(1, 1), (4, 6), (2, 1)]
    assert plan[1][0] == (("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
                          ("kda", "moe"))
    tiny = kimi_linear_config("48b-a3b", **TINY)
    tree = jax.eval_shape(build_model(tiny).init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(tree)) == tiny.num_params()
    assert [(len(s), n) for s, n in tiny.layer_plan] == [(1, 1), (3, 2),
                                                         (2, 1)]
