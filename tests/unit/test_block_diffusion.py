"""Generation by diffusion over blocks (``models/block_diffusion_moe.py``,
the serving engine's block lane, ``inference/sampling.py::block_unmask``)
against the plain reference ``benchmark/lib/reference_sdar.py``: float32,
seeded weights, logits rather than tokens wherever a tie could flip."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.lib import reference_sdar as REF
from deepspeed_tpu.inference.sampling import block_unmask
from deepspeed_tpu.models import build_model, sdar_moe_config
from deepspeed_tpu.models.transformer import TransformerConfig

TINY = dict(num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            d_model=32, vocab_size=97, max_seq_len=128, expert_d_ff=16,
            n_routed_experts=8, moe_topk=2, mask_token_id=96,
            dtype=jnp.float32)
#: (prompt, new) tokens: neither a multiple of a block, prompts across
#: chunk boundaries (chunks of 16), one shorter than a block
MIX = ((21, 11), (6, 7), (37, 9), (3, 5), (16, 8))


def ref_cfg(block_length, without=()):
    return {"heads": 4, "kv_heads": 2, "head_dim": 16, "eps": 1e-6,
            "rope_theta": 1e6, "experts": 8, "topk": 2, "renorm": True,
            "block_length": block_length, "mask_token_id": 96,
            "without": tuple(without)}


def build(block_length=4, **more):
    """A tiny model whose matrices are enlarged so that its logits are
    peaked (at std 0.02 every logit is within 0.5 of every other)."""
    model = build_model(sdar_moe_config(
        "30b-a3b", **dict(TINY, block_length=block_length, **more)))
    params = jax.tree_util.tree_map(
        lambda a: a * 4 if a.ndim >= 2 else a,
        model.init(jax.random.PRNGKey(0)))
    return model, params


def serve(model, params, steps, rule, blocks=64, slots=3, threshold=0.05):
    return ds.init_inference(model, {
        "dtype": "float32", "max_out_tokens": 128, "temperature": 0.0,
        "serving": {"enabled": True, "kv_block_size": 8,
                    "num_kv_blocks": blocks, "max_batch_slots": slots,
                    "prefill_chunk_tokens": 16, "denoising_steps": steps,
                    "remasking_strategy": rule,
                    "confidence_threshold": threshold}},
        params=params).serving_engine()


def rel_err(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_apply_under_the_block_mask_is_the_reference():
    model, params = build()
    ids = np.arange(14) * 5 % 90
    got = model.apply(params, jnp.asarray(ids)[None])[0]
    want = REF.forward(params, ids, ref_cfg(4), (0, 8))
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    for control in ("causal", "qk_norm", "renorm"):
        lacking = REF.forward(params, ids, ref_cfg(4, (control,)), (0, 8))
        assert float(jnp.abs(got - lacking).max()) > 0.02 * float(
            jnp.abs(want).max()), control


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of an expert layer (one eighth of the experts
    each) sum to the reference's layer over all of them."""
    whole, params = build()
    layer0 = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 32))
    total = jnp.zeros_like(u)
    for lo in range(8):
        share = build_model(sdar_moe_config(
            "30b-a3b", **dict(TINY, experts_held=(lo, lo + 1))))
        part = dict(layer0, experts={n: w[lo:lo + 1]
                                     for n, w in layer0["experts"].items()})
        y, counters = share._moe_sublayer(part, u)
        assert int(counters[0]) == 24 * 2
        total = total + y
    with jax.default_matmul_precision("highest"):
        want = REF.routed(layer0, u[0], ref_cfg(4), (0, 8))
    assert rel_err(total[0], want) < 1e-5


@pytest.mark.parametrize("block_length,steps,rule", [
    (4, 1, "low_confidence_static"), (4, 2, "low_confidence_static"),
    (4, 4, "low_confidence_dynamic"), (4, 2, "sequential"),
    (8, 2, "low_confidence_dynamic"), (8, 8, "sequential"),
    (8, 1, "low_confidence_static")])
def test_every_forward_of_an_engine_is_the_references(block_length, steps,
                                                      rule):
    """ONE engine, requests submitted between iterations so that they
    ride in different phases: every recorded forward replayed through the
    reference (filled tokens its argmax, filled rows its choice), the
    whole trajectory its ``generate``'s, the kept k/v those of the final
    tokens, every counter what the trajectories add up to."""
    model, params = build(block_length)
    cfg = ref_cfg(block_length)
    srv = serve(model, params, steps, rule)
    rng = np.random.default_rng(0)
    reqs, tables = [], {}
    kept = None
    pages = jax.jit(lambda pool, table: pool[:, table])

    def step():
        nonlocal kept
        more = srv.step()
        for r in srv.scheduler.running.values():
            tables[r.req_id] = list(srv.allocator.block_table(r.req_id))
        first = reqs[0]
        if kept is None and first.status is not None:
            # read in the iteration it finished in: nothing has written
            # to its pages since
            rows = len(first.prompt) + len(first.output)
            rows = rows // block_length * block_length
            table = jnp.asarray(tables[first.req_id])
            kept = [pages(pool, table).reshape(
                pool.shape[0], -1, pool.shape[-1])[:, :rows]
                for pool in (srv._pool_k, srv._pool_v)]
        return more
    for p, g in MIX:
        reqs.append(srv.submit(rng.integers(0, 90, p), max_new_tokens=g,
                               record_blocks=True))
        step()
    while step():
        pass
    srv.allocator.assert_consistent()
    assert srv.allocator.num_used == 0 and not srv._flight
    assert srv.decode_builds == 2
    denoise = commit = 0
    for r in reqs:
        want, path = REF.generate(params, r.prompt, r.max_new_tokens, cfg,
                                  (0, 8), steps, rule, 0.05)
        got = [(a, b, list(c)) for a, b, c in r.block_steps]
        judged = REF.replay(params, r.prompt, got, cfg, (0, 8), steps, rule,
                            0.05, pad_to=64)
        assert judged["logit_gap_worst"] < 1e-4
        assert judged["order_gap_worst"] <= 1e-6
        assert judged["rows_agree"] == 1.0
        assert got == path and r.output == want
        assert len(r.output) == r.max_new_tokens
        # forwards a block = denoise steps taken + 1
        by_block = {}
        for start, phase, _ in got:
            by_block.setdefault(start, []).append(phase)
        for phases in by_block.values():
            assert phases[-1] == "commit" and phases.count("commit") == 1
            assert len(phases) <= steps + 1
        denoise += sum(p == "denoise" for _, p, _ in got)
        commit += len(by_block)
    assert srv.block_counts == {
        "denoise": denoise, "commit": commit,
        "rows": block_length * (denoise + commit),
        "tokens": sum(g for _, g in MIX)}
    ahead = srv.flight_counts["ahead_dispatches"]
    if rule == "low_confidence_dynamic":
        assert ahead == 0       # the device says when a block is full
    else:
        assert ahead >= srv.flight_counts["dispatches"] - len(MIX) - 1
    # the pool holds the FINAL tokens' k / v: a commit that kept a
    # denoise forward's rows does not
    first = reqs[0]
    got = [(a, b, list(c)) for a, b, c in first.block_steps]
    for pool, want in zip(kept, REF.kept_kv(params, first.prompt, got, cfg,
                                            (0, 8))):
        assert rel_err(pool, want[:, :pool.shape[1]]) < 1e-5
    if steps > 1:
        lacking = REF.kept_kv(params, first.prompt, got,
                              ref_cfg(block_length, ("commit",)), (0, 8))
        assert rel_err(kept[0], lacking[0][:, :kept[0].shape[1]]) > 0.05


def test_the_step_counts_the_pages_its_walks_read_and_those_in_runs():
    """One dispatch — a block at 68 rows of context in a slot whose table
    is consecutive pool blocks, one at 12 in a scattered table, an idle
    slot, a chunk of 8 rows at 16 in the second slot — counts, a layer, the
    pages the lane's and the chunk's walks are handed and those of them in
    whole runs of 8 (pages of 8 rows)."""
    model, params = build()
    nl = model.config.num_layers
    tables = np.zeros((3, 16), np.int32)
    tables[0, :10] = 1 + np.arange(10)
    tables[1, :3] = (14, 12, 13)
    cache = model.init_paged_cache(24, 8, jnp.float32)
    cache.update(block_tables=jnp.asarray(tables),
                 lens=jnp.asarray([68, 12, 0], jnp.int32))
    new = model._apply_paged_mixed(
        params, cache, jnp.zeros((3, 4), jnp.int32), jnp.asarray([1, 1, 0]),
        jnp.arange(8), jnp.int32(1), jnp.int32(16), jnp.int32(8))[2]
    counted = dict(zip(model.PAGED_COUNTERS, map(int, new["counters"])))
    # 72 rows = 9 pages, the first 8 a run; 16 rows = 2 pages; the chunk's
    # 24 rows = 3
    assert counted["kv_pages_read"] == nl * (9 + 2 + 3)
    assert counted["kv_pages_in_runs"] == nl * 8


def test_a_preempted_request_recomputes_to_the_same_tokens():
    """A pool too small for three requests at their full lengths: one is
    preempted mid-generation, its open block's progress dropped, and
    recomputed from whole committed blocks to the tokens the reference
    generates."""
    model, params = build()
    srv = serve(model, params, 2, "low_confidence_static", blocks=13)
    rng = np.random.default_rng(1)
    reqs = [srv.submit(rng.integers(0, 90, p), max_new_tokens=g)
            for p, g in ((20, 24), (18, 24), (22, 24))]
    srv.run()
    assert srv.scheduler.preemption_count >= 1
    for r in reqs:
        want, _ = REF.generate(params, r.prompt, r.max_new_tokens,
                               ref_cfg(4), (0, 8), 2,
                               "low_confidence_static")
        assert r.output == want
    srv.allocator.assert_consistent()


def test_a_shared_prompt_is_served_from_the_prefix_cache():
    """A full page's k / v depend on no token past the page's end
    (``kv_block_size % block_length == 0``), so a hit resumes a prompt
    token-exactly."""
    model, params = build()
    srv = serve(model, params, 2, "low_confidence_static")
    prompt = (np.arange(29) * 7 % 90).tolist()
    a = srv.submit(prompt, max_new_tokens=6)
    srv.run()
    b = srv.submit(prompt, max_new_tokens=6)
    srv.run()
    assert b.cache_hit_tokens == 24 and b.output == a.output


def test_an_eos_inside_a_block_ends_the_request_at_its_commit():
    model, params = build()
    srv = serve(model, params, 2, "low_confidence_static")
    prompt = (np.arange(9) * 11 % 90).tolist()
    free = srv.submit(prompt, max_new_tokens=12)
    srv.run()
    eos = free.output[5]
    cut = srv.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    srv.run()
    at = free.output.index(eos)
    assert cut.output == free.output[:at + 1]
    assert srv.allocator.num_used == 0


@pytest.mark.parametrize("rule", REF.RULES)
def test_block_unmask_fills_the_references_rows(rule):
    rng = np.random.default_rng(5)
    logits = rng.normal(size=(6, 8, 33)).astype(np.float32) * 3
    block = np.where(rng.random((6, 8)) < 0.6, 32,
                     rng.integers(0, 32, (6, 8))).astype(np.int32)
    n = np.array([0, 1, 2, 3, 8, 2], np.int32)
    got = np.asarray(block_unmask(jnp.asarray(logits), jnp.asarray(block),
                                  jnp.asarray(n), mask_id=32, rule=rule,
                                  threshold=0.5))
    for s in range(6):
        x0, logc, _ = REF.draw(logits[s], 32)
        want = block[s].copy()
        if n[s]:
            fill = REF.choose(block[s] == 32, logc, int(n[s]), rule, 0.5)
            want[fill] = x0[fill]
        assert got[s].tolist() == want.tolist(), (rule, s)
    assert (got != 32).sum() >= (block != 32).sum()


def test_what_is_refused_says_why():
    model, params = build()
    for kwargs, word in ((dict(spec=True), "draft"),
                         (dict(kv_bits=8), "kv_cache_bits"),
                         (dict(mesh_model=2), "one chip"),
                         (dict(weight_quant=True), "int8"),
                         (dict(host_cache=True), "host_cache")):
        assert word in model.paged_refusal(**kwargs)
    assert model.paged_refusal() is None
    assert model.prefix_cache_refusal() is None
    assert "noised" in model.training_refusal()
    assert "multiple of block_length" in model.serving_refusal(6, 16)
    assert "multiple of block_length" in model.serving_refusal(8, 18)
    assert model.serving_refusal(8, 16) is None
    with pytest.raises(NotImplementedError, match="decode lane"):
        model.init_cache(1, 16)
    with pytest.raises(NotImplementedError, match="train"):
        ds.initialize(model=model, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    cfg = {"dtype": "float32", "max_out_tokens": 128, "temperature": 0.0}
    serving = {"enabled": True, "kv_block_size": 8, "num_kv_blocks": 16,
               "max_batch_slots": 2, "prefill_chunk_tokens": 16}
    for change, word in ((dict(kv_block_size=6), "kv_block_size 6"),
                         (dict(prefill_chunk_tokens=18), "chunk"),
                         (dict(denoising_steps=5), "denoising_steps 5"),
                         (dict(kv_cache_bits=8), "kv_cache_bits")):
        eng = ds.init_inference(model, dict(
            cfg, serving=dict(serving, **change)), params=params)
        with pytest.raises(NotImplementedError, match=word):
            eng.serving_engine()
    srv = ds.init_inference(model, dict(cfg, serving=serving),
                            params=params).serving_engine()
    with pytest.raises(NotImplementedError, match="greedily"):
        srv.submit([1, 2, 3], max_new_tokens=4, temperature=0.7)
    with pytest.raises(TypeError, match="block_diffusion_moe"):
        TransformerConfig(moe_topk=2)


def test_the_published_sizes_count_30b_whole_and_5b_on_the_share():
    whole = sdar_moe_config("30b-a3b")
    share = sdar_moe_config("30b-a3b", experts_held=(0, 16))
    assert whole.num_params() == 30_532_122_624
    assert share.num_params() == 5_164_972_032
    assert (whole.block_length, whole.mask_token_id) == (4, 151669)
    tiny = build_model(sdar_moe_config("30b-a3b", **TINY))
    shapes = jax.eval_shape(lambda: tiny.init(jax.random.PRNGKey(0)))
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        shapes)) == tiny.config.num_params()
