"""Block-sparse flash attention kernel + model wiring.

Reference coverage model: `/root/reference/tests/unit/test_sparse_attention.py`
(matmul/softmax vs dense equivalents) — here the whole attention op is
checked against masked dense attention, forward and backward, plus the
model-level attn_impl="blocksparse" integration VERDICT r2 asked for.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.sparse_attention import (
    BigBirdSparsityConfig, BSLongformerSparsityConfig, FixedSparsityConfig,
    LocalSlidingWindowSparsityConfig)
from deepspeed_tpu.ops.sparse_attention.blocksparse_flash import (
    blocksparse_attention, blocksparse_attention_bthd, compress_layout)

B, H, T, D, BLK = 2, 2, 256, 64, 64
NB = T // BLK


def qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B * H, T, D)), jnp.float32)
    return mk(), mk(), mk()


def dense_ref(q, k, v, mask):
    s = jnp.einsum("btd,bsd->bts", q, k) / np.sqrt(D)
    s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1), v)


def block_mask(layout):
    """[H, nq, nk] layout → [T, T] bool for head 0 (+ causal)."""
    m = np.zeros((T, T), bool)
    for i in range(NB):
        for j in range(NB):
            if layout[0, i, j]:
                m[i * BLK:(i + 1) * BLK, j * BLK:(j + 1) * BLK] = True
    return m & np.tril(np.ones((T, T), bool))


class TestKernel:
    def test_dense_layout_matches_causal_attention(self):
        q, k, v = qkv()
        layout = np.tril(np.ones((H, NB, NB), np.int64))
        o = blocksparse_attention(q, k, v, compress_layout(layout), BLK, H,
                                  True, None, True)
        ref = dense_ref(q, k, v, np.tril(np.ones((T, T), bool)))
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=6e-3)

    def test_window_layout_matches_masked_dense(self):
        q, k, v = qkv(1)
        layout = np.zeros((H, NB, NB), np.int64)
        for i in range(NB):
            layout[:, i, max(0, i - 1):i + 1] = 1
        o = blocksparse_attention(q, k, v, compress_layout(layout), BLK, H,
                                  True, None, True)
        ref = dense_ref(q, k, v, block_mask(layout))
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=6e-3)

    @pytest.mark.slow
    def test_grads_match_masked_dense(self):
        q, k, v = qkv(2)
        layout = np.zeros((H, NB, NB), np.int64)
        for i in range(NB):
            layout[:, i, max(0, i - 1):i + 1] = 1
        lc = compress_layout(layout)
        mask = block_mask(layout)
        f = lambda *a: jnp.sum(  # noqa: E731
            blocksparse_attention(*a, lc, BLK, H, True, None, True) ** 2)
        fr = lambda *a: jnp.sum(dense_ref(*a, mask) ** 2)  # noqa: E731
        g = jax.grad(f, (0, 1, 2))(q, k, v)
        gr = jax.grad(fr, (0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=8e-2)

    def test_per_head_layouts(self):
        """Heads with DIFFERENT layouts must each match their own mask."""
        q, k, v = qkv(3)
        layout = np.tril(np.ones((H, NB, NB), np.int64))
        layout[1] = np.eye(NB, dtype=np.int64)        # head 1: diagonal only
        o = np.asarray(blocksparse_attention(
            q, k, v, compress_layout(layout), BLK, H, True, None, True))
        full = np.asarray(dense_ref(q, k, v,
                                    np.tril(np.ones((T, T), bool))))
        diag_mask = np.zeros((T, T), bool)
        for i in range(NB):
            diag_mask[i * BLK:(i + 1) * BLK, i * BLK:(i + 1) * BLK] = True
        diag = np.asarray(dense_ref(q, k, v,
                                    diag_mask & np.tril(
                                        np.ones((T, T), bool))))
        o4 = o.reshape(B, H, T, D)
        np.testing.assert_allclose(o4[:, 0], full.reshape(B, H, T, D)[:, 0],
                                   atol=6e-3)
        np.testing.assert_allclose(o4[:, 1], diag.reshape(B, H, T, D)[:, 1],
                                   atol=6e-3)

    def test_empty_row_rejected(self):
        layout = np.tril(np.ones((H, NB, NB), np.int64))
        layout[0, 2] = 0
        with pytest.raises(ValueError, match="empty"):
            compress_layout(layout)


class TestConfigsRun:
    @pytest.mark.parametrize("cfg", [
        FixedSparsityConfig(num_heads=H, block=BLK, num_local_blocks=2,
                            num_global_blocks=1),
        LocalSlidingWindowSparsityConfig(num_heads=H, block=BLK,
                                         num_sliding_window_blocks=2),
        BigBirdSparsityConfig(num_heads=H, block=BLK, num_random_blocks=1,
                              num_sliding_window_blocks=2,
                              num_global_blocks=1),
        BSLongformerSparsityConfig(num_heads=H, block=BLK,
                                   num_sliding_window_blocks=2,
                                   global_block_indices=[0]),
    ], ids=["fixed", "sliding", "bigbird", "longformer"])
    def test_layout_families_run_and_are_causal(self, cfg):
        q, k, v = qkv(4)
        o = np.asarray(blocksparse_attention_bthd(
            q.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            k.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            v.reshape(B, H, T, D).transpose(0, 2, 1, 3), cfg,
            interpret=True))
        assert np.isfinite(o).all()
        # causality: perturbing future tokens must not change position 0
        k2 = k.at[:, BLK:].add(1.0)
        v2 = v.at[:, BLK:].add(1.0)
        o2 = np.asarray(blocksparse_attention_bthd(
            q.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            k2.reshape(B, H, T, D).transpose(0, 2, 1, 3),
            v2.reshape(B, H, T, D).transpose(0, 2, 1, 3), cfg,
            interpret=True))
        np.testing.assert_allclose(o[:, :BLK // 2], o2[:, :BLK // 2],
                                   atol=1e-5)


class TestModelIntegration:
    @pytest.mark.slow
    def test_attn_impl_blocksparse_trains(self):
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerLM, gpt2_config
        cfg = gpt2_config(
            "125m", num_layers=2, d_model=128, num_heads=2, vocab_size=64,
            max_seq_len=T, loss_chunk=0, attn_impl="blocksparse",
            sparsity_config=LocalSlidingWindowSparsityConfig(
                num_heads=2, block=BLK, num_sliding_window_blocks=2))
        engine, _, _, _ = ds.initialize(model=TransformerLM(cfg), config={
            "train_batch_size": 8, "optimizer": {
                "type": "AdamW", "params": {"lr": 1e-3}},
            "mesh": {"data": 8}, "steps_per_print": 0})
        rs = np.random.RandomState(0)
        batch = {"input_ids": rs.randint(0, 64, (8, T), dtype=np.int32)}
        losses = [float(engine.train_step(batch)["loss"])
                  for _ in range(5)]
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_missing_config_raises(self):
        from deepspeed_tpu.models import TransformerLM, gpt2_config
        cfg = gpt2_config("125m", num_layers=1, d_model=64, num_heads=2,
                          vocab_size=64, max_seq_len=T, loss_chunk=0,
                          attn_impl="blocksparse")
        m = TransformerLM(cfg)
        with pytest.raises(ValueError, match="sparsity_config"):
            m.loss(m.init(jax.random.PRNGKey(0)),
                   {"input_ids": jnp.zeros((1, T), jnp.int32)})


class TestSparseDecode:
    @pytest.mark.slow
    def test_cached_decode_matches_sparse_forward(self):
        """Greedy decode through the KV cache must agree with full-forward
        argmax where the forward runs the blocksparse kernel — i.e. the
        decode path applies the SAME layout, not dense attention."""
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import TransformerLM, gpt2_config
        scfg = LocalSlidingWindowSparsityConfig(
            num_heads=2, block=16, num_sliding_window_blocks=2)
        cfg = gpt2_config(
            "125m", num_layers=2, d_model=64, num_heads=2, vocab_size=64,
            max_seq_len=128, loss_chunk=0, dtype=jnp.float32,
            attn_impl="blocksparse", sparsity_config=scfg)
        model = TransformerLM(cfg)
        params = jax.device_get(model.init(jax.random.PRNGKey(0)))
        eng = ds.init_inference(TransformerLM(cfg), params=params,
                                config={"dtype": "float32",
                                        "max_out_tokens": 128,
                                        "prompt_bucket": 0})
        # kernel injection must NOT rewrite the deliberate blocksparse
        # choice (it would make this whole test compare dense-vs-dense)
        assert eng.module.config.attn_impl == "blocksparse"
        rs = np.random.RandomState(0)
        # prompt long enough that the window EXCLUDES early tokens
        ids = rs.randint(0, 64, (2, 48)).astype(np.int32)
        out = np.asarray(eng.generate(ids, max_new_tokens=6,
                                      temperature=0.0))
        cur = ids
        for t in range(6):
            logits = np.asarray(eng.forward(cur))   # blocksparse kernel
            nxt = logits[:, -1].argmax(-1).astype(np.int32)
            np.testing.assert_array_equal(out[:, t], nxt,
                                          err_msg=f"step {t}")
            cur = np.concatenate([cur, nxt[:, None]], axis=1)


class TestCostRouting:
    """PR-4 satellite: SparseSelfAttention routes to a dense path when
    the layout cannot beat it (pre-round chip record: sliding-window
    blocksparse 101.31 ms vs 17.02 ms dense flash at seq 8k, a 2.58x
    WIN at 16k — sparsity only pays once it prunes most of the work).
    Semantics are identical either route; the masked dense fallback is
    memory-bounded (it materializes [B, H, T, T] scores) so genuinely
    masked long-sequence layouts stay on the sparse path."""

    def test_full_and_causal_layouts_always_route_dense(self):
        """Dense-equivalent layouts: the gather path does the same T^2
        score work plus per-block overhead — dense strictly wins at any
        length."""
        from deepspeed_tpu.ops.sparse_attention import (
            DenseSparsityConfig, SparseSelfAttention)
        full = SparseSelfAttention(DenseSparsityConfig(block=16), 64)
        assert full.mask_kind == "full" and full.routes_dense(64)
        c = DenseSparsityConfig(block=512)
        c.attention = "unidirectional"
        causal = SparseSelfAttention(c, 16384)
        assert causal.mask_kind == "causal"
        assert causal.routes_dense(16384)

    def test_masked_routing_density_and_work_terms(self):
        """Masked layouts below the memory bound: dense when density is
        high (>= 0.1, the calibrated 8k-loses regime) or attended work
        per query row (density x seq) is tiny; sparse otherwise."""
        from deepspeed_tpu.ops.sparse_attention import SparseSelfAttention
        tiny = SparseSelfAttention(
            LocalSlidingWindowSparsityConfig(
                block=8, num_sliding_window_blocks=1), 64)
        assert tiny.mask_kind == "masked" and tiny.routes_dense(64)
        # sparse-enough masked layout above the work threshold at the
        # same scale: stays sparse
        sp = SparseSelfAttention(
            LocalSlidingWindowSparsityConfig(
                block=8, num_sliding_window_blocks=1), 64,
            dense_route_density=0.5, dense_route_min_tokens=1)
        assert not sp.routes_dense(64)

    def test_masked_long_sequences_stay_sparse(self):
        """The 8k/16k sliding-window layouts are genuinely masked: the
        dense fallback would materialize 8k^2+ fp32 scores (the flash
        kernel takes no mask), so they stay on the nnz-proportional
        sparse path regardless of the density terms."""
        from deepspeed_tpu.ops.sparse_attention import SparseSelfAttention
        cfg = LocalSlidingWindowSparsityConfig(
            num_heads=8, block=512, num_sliding_window_blocks=3)
        for seq in (8192, 16384):
            attn = SparseSelfAttention(cfg, seq)
            assert attn.mask_kind == "masked"
            assert not attn.routes_dense(seq), seq
            assert attn._dense_mask is None      # mask never materialized

    def test_routes_agree_numerically(self):
        """The route changes the algorithm, never the answer: force the
        same layout down both paths and compare."""
        from deepspeed_tpu.ops.sparse_attention import SparseSelfAttention
        cfg = LocalSlidingWindowSparsityConfig(
            num_heads=2, block=8, num_sliding_window_blocks=2,
            attention="unidirectional")
        dense_route = SparseSelfAttention(cfg, 64)
        sparse_route = SparseSelfAttention(cfg, 64,
                                           dense_route_density=1.1,
                                           dense_route_min_tokens=0)
        assert dense_route.routes_dense(64)
        assert not sparse_route.routes_dense(64)
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 64, 2, 16))
                   for i in range(3))
        np.testing.assert_allclose(np.asarray(dense_route(q, k, v)),
                                   np.asarray(sparse_route(q, k, v)),
                                   atol=2e-5)

    def test_dense_route_differentiable(self):
        from deepspeed_tpu.ops.sparse_attention import (
            DenseSparsityConfig, SparseSelfAttention)
        attn = SparseSelfAttention(DenseSparsityConfig(block=8), 32)
        assert attn.routes_dense(32)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 8))
        g = jax.grad(lambda q: jnp.sum(attn(q, q, q) ** 2))(q)
        assert np.isfinite(np.asarray(g)).all()
