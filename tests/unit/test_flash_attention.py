"""Flash attention kernel numerics (CPU interpret mode = exact fp32).

Coverage model: the reference's kernel-vs-torch parity suites
(`/root/reference/tests/unit/ops/transformer/`). Exercises both backward
schemes: the fused single-block kernel (whole sequence in one block) and
the two-pass dq/dkv scheme (multi-block grids).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import layers as L
from deepspeed_tpu.ops.transformer.flash_attention import (
    flash_attention, flash_attention_bthd)


def make_qkv(b, t, h, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d)) for k in ks)


def ref_attn(q, k, v):
    return L.causal_attention(q, k, v)


class TestForward:
    @pytest.mark.parametrize("t,block", [(128, (1024, 1024)),   # fused path
                                         (256, (128, 128)),     # multi-block
                                         (384, (128, 128))])
    def test_matches_xla(self, t, block):
        q, k, v = make_qkv(2, t, 4, 32)
        out = flash_attention_bthd(q, k, v, block_q=block[0],
                                   block_k=block[1])
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_attn(q, k, v)),
                                   atol=2e-5)

    def test_default_blocks_cover_long_seq(self):
        q, k, v = make_qkv(1, 2048, 2, 32)
        out = flash_attention_bthd(q, k, v)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_attn(q, k, v)),
                                   atol=2e-5)

    def test_ragged_matches_xla(self):
        """Non-block-divisible length runs in-kernel (ceil grid + tail
        masking) instead of raising — the old divisibility gate forced
        every odd training length onto the O(T²) XLA fallback."""
        q, k, v = make_qkv(1, 1536, 2, 32)
        out = flash_attention_bthd(q, k, v)  # 1536 % 1024 != 0
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_attn(q, k, v)),
                                   atol=2e-5)

    def test_gqa_matches_xla(self):
        """k/v enter at kv-head width; the kernel folds the group via its
        index maps (no jnp.repeat expansion)."""
        q, _, _ = make_qkv(2, 256, 8, 32)
        _, k, v = make_qkv(2, 256, 2, 32, seed=7)
        out = flash_attention_bthd(q, k, v, block_q=128, block_k=128)
        ref = ref_attn(q, jnp.repeat(k, 4, axis=2), jnp.repeat(v, 4, axis=2))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


class TestBackward:
    def _grads(self, fn, q, k, v):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("t,block", [(128, (1024, 1024)),   # fused
                                         (256, (128, 128)),     # two-pass
                                         (512, (128, 256))])
    @pytest.mark.slow
    def test_grads_match_xla(self, t, block):
        q, k, v = make_qkv(2, t, 4, 32, seed=1)
        fa = lambda q, k, v: flash_attention_bthd(  # noqa: E731
            q, k, v, block_q=block[0], block_k=block[1])
        g_fa = self._grads(fa, q, k, v)
        g_ref = self._grads(ref_attn, q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    @pytest.mark.slow
    def test_fused_and_two_pass_agree(self):
        """The single-block fused backward must equal the two-pass scheme
        on the same inputs."""
        q, k, v = make_qkv(2, 256, 2, 32, seed=2)
        fused = lambda q, k, v: flash_attention_bthd(  # noqa: E731
            q, k, v, block_q=1024, block_k=1024)   # t<=block → fused
        twopass = lambda q, k, v: flash_attention_bthd(  # noqa: E731
            q, k, v, block_q=128, block_k=128)
        g1 = self._grads(fused, q, k, v)
        g2 = self._grads(twopass, q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    @pytest.mark.slow
    def test_ragged_gqa_grads_match_xla(self):
        """Hardest combination in one case: ragged length (tail-masked
        ceil grid) + GQA (grouped dkv grid) + causal, through the
        two-pass backward."""
        q, _, _ = make_qkv(2, 160, 4, 32, seed=4)
        _, k, v = make_qkv(2, 160, 2, 32, seed=5)
        fa = lambda q, k, v: flash_attention_bthd(  # noqa: E731
            q, k, v, block_q=128, block_k=128)
        ref = lambda q, k, v: ref_attn(  # noqa: E731
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
        g_fa = self._grads(fa, q, k, v)
        g_ref = self._grads(ref, q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    @pytest.mark.slow
    def test_noncausal(self):
        q, k, v = make_qkv(1, 128, 2, 32, seed=3)

        def fa(q, k, v):
            return flash_attention_bthd(q, k, v, causal=False)

        def ref(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
            p = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        g1 = self._grads(fa, q, k, v)
        g2 = self._grads(ref, q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)
