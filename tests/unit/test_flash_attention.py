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
    _heads_a_pack, flash_attention, flash_attention_bthd,
    flash_attention_qkv)


def make_qkv(b, t, h, d, seed=0, kvh=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, n, d))
                 for k, n in zip(ks, (h, kvh or h, kvh or h)))


def ref_attn(q, k, v, causal=True):
    g = q.shape[2] // k.shape[2]
    return L.causal_attention(q, jnp.repeat(k, g, axis=2),
                              jnp.repeat(v, g, axis=2), causal=causal)


#: (heads, kv heads, head dim) -> heads a 128-lane pack of [B, T, H·D]
#: (None: the transposing form).  The packed form is chosen from these
#: three numbers and nothing else.
PACKED = {(2, 2, 64): 2, (16, 16, 64): 2, (8, 2, 64): 2, (4, 2, 64): 2,
          (2, 2, 128): 1, (8, 2, 128): 1, (1, 1, 256): 1}
TRANSPOSED = {(3, 3, 64): None, (6, 2, 64): None, (4, 1, 64): None,
              (4, 4, 32): None, (8, 2, 32): None}


@pytest.mark.parametrize("shape,hp", list({**PACKED, **TRANSPOSED}.items()))
def test_form_follows_from_shapes(shape, hp):
    """Packed shapes reach the kernel through reshapes alone; every other
    shape through the two transposes."""
    assert _heads_a_pack(*shape) == hp
    h, kvh, d = shape
    q, k, v = make_qkv(1, 128, h, d, kvh=kvh)
    moved = [e.primitive.name for e in jax.make_jaxpr(
        flash_attention_bthd)(q, k, v).eqns]
    assert ("transpose" in moved) == (hp is None), moved


class TestForward:
    @pytest.mark.parametrize("t,block,heads,d", [
        (128, (1024, 1024), (4, 4), 32),   # fused path
        (256, (128, 128), (4, 4), 32),     # multi-block
        (384, (128, 128), (4, 4), 32),
        # the packed form: two heads a 128-lane pack ...
        (256, (128, 128), (2, 2), 64),
        (128, (1024, 1024), (16, 16), 64),
        (1000, (1024, 1024), (2, 2), 64),  # ragged, one block
        (1000, (256, 512), (2, 2), 64),    # ragged, tails on both sides
        # ... one head a pack, MHA and GQA 8 / 2
        (256, (128, 128), (2, 2), 128),
        (256, (128, 128), (8, 2), 128),
        (200, (128, 128), (8, 2), 128),    # ragged GQA
        # ... two heads a pack that share ONE kv head (a half of its pack)
        (256, (128, 128), (8, 2), 64),
        (256, (128, 128), (4, 2), 64),
        # shapes that must take the transposing form
        (256, (128, 128), (3, 3), 64),
        (256, (128, 128), (6, 2), 64)])
    def test_matches_xla(self, t, block, heads, d):
        q, k, v = make_qkv(2 if t < 1000 else 1, t, heads[0], d,
                           kvh=heads[1])
        out = flash_attention_bthd(q, k, v, block_q=block[0],
                                   block_k=block[1])
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_attn(q, k, v)),
                                   atol=2e-5)

    @pytest.mark.parametrize("heads,d", [((2, 2), 64), ((4, 2), 128),
                                         ((8, 2), 64), ((4, 4), 32)])
    def test_fused_qkv_matches_xla(self, heads, d):
        """The projection's one ``[B, T, (H + 2 KVH) D]`` array in, read
        at three pack offsets (D = 32: sliced for the transposing
        form)."""
        h, kvh = heads
        q, k, v = make_qkv(2, 256, h, d, seed=3, kvh=kvh)
        qkv = jnp.concatenate([x.reshape(2, 256, -1) for x in (q, k, v)],
                              axis=-1)
        out = flash_attention_qkv(qkv, h, kvh, block_q=128, block_k=128)
        np.testing.assert_allclose(
            np.asarray(out.reshape(q.shape)),
            np.asarray(ref_attn(q, k, v)), atol=2e-5)

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

    @pytest.mark.parametrize("t,block,heads,d", [
        pytest.param(128, (1024, 1024), (4, 4), 32,        # fused
                     marks=pytest.mark.slow),
        pytest.param(256, (128, 128), (4, 4), 32,          # two-pass
                     marks=pytest.mark.slow),
        pytest.param(512, (128, 256), (4, 4), 32, marks=pytest.mark.slow),
        # the packed form, fused and two-pass
        (128, (1024, 1024), (2, 2), 64),
        (256, (128, 128), (2, 2), 64),
        (128, (1024, 1024), (2, 2), 128),
        (256, (128, 256), (8, 2), 128),
        (200, (128, 128), (2, 2), 64),     # ragged
        (256, (128, 256), (8, 2), 64),     # a kv head shared inside a pack
        (200, (128, 128), (4, 2), 64),
        # the transposing form
        (256, (128, 128), (3, 3), 64)])
    def test_grads_match_xla(self, t, block, heads, d):
        q, k, v = make_qkv(2, t, heads[0], d, seed=1, kvh=heads[1])
        fa = lambda q, k, v: flash_attention_bthd(  # noqa: E731
            q, k, v, block_q=block[0], block_k=block[1])
        g_fa = self._grads(fa, q, k, v)
        g_ref = self._grads(ref_attn, q, k, v)
        for a, b in zip(g_fa, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    @pytest.mark.parametrize("h,d", [
        pytest.param(2, 32, marks=pytest.mark.slow), (2, 64), (2, 128)])
    def test_fused_and_two_pass_agree(self, h, d):
        """The single-block fused backward must equal the two-pass scheme
        on the same inputs."""
        q, k, v = make_qkv(2, 256, h, d, seed=2)
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

    @pytest.mark.parametrize("block", [128, 1024],
                             ids=["two_pass", "single_block"])
    @pytest.mark.parametrize("heads,d", [((2, 2), 64), ((4, 2), 128),
                                         ((4, 4), 32)])
    def test_fused_qkv_grads_match_xla(self, heads, d, block):
        """One array in, ONE gradient out: dq | dk | dv in the sections'
        order (the single-block backward writes the three into the one
        array itself; the two-pass one's are concatenated)."""
        h, kvh = heads
        qkv = jax.random.normal(jax.random.PRNGKey(6),
                                (2, 256, (h + 2 * kvh) * d))

        def split(qkv):
            return (qkv[..., :h * d].reshape(2, 256, h, d),
                    qkv[..., h * d:(h + kvh) * d].reshape(2, 256, kvh, d),
                    qkv[..., (h + kvh) * d:].reshape(2, 256, kvh, d))
        g_fa = jax.grad(lambda x: jnp.sum(flash_attention_qkv(
            x, h, kvh, block_q=block, block_k=block) ** 2))(qkv)
        g_ref = jax.grad(lambda x: jnp.sum(ref_attn(*split(x)) ** 2))(qkv)
        np.testing.assert_allclose(np.asarray(g_fa), np.asarray(g_ref),
                                   atol=5e-4)

    @pytest.mark.parametrize("h,d", [
        pytest.param(2, 32, marks=pytest.mark.slow), (2, 64), (2, 128)])
    def test_noncausal(self, h, d):
        q, k, v = make_qkv(1, 128, h, d, seed=3)

        def fa(q, k, v):
            return flash_attention_bthd(q, k, v, causal=False)

        def ref(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            p = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        g1 = self._grads(fa, q, k, v)
        g2 = self._grads(ref, q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)


class TestModelPath:
    """`TransformerLM` hands the flash kernels the projection's own
    layout — the fused product as ONE array, or, behind rotary, q and k as
    the lane-dense rotary's outputs — and must compute what the XLA path
    (4-D heads, `apply_rotary`) computes."""

    @pytest.mark.parametrize("family,heads,head_dim,extra", [
        ("gpt2", (2, 2), 64, {}),                       # two heads a pack
        ("gpt2", (4, 2), 128, {}),                      # GQA by block index
        ("gpt2", (3, 3), 64, {}),                       # transposing form
        ("neox", (2, 2), 64, {"rotary_pct": 1.0}),
        ("neox", (2, 2), 128, {"rotary_pct": 0.25}),
        ("neox", (2, 2), 128, {"rotary_pct": 0.25,
                               "rotary_interleaved": True})])
    def test_loss_and_grads_match_the_xla_path(self, family, heads,
                                               head_dim, extra):
        from deepspeed_tpu.models import (TransformerLM, gpt2_config,
                                          neox_config)
        build = {"gpt2": lambda **kw: gpt2_config("125m", **kw),
                 "neox": lambda **kw: neox_config("1.3b", **kw)}[family]
        sizes = dict(num_layers=2, num_heads=heads[0],
                     num_kv_heads=heads[1], head_dim=head_dim,
                     d_model=heads[0] * head_dim, d_ff=256, vocab_size=512,
                     max_seq_len=128, dtype=jnp.float32, **extra)
        models = {impl: TransformerLM(build(attn_impl=impl, **sizes))
                  for impl in ("xla", "flash")}
        params = models["xla"].init(jax.random.PRNGKey(0))
        batch = {"input_ids": jax.random.randint(
            jax.random.PRNGKey(1), (2, 128), 0, 512)}
        (l_ref, g_ref), (l_fa, g_fa) = (
            jax.value_and_grad(m.loss)(params, batch)
            for m in models.values())
        np.testing.assert_allclose(float(l_fa), float(l_ref), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g_fa),
                        jax.tree_util.tree_leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)
