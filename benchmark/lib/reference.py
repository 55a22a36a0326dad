"""The plain reference: the two architectures' forward pass and next-token
loss in straightforward float32 ``jax.numpy`` — no kernels, no cache, no
batching tricks, matmul precision ``highest``.  It shares no code with
``deepspeed_tpu/models``; it reads the same parameter tree.

Follows the published descriptions (GPT-2: Radford et al. 2019, HF
``GPT2Model``; Pythia: Biderman et al. 2023, HF ``GPTNeoXModel``).  One
departure, noted: the fused qkv kernel's columns are laid out ``[3, heads,
head_dim]`` (the repo's and GPT-2's layout) where GPT-NeoX interleaves
``[heads, 3, head_dim]`` — a column permutation of a random matrix.

Layers run under ``lax.scan`` so the reference compiles in seconds; each
layer's weights are upcast to float32 inside the scan, so bf16 weights as
served cost no second full copy.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layernorm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _rotate_half(x, rotary_dim, base):
    """GPT-NeoX rotary embedding on the first ``rotary_dim`` dims of each
    head (x: [B, T, H, D]), pairing dim i with i + rotary_dim / 2."""
    t = x.shape[1]
    inv = 1.0 / base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    half = rotary_dim // 2
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(p, x, cfg):
    b, t, _ = x.shape
    h = cfg["heads"]
    qkv = _dense(p["qkv"], x).reshape(b, t, 3, h, -1)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    d = q.shape[-1]
    if cfg["family"] == "neox":
        rd = int(d * cfg["rotary_pct"])
        rd -= rd % 2
        q = _rotate_half(q, rd, cfg["rotary_base"])
        k = _rotate_half(k, rd, cfg["rotary_base"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return _dense(p["out"], o.reshape(b, t, h * d))


def _mlp(p, x, cfg):
    # GPT-2: gelu_new (tanh form); Pythia: exact gelu
    act = jax.nn.gelu(_dense(p["fc_in"], x),
                      approximate=cfg["family"] == "gpt2")
    return _dense(p["fc_out"], act)


def _block(p, x, cfg):
    eps = cfg["layernorm_eps"]
    if cfg["family"] == "neox":            # parallel residual
        return (x + _attention(p["attn"], _layernorm(p["ln1"], x, eps), cfg)
                + _mlp(p["mlp"], _layernorm(p["ln2"], x, eps), cfg))
    x = x + _attention(p["attn"], _layernorm(p["ln1"], x, eps), cfg)
    return x + _mlp(p["mlp"], _layernorm(p["ln2"], x, eps), cfg)


def logits(params, ids, cfg, layer_hook=lambda p: p):
    """[B, T] token ids -> [B, T, V] float32 logits.  ``layer_hook`` is
    applied to one layer's weights inside the scan (the four-chip cell uses
    it to gather a sharded layer where it is used)."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"].astype(jnp.float32)
        x = emb[ids]
        if cfg["family"] == "gpt2":
            x = x + params["pos_embed"]["embedding"].astype(
                jnp.float32)[:ids.shape[1]][None]

        def body(x, layer):
            return _block(_f32(layer_hook(layer)), x, cfg), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        x = _layernorm(_f32(params["ln_f"]), x, cfg["layernorm_eps"])
        if cfg["tied"]:
            return x @ emb.T
        return x @ params["lm_head"]["kernel"].astype(jnp.float32)


def loss(params, ids, cfg, layer_hook=lambda p: p):
    """Mean next-token cross entropy over [B, T] ids (T - 1 targets a
    row)."""
    lg = logits(params, ids, cfg, layer_hook)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
