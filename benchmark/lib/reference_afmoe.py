"""The plain reference of the ``afmoe`` family's language model
(Trinity-Mini): forward pass in straightforward float32 ``jax.numpy`` — no
kernel, no cache, no pages, expanded attention over the full sequence under
explicit masks, a plain loop over experts, matmul precision ``highest``.
It shares no code with ``deepspeed_tpu/``; it reads the same parameter
tree (``params["attn" | "dense" | "moe"]``, a stack each).

Follows the published config (``arcee-ai/Trinity-Mini`` ``config.json``)
and, where that has no key, the family's public modelling code as recalled
(each such point is an ``assumed`` entry of
``benchmark/configs/trinity-mini.json``)::

    x_0 = sqrt(d) * E[ids]                                 mup_enabled
    h = N1(x);  q = h Wq,  k = h Wk,  v = h Wv,  g = h Wg
    q <- rmsnorm(q; w_qn),  k <- rmsnorm(k; w_kn)          per head, BEFORE
                                                           any rotation
    sliding_attention layer:  q, k <- rotary (rotate_half, theta);
                              row i sees key j iff 0 <= i - j < window
    full_attention layer:     no positional encoding;  j <= i
    a = softmax(q k^T / sqrt(D), visible) v                query head n reads
                                                           kv head n // (H / G)
    x <- x + N2((a * sigmoid(g)) Wo)
    u = N3(x)
    l < num_dense_layers:  f = SwiGLU(u)
    otherwise:  s = sigmoid(u Wr);  pick = top k of s + b;
                w_e = route_scale * s_e / (sum of the picked s + 1e-20)
                f = Shared(u) + sum_e w_e Expert_e(u)
    x <- x + N4(f)
    logits = N(x_L) W_head

Departures, noted: (1) ``experts_held = (lo, hi)`` gives the reference the
same share of the routed experts as the chip holds (``model-configs``
guide section 4): a pick of an absent expert adds nothing, here as in the
program, and the shared expert is whole; (2) attention is computed in
blocks of ``ROWS`` query rows, each against the keys up to its last row —
the same sums, so that a prompt of a few thousand tokens fits the chip
beside the program.

``cfg["without"]`` names what the reference is made to lack or change, one
mechanism at a time (``CONTROLS``) — the builder's proof that the check
sees each of them: ``window`` (every layer sees every earlier key),
``gate`` (no sigmoid gate on the attention's output), ``nope`` (the full
layers rotate too), ``bias`` (the pick is the top k of ``s`` alone),
``scale`` (``route_scale`` left out of the weights), ``bf16`` (every
weight, activation and product in bfloat16), ``float8`` (every weight
matrix rounded to ``float8_e4m3fn`` before it is upcast: the precision
below the configuration's).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: query rows to a block of the expanded attention
ROWS = 512
#: column blocks of the head's product, one at a time
HEAD_PARTS = 8
CONTROLS = ("window", "gate", "nope", "bias", "scale", "bf16", "float8")
WINDOW, FULL = "window", "full"


def _dt(cfg):
    return jnp.bfloat16 if "bf16" in cfg.get("without", ()) else F32


def _up(w, cfg):
    if "float8" in cfg.get("without", ()) and w.ndim >= 2:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(_dt(cfg))


def _w(p, cfg):
    return _up(p["kernel"], cfg)


def _rmsnorm(p, x, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * p["scale"].astype(x.dtype))


def _rope(x, theta):
    """x [B, T, heads, D] at positions 0 .. T - 1, pairing channel i with
    i + D / 2 (rotate_half)."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(rows, keys: int, window):
    """``[len(rows), keys]`` bool: row at position ``i`` sees key ``j`` iff
    ``j <= i``, and under a ``window`` iff ``0 <= i - j < window``."""
    back = rows[:, None] - jnp.arange(keys)[None, :]
    return (back >= 0) if window is None else (back >= 0) & (back < window)


def attention(p, h, cfg, kind: str):
    """One layer's gated attention over ``h [B, T, d]``, before the
    output norm, and the keys and values it read ``[B, T, G D]`` each (a
    key normed and, in a window layer, rotated)."""
    b, t, _ = h.shape
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    without = cfg.get("without", ())
    qkv = h @ _w(p["qkv"], cfg)
    q, k, v = (a.reshape(b, t, -1, hd) for a in jnp.split(
        qkv, [nh * hd, (nh + nkv) * hd], axis=-1))
    q = _rmsnorm(p["q_norm"], q, cfg["eps"])
    k = _rmsnorm(p["k_norm"], k, cfg["eps"])
    if kind == WINDOW or "nope" in without:
        q, k = _rope(q, cfg["theta"]), _rope(k, cfg["theta"])
    window = cfg["window"] if kind == WINDOW and "window" not in without \
        else None
    kept = jnp.stack([k.reshape(b, t, -1), v.reshape(b, t, -1)])
    # query head n reads kv head n // (H / G)
    k, v = (jnp.repeat(a, nh // nkv, axis=2) for a in (k, v))
    out = []
    for r0 in range(0, t, ROWS):
        r1 = min(t, r0 + ROWS)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, r0:r1], k[:, :r1]) \
            / math.sqrt(hd)
        s = jnp.where(visible(jnp.arange(r0, r1), r1, window)[None, None],
                      s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(s.astype(F32), axis=-1).astype(
                                  s.dtype), v[:, :r1]))
    a = jnp.concatenate(out, axis=1).reshape(b, t, nh * hd)
    if "gate" not in without:
        a = a * jax.nn.sigmoid(h @ _w(p["gate"], cfg))
    return a @ _w(p["out"], cfg), kept


def ffn(p, x, cfg):
    return (jax.nn.silu(x @ _w(p["fc_gate"], cfg))
            * (x @ _w(p["fc_in"], cfg))) @ _w(p["fc_out"], cfg)


def router(p, u, cfg):
    """``(picked [.., k], weight [.., k])``: the top k of ``sigmoid(u Wr)
    + b``, weighted by the sigmoids alone, renormalised and scaled."""
    without = cfg.get("without", ())
    score = jax.nn.sigmoid(u @ _w(p["router"], cfg))
    ranked = score if "bias" in without else \
        score + p["bias"].astype(score.dtype)
    _, picked = jax.lax.top_k(ranked, cfg["topk"])
    weight = jnp.take_along_axis(score, picked, axis=-1)
    scale = 1.0 if "scale" in without else cfg["scale"]
    return picked, scale * weight / (
        jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)


def routed(p, u, cfg, experts_held=None, expert_at=None):
    """u [B, T, d] -> the held routed experts' weighted sum.
    ``experts_held = (lo, hi)``: routed experts lo .. hi - 1 are held
    (``p["experts"]``, or ``expert_at(i)`` -> the i-th held expert's three
    matrices) and the others add nothing."""
    lo, hi = experts_held or (0, cfg["experts"])
    if expert_at is None:
        def expert_at(i):
            return {name: w[i] for name, w in p["experts"].items()}
    picked, weight = router(p, u, cfg)

    def add_expert(i, y):      # an expert is picked at most once a row
        w = expert_at(i)
        mine = jnp.sum(jnp.where(picked == lo + i, weight, 0.0), axis=-1,
                       keepdims=True)
        out = (jax.nn.silu(u @ _up(w["w_gate"], cfg))
               * (u @ _up(w["w_up"], cfg))) @ _up(w["w_down"], cfg)
        return y + mine * out
    return jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))


def expert_layer(p, u, cfg, experts_held=None):
    """An expert layer's ``f``: the shared expert whole, and the held
    routed experts."""
    return ffn(p["shared"], u, cfg) + routed(p["moe"], u, cfg, experts_held)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def layer(ap, fp, x, kind: str, cfg_items: tuple, experts_held):
    """One layer: ``ap`` its attention with the norms around it, ``fp``
    its FFN (dense where it holds ``mlp``) with its two.  Returns ``(x,
    the layer's keys and values [2, B, T, G D])``."""
    cfg = dict(cfg_items)
    eps = cfg["eps"]
    with jax.default_matmul_precision("highest"):
        a, kept = attention(ap["attn"], _rmsnorm(ap["ln1"], x, eps), cfg,
                            kind)
        x = x + _rmsnorm(ap["ln_post_attn"], a, eps)
        u = _rmsnorm(fp["ln2"], x, eps)
        f = expert_layer(fp, u, cfg, experts_held) if "moe" in fp \
            else ffn(fp["mlp"], u, cfg)
        return x + _rmsnorm(fp["ln_post_mlp"], f, eps), kept


def hidden(params, ids, cfg, experts_held=None):
    """[B, T] token ids -> ``(the last layer's output [B, T, d], the FULL
    layers' keys and values float32 [2, full layers, B, T, G D])``: what a
    server keeps of a sequence for as long as it lives."""
    dt = _dt(cfg)
    x = params["embed"]["embedding"][ids].astype(dt)
    if cfg["mup"]:
        x = x * jnp.asarray(math.sqrt(x.shape[-1]), dt)
    items = tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple)) else v)
                         for k, v in cfg.items()))
    held = None if experts_held is None else tuple(experts_held)
    full = []
    for at, kind in enumerate(cfg["layer_types"]):
        stack, i = (("dense", at) if at < cfg["dense"]
                    else ("moe", at - cfg["dense"]))
        ap = jax.tree_util.tree_map(lambda a: a[at], params["attn"])
        fp = jax.tree_util.tree_map(lambda a, i=i: a[i], params[stack])
        x, kept = layer(ap, fp, x, kind, items, held)
        if kind == FULL:
            full.append(kept.astype(F32))
    return x, jnp.stack(full, axis=1)


def logits(params, ids, cfg, experts_held=None, last=None, kv=False):
    """[B, T] token ids -> float32 logits ``[B, T, V]``, or of the last
    ``last`` positions alone; with ``kv`` also :func:`hidden`'s keys and
    values."""
    x, full = hidden(params, ids, cfg, experts_held)
    if last is not None:
        x = x[:, -last:]
    lg = _head(params["ln_f"], params["lm_head"]["kernel"], x,
               tuple(sorted((k, v) for k, v in cfg.items()
                            if k in ("eps", "without"))))
    return (lg, full) if kv else lg


@functools.partial(jax.jit, static_argnums=3)
def _head(ln_f, head, x, cfg_items: tuple):
    """The final norm and the head's product, a block of the head's
    columns at a time in a LOOP: no float32 copy of more than one block of
    the ``[d, vocabulary]`` matrix exists at once (1.6 GB whole at 200,192:
    it does not fit the chip beside the program)."""
    cfg = dict(cfg_items)
    vocab = head.shape[1]
    parts = HEAD_PARTS if vocab % HEAD_PARTS == 0 else 1
    width = vocab // parts
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(ln_f, x, cfg["eps"])

        def block(at, out):
            w = jax.lax.dynamic_slice_in_dim(head, at * width, width, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, (x @ _up(w, cfg)).astype(F32), at * width, axis=-1)
        return jax.lax.fori_loop(
            0, parts, block, jnp.zeros(x.shape[:-1] + (vocab,), F32))


def settings(config: dict) -> dict:
    """The reference's settings from a configuration file's published
    keys (``benchmark/configs/trinity-mini.json``)."""
    names = {"sliding_attention": WINDOW, "full_attention": FULL}
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "window": config["sliding_window"],
            "layer_types": tuple(names[t] for t in config["layer_types"]),
            "dense": config["num_dense_layers"],
            "mup": bool(config["mup_enabled"]),
            "experts": config["published"]["num_experts"]
            if "published" in config else config["num_experts"],
            "topk": config["num_experts_per_tok"],
            "scale": float(config["route_scale"])}
