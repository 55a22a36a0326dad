"""The plain reference for the ZAYA1 block: compressed convolutional
attention and a top-1 expert layer behind a router MLP, forward pass, loss
and (through ``jax.grad``) gradients, in straightforward float32
``jax.numpy`` at matmul precision ``highest`` — no kernel, no row layout,
no recomputation; a loop over the experts; expanded attention (every query
head its own copy of its key-value head) in blocks of query rows.  It
shares no code with ``deepspeed_tpu/models``; it reads the same parameter
tree.

Layer ``l`` (``config.json`` keys in backticks; the rest is listed under
``assumed`` in ``benchmark/configs/zaya1-8b.json``), input ``x [T, d]``,
``r_{l-1} [T, R]`` the router state of the layer before (0 for the first
layer held)::

    h = RMSNorm(x);  q~ = h Wq  (`num_attention_heads` x `head_dim`)
    k~ = h Wk  (`num_key_value_heads` x `head_dim`)
    v_t = [h_t Wv1 ; h_{t-1} Wv2],  h_{-1} = 0;  split into the kv heads
    z = [q~ ; k~];  z1_t = sum_{j < cca_time0} a_j * z_{t-j} + a0
    z2_t^(g) = sum_{j < cca_time1} B_j^(g) z1_{t-j}^(g) + b^(g)   per head
    q = z2_q + (q~ + rep(k~)) / 2;   k = z2_k + (mean(q~) + k~) / 2
    q <- sqrt(D) q / |q|;  k <- tau_g sqrt(D) k / |k|
    rotary (rotate-half) on the first `partial_rotary_factor` D dims
    x <- x + causal softmax(q k^T / sqrt(D)) v Wo

    u = RMSNorm(x);  r_l = u Wr + gamma_l * r_{l-1}
    s = W3 gelu(W2 gelu(W1 RMSNorm(r_l)));  p = softmax(s)
    e = argmax(p + kappa b);  x <- x + p_e Wdown_e (silu(u Wgate_e) * (u Wup_e))
    loss += sum(stop_gradient(f - 1 / E) * (b - stop_gradient(b)))

``f_e`` is the share of the picks that chose ``e``: the term is 0 and
gives ``b`` the gradient ``f - 1 / E``; ``kappa = cfg["bias_unit"]`` is
the unit the stored bias is in (the configuration file's
``router_bias_unit``).  ``cfg["held"] = (lo, hi)`` is the share of the
experts this chip holds: a pick outside it adds nothing.

``picks [L, B, T]`` (``hidden``, ``loss``) takes every layer's choice as
given instead of the argmax — the weight is still the reference's own
``p_e``.  The cell's gradient comparison hands in the program's picks: a
near-tie that bfloat16 decides the other way is then counted as a flipped
pick (``own_picks`` says what the reference would have chosen) and not as
a wrong gradient.

``cfg["without"]`` names parts to leave out — ``conv``, ``qk_mean``,
``value_shift``, ``key_temperature``, ``router_carry`` — for the controls
that show the comparison would notice (``PERF.md`` §4); a cell never sets
it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows to a block of the expanded attention
QUERY_BLOCK = 1024


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(p, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _delay(a, by):
    """``a [B, T, ...]`` at position ``t - by``, zero before the start."""
    if not by:
        return a
    return jnp.concatenate([jnp.zeros_like(a[:, :by]), a[:, :-by]], axis=1)


def _rotate_half(x, rotary_dim, theta):
    """x [B, T, H, D]: dims ``i`` and ``i + rotary_dim / 2`` of the first
    ``rotary_dim`` rotate by ``t * theta ** (-2 i / rotary_dim)``."""
    t = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    half = rotary_dim // 2
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _causal_attention(q, k, v):
    """q, k, v [B, T, H, D] (k and v already expanded to H heads)."""
    t, d = q.shape[1], q.shape[-1]
    out = []
    for at in range(0, t, QUERY_BLOCK):
        end = min(at + QUERY_BLOCK, t)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, at:end], k[:, :end]) \
            / jnp.sqrt(jnp.float32(d))
        seen = (jnp.arange(at, end)[:, None] >= jnp.arange(end)[None, :])
        s = jnp.where(seen[None, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                              v[:, :end]))
    return jnp.concatenate(out, axis=1)


def attention(p, h, cfg):
    """The CCA sublayer on ``h = RMSNorm(x)``: ``[B, T, d] -> [B, T, d]``."""
    without = cfg.get("without", ())
    b, t, _ = h.shape
    nh, nkv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    group = nh // nkv
    q_in = h @ p["q"]["kernel"]
    k_in = h @ p["k"]["kernel"]
    earlier = h if "value_shift" in without else _delay(h, 1)
    v = jnp.concatenate([h @ p["v1"]["kernel"],
                         earlier @ p["v2"]["kernel"]], axis=-1)
    v = v.reshape(b, t, nkv, hd)
    z = jnp.concatenate([q_in, k_in], axis=-1)
    if "conv" in without:
        z2 = jnp.zeros((b, t, nh + nkv, hd), jnp.float32)
    else:
        taps0, taps1 = p["conv0"]["taps"], p["conv1"]["taps"]
        z1 = p["conv0"]["bias"] + sum(
            taps0[j] * _delay(z, j) for j in range(taps0.shape[0]))
        z1 = z1.reshape(b, t, nh + nkv, hd)
        z2 = p["conv1"]["bias"].reshape(nh + nkv, hd) + sum(
            jnp.einsum("btgc,gcd->btgd", _delay(z1, j), taps1[j])
            for j in range(taps1.shape[0]))
    qt = q_in.reshape(b, t, nkv, group, hd)
    kt = k_in.reshape(b, t, nkv, hd)
    if "qk_mean" in without:
        q_mix, k_mix = qt, kt
    else:
        q_mix = (qt + kt[:, :, :, None]) / 2
        k_mix = (qt.mean(axis=3) + kt) / 2
    q = z2[:, :, :nh] + q_mix.reshape(b, t, nh, hd)
    k = z2[:, :, nh:] + k_mix
    q = jnp.sqrt(jnp.float32(hd)) * q / jnp.linalg.norm(
        q, axis=-1, keepdims=True)
    k = jnp.sqrt(jnp.float32(hd)) * k / jnp.linalg.norm(
        k, axis=-1, keepdims=True)
    if "key_temperature" not in without:
        k = k * p["tau"][:, None]
    q = _rotate_half(q, cfg["rotary_dim"], cfg["rope_theta"])
    k = _rotate_half(k, cfg["rotary_dim"], cfg["rope_theta"])
    o = _causal_attention(q, jnp.repeat(k, group, axis=2),
                          jnp.repeat(v, group, axis=2))
    return o.reshape(b, t, nh * hd) @ p["out"]["kernel"]


def router(p, u, r_prev, cfg):
    """``(r_l [B, T, R], p [B, T, E])``: the router state and the softmax
    of the MLP's scores."""
    r = u @ p["in"]["kernel"]
    if "router_carry" not in cfg.get("without", ()):
        r = r + p["gamma"] * r_prev
    a = _rms(p["norm"], r, cfg["eps"])
    a = jax.nn.gelu(a @ p["fc1"]["kernel"], approximate=False)
    a = jax.nn.gelu(a @ p["fc2"]["kernel"], approximate=False)
    return r, jax.nn.softmax(a @ p["fc3"]["kernel"], axis=-1)


def experts(p, u, r_prev, cfg, held=None, pick=None):
    """The expert sublayer on ``u = RMSNorm(x)``: ``(the held experts'
    part of its output, r_l, the balance term)``; ``held = (lo, hi)``
    overrides ``cfg["held"]``, ``pick [B, T]`` the argmax."""
    lo, hi = held or cfg["held"]
    r, prob = router(p["router"], u, r_prev, cfg)
    if pick is None:
        pick = jnp.argmax(prob + cfg["bias_unit"] * p["bias"], axis=-1)
    weight = jnp.take_along_axis(prob, pick[..., None], axis=-1)
    y = jnp.zeros_like(u)
    for e in range(lo, hi):
        w = {n: m[e - cfg["held"][0]] for n, m in p["experts"].items()}
        out = (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
        y = y + jnp.where(pick[..., None] == e, weight, 0.0) * out
    n = prob.shape[-1]
    share = jnp.mean(jax.nn.one_hot(pick, n).reshape(-1, n), axis=0)
    balance = jnp.sum(jax.lax.stop_gradient(share - 1.0 / n)
                      * (p["bias"] - jax.lax.stop_gradient(p["bias"])))
    return y, r, balance


def layer(p, x, r_prev, cfg, pick=None):
    """One layer: ``(x, r_l, the balance term)``."""
    x = x + attention(p["attn"], _rms(p["ln1"], x, cfg["eps"]), cfg)
    y, r, balance = experts(p["moe"], _rms(p["ln2"], x, cfg["eps"]), r_prev,
                            cfg, pick=pick)
    return x + y, r, balance


def hidden(params, ids, cfg, wrap_layer=lambda f: f, picks=None):
    """[B, T] token ids -> ``(final hidden states after the last norm
    [B, T, d], the balance terms' sum)``.  ``wrap_layer`` wraps the
    function of one layer (the cell's runner passes ``jax.checkpoint``
    so that the gradient at 8,192 positions fits on the chip)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(jnp.float32)[ids]
        one = wrap_layer(lambda p, x, r, pick: layer(_f32(p), x, r, cfg,
                                                     pick))

        def body(carry, layer_in):
            x, r, total = carry
            p, pick = layer_in
            x, r, balance = one(p, x, r, pick)
            return (x, r, total + balance), None
        width = params["blocks"]["moe"]["router"]["gamma"].shape[-1]
        (x, _, balance), _ = jax.lax.scan(
            body, (x, jnp.zeros(x.shape[:2] + (width,), jnp.float32),
                   jnp.zeros((), jnp.float32)), (params["blocks"], picks))
        return _rms(_f32(params["ln_f"]), x, cfg["eps"]), balance


def own_picks(params, ids, cfg):
    """``[L, B, T]``: what every layer of the reference chooses."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(jnp.float32)[ids]

        def body(carry, p):
            x, r_prev = carry
            p = _f32(p)
            mid = x + attention(p["attn"], _rms(p["ln1"], x, cfg["eps"]),
                                cfg)
            u = _rms(p["ln2"], mid, cfg["eps"])
            prob = router(p["moe"]["router"], u, r_prev, cfg)[1]
            pick = jnp.argmax(prob + cfg["bias_unit"] * p["moe"]["bias"],
                              axis=-1)
            y, r, _ = experts(p["moe"], u, r_prev, cfg, pick=pick)
            return (mid + y, r), pick
        width = params["blocks"]["moe"]["router"]["gamma"].shape[-1]
        return jax.lax.scan(
            body, (x, jnp.zeros(x.shape[:2] + (width,), jnp.float32)),
            params["blocks"])[1]


def loss(params, ids, cfg, wrap_layer=lambda f: f, picks=None):
    """Mean next-token cross entropy over [B, T] ids (T - 1 targets a
    row) under the tied head, plus the balance terms (value 0)."""
    with jax.default_matmul_precision("highest"):
        x, balance = hidden(params, ids, cfg, wrap_layer, picks)
        lg = (x @ params["embed"]["embedding"].astype(jnp.float32).T)[:, :-1]
        logp = jax.nn.log_softmax(lg, axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
        return -picked.mean() + balance
