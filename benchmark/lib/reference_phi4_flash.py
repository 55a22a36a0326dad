"""The plain reference for the ``phi4flash`` block (Phi-4-mini-flash):
state-space layers, window-attention layers, one full-attention layer,
and a cross decoder of gated memory units and cross-attention layers that
re-read the full layer's keys and values — the forward pass in
straightforward float32 ``jax.numpy`` at matmul precision ``highest``: no
cache, no kernel, no paging; the recurrence as a loop over positions (in
blocks of rows, so that a long sequence's ``[rows, d_inner]`` planes fit);
the window as a mask.  It shares no code with ``deepspeed_tpu/models``;
it reads the same parameter tree.

Layers, 0-based (``cfg["pairs_self"]`` = P, ``cfg["pairs_cross"]`` = Q;
published: 8 and 7), every one ``x <- x + mixer(LN(x))``, ``x <- x +
W_down(silu(g) * u)`` with ``[g, u] = LN'(x) W_gate_up``::

    0, 2, .., 2P      state space (Mamba-1):
        [u, z] = h W_in;  c_t = silu(sum_{j<4} w_j * u_{t-j} + b)  (zero
        history);  [r, B_t, C_t] = c_t W_x;  D_t = softplus(r W_dt + b_dt)
        A = -exp(A_log);  S_t = exp(D_t A) * S_{t-1} + (D_t c_t) (x) B_t
        y_t = S_t C_t + D_skip * c_t;  out = (y_t * silu(z_t)) W_out
        layer 2P also exports m_t = y_t (before the gate): the memory
    1, 3, .., 2P-1    window attention, grouped-query heads, NO positional
        encoding: [q, k, v] = h W_qkv + b; t attends s iff 0 <= t - s <
        `sliding_window`; softmax scale 1 / sqrt(head_dim); out = o W_o + b_o
    2P+1              full attention: the same without the window
    2P+2, 2P+4, ..    gated memory unit: out = (m_t * silu(h W_1)) W_2
    2P+3, 2P+5, ..    cross attention: q = h W_q + b against layer 2P+1's
        k, v (s <= t); out = o W_o + b_o

Tied embedding and head, a final LayerNorm.

ASSUMED (the catalog's ``config`` has no key for them; listed under
``assumed`` in ``benchmark/configs/phi-4-mini-flash-reasoning.json``):
``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_expand`` 2, ``dt_rank``
ceil(hidden / 16); a bias on the convolution and none on the state-space
projections; LayerNorm with bias; biases on ``W_qkv``, ``W_q``, ``W_o``;
the window counts the position itself; the window layers are the odd ones
below the full layer; query head ``h`` reads key-value head ``h // (H /
Hkv)``.  DEPARTURE: the model card speaks of differential attention in the
attention layers; ``config.json`` has no key for it and the catalog's
``described_as`` does not list it, so program and reference alike use
plain softmax attention.

``cfg["without"]`` names ONE mechanism to leave out, for the controls
that show the cell's comparison would notice (``PERF.md`` section 4): a
cell never sets it.  ``window`` (the window layers attend everything),
``state_carry`` (the state reset at every ``cfg["chunk"]`` rows: a chunk
boundary), ``memory`` (``m`` replaced by ones), ``cross_kv`` (the cross
layers read zeros: the keys and values the full layer wrote are exported
as they were), ``float8`` (every weight matrix rounded to e4m3),
``bf16_state`` (the state kept in bfloat16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: rows to a block of the recurrence and of the attention's query rows
ROW_BLOCK = 512


def settings(config: dict) -> dict:
    """The reference's settings from a configuration file's keys."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "window": config["sliding_window"],
            "eps": config["layer_norm_eps"],
            "state": config["mamba_d_state"],
            "dt_rank": config["mamba_dt_rank"],
            "without": ()}


def _ln(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _weights(tree, without):
    """One layer's weights as float32 copies, where they are used — the
    whole model in float32 would not fit beside the engine; ``float8``:
    every matrix through e4m3 first."""
    def leaf(a):
        a = a.astype(jnp.float32)
        if "float8" in without and a.ndim >= 2:
            a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a
    return jax.tree_util.tree_map(leaf, tree)


def _mlp(p, h):
    g, u = jnp.split(h @ p["gate_up"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ p["down"]["kernel"]


def state_space(p, h, cfg, state0=None):
    """``h [T, d]`` -> ``(out [T, d], y [T, d_inner], the state after
    the last position [d_inner, n])``."""
    t = h.shape[0]
    n, r = cfg["state"], cfg["dt_rank"]
    u, z = jnp.split(h @ p["in_proj"]["kernel"], 2, axis=-1)
    k = p["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1])), u])
    c = jax.nn.silu(sum(p["conv_w"][j] * padded[k - 1 - j:k - 1 - j + t]
                        for j in range(k)) + p["conv_b"])
    xp = c @ p["x_proj"]["kernel"]
    step = jax.nn.softplus(xp[:, :r] @ p["dt_proj"]["kernel"]
                           + p["dt_proj"]["bias"])
    bm, cm = xp[:, r:r + n], xp[:, r + n:]
    a = -jnp.exp(p["a_log"])
    low = "bf16_state" in cfg["without"]
    reset = cfg.get("chunk") if "state_carry" in cfg["without"] else None

    def row(s, xs):
        ct, dt, bt, ct_out, i = xs
        if reset:
            s = jnp.where(i % reset == 0, 0.0, s)
        s = jnp.exp(dt[:, None] * a) * s + (dt * ct)[:, None] * bt[None]
        if low:
            # (an explicit rounding: a pair of casts is dropped on the chip
            # under XLA's allowance for excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, s @ ct_out + p["d_skip"] * ct

    s = jnp.zeros((u.shape[1], n)) if state0 is None else state0
    ys = []
    for at in range(0, t, ROW_BLOCK):            # in blocks, so that it fits
        sl = slice(at, min(t, at + ROW_BLOCK))
        s, y = jax.lax.scan(row, s, (c[sl], step[sl], bm[sl], cm[sl],
                                     jnp.arange(sl.start, sl.stop)))
        ys.append(y)
    y = jnp.concatenate(ys)
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], y, s


def attend(q, k, v, cfg, window=None):
    """q ``[T, H, hd]`` against k, v ``[T, Hkv, hd]`` at the same
    positions, causally; each query head its own copy of its key-value
    head; in blocks of query rows."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    pos = jnp.arange(t)
    out = []
    for at in range(0, t, ROW_BLOCK):
        qp = pos[at:at + ROW_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", q[at:at + ROW_BLOCK], k) \
            / jnp.sqrt(float(hd))
        seen = pos[None, :] <= qp[:, None]
        if window is not None:
            seen = seen & (qp[:, None] - pos[None, :] < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out).reshape(t, h * hd)


def self_attention(p, h, cfg, window):
    nh, nkv = cfg["heads"], cfg["kv_heads"]
    hd = p["out"]["kernel"].shape[0] // nh
    qkv = h @ p["qkv"]["kernel"] + p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
    t = h.shape[0]
    k, v = k.reshape(t, nkv, hd), v.reshape(t, nkv, hd)
    o = attend(q.reshape(t, nh, hd), k, v, cfg, window)
    return o @ p["out"]["kernel"] + p["out"]["bias"], (k, v), o


def cross_attention(p, h, kv, cfg):
    nh = cfg["heads"]
    q = h @ p["q"]["kernel"] + p["q"]["bias"]
    o = attend(q.reshape(h.shape[0], nh, -1), *kv, cfg)
    return o @ p["out"]["kernel"] + p["out"]["bias"], o


def hidden(params, ids, cfg, states=False):
    """``ids [T]`` -> the stack's output before the final norm ``[T, d]``
    (and, with ``states``, every state-space layer's last state ``[P + 1,
    d_inner, n]``, the full layer's keys and values ``[2, T, Hkv x hd]``:
    what the cross layers read, and what the full layer's attention and
    each cross layer's gave every position, before the output projection,
    ``[1 + Q, T, H x hd]``: what they made of it).  A ``fori_loop`` over
    the pairs of each part of the stack, so that one pair's float32
    weights exist at a time."""
    without = cfg["without"]
    eps = cfg["eps"]
    x = params["embed"]["embedding"][ids].astype(jnp.float32)
    t = x.shape[0]

    def shell(bp, x, mixer):
        out, aux = mixer(bp["mixer"], _ln(bp["ln1"], x, eps))
        x = x + out
        return x + _mlp(bp["mlp"], _ln(bp["ln2"], x, eps)), aux

    def ssm(mp, h):
        out, y, s = state_space(mp, h, cfg)
        return out, (y, s)

    def pair(tree, i):
        return _weights(jax.tree_util.tree_map(lambda a: a[i], tree),
                        without)

    window = None if "window" in without else cfg["window"]
    pairs = params["self"]["a"]["ln1"]["scale"].shape[0]
    mid = _weights(params["mid"], without)
    d_inner, n = mid["a"]["mixer"]["a_log"].shape

    def self_pair(i, carry):
        x, last = carry
        bp = pair(params["self"], i)
        x, (_, s) = shell(bp["a"], x, ssm)
        x, _ = shell(bp["b"], x,
                     lambda mp, h: (self_attention(mp, h, cfg, window)[0],
                                    None))
        return x, last.at[i].set(s)
    x, last = jax.lax.fori_loop(
        0, pairs, self_pair,
        (x, jnp.zeros((pairs + 1, d_inner, n), jnp.float32)))
    x, (m, s) = shell(mid["a"], x, ssm)
    last = last.at[pairs].set(s)
    got = {}

    def full(mp, h):
        out, kv, got["o"] = self_attention(mp, h, cfg, None)
        return out, kv
    x, kv = shell(mid["b"], x, full)
    if "memory" in without:
        m = jnp.ones_like(m)
    wrote = kv                # what the full layer wrote, whatever is read
    if "cross_kv" in without:
        kv = tuple(jnp.zeros_like(a) for a in kv)

    def cross_pair(i, carry):
        x, reads = carry
        bp = pair(params["cross"], i)
        x, _ = shell(bp["a"], x, lambda mp, h: ((
            m * jax.nn.silu(h @ mp["w1"]["kernel"])) @ mp["w2"]["kernel"],
            None))
        x, o = shell(bp["b"], x,
                     lambda mp, h: cross_attention(mp, h, kv, cfg))
        return x, reads.at[1 + i].set(o)
    crosses = params["cross"]["a"]["ln1"]["scale"].shape[0]
    reads = jnp.zeros((1 + crosses,) + got["o"].shape).at[0].set(got["o"])
    x, reads = jax.lax.fori_loop(0, crosses, cross_pair, (x, reads))
    return (x, last, jnp.stack([a.reshape(t, -1) for a in wrote]), reads
            ) if states else x


#: rows of the embedding to one product of the head
VOCAB_BLOCK = 32768


def logits(params, ids, cfg, states=False, last=None):
    """``ids [B, T]`` -> logits ``[B, T, V]`` float32 (``last``: of the
    last ``last`` positions only, ``[B, last, V]``), a sequence at a time
    (and, with ``states``, each sequence's last states ``[B, P + 1,
    d_inner, n]`` — after ITS last position, so pad nothing — the full
    layer's keys and values ``[B, 2, T, Hkv x hd]`` and the eight
    attentions' outputs over them ``[B, 1 + Q, T or last, H x hd]``)."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        ln_f = _weights(params["ln_f"], ())
        out, sts, kvs, reads = [], [], [], []
        for row in ids:
            x = hidden(params, row, cfg, states)
            if states:
                x, s, kv, o = x
                sts.append(s)
                kvs.append(kv)
                reads.append(o if last is None else o[:, -last:])
            x = _ln(ln_f, x if last is None else x[-last:], cfg["eps"])
            out.append(jnp.concatenate(
                [x @ _weights(emb[at:at + VOCAB_BLOCK], cfg["without"]).T
                 for at in range(0, emb.shape[0], VOCAB_BLOCK)], axis=-1))
        out = jnp.stack(out)
        return (out, jnp.stack(sts), jnp.stack(kvs), jnp.stack(reads)
                ) if states else out
