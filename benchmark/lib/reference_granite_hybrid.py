"""The plain reference for the dense ``granitemoehybrid`` block (Granite
4.0-H): Mamba-2 layers and position-free grouped-query attention layers in
the order ``layer_types`` lists — the forward pass in straightforward
float32 ``jax.numpy`` at matmul precision ``highest``: no cache, no
kernel, no paging, no blocked form; the recurrence as a loop over
positions; causality as a mask.  It shares no code with
``deepspeed_tpu/models``; it reads the same parameter tree.

With ``d`` the hidden size, ``e`` / ``r`` / ``s`` the embedding, residual
and logits multipliers and ``m`` the attention multiplier::

    x_0 = e * E[ids]
    x <- x + r * mixer_l(RMSNorm(x))
    x <- x + r * W_down(silu(a) * b),   [a, b] = RMSNorm'(x) W_gate_up
    logits = RMSNorm_f(x) E^T / s

    mamba:      [z, xBC] = h W_in;  dt = h W_dt  (W_in's last H columns)
                c_t = silu(sum_{j<4} w_j * xBC_{t-j} + b)   (zero history)
                [x_t, B_t, C_t] = c_t   (x_t as H heads of P; B_t, C_t [N])
                D_t = softplus(dt_t + dt_bias);  A = -exp(A_log)   ([H])
                S_t^h = exp(D_t^h A^h) S_{t-1}^h + D_t^h x_t^h (x) B_t
                y_t^h = S_t^h C_t + D_skip^h x_t^h
                out = RMSNorm_g(y_t * silu(z_t)) W_o
    attention:  [q, k, v] = h W_qkv;  softmax(m q k^T) over s <= t, query
                head j reading key-value head j // (H_q / H_kv); NO
                positional encoding;  out = o W_o

ASSUMED (the catalog's ``config`` has no key for them; listed under
``assumed`` in ``benchmark/configs/granite-4.0-h-micro.json``): the seeded
init (``A_log`` the log of uniform [1, 16] a head, ``D_skip`` 1,
``dt_bias`` the inverse softplus of steps log-uniform in [1e-3, 1e-1], the
convolution's taps uniform in +-1/2, the attention's ``W_q`` and ``W_k``
normal at the std that gives a logit a standard deviation of 2.5, every
other matrix normal std 0.02, norms ones); the gate BEFORE the gated norm
and one norm group over all of ``d_inner`` (the family's own code at
``mamba_n_groups`` 1); a bias on the convolution (``mamba_conv_bias``) and
none on any projection;
``mamba_chunk_size`` is the block of a blocked form and changes no result
in exact arithmetic, so nothing here reads it; the state is float32.

``cfg["without"]`` names ONE mechanism to change, for the controls that
show the cell's comparison would notice (``PERF.md`` section 4): a cell
never sets it.  ``state_carry`` (the state reset at every ``cfg["chunk"]``
rows: a chunk boundary), ``decay`` (``A`` = 0), ``d_skip`` (dropped),
``gate_order`` (the gate applied AFTER the norm), ``attn_scale`` (``1 /
sqrt(head_dim)``), ``rotary`` (rotary positions in the attention layers),
``residual_multiplier`` (1), ``logits_scaling`` (1), ``bf16_state`` (the
state kept in bfloat16).

:func:`first_state` is the one place that rounds anything: the first
layer's state from inputs rounded to the type the configuration serves
its activations in, so that what is left between it and the program's
state is the state path's own arithmetic — which the configuration
states as float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows to a block of the attention
ROW_BLOCK = 512
#: rows of the embedding to one product of the head
VOCAB_BLOCK = 32768


def settings(config: dict, without=()) -> dict:
    """The reference's settings from a configuration file's keys."""
    return {"layer_types": tuple(config["layer_types"]),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "eps": config["rms_norm_eps"],
            "ssm_heads": config["mamba_n_heads"],
            "ssm_head_dim": config["mamba_d_head"],
            "state": config["mamba_d_state"],
            "attention_multiplier": config["attention_multiplier"],
            "embedding_multiplier": config["embedding_multiplier"],
            "residual_multiplier": config["residual_multiplier"],
            "logits_scaling": config["logits_scaling"],
            "rope_theta": config["rope_theta"],
            "without": tuple(without)}


def _rms(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _act(x, cfg):
    """``x`` as a tensor the served model keeps between two operations:
    rounded to ``cfg["activations"]`` where :func:`first_state` set it
    (an explicit rounding: a pair of casts is dropped on the chip under
    XLA's allowance for excess precision), and as it is everywhere else."""
    dtype = cfg.get("activations")
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def mamba(p, h, cfg):
    """``h [T, d]`` -> ``(out [T, d], the state after the last position
    [H, P, N])``."""
    t = h.shape[0]
    nh, hp, n = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["state"]
    di = nh * hp
    without = cfg["without"]
    zx = _act(h @ p["in_proj"]["kernel"], cfg)
    z, xbc = zx[:, :di], zx[:, di:]
    dt = jax.nn.softplus(h @ p["dt_proj"]["kernel"] + p["dt_bias"])
    k = p["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    c = jax.nn.silu(sum(p["conv_w"][j] * padded[k - 1 - j:k - 1 - j + t]
                        for j in range(k)) + p["conv_b"])
    x = c[:, :di].reshape(t, nh, hp)
    bm, cm = c[:, di:di + n], c[:, di + n:]
    a = -jnp.exp(p["a_log"])
    if "decay" in without:
        a = jnp.zeros_like(a)
    d_skip = 0.0 if "d_skip" in without else p["d_skip"][:, None]
    reset = cfg.get("chunk") if "state_carry" in without else None

    def row(s, xs):
        xt, dtt, bt, ct, i = xs
        if reset:
            s = jnp.where(i % reset == 0, 0.0, s)
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        if "bf16_state" in without:
            # (an explicit rounding: a pair of casts is dropped on the chip
            # under XLA's allowance for excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, s @ ct + d_skip * xt

    s, y = jax.lax.scan(row, jnp.zeros((nh, hp, n)),
                        (x, dt, bm, cm, jnp.arange(t)))
    y = y.reshape(t, di)
    if "gate_order" in without:
        g = _rms(p["norm"], y, cfg["eps"]) * jax.nn.silu(z)
    else:
        g = _rms(p["norm"], y * jax.nn.silu(z), cfg["eps"])
    return g @ p["out_proj"]["kernel"], s


def _rotary(x, theta):
    """``x [T, H, hd]`` rotated by its position (rotate-half pairing): the
    ``rotary`` control only; the model has no positions."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2) / hd)
    ang = jnp.arange(t)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, h, cfg):
    """``h [T, d]`` -> ``(out [T, d], (k, v) [T, Hkv x hd] as written)``:
    every query head its own copy of its key-value head, causal under a
    mask, in blocks of query rows."""
    t = h.shape[0]
    nh, nkv = cfg["heads"], cfg["kv_heads"]
    hd = p["out"]["kernel"].shape[0] // nh
    without = cfg["without"]
    q, k, v = jnp.split(h @ p["qkv"]["kernel"],
                        [nh * hd, (nh + nkv) * hd], axis=-1)
    q, k, v = (q.reshape(t, nh, hd), k.reshape(t, nkv, hd),
               v.reshape(t, nkv, hd))
    wrote = (k.reshape(t, -1), v.reshape(t, -1))
    if "rotary" in without:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    scale = (1.0 / jnp.sqrt(float(hd)) if "attn_scale" in without
             else cfg["attention_multiplier"])
    kk, vv = jnp.repeat(k, nh // nkv, axis=1), jnp.repeat(v, nh // nkv, 1)
    pos = jnp.arange(t)
    out = []
    for at in range(0, t, ROW_BLOCK):
        s = jnp.einsum("qhd,khd->hqk", q[at:at + ROW_BLOCK], kk) * scale
        seen = pos[None, :] <= pos[at:at + ROW_BLOCK, None]
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              vv))
    o = jnp.concatenate(out).reshape(t, nh * hd)
    return o @ p["out"]["kernel"], wrote


def hidden(params, ids, cfg):
    """``ids [T]`` -> ``(the stack's output before the final norm [T, d],
    every mamba layer's last state [M, H, P, N], the attention layers'
    keys and values as written [A, 2, T, Hkv x hd])``.  A layer at a time:
    a ``fori_loop`` over each run of mamba layers, so that one layer's
    float32 weights exist at a time."""
    eps = cfg["eps"]
    r = 1.0 if "residual_multiplier" in cfg["without"] \
        else cfg["residual_multiplier"]
    x = cfg["embedding_multiplier"] * params["embed"]["embedding"][ids] \
        .astype(jnp.float32)
    t = x.shape[0]
    kinds = cfg["layer_types"]
    n_m = kinds.count("mamba")
    states = jnp.zeros((n_m, cfg["ssm_heads"], cfg["ssm_head_dim"],
                        cfg["state"]), jnp.float32)
    kvs = []

    def layer(kind, i):
        return _f32(jax.tree_util.tree_map(lambda a: a[i], params[kind]))

    def shell(bp, x, mixer):
        out, aux = mixer(bp["mixer"], _rms(bp["ln1"], x, eps))
        x = x + r * out
        a, b = jnp.split(_rms(bp["ln2"], x, eps)
                         @ bp["mlp"]["gate_up"]["kernel"], 2, axis=-1)
        return x + r * ((jax.nn.silu(a) * b) @ bp["mlp"]["down"]["kernel"]), \
            aux

    def mamba_layer(i, carry):
        x, states = carry
        x, s = shell(layer("mamba", i), x, lambda p, h: mamba(p, h, cfg))
        return x, states.at[i].set(s)

    at, l = {"mamba": 0, "attention": 0}, 0
    while l < len(kinds):
        kind, run = kinds[l], 1
        while l + run < len(kinds) and kinds[l + run] == kind:
            run += 1
        if kind == "mamba":
            x, states = jax.lax.fori_loop(at[kind], at[kind] + run,
                                          mamba_layer, (x, states))
        else:
            for i in range(at[kind], at[kind] + run):
                x, kv = shell(layer(kind, i), x,
                              lambda p, h: attention(p, h, cfg))
                kvs.append(jnp.stack(kv))
        at[kind] += run
        l += run
    return x, states, jnp.stack(kvs).reshape(len(kvs), 2, t, -1)


def first_state(params, ids, cfg, activations):
    """``ids [T]`` -> the FIRST layer's state after the last position ``[H,
    P, N]`` (a ``mamba`` layer), from inputs rounded where the served
    model keeps a tensor in ``activations`` between two operations: the
    scaled embedding, the norm's output, the in-projection's output.  What
    follows them is float32 here as in the configuration: the step (its
    product accumulates in float32 and is never rounded), the
    convolution, the recurrence.  The program's state differs from this
    one by what the state path itself does, not by what it was fed."""
    with jax.default_matmul_precision("highest"):
        cfg = dict(cfg, activations=activations)
        bp = _f32(jax.tree_util.tree_map(lambda a: a[0], params["mamba"]))
        x = _act(cfg["embedding_multiplier"]
                 * params["embed"]["embedding"][ids].astype(jnp.float32), cfg)
        return mamba(bp["mixer"], _act(_rms(bp["ln1"], x, cfg["eps"]), cfg),
                     cfg)[1]


def logits(params, ids, cfg, states=False, last=None):
    """``ids [B, T]`` -> logits ``[B, T, V]`` float32 (``last``: of the
    last ``last`` positions only), a sequence at a time (and, with
    ``states``, each sequence's mamba states after ITS last position ``[B,
    M, H, P, N]`` — so pad nothing — and the attention layers' keys and
    values ``[B, A, 2, T, Hkv x hd]``)."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        ln_f = _f32(params["ln_f"])
        s = 1.0 if "logits_scaling" in cfg["without"] \
            else cfg["logits_scaling"]
        out, sts, kvs = [], [], []
        for row in ids:
            x, st, kv = hidden(params, row, cfg)
            sts.append(st)
            kvs.append(kv)
            x = _rms(ln_f, x if last is None else x[-last:], cfg["eps"])
            out.append(jnp.concatenate(
                [x @ emb[at:at + VOCAB_BLOCK].astype(jnp.float32).T
                 for at in range(0, emb.shape[0], VOCAB_BLOCK)],
                axis=-1) / s)
        out = jnp.stack(out)
        return (out, jnp.stack(sts), jnp.stack(kvs)) if states else out
