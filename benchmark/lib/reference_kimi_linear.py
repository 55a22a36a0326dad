"""The plain reference for the ``kimi_linear`` block (Kimi Linear): gated
delta-rule linear-attention layers (KDA) and position-free latent
attention (MLA) in the order the configuration lists, one leading dense
layer and then expert layers — the forward pass in straightforward float32
``jax.numpy`` at matmul precision ``highest``: no cache, no kernel, no
paging, no blocked form; the recurrence as a loop over positions; the
expanded attention under a causal mask; a loop over experts.  It shares no
code with ``deepspeed_tpu/``; it reads the same parameter tree.

Pre-norm, RMSNorm, no bias anywhere, untied head::

    x <- x + mixer_l(RMSNorm(x));   x <- x + F_l(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head

    KDA (H heads, K = V = head_dim; per head):
        [q~, k~, v~] = h W_qkv; each through its own causal depthwise
        convolution (taps w_j weigh row t - j, zero history, no bias), silu
        q = l2norm(q~) / sqrt(K),  k = l2norm(k~),  v = v~     (eps 1e-6)
        g = -exp(A_log[h]) * softplus((h W_fa) W_fb + dt_bias)    [H, K]
        beta = sigmoid(h W_b)                                      [H]
        S' = diag(exp g) S;  S <- S' + beta k (v - S'^T k)^T;  o = S^T q
        out = (RMSNorm_head(o) * sigmoid((h W_ga) W_gb)) W_o
    MLA (one full query projection, NO rotation anywhere):
        q = h W_q  per head (nope | rope lanes);  [c_raw | k_r] = h W_kva
        c = RMSNorm(c_raw);  [k_n | v] = c W_kvb  per head
        score = (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal softmax
    F_l:  SwiGLU(d_ff) in the leading dense layers; after them
        s = sigmoid(u W_r); the k chosen are the top k of s + bias;
        w_i = scale s_i / (sum of the chosen s + 1e-20)
        Shared(u) + sum_{i chosen, held} w_i Expert_i(u)

Departures, noted: (1) ``W_q``, ``W_k``, ``W_v`` of a KDA layer are read as
the ONE matrix ``qkv`` the program stores (columns ``[q | k | v]``) and
their three convolutions as one over its channels; (2) ``experts_held =
(lo, hi)`` gives the reference the same share of the routed experts as the
chip holds (``model-configs`` guide section 4): picks of an absent expert
add nothing, here as in the program, and the shared expert is whole; (3)
``num_expert_group`` 1 / ``topk_group`` 1: the grouped top-k is the plain
one; (4) what the public ``config.json`` does not say (the gates'
parameterisation, the l2norm's eps, every initialisation) is under
``assumed`` in ``benchmark/configs/kimi-linear-48b-a3b.json``.

A layer at a time: every layer is a jitted call of its own on the layer's
index, its matrices sliced out of their stacks and upcast where they are
used (one expert at a time), so that the reference fits beside the served
weights.

``cfg["without"]`` names mechanisms to change, for the controls that show
the cell's comparison would notice (``PERF.md`` section 4); a cell never
sets it: ``delta`` (no delta term: ``S <- S' + beta k v^T``),
``scalar_decay`` (a head's decay the mean of its channels'), ``conv`` (no
convolution: the projections straight into the silu), ``l2norm``,
``out_gate``, ``rotary`` (rotary positions on the rope lanes of MLA),
``renorm`` (the chosen weights not renormalised), ``scale`` (no 2.446),
``shared`` (no shared expert), ``experts`` (the held experts add
nothing), ``state_carry`` (the state reset at every ``cfg["chunk"]``
rows), ``bf16_state`` (the state kept in bfloat16), ``float8`` (every
weight matrix rounded to ``float8_e4m3fn``: the precision below the
configuration's).

:func:`first_state` is the one place that rounds activations: the first
layer's state from inputs rounded where the served model keeps a tensor in
its activations' type, so that what is left between it and the program's
state is the state path's own arithmetic — float32, as the configuration
states it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: columns of the head's matrix to one product; query rows to a block of
#: the attention
VOCAB_BLOCK = 32768
ROW_BLOCK = 512
L2_EPS = 1e-6


def settings(config: dict, without=()) -> dict:
    """The reference's settings from a configuration file's keys."""
    lin = config["linear_attn_config"]
    kda = set(lin["kda_layers"])
    return {"layer_types": tuple(
                "kda" if at in kda else "mla"
                for at in range(1, config["num_hidden_layers"] + 1)),
            "first_k_dense": config["first_k_dense_replace"],
            "heads": config["num_attention_heads"],
            "qk_nope_head_dim": config["qk_nope_head_dim"],
            "qk_rope_head_dim": config["qk_rope_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "kv_lora_rank": config["kv_lora_rank"],
            "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "n_routed_experts": config["published"]["num_experts"]
            if "published" in config else config["num_experts"],
            "moe_topk": config["num_experts_per_token"],
            "scale": float(config["routed_scaling_factor"]),
            "without": tuple(without)}


def _up(w, cfg):
    if "float8" in cfg["without"] and w.ndim >= 2:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _w(p, cfg):
    return _up(p["kernel"], cfg)


def _rms(p, x, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * p["scale"].astype(F32))


def _act(x, cfg):
    """``x`` as a tensor the served model keeps between two operations:
    rounded to ``cfg["activations"]`` where :func:`first_state` set it (an
    explicit rounding: a pair of casts is dropped on the chip under XLA's
    allowance for excess precision), and as it is everywhere else."""
    dtype = cfg.get("activations")
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _l2norm(a):
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)


def kda(p, h, cfg):
    """``h [T, d]`` -> ``(out [T, d], the state after the last position
    [H, K, V])``."""
    t = h.shape[0]
    nh, hd = cfg["kda_heads"], cfg["kda_head_dim"]
    without = cfg["without"]
    qkv = _act(h @ _w(p["qkv"], cfg), cfg)
    if "conv" not in without:
        taps = p["conv_w"].astype(F32)
        n = taps.shape[0]
        padded = jnp.concatenate([jnp.zeros((n - 1, qkv.shape[1])), qkv])
        qkv = sum(taps[j] * padded[n - 1 - j:n - 1 - j + t]
                  for j in range(n))
    q, k, v = jax.nn.silu(qkv).reshape(t, 3, nh, hd).transpose(1, 0, 2, 3)
    if "l2norm" not in without:
        q, k = _l2norm(q), _l2norm(k)
    q = q / hd ** 0.5
    low = _act(h @ _w(p["f_a"], cfg), cfg)
    g = -jnp.exp(p["a_log"].astype(F32))[:, None] * jax.nn.softplus(
        low @ _w(p["f_b"], cfg) + p["dt_bias"].astype(F32)
    ).reshape(t, nh, hd)
    if "scalar_decay" in without:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ _w(p["beta"], cfg))
    reset = cfg.get("chunk") if "state_carry" in without else None

    def row(s, xs):
        qt, kt, vt, gt, bt, i = xs
        if reset:
            s = jnp.where(i % reset == 0, 0.0, s)
        s = jnp.exp(gt)[:, :, None] * s                      # [H, K, V]
        write = vt if "delta" in without else \
            vt - jnp.einsum("hkv,hk->hv", s, kt)
        s = s + (bt[:, None] * kt)[:, :, None] * write[:, None, :]
        if "bf16_state" in without:
            s = jax.lax.reduce_precision(s, exponent_bits=8,
                                         mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, qt)
    s, o = jax.lax.scan(row, jnp.zeros((nh, hd, hd)),
                        (q, k, v, g, beta, jnp.arange(t)))
    o = _rms(p["o_norm"], o, cfg["eps"]).reshape(t, nh * hd)
    if "out_gate" not in without:
        o = o * jax.nn.sigmoid(
            _act(h @ _w(p["g_a"], cfg), cfg) @ _w(p["g_b"], cfg))
    return o @ _w(p["out"], cfg), s


def _rope(x, theta):
    """``x [T, .., D]`` rotated by its position (rotate-half pairing): the
    ``rotary`` control only; the model rotates nothing."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(t, dtype=F32)[:, None] * inv[None]).reshape(
        (t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, h, cfg):
    """``h [T, d]`` -> ``(out [T, d], the latent rows as written ``[c |
    k_r]`` [T, r + rope])``."""
    t = h.shape[0]
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv = cfg["kv_lora_rank"]
    q = (h @ _w(p["q_b"], cfg)).reshape(t, nh, dn + dr)
    kv = h @ _w(p["kv_a"], cfg)
    c = _rms(p["kv_norm"], kv[:, :rkv], cfg["eps"])
    k_r = kv[:, rkv:]
    wrote = jnp.concatenate([c, k_r], axis=-1)
    q_n, q_r = q[..., :dn], q[..., dn:]
    if "rotary" in cfg["without"]:
        q_r, k_r = _rope(q_r, cfg["rope_theta"]), _rope(k_r,
                                                        cfg["rope_theta"])
    kvb = (c @ _w(p["kv_b"], cfg)).reshape(t, nh, dn + dv)
    k_n, v = kvb[..., :dn], kvb[..., dn:]
    pos = jnp.arange(t)
    out = []
    for at in range(0, t, ROW_BLOCK):        # query rows in blocks
        rows = slice(at, at + ROW_BLOCK)
        s = (jnp.einsum("qhd,khd->hqk", q_n[rows], k_n)
             + jnp.einsum("qhd,kd->hqk", q_r[rows], k_r)) / (dn + dr) ** 0.5
        s = jnp.where((pos[None, :] <= pos[rows, None])[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              v))
    o = jnp.concatenate(out)
    return o.reshape(t, nh * dv) @ _w(p["out"], cfg), wrote


def ffn(p, x, cfg):
    return (jax.nn.silu(x @ _w(p["fc_gate"], cfg))
            * (x @ _w(p["fc_in"], cfg))) @ _w(p["fc_out"], cfg)


def gate(p, u, cfg):
    """``(chosen [.., k], weight [.., k])``: sigmoid scores, the top k of
    score + bias, weights renormalised and scaled."""
    score = jax.nn.sigmoid(u @ _w(p["router"], cfg))
    _, chosen = jax.lax.top_k(score + p["bias"].astype(F32),
                              cfg["moe_topk"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if "renorm" not in cfg["without"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight * (1.0 if "scale" in cfg["without"]
                             else cfg["scale"])


def routed(p, u, cfg, experts_held=None, expert_at=None):
    """``u [T, d]`` -> the held routed experts' weighted sum.
    ``experts_held = (lo, hi)``: routed experts lo .. hi - 1 are held
    (``p["experts"]``, or ``expert_at(i)`` -> the i-th held expert's three
    matrices) and the others add nothing."""
    lo, hi = experts_held or (0, cfg["n_routed_experts"])
    if "experts" in cfg["without"]:
        hi = lo
    if expert_at is None:
        def expert_at(i):
            return {name: w[i] for name, w in p["experts"].items()}
    chosen, weight = gate(p, u, cfg)

    def add_expert(i, y):      # an expert is chosen at most once a row
        w = expert_at(i)
        mine = jnp.sum(jnp.where(chosen == lo + i, weight, 0.0), axis=-1,
                       keepdims=True)
        out = (jax.nn.silu(u @ _up(w["w_gate"], cfg))
               * (u @ _up(w["w_up"], cfg))) @ _up(w["w_down"], cfg)
        return y + mine * out
    return jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))


def moe(p, u, cfg, experts_held=None, expert_at=None):
    """The expert layer's ``F_l``: shared expert + held routed experts.
    ``p`` holds ``moe`` (router, bias, experts) and ``shared``."""
    y = routed(p["moe"], u, cfg, experts_held, expert_at)
    return y if "shared" in cfg["without"] else y + ffn(p["shared"], u, cfg)


@functools.lru_cache(maxsize=None)
def _layer_fn(mixer: str, ffn_kind: str, frozen_cfg: tuple, held):
    """One layer as a jitted function of ``(the model's stacks, x [T, d],
    the layer's index in its mixer's stack and in its FFN's)`` ->
    ``(x, what the mixer left: a state or the latent rows)``."""
    cfg = dict(frozen_cfg)

    def layer(params, x, at_mixer, at_ffn):
        with jax.default_matmul_precision("highest"):
            def sliced(tree, i):
                return jax.tree_util.tree_map(lambda a: a[i], tree)
            mp = sliced(params[mixer], at_mixer)
            hn = _act(_rms(mp["ln1"], x, cfg["eps"]), cfg)
            out, left = (kda(mp["mixer"], hn, cfg) if mixer == "kda"
                         else mla(mp["attn"], hn, cfg))
            x = x + out
            stack = params[ffn_kind]
            if ffn_kind == "dense":
                fp = sliced(stack, at_ffn)
                return x + ffn(fp["mlp"], _rms(fp["ln2"], x, cfg["eps"]),
                               cfg), left
            experts = stack["moe"]["experts"]
            fp = sliced(dict(stack, moe={k: v for k, v in stack["moe"].items()
                                         if k != "experts"}), at_ffn)
            f = moe(fp, _rms(fp["ln2"], x, cfg["eps"]), cfg, held,
                    lambda i: {n: w[at_ffn, i] for n, w in experts.items()})
            return x + f, left
    return jax.jit(layer)


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted(cfg.items(), key=lambda kv: kv[0]))


def hidden(params, ids, cfg, experts_held=None):
    """``ids [T]`` -> ``(the stack's output before the final norm [T, d],
    every KDA layer's last state [kda layers, H, K, V], the MLA layers'
    latent rows as written [mla layers, T, r + rope])``."""
    x = params["embed"]["embedding"][ids].astype(F32)
    held = tuple(experts_held) if experts_held else None
    at = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    states, latents = [], []
    for l, mixer in enumerate(cfg["layer_types"]):
        ffn_kind = "dense" if l < cfg["first_k_dense"] else "moe"
        x, left = _layer_fn(mixer, ffn_kind, _frozen(cfg), held)(
            params, x, at[mixer], at[ffn_kind])
        (states if mixer == "kda" else latents).append(left)
        at[mixer] += 1
        at[ffn_kind] += 1
    return x, jnp.stack(states), jnp.stack(latents)


def first_state(params, ids, cfg, activations):
    """``ids [T]`` -> the FIRST layer's state after the last position ``[H,
    K, V]`` (a KDA layer), from inputs rounded where the served model keeps
    a tensor in ``activations`` between two operations: the embedding, the
    norm's output, the outputs of ``W_qkv`` and of the gates' inner
    projections.  What follows them is float32 here as in the
    configuration: the convolution, the norms of ``q`` and ``k``, the
    decay (its product accumulates in float32 and is never rounded),
    ``beta``, the recurrence."""
    if cfg["layer_types"][0] != "kda":
        raise ValueError("the first layer is not a KDA layer")
    with jax.default_matmul_precision("highest"):
        cfg = dict(cfg, activations=activations)
        bp = jax.tree_util.tree_map(lambda a: a[0], params["kda"])
        x = _act(params["embed"]["embedding"][ids].astype(F32), cfg)
        return kda(bp["mixer"], _act(_rms(bp["ln1"], x, cfg["eps"]), cfg),
                   cfg)[1]


def logits(params, ids, cfg, experts_held=None, states=False, last=None):
    """``ids [B, T]`` -> logits ``[B, T, V]`` float32 (``last``: of the
    last ``last`` positions only), a sequence at a time (and, with
    ``states``, each sequence's KDA states after ITS last position ``[B,
    kda layers, H, K, V]`` — so pad nothing — and the MLA layers' latent
    rows ``[B, mla layers, T, r + rope]``)."""
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"]["kernel"]
        out, sts, lats = [], [], []
        for row in ids:
            x, st, lat = hidden(params, row, cfg, experts_held)
            sts.append(st)
            lats.append(lat)
            x = _rms(params["ln_f"], x if last is None else x[-last:],
                     cfg["eps"])
            out.append(jnp.concatenate(
                [x @ _up(head[:, at:at + VOCAB_BLOCK], cfg)
                 for at in range(0, head.shape[1], VOCAB_BLOCK)], axis=-1))
        out = jnp.stack(out)
        return (out, jnp.stack(sts), jnp.stack(lats)) if states else out
