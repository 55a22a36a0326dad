"""The plain reference of openPangu-Ultra-MoE's language model: forward
pass in straightforward float32 ``jax.numpy`` — no kernel, no cache,
expanded-form attention over the full sequence, a plain loop over experts,
matmul precision ``highest``.  It shares no code with ``deepspeed_tpu/``;
it reads the same parameter tree.

Follows the published config (``FreedomIntelligence/openPangu-Ultra-MoE-
718B`` ``config.json``: ``sandwich_norm``, ``first_k_dense_replace``,
``n_shared_experts``, ``norm_topk_prob``, ``routed_scaling_factor``):

    MLA(x):  c_q = RMSNorm(x W_qa);  [q_nope | q_rope] = c_q W_qb   per head (128 | 64)
             [c_raw | k_rope_raw] = x W_kva  (512 | 64);  c = RMSNorm(c_raw)
             [k_nope | v] = c W_kvb          per head (128 | 128)
             q_rope, k_rope = RoPE(...), k_rope one head for all
             score = (q_nope.k_nope + q_rope.k_rope) / sqrt(192)
             out = concat_h(softmax(score) v) W_o
    MoE(u):  s = sigmoid(u W_r); the k chosen are the top k of s;
             w_i = scale s_i / (sum of the chosen s + 1e-20)
             y = Shared(u) + sum_i w_i Expert_i(u),   Shared, Expert_i SwiGLU
    layer l: a = x + N_post_attn(MLA(N_in x))
             y = a + N_post_mlp(F_l(N_pre_mlp a)),  F_l a dense SwiGLU for
             l < first_k_dense_replace and MoE after
    then a final RMSNorm and the untied head.

Departures, noted: (1) rotary dims are paired i with i + 32 (rotate-half)
— a column permutation of the seeded ``W_qb`` / ``W_kva``; (2)
``experts_held = (lo, hi)`` gives the reference the same share of the
routed experts as the chip holds (``model-configs`` guide section 4):
picks of an absent expert add nothing, here as in the program, and the
shared expert is whole; (3) the multi-token-prediction module
(``num_nextn_predict_layers``) is no part of the published forward pass
of the 61 layers and is not here.

Weights are upcast one matrix at a time, where they are used, so bf16
weights as served cost no float32 copy of more than one matrix; layers
and experts run under ``lax.fori_loop`` with each matrix sliced out of
its stack where it is used.  ``leave_out`` may name ``float8``: every
weight matrix is then rounded to ``float8_e4m3fn`` before it is upcast —
the precision below the configuration's, which the cell's limits must
refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _up(w, cfg):
    if cfg.get("float8") and w.ndim >= 2:
        w = w.astype(jnp.float8_e4m3fn)
    return w.astype(F32)


def _w(p, cfg):
    return _up(p["kernel"], cfg)


def _rmsnorm(p, x, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * p["scale"].astype(F32))


def _rope(x, theta):
    """x [B, T, ..., D] at positions 0 .. T - 1, pairing dim i with
    i + D / 2."""
    t, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, cfg):
    b, t, _ = x.shape
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["eps"]
    c_q = _rmsnorm(p["q_norm"], x @ _w(p["q_a"], cfg), eps)
    q = (c_q @ _w(p["q_b"], cfg)).reshape(b, t, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])
    kv = x @ _w(p["kv_a"], cfg)
    c = _rmsnorm(p["kv_norm"], kv[..., :rkv], eps)
    k_rope = _rope(kv[..., rkv:], cfg["rope_theta"])          # [B, T, dr]
    kvb = (c @ _w(p["kv_b"], cfg)).reshape(b, t, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) / (dn + dr) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, nh * dv) @ _w(p["out"], cfg)


def ffn(p, x, cfg):
    return (jax.nn.silu(x @ _w(p["fc_gate"], cfg))
            * (x @ _w(p["fc_in"], cfg))) @ _w(p["fc_out"], cfg)


def gate(p, u, cfg, renormalize=True):
    """``(chosen [.., k], weight [.., k])`` of the sigmoid router."""
    score = jax.nn.sigmoid(u @ _w(p["router"], cfg))
    _, chosen = jax.lax.top_k(score, cfg["moe_topk"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if renormalize:
        weight = cfg["scale"] * weight / (
            jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return chosen, weight


def routed(p, u, cfg, experts_held=None, expert_at=None, renormalize=True):
    """u [B, T, h] -> the held routed experts' weighted sum.
    ``experts_held = (lo, hi)``: routed experts lo .. hi - 1 are held
    (``p["experts"]``, or ``expert_at(i)`` -> the i-th held expert's
    three matrices) and the others add nothing."""
    lo, hi = experts_held or (0, cfg["n_routed_experts"])
    if expert_at is None:
        def expert_at(i):
            return {name: w[i] for name, w in p["experts"].items()}
    chosen, weight = gate(p, u, cfg, renormalize)

    def add_expert(i, y):      # an expert is chosen at most once a row
        w = expert_at(i)
        mine = jnp.sum(jnp.where(chosen == lo + i, weight, 0.0), axis=-1,
                       keepdims=True)
        out = (jax.nn.silu(u @ _up(w["w_gate"], cfg))
               * (u @ _up(w["w_up"], cfg))) @ _up(w["w_down"], cfg)
        return y + mine * out
    return jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))


def moe(p, u, cfg, experts_held=None, leave_out=(), expert_at=None):
    """The expert layer's ``F_l``: shared expert + held routed experts.
    ``p`` holds ``moe`` (router, experts) and ``shared``."""
    if "experts" in leave_out:             # the held experts add nothing
        experts_held = (0, 0)
    y = routed(p["moe"], u, cfg, experts_held, expert_at,
               "renorm" not in leave_out)
    return y if "shared" in leave_out else y + ffn(p["shared"], u, cfg)


def block(p, x, cfg, experts_held=None, leave_out=(), expert_at=None):
    """One layer, dense (``p`` holds ``mlp``) or expert (``moe`` and
    ``shared``).  ``leave_out`` names parts to drop (``post_norms``: the
    two output norms of the sandwich; ``shared``; ``dense_ffn``;
    ``renorm``: the renormalisation and the scaling factor; ``experts``):
    the builder's proof that the check sees each of them (``PERF.md``)."""
    eps = cfg["eps"]

    def post(name, y):
        return y if "post_norms" in leave_out else _rmsnorm(p[name], y, eps)
    a = x + post("ln_post_attn",
                 mla(p["attn"], _rmsnorm(p["ln_in"], x, eps), cfg))
    u = _rmsnorm(p["ln_pre_mlp"], a, eps)
    if "moe" in p:
        f = moe(p, u, cfg, experts_held, leave_out, expert_at)
    elif "dense_ffn" in leave_out:
        return a
    else:
        f = ffn(p["mlp"], u, cfg)
    return a + post("ln_post_mlp", f)


def logits(params, ids, cfg, experts_held=None, leave_out=()):
    """[B, T] token ids -> [B, T, V] float32 logits."""
    cfg = dict(cfg, float8="float8" in leave_out)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[ids]

        dense = params.get("dense_blocks")
        if dense is not None:
            def dense_layer(at, x):
                p = jax.tree_util.tree_map(lambda a: a[at], dense)
                return block(p, x, cfg, leave_out=leave_out)
            x = jax.lax.fori_loop(
                0, jax.tree_util.tree_leaves(dense)[0].shape[0],
                dense_layer, x)

        blocks = params["blocks"]
        experts = blocks["moe"]["experts"]
        rest = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                                 if k != "experts"})

        def layer(at, x):
            # one layer's matrices, and one expert's, sliced where used
            p = jax.tree_util.tree_map(lambda a: a[at], rest)
            return block(p, x, cfg, experts_held, leave_out,
                         lambda i: {n: w[at, i] for n, w in experts.items()})
        x = jax.lax.fori_loop(0, experts["w_up"].shape[0], layer, x)
        x = _rmsnorm(params["ln_f"], x, cfg["eps"])
        return x @ _w(params["lm_head"], cfg)


def settings(config: dict) -> dict:
    """The reference's settings from a configuration file's published
    keys (``benchmark/configs/openpangu-ultra-moe.json``)."""
    return {"heads": config["num_attention_heads"],
            "qk_nope_head_dim": config["qk_nope_head_dim"],
            "qk_rope_head_dim": config["qk_rope_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "kv_lora_rank": config["kv_lora_rank"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "n_routed_experts": config["published"]["n_routed_experts"]
            if "published" in config else config["n_routed_experts"],
            "moe_topk": config["num_experts_per_tok"],
            "scale": float(config["routed_scaling_factor"])}
