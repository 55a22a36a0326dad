"""The plain reference of LongCat-Flash's language model: forward pass in
straightforward float32 ``jax.numpy`` — no kernel, no cache, expanded-form
attention, a plain loop over experts, matmul precision ``highest``.  It
shares no code with ``deepspeed_tpu/``; it reads the same parameter tree.

Follows the published config (``meituan-longcat/LongCat-Flash-Omni``
``config.json``) and the family's description (LongCat-Flash technical
report, 2025: shortcut-connected MoE, zero-computation experts, MLA):

    MLA(x):  c_q = RMSNorm(x W_qa) * sqrt(h / r_q)
             [q_nope | q_rope] = c_q W_qb            per head (128 | 64)
             [c_raw | k_rope_raw] = x W_kva          (512 | 64)
             c = RMSNorm(c_raw) * sqrt(h / r_kv)
             [k_nope | v] = c W_kvb                  per head (128 | 128)
             q_rope, k_rope = RoPE(...), k_rope one head for all
             score = (q_nope.k_nope + q_rope.k_rope) / sqrt(192)
             out = concat_h(softmax(score) v) W_o
    MoE(u):  p = softmax(u W_r) over E + Z; the k chosen are the top k of
             p + b; w_i = s p_i (not renormalised);
             y = sum_{i < E} w_i (silu(u W_g,i) * (u W_u,i)) W_d,i
               + sum_{i >= E} w_i u
    layer:   a = x + MLA_0(N_0 x); u = N_1 a; m = MoE(u)
             b = a + FFN_0(u); c = b + MLA_1(N_2 b)
             y = c + FFN_1(N_3 c) + m
    then a final RMSNorm and the untied head.

Departures, noted: (1) rotary dims are paired i with i + 32 (rotate-half)
where the published code interleaves pairs — a column permutation of the
seeded ``W_qb`` / ``W_kva``; (2) ``experts_held = (lo, hi)`` gives the
reference the same share of the routed experts as the chip holds
(``model-configs`` guide section 4): picks of an absent expert add
nothing, here as in the program; (3) the selection bias ``b`` is a seeded
parameter, not a trained one.

Weights are upcast one matrix at a time, where they are used, so bf16
weights as served cost no float32 copy of more than one matrix; layers
and experts run under ``lax.fori_loop`` with each matrix sliced out of
the stack where it is used (a scan over the stack would first copy a
whole layer's weights out of it, and an unrolled loop takes two minutes
to compile).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _up(w):
    return w.astype(F32)


def _w(p):
    return _up(p["kernel"])


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
    b, t, h = x.shape
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rq, rkv, eps = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["eps"]
    c_q = _rmsnorm(p["q_norm"], x @ _w(p["q_a"]), eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * (h / rq) ** 0.5
    q = (c_q @ _w(p["q_b"])).reshape(b, t, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])
    kv = x @ _w(p["kv_a"])
    c = _rmsnorm(p["kv_norm"], kv[..., :rkv], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * (h / rkv) ** 0.5
    k_rope = _rope(kv[..., rkv:], cfg["rope_theta"])          # [B, T, dr]
    kvb = (c @ _w(p["kv_b"])).reshape(b, t, nh, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)) / (dn + dr) ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, nh * dv) @ _w(p["out"])


def ffn(p, x):
    return (jax.nn.silu(x @ _w(p["fc_gate"])) * (x @ _w(p["fc_in"]))
            ) @ _w(p["fc_out"])


def moe(p, u, cfg, experts_held=None, zero_experts=True, expert_at=None):
    """u [B, T, h].  ``experts_held = (lo, hi)``: routed experts lo ..
    hi - 1 are held (``p["experts"]``, or ``expert_at(i)`` -> the i-th
    held expert's three matrices) and the others add nothing."""
    n, k, s = cfg["n_routed_experts"], cfg["moe_topk"], cfg["scale"]
    lo, hi = experts_held or (0, n)
    if expert_at is None:
        def expert_at(i):
            return {name: w[i] for name, w in p["experts"].items()}
    prob = jax.nn.softmax(u @ _w(p["router"]), axis=-1)
    _, chosen = jax.lax.top_k(prob + p["bias"].astype(F32), k)
    weight = s * jnp.take_along_axis(prob, chosen, axis=-1)  # [B, T, k]

    def add_expert(i, y):      # an expert is chosen at most once a row
        w = expert_at(i)
        mine = jnp.sum(jnp.where(chosen == lo + i, weight, 0.0), axis=-1,
                       keepdims=True)
        out = (jax.nn.silu(u @ _up(w["w_gate"]))
               * (u @ _up(w["w_up"]))) @ _up(w["w_down"])
        return y + mine * out
    y = jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))
    if zero_experts:
        y = y + u * jnp.sum(jnp.where(chosen >= n, weight, 0.0), axis=-1,
                            keepdims=True)
    return y


def block(p, x, cfg, experts_held=None, leave_out=(), expert_at=None):
    """One layer.  ``leave_out`` names parts to drop (``shortcut``,
    ``zero_experts``, ``experts``, ``attn1``): the builder's proof that
    the check sees each of them (``PERF.md``)."""
    eps = cfg["eps"]
    a = x + mla(p["attn0"], _rmsnorm(p["ln0"], x, eps), cfg)
    u = _rmsnorm(p["ln1"], a, eps)
    if "experts" in leave_out:             # the held experts add nothing
        experts_held = (0, 0)
    m = moe(p["moe"], u, cfg, experts_held,
            "zero_experts" not in leave_out, expert_at)
    b = a + ffn(p["mlp0"], u)
    c = b if "attn1" in leave_out else \
        b + mla(p["attn1"], _rmsnorm(p["ln2"], b, eps), cfg)
    y = c + ffn(p["mlp1"], _rmsnorm(p["ln3"], c, eps))
    return y if "shortcut" in leave_out else y + m


def logits(params, ids, cfg, experts_held=None, leave_out=()):
    """[B, T] token ids -> [B, T, V] float32 logits.  ``leave_out`` may
    also name ``q_scale`` / ``kv_scale``: that MLA scale factor is then
    left at 1."""
    cfg = dict(cfg, mla_scale_q_lora=cfg["mla_scale_q_lora"]
               and "q_scale" not in leave_out,
               mla_scale_kv_lora=cfg["mla_scale_kv_lora"]
               and "kv_scale" not in leave_out)
    with jax.default_matmul_precision("highest"):
        x = _up(params["embed"]["embedding"])[ids]

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
        return x @ _w(params["lm_head"])


def settings(config: dict) -> dict:
    """The reference's settings from a configuration file's published
    keys (``benchmark/configs/longcat-flash-omni.json``)."""
    return {"heads": config["num_attention_heads"],
            "qk_nope_head_dim": config["qk_nope_head_dim"],
            "qk_rope_head_dim": config["qk_rope_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "q_lora_rank": config["q_lora_rank"],
            "kv_lora_rank": config["kv_lora_rank"],
            "mla_scale_q_lora": config["mla_scale_q_lora"],
            "mla_scale_kv_lora": config["mla_scale_kv_lora"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "n_routed_experts": config["published"]["n_routed_experts"]
            if "published" in config else config["n_routed_experts"],
            "moe_topk": config["moe_topk"],
            "scale": float(config["routed_scaling_factor"])}
