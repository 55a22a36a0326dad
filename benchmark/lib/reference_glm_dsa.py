"""The plain reference of GLM-5.2's language model (``glm_moe_dsa``):
forward pass in straightforward float32 ``jax.numpy`` — no kernel, no
cache, expanded-form attention over the full sequence with the learned
sparse selection as a MASK built from ``lax.top_k`` of the dense score
matrix, a plain loop over experts, matmul precision ``highest``.  It
shares no code with ``deepspeed_tpu/``; it reads the same parameter tree.
The norm, the rotary pairing, the SwiGLU and the float8 rounding are
``reference_openpangu_ultra_moe.py``'s, imported, not copied.

Follows the published config (``zai-org/GLM-5.2`` ``config.json``) and,
for the indexer, the published DSA form:

    h = RMSNorm_in(x)
    MLA:     c_q = RMSNorm(h W_qa);  [q_nope | q_rope] = c_q W_qb   per head (192 | 64)
             [c_raw | k_rope_raw] = h W_kva  (512 | 64);  c = RMSNorm(c_raw)
             [k_nope | v] = c W_kvb          per head (192 | 256)
             q_rope, k_rope = RoPE(...), k_rope one head for all
             score = (q_nope.k_nope + q_rope.k_rope) / sqrt(256)
    indexer (a ``full`` layer):  qI = c_q W_Iq  per head (32 x 128)
             kI = LayerNorm(h W_Ik) (128, one key a token); RoPE on the
             first 64 dims of both;  w = h W_Iw / sqrt(32 x 128)
             I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),  s <= t
             S_t = the index_topk largest I[t, .] (all while t < index_topk)
             a ``shared`` layer uses the S_t of the ``full`` layer before it
    a = x + concat_h(softmax(score over S_t) v) W_o
    y = a + F_l(RMSNorm_post(a)),  F_l a dense SwiGLU for l <
             first_k_dense_replace, else Shared(u) + sum_i g_i Expert_i(u)
             with s = sigmoid(u W_r), the 8 chosen the top 8 of s + b,
             g_i = 2.5 s_i / (sum of the chosen s + 1e-20)
    then a final RMSNorm and the untied head.

Departures, noted: (1) rotary dims are paired i with i + 32 (rotate-half)
where the config says ``rope_interleave`` / ``indexer_rope_interleave``
— a column permutation of the seeded ``W_qb`` / ``W_kva`` / ``W_Iq`` /
``W_Ik``; (2) the indexer has no Hadamard rotation (an orthogonal
rotation of qI and kI leaves every dot product as it is) and no FP8; its
LayerNorm has a bias and the config's ``rms_norm_eps``; (3) ``experts_held
= (lo, hi)`` gives the reference the same share of the routed experts as
the chip holds (``model-configs`` guide section 4); (4) scores equal to
a row's ``index_topk``-th largest are all kept (ties); (5) the
multi-token-prediction module is not here.

Attention and the indexer's scores run in blocks of ``cfg["block"]``
query rows (``lax.map``), so that 6 k tokens at the published widths fit
beside a serving engine.  ``leave_out`` may name ``float8`` (every weight
matrix rounded to ``float8_e4m3fn``), ``selection`` (attend to every
earlier token), ``relu``, ``w`` (every index head weighs 1), ``shared``
(a ``shared`` layer selects for itself, with the indexer before it),
``experts`` (the held experts add nothing), ``shared_expert``, ``bias``
(the gate picks by score alone): the builder's proof that the cell's
limits see each of them (``PERF.md``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference_openpangu_ultra_moe import F32, _rmsnorm, _rope, _up, _w, ffn


def _layernorm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)
            + p["bias"].astype(F32))


def _blocks(fn, t, block, *rows):
    """``fn`` over blocks of ``block`` query rows of ``rows [B, T, ..]``
    (padded to whole blocks), back as ``[B, T, ..]``."""
    pad = -t % block
    n = (t + pad) // block

    def split(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((a.shape[0], n, block) + a.shape[2:]),
                            1, 0)
    out = jax.lax.map(lambda xs: fn(xs[0] * block, *xs[1:]),
                      (jnp.arange(n),) + tuple(split(a) for a in rows))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], n * block) + out.shape[3:])[:, :t]


def select(ip, p_attn, h, cfg, leave_out=()):
    """The selection of one ``full`` layer as a mask ``[B, T, T]``."""
    b, t, _ = h.shape
    nh, dh, dr = cfg["index_heads"], cfg["index_head_dim"], \
        cfg["qk_rope_head_dim"]
    eps, k = cfg["eps"], cfg["index_topk"]

    def rotate(x):
        return jnp.concatenate([_rope(x[..., :dr], cfg["rope_theta"]),
                                x[..., dr:]], axis=-1)
    c_q = _rmsnorm(p_attn["q_norm"], h @ _w(p_attn["q_a"], cfg), eps)
    q = rotate((c_q @ _w(ip["wq"], cfg)).reshape(b, t, nh, dh))
    key = rotate(_layernorm(ip["k_norm"], h @ _w(ip["wk"], cfg), eps))
    w = (h @ _w(ip["weights"], cfg)) / (nh * dh) ** 0.5
    if "w" in leave_out:
        w = jnp.ones_like(w)
    at = jnp.arange(t)

    def rows(q0, q, w):                         # [B, bq, J, D], [B, bq, J]
        s = jnp.einsum("bqjd,bkd->bqjk", q, key)
        if "relu" not in leave_out:
            s = jnp.maximum(s, 0.0)
        score = jnp.einsum("bqjk,bqj->bqk", s, w)
        causal = at[None, :] <= (q0 + jnp.arange(q.shape[1]))[:, None]
        score = jnp.where(causal[None], score, -jnp.inf)
        kth = jax.lax.top_k(score, min(k, t))[0][..., -1:]
        return causal[None] & (score >= kth)
    return _blocks(rows, t, cfg["block"], q, w)


def mla(p, h, chosen, cfg):
    """Latent attention over the ``chosen [B, T, T]`` positions."""
    b, t, _ = h.shape
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rkv, eps = cfg["kv_lora_rank"], cfg["eps"]
    c_q = _rmsnorm(p["q_norm"], h @ _w(p["q_a"], cfg), eps)
    q = (c_q @ _w(p["q_b"], cfg)).reshape(b, t, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cfg["rope_theta"])],
                        axis=-1)
    kv = h @ _w(p["kv_a"], cfg)
    c = _rmsnorm(p["kv_norm"], kv[..., :rkv], eps)
    k_rope = _rope(kv[..., rkv:], cfg["rope_theta"])          # [B, T, dr]
    kvb = (c @ _w(p["kv_b"], cfg)).reshape(b, t, nh, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope[:, :, None], (b, t, nh, dr))],
        axis=-1)
    v = kvb[..., dn:]

    def rows(q0, q, chosen):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (dn + dr) ** 0.5
        s = jnp.where(chosen[:, None], s, -jnp.inf)
        # a padded row has chosen nothing: no NaN from its softmax
        s = jnp.where(jnp.any(chosen, axis=-1)[:, None, :, None], s, 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    o = _blocks(rows, t, cfg["block"], q, chosen)
    return o.reshape(b, t, nh * dv) @ _w(p["out"], cfg)


def gate(p, u, cfg, leave_out=()):
    """``(chosen [.., k], weight [.., k])``: sigmoid scores, the top k of
    score + bias, weights renormalised and scaled."""
    score = jax.nn.sigmoid(u @ _w(p["router"], cfg))
    by = score if "bias" in leave_out else score + p["bias"].astype(F32)
    _, chosen = jax.lax.top_k(by, cfg["moe_topk"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, cfg["scale"] * weight / (
        jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)


def routed(p, u, cfg, experts_held=None, expert_at=None, leave_out=()):
    """u [B, T, h] -> the held routed experts' weighted sum (experts lo ..
    hi - 1 are ``p["experts"]`` or ``expert_at(i)``; the others add
    nothing)."""
    lo, hi = experts_held or (0, cfg["n_routed_experts"])
    if expert_at is None:
        def expert_at(i):
            return {name: w[i] for name, w in p["experts"].items()}
    chosen, weight = gate(p, u, cfg, leave_out)

    def add_expert(i, y):      # an expert is chosen at most once a row
        w = expert_at(i)
        mine = jnp.sum(jnp.where(chosen == lo + i, weight, 0.0), axis=-1,
                       keepdims=True)
        out = (jax.nn.silu(u @ _up(w["w_gate"], cfg))
               * (u @ _up(w["w_up"], cfg))) @ _up(w["w_down"], cfg)
        return y + mine * out
    return jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))


def moe(p, u, cfg, experts_held=None, leave_out=(), expert_at=None):
    """The expert layer's ``F_l``: shared expert + held routed experts."""
    if "experts" in leave_out:
        experts_held = (0, 0)
    y = routed(p["moe"], u, cfg, experts_held, expert_at, leave_out)
    return y if "shared_expert" in leave_out else y + ffn(p["shared"], u, cfg)


def logits(params, ids, cfg, experts_held=None, leave_out=(), last=None,
           return_selection=False):
    """[B, T] token ids -> [B, T, V] float32 logits (of the ``last``
    positions only where given); with ``return_selection`` also every
    layer's mask."""
    cfg = dict(cfg, float8="float8" in leave_out)
    kinds = cfg["indexer_types"]
    eps = cfg["eps"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"].astype(F32)[ids]
        b, t = ids.shape
        dense = params.get("dense_blocks")
        leading = (0 if dense is None
                   else jax.tree_util.tree_leaves(dense)[0].shape[0])
        experts = params["blocks"]["moe"]["experts"]
        rest = dict(params["blocks"],
                    moe={k: v for k, v in params["blocks"]["moe"].items()
                         if k != "experts"})
        chosen, full_at, masks = None, -1, []
        for at, kind in enumerate(kinds):
            tree, idx = (dense, at) if at < leading else (rest, at - leading)
            p = jax.tree_util.tree_map(lambda a: a[idx], tree)
            h = _rmsnorm(p["ln_in"], x, eps)
            full_at += kind == "full"
            if "selection" in leave_out:
                chosen = jnp.broadcast_to(
                    jnp.tril(jnp.ones((t, t), bool))[None], (b, t, t))
            elif kind == "full" or "shared" in leave_out:
                ip = jax.tree_util.tree_map(lambda a: a[full_at],
                                            params["indexer"])
                chosen = select(ip, p["attn"], h, cfg, leave_out)
            masks.append(chosen)
            a = x + mla(p["attn"], h, chosen, cfg)
            u = _rmsnorm(p["ln_post"], a, eps)
            if "moe" in p:
                f = moe(p, u, cfg, experts_held, leave_out,
                        lambda i: {n: w[idx, i] for n, w in experts.items()})
            else:
                f = ffn(p["mlp"], u, cfg)
            x = a + f
        if last is not None:
            x = x[:, -last:]
        out = _rmsnorm(params["ln_f"], x, eps) @ _w(params["lm_head"], cfg)
        return (out, jnp.stack(masks)) if return_selection else out


def settings(config: dict) -> dict:
    """The reference's settings from a configuration file's published
    keys (``benchmark/configs/glm-5.2.json``)."""
    return {"heads": config["num_attention_heads"],
            "qk_nope_head_dim": config["qk_nope_head_dim"],
            "qk_rope_head_dim": config["qk_rope_head_dim"],
            "v_head_dim": config["v_head_dim"],
            "kv_lora_rank": config["kv_lora_rank"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_parameters"]["rope_theta"]),
            "index_heads": config["index_n_heads"],
            "index_head_dim": config["index_head_dim"],
            "index_topk": config["index_topk"],
            "indexer_types": tuple(config["indexer_types"]),
            "n_routed_experts": config["published"]["n_routed_experts"]
            if "published" in config else config["n_routed_experts"],
            "moe_topk": config["num_experts_per_tok"],
            "scale": float(config["routed_scaling_factor"]),
            "block": 128}
