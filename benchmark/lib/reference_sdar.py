"""The plain reference for the ``sdar_moe`` block (SDAR-30B-A3B-Chat) and
its generation by diffusion over blocks — straightforward float32
``jax.numpy`` at matmul precision ``highest``: no kernel, no cache, no
paging, no batching; visibility as a dense ``[T, T]`` mask; the whole
prefix recomputed in every forward.  It shares no code with
``deepspeed_tpu/models``; it reads the same parameter tree, a layer at a
time (a float32 copy of every layer at once would not fit beside the
served weights).

Pre-norm block, RMSNorm eps ``rms_norm_eps``, no bias anywhere::

    h = N1(x);  q = h Wq (H heads of D);  k = h Wk, v = h Wv (G heads of D)
    q <- rmsnorm(q; w_qn), k <- rmsnorm(k; w_kn)   over a head's D channels
    q, k <- rotary, rotate_half pairing, theta ``rope_theta``, all D
    x <- x + softmax(q k^T / sqrt(D), visible) v Wo   head h reads kv h // (H/G)
    u = N2(x);  p = softmax(u Wg) over all E (float32);  the top k of p,
    weights p_e / sum of the chosen p;  x <- x + sum_e w_e Expert_e(u)
    logits = N_f(x) W_head   float32; row i's logits predict the token AT i

**Visibility by blocks** (block length ``B``): row ``i`` sees key ``j`` iff
``j // B <= i // B``.  On a share (``held = (lo, hi)``) picks of experts
outside it add nothing.

**Generation** (:func:`generate`; the family's published
``block_diffusion_generate`` as recalled, ``benchmark/configs/
sdar-30b-a3b-chat.json`` lists what is assumed): positions ``ceil((P + G)
/ B) * B``, the prompt then the mask token ``M``.  For each block from
``P // B`` on, left to right, for ``s = 0 .. T``: if the block holds no
``M``, it is run once more and ITS k / v are the ones kept (the commit);
else every row draws ``x0`` (greedy: the argmax, ``M`` itself excluded)
with confidence ``c = softmax(logits)[x0]`` and, among the rows that still
hold ``M``, ``n_s`` are filled (``get_num_transfer_tokens(B, T)``: ``B //
T``, the first ``B mod T`` steps one more): ``low_confidence_static`` the
``n_s`` of highest ``c`` (ties: the leftmost), ``low_confidence_dynamic``
every row with ``c > threshold`` if there are at least ``n_s`` of them
else the ``n_s`` of highest ``c``, ``sequential`` the ``n_s`` leftmost.  A
filled row never changes again.

``cfg["without"]`` names ONE mechanism to change, for the controls that
show a cell's comparison would notice (``PERF.md`` section 4): a cell
never sets it.  ``causal`` (a row sees only keys at or before itself),
``qk_norm`` (no norm on q and k), ``rotary`` (the interleaved pairing),
``renorm`` (the picks' weights not renormalised), ``shift`` (row ``i``'s
logits fill row ``i + 1``: the next-token convention), ``commit`` (a
block's kept k / v are those of its LAST DENOISE forward, computed while
rows still held ``M``), ``float8`` (every matrix rounded to e4m3),
``bf16_softmax`` (scores and probabilities rounded to bfloat16).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

RULES = ("low_confidence_static", "low_confidence_dynamic", "sequential")
CONTROLS = ("causal", "qk_norm", "rotary", "renorm", "shift", "commit",
            "float8", "bf16_softmax")
#: query rows to a block of the attention, rows to a block of an expert
ROW_BLOCK = 512


def settings(config: dict, without=()) -> dict:
    """The reference's settings from a configuration file's keys."""
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "experts": config["published"]["num_experts"],
            "topk": config["num_experts_per_tok"],
            "renorm": bool(config["norm_topk_prob"]),
            "block_length": int(config["generation"]["block_length"]),
            "mask_token_id": int(config["generation"]["mask_token_id"]),
            "without": tuple(without)}


def num_transfer_tokens(block_length: int, steps: int) -> list:
    """Rows filled at each denoise step: ``B // T``, the first ``B mod T``
    one more."""
    base, more = divmod(block_length, steps)
    return [base + (s < more) for s in range(steps)]


def _rms(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _weights(tree, cfg):
    """A layer's weights in float32 (``float8``: matrices through e4m3)."""
    def one(a):
        a32 = a.astype(jnp.float32)
        if "float8" in cfg["without"] and a.ndim >= 2:
            a32 = a32.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return a32
    return jax.tree_util.tree_map(one, tree)


def _rotary(x, positions, theta, interleaved):
    """x [T, heads, D] rotated over all D channels at ``positions``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def visibility(t: int, block_length: int, cfg) -> jax.Array:
    """[t, t] bool: what row i sees."""
    at = jnp.arange(t)
    if "causal" in cfg["without"]:
        return at[None, :] <= at[:, None]
    at = at // block_length
    return at[None, :] <= at[:, None]


def attention(p, h, cfg):
    """``h [T, d]`` (normed) -> ``(out [T, d], k [T, G * D] as attended,
    v [T, G * D])``."""
    t = h.shape[0]
    nh, g, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qkv = h @ p["qkv"]["kernel"]
    q, k, v = (a.reshape(t, -1, d) for a in jnp.split(
        qkv, [nh * d, (nh + g) * d], axis=-1))
    if "qk_norm" not in cfg["without"]:
        q = _rms(p["q_norm"]["scale"], q, cfg["eps"])
        k = _rms(p["k_norm"]["scale"], k, cfg["eps"])
    pos = jnp.arange(t)
    interleaved = "rotary" in cfg["without"]
    q = _rotary(q, pos, cfg["rope_theta"], interleaved)
    k = _rotary(k, pos, cfg["rope_theta"], interleaved)
    seen = visibility(t, cfg["block_length"], cfg)
    kk = jnp.repeat(k, nh // g, axis=1)
    vv = jnp.repeat(v, nh // g, axis=1)
    low = "bf16_softmax" in cfg["without"]
    outs = []
    for at in range(0, t, ROW_BLOCK):           # in row blocks: it must fit
        s = jnp.einsum("qhd,khd->hqk", q[at:at + ROW_BLOCK], kk) \
            / math.sqrt(d)
        if low:
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        s = jnp.where(seen[at:at + ROW_BLOCK][None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if low:
            pr = pr.astype(jnp.bfloat16).astype(jnp.float32)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, vv))
    o = jnp.concatenate(outs).reshape(t, nh * d)
    return o @ p["out"]["kernel"], k.reshape(t, g * d), v.reshape(t, g * d)


def routing(p, u, cfg):
    """``u [T, d]`` -> ``(picks [T, k], weights [T, k])``."""
    pr = jax.nn.softmax(u @ p["router"]["kernel"], axis=-1)
    w, idx = jax.lax.top_k(pr, cfg["topk"])
    if cfg["renorm"] and "renorm" not in cfg["without"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def routed(p, u, cfg, held, expert_at=None):
    """The held experts' part of the expert layer: a loop over experts,
    each over every row, weighted by the row's pick of it (0 where it has
    none).  ``expert_at(i)`` gives held expert ``i``'s three matrices
    (default: ``p["experts"]`` stacked on a leading axis)."""
    lo, hi = held
    idx, w = routing(p, u, cfg)
    if expert_at is None:
        def expert_at(i):
            return {n: a[i] for n, a in p["experts"].items()}
    y = jnp.zeros_like(u)
    for e in range(lo, hi):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1, keepdims=True)
        ex = expert_at(e - lo)
        y = y + we * ((jax.nn.silu(u @ ex["w_gate"]) * (u @ ex["w_up"]))
                      @ ex["w_down"])
    return y


def layer(p, x, cfg, held):
    """One layer over ``x [T, d]``: ``(x, k, v)``."""
    p = _weights(p, cfg)
    a, k, v = attention(p["attn"], _rms(p["ln1"]["scale"], x, cfg["eps"]),
                        cfg)
    x = x + a
    x = x + routed(p["moe"], _rms(p["ln2"]["scale"], x, cfg["eps"]), cfg,
                   held)
    return x, k, v


_LAYER = {}


def _layer_fn(cfg, held):
    """:func:`layer`, jitted once a setting (a layer at a time: one
    layer's float32 copy is what fits beside the served weights)."""
    key = (tuple(sorted((k, v) for k, v in cfg.items())), tuple(held))
    if key not in _LAYER:
        def fn(p, x):
            with jax.default_matmul_precision("highest"):
                return layer(p, x, cfg, held)
        _LAYER[key] = jax.jit(fn)
    return _LAYER[key]


_TAKE = jax.jit(lambda blocks, at: jax.tree_util.tree_map(
    lambda a: a[at], blocks))


def forward(params, ids, cfg, held, rows=None, kv=False):
    """``ids [T]`` -> float32 logits of ``rows`` (every row by default)
    ``[len(rows), V]``; with ``kv`` also every layer's keys as attended
    (normed, rotated) and values, ``[L, T, G * D]`` each."""
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"]["embedding"][ids].astype(jnp.float32)
    if "float8" in cfg["without"]:
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    fn = _layer_fn(cfg, held)
    depth = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
    ks, vs = [], []
    for at in range(depth):
        x, k, v = fn(_TAKE(params["blocks"], at), x)
        if kv:
            ks.append(k)
            vs.append(v)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = _head(params["ln_f"], params["lm_head"], x, cfg["eps"],
                   "float8" in cfg["without"])
    return (logits, jnp.stack(ks), jnp.stack(vs)) if kv else logits


@jax.jit
def _head_plain(ln, head, x, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(ln["scale"].astype(jnp.float32), x, eps) \
            @ head["kernel"].astype(jnp.float32)


@jax.jit
def _head_float8(ln, head, x, eps):
    with jax.default_matmul_precision("highest"):
        w = head["kernel"].astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return _rms(ln["scale"].astype(jnp.float32), x, eps) @ w


def _head(ln, head, x, eps, float8):
    return (_head_float8 if float8 else _head_plain)(ln, head, x, eps)


def draw(logits, mask_id: int):
    """``logits [B, V]`` -> ``(x0 [B], log confidence [B], the logits with
    the mask token's excluded)``, greedy, in numpy."""
    lg = np.array(logits, np.float64)
    lg[:, mask_id] = -np.inf
    x0 = lg.argmax(axis=-1)
    top = lg.max(axis=-1)
    logc = -np.log(np.exp(lg - top[:, None]).sum(axis=-1))
    return x0, logc, lg


def choose(masked, logc, n: int, rule: str, threshold: float):
    """The rows to fill: ``masked [B]`` bool (rows that hold the mask),
    ``logc [B]`` their log confidences, ``n`` this step's count."""
    if rule not in RULES:
        raise ValueError(f"rule {rule!r} is none of {RULES}")
    at = np.flatnonzero(masked)
    if rule == "sequential":
        return at[:n]
    # the n of highest confidence, ties to the leftmost
    best = at[np.argsort(-logc[at], kind="stable")[:n]]
    if rule == "low_confidence_dynamic":
        high = at[logc[at] > math.log(threshold)] if threshold > 0 else at
        if len(high) >= n:
            return high
    return np.sort(best)


def block_logits(params, tokens, start, cfg, held):
    """The logits that fill the block at ``tokens[start : start + B]``
    (``shift``: those of the rows one to the left)."""
    b = cfg["block_length"]
    rows = np.arange(start, start + b)
    if "shift" in cfg["without"]:
        rows = np.maximum(rows - 1, 0)
    return forward(params, tokens, cfg, held, rows=rows)


def generate(params, prompt, new_tokens: int, cfg, held, steps: int,
             rule: str = "low_confidence_dynamic", threshold: float = 0.9):
    """The published loop.  Returns ``(the new tokens, the trajectory)``:
    one record a forward, ``(block start, "denoise" | "commit", the
    block's tokens after it)``."""
    b, m = cfg["block_length"], cfg["mask_token_id"]
    prompt = [int(t) for t in prompt]
    p = len(prompt)
    total = -(-(p + new_tokens) // b) * b
    x = np.array(prompt + [m] * (total - p), np.int64)
    n = num_transfer_tokens(b, steps)
    path = []
    for start in range(p // b * b, total, b):
        for s in range(steps + 1):
            cur = x[start:start + b]
            masked = cur == m
            if not masked.any():
                path.append((start, "commit", cur.tolist()))
                break
            x0, logc, _ = draw(block_logits(params, x[:start + b], start,
                                            cfg, held), m)
            fill = choose(masked, logc, n[s], rule, threshold)
            x[start + fill] = x0[fill]
            path.append((start, "denoise", x[start:start + b].tolist()))
    return x[p:p + new_tokens].tolist(), path


def replay(params, prompt, path, cfg, held, steps: int, rule: str,
           threshold: float = 0.9, pad_to: int = 0):
    """Judge a trajectory of forwards the engine recorded (``path``:
    ``(block start, phase, the block's tokens after the forward)``, in
    order): every denoise forward is run here over the final tokens of
    everything before its block and the block as it stood.  Returns
    ``{"logit_gap_worst": over every filled row, this reference's best
    logit minus its logit of the token filled; "order_gap_worst": over
    every denoise forward (not under the sequential rule), this
    reference's log confidence of the best masked row NOT filled minus
    that of the worst filled (at most 0 where the choices agree);
    "rows_agree": the share of denoise forwards whose filled rows are this
    reference's choice; "argmax_share": filled tokens that are its argmax;
    "final": the sequence as committed}``.  ``pad_to`` pads every forward
    to one length (rows past a block see nothing of it and are seen by
    nothing before them), so one program serves them all."""
    b, m = cfg["block_length"], cfg["mask_token_id"]
    prompt = [int(t) for t in prompt]
    first = len(prompt) // b * b
    x = list(prompt[:first])
    cur = {}                              # block start -> tokens as they stand
    taken = {}                            # block start -> denoise steps taken
    n = num_transfer_tokens(b, steps)
    gap, order = 0.0, -math.inf
    agree = forwards = exact = filled_n = 0
    for start, phase, after in path:
        if start not in cur:
            cur[start] = (prompt[start:] + [m] * b)[:b] if start == first \
                else [m] * b
        before = np.array(cur[start])
        after = np.array(after)
        if phase == "commit":
            if len(x) != start or (before != after).any() \
                    or (after == m).any():
                return {"logit_gap_worst": math.inf,
                        "order_gap_worst": math.inf, "rows_agree": 0.0,
                        "argmax_share": 0.0, "final": x}
            x.extend(int(t) for t in after)
            continue
        tokens = x[:start] + before.tolist()
        tokens += [m] * (max(pad_to, len(tokens)) - len(tokens))
        x0, logc, lg = draw(block_logits(params, np.array(tokens), start,
                                         cfg, held), m)
        masked = before == m
        filled = np.flatnonzero(masked & (after != m))
        kept = (before == after) | masked
        if not kept.all() or len(filled) == 0:
            return {"logit_gap_worst": math.inf, "order_gap_worst": math.inf,
                    "rows_agree": 0.0, "argmax_share": 0.0, "final": x}
        for r in filled:
            gap = max(gap, float(lg[r].max() - lg[r][after[r]]))
            exact += int(x0[r] == after[r])
            filled_n += 1
        rest = np.flatnonzero(masked & (after == m))
        if rule != "sequential" and len(rest):
            order = max(order, float(logc[rest].max() - logc[filled].min()))
        step = taken.get(start, 0)
        want = choose(masked, logc, n[min(step, steps - 1)], rule, threshold)
        agree += int(sorted(want) == sorted(filled))
        forwards += 1
        taken[start] = step + 1
        cur[start] = after.tolist()
    return {"logit_gap_worst": gap,
            "order_gap_worst": 0.0 if order == -math.inf else max(order,
                                                                  -1e30),
            "rows_agree": agree / max(forwards, 1),
            "argmax_share": exact / max(filled_n, 1), "final": x}


def kept_kv(params, prompt, path, cfg, held):
    """What the pool should hold of a sequence once its trajectory has
    committed: every layer's keys (as attended) and values over the FINAL
    tokens, ``[L, rows, G * D]`` each — under the control ``commit``, over
    each generated block as its LAST DENOISE forward saw it."""
    b, m = cfg["block_length"], cfg["mask_token_id"]
    prompt = [int(t) for t in prompt]
    first = len(prompt) // b * b
    final, seen = list(prompt[:first]), list(prompt[:first])
    cur = {}
    for start, phase, after in path:
        if start not in cur:
            cur[start] = [(prompt[start:] + [m] * b)[:b] if start == first
                          else [m] * b]
        if phase == "commit":
            final.extend(after)
            # the block as the last denoise forward was fed it
            seen.extend(cur[start][-2] if len(cur[start]) > 1
                        else cur[start][-1])
        else:
            cur[start].append(list(after))
    ids = seen if "commit" in cfg["without"] else final
    _, k, v = forward(params, np.array(ids), cfg, held, rows=[0], kv=True)
    return k, v
