"""Functional NN layers (pure init/apply, pytree params).

This is the compute vocabulary of the model zoo. Where the reference fuses
these into CUDA kernels (`/root/reference/csrc/transformer/` — gelu, layernorm,
softmax, dropout, transform kernels), we express them as jnp ops and let XLA
fuse them into the surrounding matmuls; Pallas kernels replace only the ops
XLA can't schedule well (attention — see `deepspeed_tpu/ops/`).

Params are plain nested dicts so every parallelism layer (ZeRO, TP, PP) can
operate on them as pytrees with partition-spec trees.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def normal_init(rng, shape, stddev=0.02, dtype=jnp.float32):
    return (stddev * jax.random.normal(rng, shape)).astype(dtype)


def scaled_init(rng, shape, stddev, num_layers, dtype=jnp.float32):
    """GPT-2 style residual-branch init: stddev / sqrt(2 * num_layers)."""
    return normal_init(rng, shape, stddev / math.sqrt(2.0 * num_layers), dtype)


def zeros_init(_rng, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones_init(_rng, shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Dense
# ---------------------------------------------------------------------------
def dense_init(rng, in_dim: int, out_dim: int, use_bias: bool = True,
               stddev: float = 0.02, dtype=jnp.float32):
    p = {"kernel": normal_init(rng, (in_dim, out_dim), stddev, dtype)}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,), dtype)
    return p


def dense_apply(params, x, *, precision=None):
    # Kernel is cast to the activation dtype so fp32 master params don't
    # silently promote the whole stream to fp32 (bf16 in → bf16 out).
    y = jnp.einsum("...i,io->...o", x, params["kernel"].astype(x.dtype),
                   precision=precision)
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# LayerNorm / RMSNorm — computed in fp32 regardless of activation dtype,
# matching the reference's normalize_kernels.cu accumulation behavior.
# ---------------------------------------------------------------------------
def layernorm_init(_rng, dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(params, x, eps: float = 1e-5):
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(orig_dtype)


def rmsnorm_init(_rng, dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(params, x, eps: float = 1e-6):
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
def gelu(x):
    # tanh approximation — same variant as the reference's gelu_kernels.cu.
    return jax.nn.gelu(x, approximate=True)


ACT_FNS = {
    "gelu": gelu,
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
}


# ---------------------------------------------------------------------------
# Rotary position embeddings (GPT-NeoX style)
# ---------------------------------------------------------------------------
def rotary_freqs(head_dim: int, rotary_dim: int, max_seq: int,
                 base: float = 10000.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    inv = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                      # [T, rotary_dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary(x, cos, sin, positions=None, interleaved=True):
    """x: [B, T, H, Dh]; rotate first rotary_dim dims.

    ``interleaved=True`` — GPT-J/RoFormer "rotate_every_two" pairing
    (dims 2i, 2i+1), the reference's rotate_every_two path in
    `csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu`.
    ``interleaved=False`` — GPT-NeoX "rotate_half" pairing (dims i, i+d/2),
    the convention of the NeoX family and HF GPTNeoX.
    """
    rotary_dim = cos.shape[-1] * 2
    x_rot, x_pass = x[..., :rotary_dim], x[..., rotary_dim:]
    if positions is None:
        c = cos[None, :x.shape[1], None, :]
        s = sin[None, :x.shape[1], None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    if interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        y1 = x1 * c - x2 * s
        y2 = x2 * c + x1 * s
        y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    else:
        half = rotary_dim // 2
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        y1 = x1 * c - x2 * s
        y2 = x2 * c + x1 * s
        y = jnp.concatenate([y1, y2], axis=-1)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def apply_rotary_lanes(x, cos, sin, head_dim: int, interleaved=True):
    """`apply_rotary` at positions 0..T-1 on ``x [B, T, H * head_dim]``,
    the projection's own layout, for the flash kernels.

    Nothing here splits the lane dimension into heads or narrows it to
    the rotary width (on the TPU either is a re-layout of the whole array:
    a ``[.., H, Dh]`` view tiles differently, and a 16-wide operand is
    laid out time-minor): a lane's partner is the array shifted by the
    pair distance, picked by the lane's number, and the cosines and
    signed sines are full-width ``[T, H * head_dim]`` tables (ones and
    zeros past the rotary width).  The rotation is orthogonal, so the
    backward is the same pass with the sines negated."""
    t, width = x.shape[1], x.shape[2]
    half = cos.shape[-1]
    cos, sin = cos[:t].astype(jnp.float32), sin[:t].astype(jnp.float32)
    rest = jnp.zeros((t, head_dim - 2 * half), jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2) % head_dim
    if interleaved:
        shift, first = 1, lane % 2 == 0
        pair = lambda a, b: jnp.stack([a, b], axis=-1).reshape(t, 2 * half)
    else:
        shift, first = half, lane < half
        pair = lambda a, b: jnp.concatenate([a, b], axis=-1)
    c, s = (jnp.concatenate([tab, fill] * (width // head_dim), axis=-1)
            for tab, fill in ((pair(cos, cos), rest + 1.0),
                              (pair(-sin, sin), rest)))
    zero = jnp.zeros((), x.dtype)
    up = jax.lax.pad(x[..., shift:], zero, [(0, 0, 0)] * 2 + [(0, shift, 0)])
    down = jax.lax.pad(x[..., :-shift], zero,
                       [(0, 0, 0)] * 2 + [(shift, 0, 0)])
    # a pair's first lane takes the one `shift` above it, its second the
    # one below; past the rotary width s is 0
    return (x * c + jnp.where(first, up, down) * s).astype(x.dtype)


def _rotary_lanes_fwd(x, cos, sin, head_dim, interleaved):
    return apply_rotary_lanes(x, cos, sin, head_dim, interleaved), (cos, sin)


def _rotary_lanes_bwd(head_dim, interleaved, res, dy):
    cos, sin = res
    return (apply_rotary_lanes(dy, cos, -sin, head_dim, interleaved),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


apply_rotary_lanes.defvjp(_rotary_lanes_fwd, _rotary_lanes_bwd)


# ---------------------------------------------------------------------------
# Attention core (XLA path; Pallas flash kernel replaces this on TPU hot path)
# ---------------------------------------------------------------------------
def causal_attention(q, k, v, *, mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     kv_positions_offset: int = 0,
                     causal: bool = True,
                     bias: Optional[jnp.ndarray] = None):
    """q,k,v: [B, Tq, H, Dh] / [B, Tk, H, Dh]. Softmax in fp32 (the reference's
    softmax_kernels.cu accumulates fp32 too). Returns [B, Tq, H, Dh].

    ``causal=False`` — encoder (bidirectional) attention. ``bias`` —
    additive fp32 logit bias broadcastable to [B, H, Tq, Tk] (ALiBi)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # bf16 operands, fp32 accumulation — MXU-native mixed precision.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    tq, tk = q.shape[1], k.shape[1]
    if causal:
        q_pos = jnp.arange(tq) + kv_positions_offset
        k_pos = jnp.arange(tk)
        cmask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(cmask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def gqa_attention(q, k, v, *, mask: Optional[jnp.ndarray] = None,
                  scale: Optional[float] = None,
                  kv_positions_offset: int = 0, causal: bool = True,
                  bias: Optional[jnp.ndarray] = None):
    """Grouped-query attention WITHOUT materializing expanded k/v:
    q [B,Tq,H,Dh] with H = G·Hkv groups attends k/v [B,Tk,Hkv,Dh] via a
    group einsum — peak working set stays at the kv-width cache (the
    memory moment GQA exists for). ``mask`` broadcastable to
    [B,1,1,Tq,Tk]; ``bias`` to [B,H,Tq,Tk] (regrouped internally)."""
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(b, tq, nkv, g, hd)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        bias = jnp.broadcast_to(
            bias.astype(jnp.float32),
            bias.shape[:-3] + (nh,) + bias.shape[-2:])
        logits = logits + bias.reshape(bias.shape[:-3] + (nkv, g)
                                       + bias.shape[-2:])
    tk = k.shape[1]
    if causal:
        q_pos = jnp.arange(tq) + kv_positions_offset
        cmask = q_pos[:, None] >= jnp.arange(tk)[None, :]
        logits = jnp.where(cmask[None, None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, tq, nh, hd)


def gqa_attention_at(q, k, v, q_pos, window: Optional[int], scale: float):
    """:func:`gqa_attention` of q ``[B, Tq, H, hd]`` at positions ``q_pos
    [Tq]`` against k, v ``[B, Tk, Hkv, hd]`` at positions ``0 .. Tk - 1``:
    a row sees every key up to its own position, or with a ``window`` the
    ``window`` keys that end there (``0 <= q_pos - k_pos < window``)."""
    k_pos = jnp.arange(k.shape[1])
    seen = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
    with jax.named_scope("attn_kernel"):
        return gqa_attention(q, k, v, causal=False, scale=scale,
                             mask=seen[None, None, None])


def alibi_slopes(num_heads: int) -> jnp.ndarray:
    """ALiBi head slopes (Press et al.; BLOOM's build_alibi_tensor,
    HF modeling_bloom.py): powers of 2^(-8/n) with the non-power-of-two
    extension interleaving from 2^(-4/n)."""
    import math as _m
    n = 2 ** _m.floor(_m.log2(num_heads))
    base = 2.0 ** (-(2.0 ** -(_m.log2(n) - 3)))
    slopes = [base ** (i + 1) for i in range(n)]
    if n < num_heads:
        extra_base = 2.0 ** (-(2.0 ** -(_m.log2(2 * n) - 3)))
        extra = [extra_base ** (i + 1) for i in range(0, 2 * (num_heads - n),
                                                      2)]
        slopes += extra
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(num_heads: int, tk: int, q_positions) -> jnp.ndarray:
    """[H, Tq, Tk] additive bias: -slope_h * |q_pos - k_pos| — equals the
    BLOOM causal convention on the visible (k <= q) region and stays a
    distance PENALTY (never a boost) for future keys when used
    bidirectionally."""
    slopes = alibi_slopes(num_heads)                     # [H]
    k_pos = jnp.arange(tk)
    rel = -jnp.abs(k_pos[None, :] - q_positions[:, None])   # [Tq, Tk] <= 0
    return slopes[:, None, None] * rel[None].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
def embedding_init(rng, vocab: int, dim: int, stddev=0.02, dtype=jnp.float32):
    return {"embedding": normal_init(rng, (vocab, dim), stddev, dtype)}


def embedding_apply(params, ids, dtype=None):
    emb = params["embedding"]
    if dtype is not None:
        emb = emb.astype(dtype)
    return jnp.take(emb, ids, axis=0)


def embedding_apply_onehot(params, ids, dtype=None):
    """Embedding lookup as one_hot @ table — the gather-free form that
    GSPMD can partition when the vocab dim is sharded (TP embeddings under
    manual collectives; the reference shards embeddings the same way via
    VocabParallelEmbedding-style masking)."""
    emb = params["embedding"]
    if dtype is not None:
        emb = emb.astype(dtype)
    oh = jax.nn.one_hot(ids, emb.shape[0], dtype=emb.dtype)
    return jnp.einsum("...v,vd->...d", oh, emb)


def embedding_attend(params, x):
    """Tied-softmax projection: x @ embedding.T — bf16 operands, fp32
    accumulation (logits come out fp32 without a fp32 matmul)."""
    return jnp.einsum("...d,vd->...v", x,
                      params["embedding"].astype(x.dtype),
                      preferred_element_type=jnp.float32)
