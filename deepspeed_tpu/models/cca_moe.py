"""Compressed convolutional attention (CCA) and a top-1 expert layer behind
a router MLP (the ZAYA1 family), as a block definition behind
``TransformerLM``'s interfaces — the one expert block that TRAINS.

Layer ``l``, input ``x [T, d]`` and the router state ``r_{l-1} [T, R]`` of
the layer before (0 for the first layer held)::

    h = N(x);  q~ = h Wq  (H heads of D);  k~ = h Wk  (G heads of D)
    v_t = [h_t Wv1 ; h_{t-1} Wv2]                      the value shift
    z = [q~ ; k~];  z1_t = sum_j a_j * z_{t-j} + a0    depthwise, causal
    z2_t^(g) = sum_j B_j^(g) z1_{t-j}^(g) + b^(g)      inside each head
    q = z2_q + (q~ + rep(k~)) / 2;  k = z2_k + (mean(q~) + k~) / 2
    q <- sqrt(D) q / |q|;  k <- tau_g sqrt(D) k / |k|;  rotary (half)
    x <- x + softmax(q k^T / sqrt(D), causal) v Wo

    u = N(x);  r_l = u Wr + gamma_l * r_{l-1}
    s = W3 gelu(W2 gelu(W1 N(r_l)));  p = softmax(s)   float32
    e = argmax(p + b);  x <- x + p_e Expert_e(u)       Expert = SwiGLU

Attention runs in a latent half the model's width (``H D = d / 2``), ``H``
query heads to ``G`` key-value heads; every history is zero before the
sequence.  The router state rides the layer scan.  The experts are ONE
CHIP'S SHARE (``experts_held``) through ``moe/dropless.py``: the router
keeps its published width, a pick of an absent expert adds nothing.

Balance without an auxiliary loss: ``b`` moves the choice and never the
weight, and its gradient is DEFINED as ``f - 1 / E`` (``f_e`` the share
of the step's picks that chose ``e``) by a term of value 0 in the loss,
so the optimizer the job already runs moves it.  The stored ``b`` is in
units of ``ROUTER_BIAS_UNIT`` (``e = argmax(p + ROUTER_BIAS_UNIT * b)``):
a constant of the block, not an option.

``loss`` returns ``(loss, counters)``; the engine's fused step carries
the counters into ``train_step``'s result.  The block has no decode path:
a slot's convolution and shift history has no place in a cache yet
(``_paged_supported``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from ..moe import dropless
from .transformer import TransformerConfig, TransformerLM

#: rows of one expert a step of the grouped product takes when training:
#: deep enough to fill the matrix unit where an expert sees ~1,000 rows
TRAIN_TILE_ROWS = 256
#: what ``loss`` counts, summed over the layers (``dropless.COUNTERS``'
#: names) — and the largest selection bias of any layer
COUNTERS = ("moe_picks", "moe_picks_held", "moe_rows_max_expert",
            "moe_experts_touched")

#: the unit the stored selection bias is in: ``e = argmax(p +
#: ROUTER_BIAS_UNIT * b)``.  ``b`` is a vector the job's optimizer moves by
#: ``lr`` a step whatever its gradient's size (Adam normalises), while a
#: router logit behind a matrix of fan-in ``R`` moves by about ``lr * R *
#: mean|activation|`` a step — the reason Adam's step for a vector is
#: scaled by the fan-in of the matrices beside it (Yang et al. 2022,
#: "Tensor Programs V", table 3).  At ``R`` = 256 and gelu activations
#: (mean magnitude 0.4) that is about 100: a step of ``lr`` moves the
#: choice as far as it moves a logit.  One value for every size: 100,
#: 1,000 and 10,000 were read on the chip (``PERF.md`` section 6), nothing
#: between; the issue's letter (1) leaves the rule a hundred times slower
#: than the router it balances.
ROUTER_BIAS_UNIT = 100.0

_NO_DECODE = (
    "the CCA block trains and does not decode: a slot's convolution "
    "history (the last cca_time0 + cca_time1 - 2 rows of q~ and k~) and "
    "its value-shift history (one row of h Wv2) have no place in the "
    "paged pool or the dense KV cache yet, and prefix hits would have to "
    "snapshot them at block boundaries (ROADMAP B18)")


@dataclasses.dataclass(frozen=True)
class CCAMoEConfig(TransformerConfig):
    """``TransformerConfig``'s sizes (``d_model``, ``num_heads``,
    ``num_kv_heads``, ``head_dim``, ``rotary_pct``, ``rotary_base``, ...)
    plus the convolutions', the router MLP's and the experts'.  The flags
    of the standard block that this block does not read are pinned by
    :func:`models.transformer.zaya_config`."""
    cca_time0: int = 2
    cca_time1: int = 2
    expert_d_ff: int = 2048
    n_routed_experts: int = 16
    router_hidden: int = 256
    #: the contiguous range (lo, hi) of the experts held here; () = all
    experts_held: tuple = ()
    #: the depth the two output projections' init is scaled by (0.02 /
    #: sqrt(2 * depth)): the published stack's, whatever part is held;
    #: 0 = ``num_layers``
    init_depth: int = 0

    @classmethod
    def model_class(cls):
        return CCAMoELM

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    @property
    def conv_heads(self) -> int:
        return self.num_heads + self.kv_heads

    def layer_params(self) -> Dict[str, int]:
        """One layer's parameters by part; ``expert`` is ONE expert."""
        d, hd, r = self.d_model, self.hdim, self.router_hidden
        width = self.conv_heads * hd
        return {
            "projections": (d * self.num_heads * hd + d * self.kv_heads * hd
                            + 2 * d * (self.kv_heads * hd // 2)
                            + self.num_heads * hd * d),
            "convolutions": ((self.cca_time0 + 1) * width
                             + self.cca_time1 * self.conv_heads * hd * hd
                             + width + self.kv_heads),
            "router": (d * r + 2 * r + 2 * r * r
                       + r * self.n_routed_experts + self.n_routed_experts),
            "norms": 2 * d,
            "expert": 3 * d * self.expert_d_ff}

    def num_params(self) -> int:
        """Held here: every layer's own part and its held experts, the
        (tied) embedding and the final norm."""
        lo, hi = self.held
        part = self.layer_params()
        layer = sum(v for k, v in part.items() if k != "expert")
        return (self.num_layers * (layer + (hi - lo) * part["expert"])
                + self.vocab_size * self.d_model + self.d_model)


def _shift(a: jax.Array, by: int) -> jax.Array:
    """``a [B, T, ...]`` delayed by ``by`` positions, zeros before the
    sequence."""
    if not by:
        return a
    pad = [(0, 0)] * a.ndim
    pad[1] = (by, 0)
    return jnp.pad(a[:, :a.shape[1] - by], pad)


class CCAMoELM(TransformerLM):
    """``TransformerLM`` with the CCA + top-1 expert block: the same
    ``init`` / ``apply`` / ``loss`` / ``partition_specs`` surface, trained
    by ``ds.initialize`` through the fused step, the flash kernels and the
    chunked loss head."""

    def __init__(self, config: CCAMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        lo, hi = c.held
        if not 0 <= lo < hi <= c.n_routed_experts:
            raise ValueError(f"experts_held {c.experts_held} is not a "
                             f"range of the {c.n_routed_experts} experts")
        if c.pos_embedding != "rotary" or c.rotary_interleaved:
            raise ValueError("the CCA block rotates half of each head "
                             "(pos_embedding='rotary', rotate-half)")
        if c.kv_heads * c.hdim % 2:
            raise ValueError("the value shift splits kv_heads * head_dim "
                             "in two")

    # -- refusals ----------------------------------------------------------
    def _paged_supported(self) -> Optional[str]:
        return _NO_DECODE

    def init_cache(self, batch, max_len, dtype=None):
        raise NotImplementedError(_NO_DECODE)

    # -- init --------------------------------------------------------------
    def init_superblock(self, k) -> Dict:
        c, dt = self.config, self.config.param_dtype
        d, hd, r = c.d_model, c.hdim, c.router_hidden
        lo, hi = c.held
        width, half_v = c.conv_heads * hd, c.kv_heads * hd // 2
        out_std = 0.02 / math.sqrt(2.0 * (c.init_depth or c.num_layers))
        ks = jax.random.split(k, 12)

        def normal(key, shape, std=0.02):
            return L.normal_init(key, shape, std, dt)
        return {
            "ln1": L.rmsnorm_init(None, d, dt),
            "attn": {
                "q": {"kernel": normal(ks[0], (d, c.num_heads * hd))},
                "k": {"kernel": normal(ks[1], (d, c.kv_heads * hd))},
                "v1": {"kernel": normal(ks[2], (d, half_v))},
                "v2": {"kernel": normal(ks[3], (d, half_v))},
                "conv0": {"taps": normal(ks[4], (c.cca_time0, width)),
                          "bias": jnp.zeros((width,), dt)},
                "conv1": {"taps": normal(
                    ks[5], (c.cca_time1, c.conv_heads, hd, hd)),
                    "bias": jnp.zeros((width,), dt)},
                "tau": jnp.ones((c.kv_heads,), dt),
                "out": {"kernel": normal(ks[6], (c.num_heads * hd, d),
                                         out_std)},
            },
            "ln2": L.rmsnorm_init(None, d, dt),
            "moe": {
                "router": {
                    "in": {"kernel": normal(ks[7], (d, r))},
                    "gamma": jnp.zeros((r,), dt),
                    "norm": L.rmsnorm_init(None, r, dt),
                    "fc1": {"kernel": normal(ks[8], (r, r))},
                    "fc2": {"kernel": normal(ks[9], (r, r))},
                    "fc3": {"kernel": normal(ks[10],
                                             (r, c.n_routed_experts))},
                },
                "bias": jnp.zeros((c.n_routed_experts,), dt),
                "experts": dropless.init_experts(
                    ks[11], hi - lo, d, c.expert_d_ff, 0.02, out_std, dt),
            },
        }

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block has no tensor-parallel rules
        (ZeRO partitions its state over the data axis as any model's)."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- the sublayers -----------------------------------------------------
    def _cca(self, p, h):
        """``h [B, T, d]`` (normed) -> the attention sublayer's output."""
        c = self.config
        b, t, _ = h.shape
        nh, nkv, hd = c.num_heads, c.kv_heads, c.hdim
        group = nh // nkv
        f32 = jnp.float32
        with jax.named_scope("attn_proj"):
            q_in = L.dense_apply(p["q"], h)
            k_in = L.dense_apply(p["k"], h)
            v = jnp.concatenate(
                [L.dense_apply(p["v1"], h),
                 _shift(L.dense_apply(p["v2"], h), 1)],
                axis=-1).reshape(b, t, nkv, hd)
        with jax.named_scope("attn_conv"):
            z = jnp.concatenate([q_in, k_in], axis=-1).astype(f32)
            taps0 = p["conv0"]["taps"].astype(f32)
            z1 = p["conv0"]["bias"].astype(f32) + sum(
                taps0[j] * _shift(z, j) for j in range(c.cca_time0))
            # float32 operands at the default precision: one bfloat16
            # pass on the chip, and a product XLA:CPU has (it has no
            # batched bfloat16 x bfloat16 -> float32)
            z1 = z1.reshape(b, t, c.conv_heads, hd)
            taps1 = p["conv1"]["taps"].astype(f32)
            z2 = p["conv1"]["bias"].astype(f32).reshape(
                c.conv_heads, hd) + sum(
                jnp.einsum("btgc,gcd->btgd", _shift(z1, j), taps1[j])
                for j in range(c.cca_time1))
            qt = z[..., :nh * hd].reshape(b, t, nkv, group, hd)
            kt = z[..., nh * hd:].reshape(b, t, nkv, hd)
            q = z2[:, :, :nh] + (
                0.5 * (qt + kt[:, :, :, None])).reshape(b, t, nh, hd)
            k = z2[:, :, nh:] + 0.5 * (qt.mean(axis=3) + kt)
            unit = math.sqrt(hd)
            q = q * (unit * jax.lax.rsqrt(
                jnp.sum(q * q, axis=-1, keepdims=True) + 1e-12))
            k = k * (unit * p["tau"].astype(f32)[:, None] * jax.lax.rsqrt(
                jnp.sum(k * k, axis=-1, keepdims=True) + 1e-12))
            cos, sin = self._cos.astype(f32), self._sin.astype(f32)
            q = L.apply_rotary(q, cos, sin, interleaved=False).astype(h.dtype)
            k = L.apply_rotary(k, cos, sin, interleaved=False).astype(h.dtype)
        with jax.named_scope("attn_kernel"):
            if c.attn_impl == "flash":
                from ..ops.transformer.flash_attention import (
                    flash_attention_bthd)
                o = flash_attention_bthd(q, k, v, causal=True,
                                         mesh=self.mesh)
            else:
                o = L.gqa_attention(q, k, v, causal=True)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o.reshape(b, t, nh * hd))

    def _router_logits(self, p, u, r_prev):
        """``(r_l [B, T, R] float32, s [B * T, E] float32)``.  The state
        and the MLP behind it are float32 (a quarter of a per cent of the
        layer's operations): a pick is an argmax over near-equal scores."""
        f32 = jnp.float32
        with jax.named_scope("router"):
            r = jnp.einsum("bth,hr->btr", u,
                           p["in"]["kernel"].astype(u.dtype),
                           preferred_element_type=f32)
            r = r + p["gamma"].astype(f32) * r_prev
            a = L.rmsnorm_apply(p["norm"], r, eps=self.config.layernorm_eps)
            for name in ("fc1", "fc2"):
                a = jax.nn.gelu(jnp.dot(
                    a, p[name]["kernel"].astype(f32),
                    precision="highest"), approximate=False)
            s = jnp.dot(a, p["fc3"]["kernel"].astype(f32),
                        precision="highest")
            return r, s.reshape(-1, s.shape[-1])

    def _route(self, p, u, r_prev):
        """``(r_l, the rows' top-1 picks over all the router's outputs)``:
        the router MLP's scores, the selection bias in its unit."""
        r, logits = self._router_logits(p["router"], u, r_prev)
        bias = ROUTER_BIAS_UNIT * p["bias"].astype(jnp.float32)
        return r, dropless.route_logits(logits, bias, 1, 1.0)

    def _moe(self, p, u, r_prev):
        """``u [B, T, d]`` -> ``(this chip's part of the experts' output,
        r_l, the balance term, counters int32 [len(COUNTERS)], max |b|)``."""
        c = self.config
        b, t, h = u.shape
        outs = c.n_routed_experts
        r, routing = self._route(p, u, r_prev)
        with jax.named_scope("router"):
            share = jnp.mean(jax.nn.one_hot(routing.index[:, 0], outs,
                                            dtype=jnp.float32), axis=0)
            stored = p["bias"].astype(jnp.float32)
            balance = jnp.sum(jax.lax.stop_gradient(share - 1.0 / outs)
                              * (stored - jax.lax.stop_gradient(stored)))
            bias_max = ROUTER_BIAS_UNIT * jnp.max(jnp.abs(stored))
        y, counted = dropless.expert_share(
            p["experts"], u.reshape(b * t, h), routing, outs, c.held,
            pass_rows=None, tile_rows=TRAIN_TILE_ROWS)
        by_name = dict(zip(dropless.COUNTERS, counted))
        return (y.reshape(b, t, h), r, balance,
                jnp.stack([by_name[n] for n in COUNTERS]), bias_max)

    def _cca_block(self, bp, x, r_prev):
        norm = self._norm_fn()
        x = self.constrain(x)
        a = self._cca(bp["attn"], norm(bp["ln1"], x))
        with jax.named_scope("residual"):
            x = x + a
        m, r, balance, counted, bias_max = self._moe(
            bp["moe"], norm(bp["ln2"], x), r_prev)
        with jax.named_scope("residual"):
            x = x + m
        return self.constrain(x), r, balance, counted, bias_max

    # -- full sequences ----------------------------------------------------
    def _forward(self, params, input_ids):
        """Forward up to the final norm: ``(x [B, T, d], the balance
        terms' sum, {counter: value})``."""
        c = self.config
        x = self._embed_tokens(params, input_ids)
        layer = self._remat(lambda bp, x, r: self._cca_block(
            self.block_transform(bp), x, r))

        def scan_fn(carry, bp):
            x, r, balance, counted, bias_max = carry
            x, r, bal, cnt, bmax = layer(bp, x, r)
            return (x, r, balance + bal, counted + cnt,
                    jnp.maximum(bias_max, bmax)), None
        zero = jnp.zeros((), jnp.float32)
        (x, _, balance, counted, bias_max), _ = jax.lax.scan(
            scan_fn,
            (x, jnp.zeros(x.shape[:2] + (c.router_hidden,), jnp.float32),
             zero, jnp.zeros((len(COUNTERS),), jnp.int32), zero),
            params["blocks"])
        counters = dict(zip(COUNTERS, counted))
        counters["router_bias_abs_max"] = bias_max
        return self._norm_fn("head")(params["ln_f"], x), balance, counters

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        x, balance, _ = self._forward(params, input_ids)
        return x, balance

    def loss(self, params, batch):
        """``(causal LM loss + the balance terms (value 0), counters)``:
        ``batch`` as ``TransformerLM.loss`` takes it."""
        labels, mask = self._targets(batch)
        x, balance, counters = self._forward(params, batch["input_ids"])
        with jax.named_scope("loss"):
            return (self.nll_from_hidden(params, x, labels, mask) + balance,
                    counters)
