"""The shortcut-connected latent-attention MoE block (LongCat-Flash
family), as a block definition of its own behind ``TransformerLM``'s
interfaces.

One published "layer" is two latent-attention (MLA) sublayers, two dense
SwiGLU FFNs and one MoE whose input is taken after the first attention
and whose output is added after the second FFN (the shortcut)::

    a = x + MLA_0(N_0(x));  u = N_1(a);  m = MoE(u)
    b = a + FFN_0(u);       c = b + MLA_1(N_2(b))
    y = c + FFN_1(N_3(c)) + m

RMSNorm, no bias anywhere, untied head.  ``MLA`` with both latents scaled
after their norms (``* sqrt(h / r)``), ``MoE`` a softmax top-12 router
with a selection bias over 512 routed and 256 identity experts, weights
not renormalised, and this chip's share of the routed experts.  Latent
attention, the latent pool, the paged mixed step, the router's and the
experts' parameters and every refusal are ``models/latent_moe.py``'s;
this file is the block: two attention sublayers a layer, every layer
alike.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax

from . import layers as L
from .latent_moe import LatentMoEConfig, LatentMoELM


@dataclasses.dataclass(frozen=True)
class ShortcutMoEConfig(LatentMoEConfig):
    """``LatentMoEConfig`` with the family's gate and scales as defaults
    (:func:`models.transformer.longcat_flash_config` gives the published
    sizes)."""
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    zero_expert_num: int = 256
    routed_scaling_factor: float = 6.0

    @classmethod
    def model_class(cls):
        return ShortcutMoELM

    def num_params(self) -> int:
        d = self.d_model
        per_layer = (2 * self.mla_params() + 2 * 3 * d * self.ff_dim
                     + self.moe_params() + 4 * d)
        return (self.num_layers * per_layer + 2 * self.vocab_size * d + d)


class ShortcutMoELM(LatentMoELM):
    """``LatentMoELM`` with the shortcut block as its scanned unit."""

    ATTN_SUBLAYERS = 2

    def init_superblock(self, k) -> Dict:
        d, dt = self.config.d_model, self.config.param_dtype
        ks = jax.random.split(k, 5)
        blk = {f"ln{i}": L.rmsnorm_init(None, d, dt) for i in range(4)}
        blk.update(attn0=self._mla_init(ks[0]), attn1=self._mla_init(ks[1]),
                   mlp0=self._ffn_init(ks[2]), mlp1=self._ffn_init(ks[3]),
                   moe=self._moe_init(ks[4]))
        return blk

    def _latent_block(self, bp, x, attend, pools=None, row_valid=None,
                      stack=None):
        """The layer above (``LatentMoELM._latent_block``'s contract)."""
        norm = self._norm_fn()
        x = self.constrain(x)
        o, pools = attend(0, bp["attn0"], norm(bp["ln0"], x), pools)
        with jax.named_scope("residual"):
            a = x + o
        u = norm(bp["ln1"], a)
        m, counters = self._moe_sublayer(bp["moe"], u, row_valid, stack)
        f = self._mlp(bp["mlp0"], u)
        with jax.named_scope("residual"):
            bb = a + f
        o, pools = attend(1, bp["attn1"], norm(bp["ln2"], bb), pools)
        with jax.named_scope("residual"):
            cc = bb + o
        f = self._mlp(bp["mlp1"], norm(bp["ln3"], cc))
        with jax.named_scope("residual"):
            y = cc + f + m
        return self.constrain(y), pools, counters
