"""The sandwich-norm latent-attention block with leading dense layers and
a shared expert (openPangu-Ultra-MoE family), as a block definition
behind ``TransformerLM``'s interfaces.

Every sublayer is normed on the way in AND on the way out, before its
residual add::

    a = x + N_post_attn(MLA(N_in(x)))
    y = a + N_post_mlp(F_l(N_pre_mlp(a)))
    F_l = SwiGLU(d_ff)                                  l < first_k_dense
        = Shared(u) + sum_{e in top-k, e held} w_e Expert_e(u)   otherwise

RMSNorm, no bias anywhere, untied head.  ``MLA`` with no scale on its
latents; the router is a sigmoid gate with no bias whose chosen weights
are renormalised and scaled; ``Shared`` is a SwiGLU of ``n_shared_experts
* expert_d_ff`` that every row goes through, whatever the router says —
whole on every chip of a deployment, so computed here for every row and
not part of ``experts_held``'s share.

Two kinds of layer in one stack: the ``first_k_dense`` leading layers are
``params["dense_blocks"]`` and the expert layers ``params["blocks"]``
(``scan_length = num_layers - first_k_dense``).  That stack and its
shared expert (``DenseLeadMoELM``), latent attention, the one pool across
both kinds of layer (layer ``l`` of the whole stack at block offset ``l *
num_blocks``), the paged mixed step and every refusal are
``models/latent_moe.py``'s; this file is the block.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax

from . import layers as L
from .latent_moe import DenseLeadMoEConfig, DenseLeadMoELM

_NORMS = ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp")


@dataclasses.dataclass(frozen=True)
class SandwichMoEConfig(DenseLeadMoEConfig):
    """``DenseLeadMoEConfig`` with the family's gate as defaults
    (:func:`models.transformer.openpangu_ultra_moe_config` gives the
    published sizes)."""
    n_routed_experts: int = 256
    moe_topk: int = 8
    routed_scaling_factor: float = 2.5
    router_scoring: str = "sigmoid"
    router_bias: bool = False
    norm_topk_prob: bool = True

    @classmethod
    def model_class(cls):
        return SandwichMoELM

    def num_params(self) -> int:
        return self.stack_params(self.mla_params()
                                 + len(_NORMS) * self.d_model)


class SandwichMoELM(DenseLeadMoELM):
    """``DenseLeadMoELM`` with the sandwich-norm block."""

    ATTN_SUBLAYERS = 1

    def _shell_init(self, k) -> Dict:
        d, dt = self.config.d_model, self.config.param_dtype
        blk = {n: L.rmsnorm_init(None, d, dt) for n in _NORMS}
        blk["attn"] = self._mla_init(k)
        return blk

    def _latent_block(self, bp, x, attend, pools=None, row_valid=None,
                      stack=None):
        """The layer above (``LatentMoELM._latent_block``'s contract)."""
        norm = self._norm_fn()
        x = self.constrain(x)
        o, pools = attend(0, bp["attn"], norm(bp["ln_in"], x), pools)
        o = norm(bp["ln_post_attn"], o)
        with jax.named_scope("residual"):
            a = x + o
        f, counters = self._ffn_sublayer(bp, norm(bp["ln_pre_mlp"], a),
                                         row_valid, stack)
        f = norm(bp["ln_post_mlp"], f)
        with jax.named_scope("residual"):
            y = a + f
        return self.constrain(y), pools, counters
