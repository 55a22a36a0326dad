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
(``scan_length = num_layers - first_k_dense``).  Latent attention, the
one pool across both kinds of layer (layer ``l`` of the whole stack at
block offset ``l * num_blocks``), the paged mixed step and every refusal
are ``models/latent_moe.py``'s; this file is the block.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from . import layers as L
from ..moe import dropless
from .latent_moe import LatentMoEConfig, LatentMoELM

_NORMS = ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp")


@dataclasses.dataclass(frozen=True)
class SandwichMoEConfig(LatentMoEConfig):
    """``LatentMoEConfig`` with the family's gate as defaults
    (:func:`models.transformer.openpangu_ultra_moe_config` gives the
    published sizes), the count of leading dense layers and of shared
    experts."""
    n_routed_experts: int = 256
    moe_topk: int = 8
    routed_scaling_factor: float = 2.5
    router_scoring: str = "sigmoid"
    router_bias: bool = False
    norm_topk_prob: bool = True
    first_k_dense: int = 3
    n_shared_experts: int = 1

    @classmethod
    def model_class(cls):
        return SandwichMoELM

    @property
    def scan_length(self) -> int:
        """The expert layers: what ``params["blocks"]`` stacks."""
        return self.num_layers - self.first_k_dense

    def num_params(self) -> int:
        d = self.d_model
        shell = self.mla_params() + len(_NORMS) * d
        dense = shell + 3 * d * self.ff_dim
        expert = (shell + self.moe_params()
                  + 3 * d * self.n_shared_experts * self.expert_d_ff)
        return (self.first_k_dense * dense + self.scan_length * expert
                + 2 * self.vocab_size * d + d)


class SandwichMoELM(LatentMoELM):
    """``LatentMoELM`` with the sandwich-norm block: ``first_k_dense``
    dense layers, then the scanned expert layers."""

    ATTN_SUBLAYERS = 1
    PAGED_COUNTERS = LatentMoELM.PAGED_COUNTERS + ("moe_rows_shared",)

    def __init__(self, config: SandwichMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        if not 0 <= config.first_k_dense < config.num_layers:
            raise ValueError(
                f"first_k_dense {config.first_k_dense} leaves no expert "
                f"layer among {config.num_layers}")

    # -- init --------------------------------------------------------------
    def _shell_init(self, k) -> Dict:
        d, dt = self.config.d_model, self.config.param_dtype
        blk = {n: L.rmsnorm_init(None, d, dt) for n in _NORMS}
        blk["attn"] = self._mla_init(k)
        return blk

    def init_dense_block(self, k) -> Dict:
        ka, kf = jax.random.split(k)
        return dict(self._shell_init(ka), mlp=self._ffn_init(kf))

    def init_superblock(self, k) -> Dict:
        """One expert layer."""
        c = self.config
        ka, km, ks = jax.random.split(k, 3)
        return dict(self._shell_init(ka), moe=self._moe_init(km),
                    shared=self._ffn_init(
                        ks, c.n_shared_experts * c.expert_d_ff))

    def init_resident(self, rng) -> Dict:
        """Embedding, final norm, head — and the leading dense layers,
        which no scan over ``blocks`` streams."""
        params = super().init_resident(rng)
        k = self.config.first_k_dense
        if k:
            params["dense_blocks"] = jax.vmap(self.init_dense_block)(
                jax.random.split(jax.random.split(rng, 8)[6], k))
        return params

    def _leading_blocks(self, params) -> Optional[Dict]:
        return params.get("dense_blocks")

    # -- the layer ---------------------------------------------------------
    def expert_layer(self, bp, u, row_valid=None, stack=None):
        """An expert layer's ``F_l``: u [B, T, h] -> ``(Shared(u) + this
        chip's part of the routed experts' output, counters)``.  The
        shared expert is a plain SwiGLU over every row, whatever the
        router says; ``stack`` as in ``_latent_block``."""
        routed, counters = self._moe_sublayer(bp["moe"], u, row_valid,
                                              stack)
        shared = self._mlp(bp["shared"], u, scope="shared_expert")
        with jax.named_scope("expert_layout"):
            return shared + routed, counters

    def _latent_block(self, bp, x, attend, pools=None, row_valid=None,
                      stack=None):
        """The layer above (``LatentMoELM._latent_block``'s contract); a
        dense layer is one whose parameters hold ``mlp`` and no ``moe``,
        and counts nothing."""
        norm = self._norm_fn()
        x = self.constrain(x)
        o, pools = attend(0, bp["attn"], norm(bp["ln_in"], x), pools)
        o = norm(bp["ln_post_attn"], o)
        with jax.named_scope("residual"):
            a = x + o
        u = norm(bp["ln_pre_mlp"], a)
        if "moe" in bp:
            f, counters = self.expert_layer(bp, u, row_valid, stack)
        else:
            f = self._mlp(bp["mlp"], u)
            counters = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        f = norm(bp["ln_post_mlp"], f)
        with jax.named_scope("residual"):
            y = a + f
        return self.constrain(y), pools, counters

    def _extra_counters(self, row_valid) -> list:
        """``moe_rows_shared``: every row that carries a token goes
        through the shared expert of every expert layer."""
        return [jnp.sum(row_valid, dtype=jnp.int32)
                * self.config.scan_length]
