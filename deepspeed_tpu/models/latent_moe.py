"""Latent attention (MLA) over a paged latent pool, and one chip's share
of a dropless routed-expert layer: what every block built from the two
shares, behind ``TransformerLM``'s interfaces.

A block definition (``models/shortcut_moe.py``, ``models/sandwich_moe.py``)
subclasses :class:`LatentMoELM` and says three things: how many attention
sublayers one of its layers has (``ATTN_SUBLAYERS``), one layer's
parameters (``init_superblock``), and one layer's arithmetic
(``_latent_block``), written against an ``attend`` callback so that the
same function runs full sequences and the paged mixed step.  Everything
else is here, once:

``MLA``: low-rank queries (``q_a`` -> RMSNorm -> ``q_b``, per head
``qk_nope_head_dim`` no-rope | ``qk_rope_head_dim`` rotary), one shared
latent ``c`` of rank ``kv_lora_rank`` (RMSNorm) that ``kv_b`` expands to
per-head keys and values, and ONE rotary key head shared by all heads;
``mla_scale_q_lora`` / ``mla_scale_kv_lora`` multiply the two normed
latents by ``sqrt(d_model / rank)``.  ``MoE``: ``moe/dropless.py`` — a
top-k router in the form the configuration names (``router_scoring``,
``router_bias``, ``norm_topk_prob``) over the routed experts and any
identity experts, and this chip's share of the routed experts
(``experts_held``).

``init`` and checkpoints hold ``q_b`` and ``kv_b`` as published, the split
of a head's parts INSIDE the head.  The sublayers read ONE other layout,
``q_sections`` / ``w_uk`` / ``w_uv`` (``serving_params``): an inference
engine makes it once as it places the weights, and the model's entry
points make it in the caller's program for a tree that still has the
published keys.

Full sequences (``apply``) run the EXPANDED form in plain XLA.  These
blocks do not train: latent attention has no training kernel
(``training_refusal``; the experts' grouped product differentiates, and
``models/cca_moe.py`` trains through it).  Serving runs the ABSORBED form through
the paged path: the pool row of a token is ``[c | k_rope | 0]`` for each
attention sublayer of each layer — ONE buffer ``k [sublayers, num_blocks,
block, lanes]`` (there is no second operand: the pool's ``v`` is None) —
and ``ops/transformer/paged_decode_attention.py``'s latent kernel attends
every head against one shared page.  Everything the engine, the scheduler
and the allocator do is unchanged: a block is 16 tokens whatever a row
holds.

A stack may begin with layers of another kind (``_leading_blocks``: dense
layers before the expert layers): they run as a short scan of their own
before the scan over ``params["blocks"]``, through the same pool — layer
``l`` of the whole stack at sublayer index ``ATTN_SUBLAYERS * l`` — while
the expert stack is indexed by the layer's number among ``blocks``.
:class:`DenseLeadMoELM` is that stack with a shared expert in every
expert layer, for the block definitions that have both
(``models/sandwich_moe.py``, ``models/sparse_latent_moe.py``).

Of the one serving step (``TransformerLM._apply_paged_mixed``) this file
brings the latent blocks' layers (``_paged_layers``: the two scans), their
walk and their counters.  The scans carry the cache's pools, or with them
whatever one layer hands the next (``_paged_carry``); what a layer is told
of itself (``_layer_meta``) is its block offset into the pool, or more.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from ..moe import dropless
from ..observability.overlap import scoped
from .transformer import (MixedStep, TransformerConfig, TransformerLM,
                          lane_pool_rows, scatter_rows)


@functools.partial(jax.jit, static_argnums=(1, 2))
def head_sections(w, heads: int, cut: int):
    """A projection's columns ``w [.., heads * width]`` whose every head
    is used in two parts, ``[cut | width - cut]``, in TWO SECTIONS: ``[the
    first part of all heads | the second part of all heads]``.  Stored as
    published, with the split INSIDE a head, the parts are cut after a
    reshape of the product's output, which XLA folds into the product as
    a form that wants the weight transposed: a copy of the layer's whole
    slice, every layer of every dispatch.  Over the sections the parts
    are lane ranges of a plain product's output, the weight read where
    it lies."""
    lead = w.shape[:-1]
    per_head = w.reshape(*lead, heads, -1)
    rest = per_head.shape[-1] - cut
    return jnp.concatenate(
        [per_head[..., :cut].reshape(*lead, heads * cut),
         per_head[..., cut:].reshape(*lead, heads * rest)], axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def mla_serving_layout(q_b, kv_b, heads: int, nope: int, rope: int):
    """The two up-projections of latent attention, published ``q_b [..,
    r_q, H * (nope + rope)]`` and ``kv_b [.., r_kv, H * (nope + dv)]``
    (any leading axes: a stack of layers), as the step reads them:
    ``(q_b in sections [.., r_q, H * nope | H * rope]``
    (:func:`head_sections`), ``W_UK [.., H, r_kv, nope], W_UV [.., H,
    r_kv, dv])``, head-major so that the absorbed products batch over a
    leading axis of the weight as stored."""
    kv = jnp.moveaxis(kv_b.reshape(*kv_b.shape[:-1], heads, -1), -2, -3)
    return (head_sections(q_b, heads, nope), kv[..., :nope],
            kv[..., nope:])


#: std of a seeded selection bias, in units of the mean score 1 / outputs:
#: large enough to move choices, as a trained bias does
ROUTER_BIAS_SCALE = 0.25


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig(TransformerConfig):
    """``TransformerConfig``'s sizes (``d_model``, ``num_heads``, ``d_ff``
    = the dense FFNs' width, ``num_layers``, ``vocab_size``,
    ``max_seq_len``) plus latent attention's and the routed experts'.
    The flags of the standard block that these blocks do not read are
    pinned by the builders in ``models/transformer.py``."""
    #: None: ONE full query projection (``q_b [d_model, H * (nope + rope)]``
    #: and no ``q_a`` / ``q_norm``)
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e7
    #: False: the ``qk_rope_head_dim`` lanes stay in the head and in the
    #: pool row but are not rotated (a position-free latent attention)
    mla_rotary: bool = True
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    expert_d_ff: int = 2048
    n_routed_experts: int = 512
    zero_expert_num: int = 0
    moe_topk: int = 12
    routed_scaling_factor: float = 1.0
    #: the gate's form (``moe/dropless.py::route``)
    router_scoring: str = "softmax"
    router_bias: bool = True
    norm_topk_prob: bool = False
    #: the contiguous range (lo, hi) of the routed experts held here;
    #: () = all of them
    experts_held: tuple = ()

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    def mla_params(self) -> int:
        d, h = self.d_model, self.num_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        q = (d * h * (dn + dr) if rq is None
             else d * rq + rq + rq * h * (dn + dr))
        return (q + d * (rkv + dr) + rkv + rkv * h * (dn + dv)
                + h * dv * d)

    def moe_params(self) -> int:
        """Router (and its bias) and the held experts of one layer."""
        lo, hi = self.held
        outs = self.router_outputs
        return (self.d_model * outs + (outs if self.router_bias else 0)
                + (hi - lo) * 3 * self.d_model * self.expert_d_ff)


class ExpertFFN:
    """The FFN sublayers of a block whose stack mixes dense layers and
    expert layers (``moe/dropless.py``'s share of the routed experts, a
    shared expert beside them), whatever its attention is — a mixin
    beside a ``TransformerLM``: the latent blocks here, and
    ``models/window_moe.py``'s block over a k/v pool.  It reads the
    config's ``expert_d_ff``, ``n_routed_experts``, ``router_outputs``,
    ``moe_topk``, ``routed_scaling_factor``, ``router_scoring``,
    ``router_bias``, ``norm_topk_prob``, ``held`` and, for the shared
    expert, ``n_shared_experts``; the block says what its out / down
    projections' init is scaled by (``_out_depth``)."""

    # -- init --------------------------------------------------------------
    def _out_depth(self) -> int:
        return self.config.num_layers

    def _ffn_init(self, k, width: Optional[int] = None):
        """A SwiGLU FFN of ``width`` (the dense FFNs' by default)."""
        c, dt = self.config, self.config.param_dtype
        width = width or c.ff_dim
        k1, k2, k3 = jax.random.split(k, 3)
        return {"fc_gate": L.dense_init(k1, c.d_model, width, False, 0.02,
                                        dt),
                "fc_in": L.dense_init(k2, c.d_model, width, False, 0.02, dt),
                "fc_out": {"kernel": L.scaled_init(
                    k3, (width, c.d_model), 0.02, self._out_depth(), dt)}}

    def _moe_init(self, k):
        c, dt = self.config, self.config.param_dtype
        lo, hi = c.held
        outs = c.router_outputs
        k1, k2, k3 = jax.random.split(k, 3)
        moe = {
            "router": L.dense_init(k1, c.d_model, outs, False, 0.02, dt),
            "experts": dropless.init_experts(
                k3, hi - lo, c.d_model, c.expert_d_ff, 0.02,
                0.02 / math.sqrt(2.0 * self._out_depth()), dt)}
        if c.router_bias:
            moe["bias"] = (ROUTER_BIAS_SCALE / outs
                           * jax.random.normal(k2, (outs,))).astype(dt)
        return moe

    def _moe_sublayer(self, p, u, row_valid=None, stack=None):
        """u [B, T, h] -> (this chip's part of the routed experts' output,
        counters).  ``stack = (every layer's experts, this layer's
        index)`` where the caller kept the expert stack out of its layer
        scan."""
        c = self.config
        b, t, h = u.shape
        flat = u.reshape(b * t, h)
        routing = dropless.route(
            flat, p["router"]["kernel"], p.get("bias"), c.moe_topk,
            c.routed_scaling_factor, scoring=c.router_scoring,
            renormalize=c.norm_topk_prob)
        experts, layer = stack or (p["experts"], None)
        y, counters = dropless.expert_share(
            experts, flat, routing, c.n_routed_experts, c.held, row_valid,
            layer=layer)
        return y.reshape(b, t, h), counters

    # -- the FFN sublayer of either kind of layer --------------------------
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

    def _ffn_sublayer(self, bp, u, row_valid=None, stack=None):
        """``F_l`` of either kind of layer: a dense layer is one whose
        parameters hold ``mlp`` and no ``moe``, and counts nothing."""
        if "moe" in bp:
            return self.expert_layer(bp, u, row_valid, stack)
        return (self._mlp(bp["mlp"], u),
                jnp.zeros((len(dropless.COUNTERS),), jnp.int32))


class LatentMoELM(ExpertFFN, TransformerLM):
    """``TransformerLM`` for blocks of latent attention and routed
    experts: same ``init`` / ``apply`` / ``init_paged_cache`` /
    ``partition_specs`` surface, its layers of the one serving step
    (``_paged_layers``); the block definition brings the scanned unit."""

    #: attention sublayers in one layer of the block
    ATTN_SUBLAYERS = 1
    #: what the serving step counts in the program, a dispatch (the
    #: serving engine carries them out on its one result array)
    WALK_COUNTERS = ("latent_tokens_read", "latent_pages_read",
                     "latent_pages_in_runs")
    PAGED_COUNTERS = dropless.COUNTERS + WALK_COUNTERS

    def __init__(self, config: LatentMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        lo, hi = c.held
        if not 0 <= lo < hi <= c.n_routed_experts:
            raise ValueError(f"experts_held {c.experts_held} is not a "
                             f"range of the {c.n_routed_experts} experts")
        if c.mla_rotary:
            self._cos, self._sin = L.rotary_freqs(
                c.qk_rope_head_dim, c.qk_rope_head_dim, c.max_seq_len,
                c.rope_theta)
        self._sm_scale = 1.0 / math.sqrt(c.qk_nope_head_dim
                                         + c.qk_rope_head_dim)
        self._q_scale = (math.sqrt(c.d_model / c.q_lora_rank)
                         if c.mla_scale_q_lora and c.q_lora_rank else 1.0)
        self._kv_scale = (math.sqrt(c.d_model / c.kv_lora_rank)
                          if c.mla_scale_kv_lora else 1.0)

    # -- what a block definition brings ------------------------------------
    def _latent_block(self, bp, x, attend, pools=None, row_valid=None,
                      stack=None):
        """One layer.  ``attend(j, p, x_normed, pools) -> (out, pools)``
        is attention sublayer ``j``; ``pools`` is whatever state it
        threads (the paged path's pool, nothing for full sequences);
        ``stack = (every layer's experts, this layer's index)`` where the
        caller kept the expert stack out of its layer scan.  Returns
        ``(y, pools, the MoE sublayer's counters)``."""
        raise NotImplementedError

    def _leading_blocks(self, params) -> Optional[Dict]:
        """The stacked parameters of the layers that run before
        ``params["blocks"]``, or None."""
        return None

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the latent-attention MoE block serves and does not train "
                "yet: its latent attention has no training kernel (ROADMAP "
                "B8); its experts' grouped product (moe/dropless.py "
                "grouped_matmul) differentiates, as models/cca_moe.py "
                "trains through it")

    def init_cache(self, batch, max_len, dtype=None):
        raise NotImplementedError(
            "the latent-attention MoE block has no dense KV cache "
            "(generate()): it decodes through the paged serving path only")

    def _paged_supported(self) -> Optional[str]:
        return None

    def paged_refusal(self, kv_bits: int = 0, spec: bool = False,
                      mesh_model: int = 1, mesh_data: int = 1,
                      host_cache: bool = False,
                      weight_quant: bool = False) -> Optional[str]:
        """Why the serving engine cannot be built this way around the
        latent pool, or None."""
        if weight_quant:
            return ("int8 weight-only serving (quant.enabled): the expert "
                    "stack is read in place by the grouped-product kernel, "
                    "not dequantized a layer at a time")
        if spec:
            return ("the speculative lane does not verify through the "
                    "latent pool yet (the draft's k/v pool and the "
                    "target's latent pool have no common row)")
        if kv_bits:
            return (f"serving.kv_cache_bits={kv_bits}: a latent row is "
                    f"already the compressed cache; int8 / int4 latent "
                    f"rows have no quantizer or kernel path")
        if mesh_model > 1 or mesh_data > 1:
            return (
                "the latent-attention MoE block serves on one chip: its "
                "attention has one shared latent row a token (nothing to "
                "shard over heads in the pool) and its experts are not "
                "exchanged across chips yet (ROADMAP B6) — use "
                "serving.mesh data=1, model=1")
        if host_cache:
            return ("serving.host_cache: the host tier's block codec "
                    "encodes kv_heads x head_dim rows of k and v, not "
                    "latent rows nor a selection's indexer keys")
        return None

    # -- init --------------------------------------------------------------
    def _out_depth(self) -> int:
        """What the out / down projections' init is scaled by: the
        attention sublayers of the stack (each comes with one FFN)."""
        return self.ATTN_SUBLAYERS * self.config.num_layers

    def _mla_init(self, k):
        c, dt = self.config, self.config.param_dtype
        d, h = c.d_model, c.num_heads
        k1, k2, k3, k4, k5 = jax.random.split(k, 5)
        q_in = {} if c.q_lora_rank is None else {
            "q_a": L.dense_init(k1, d, c.q_lora_rank, False, 0.02, dt),
            "q_norm": L.rmsnorm_init(None, c.q_lora_rank, dt)}
        return {
            **q_in,
            "q_b": L.dense_init(
                k2, c.q_lora_rank or d,
                h * (c.qk_nope_head_dim + c.qk_rope_head_dim), False,
                self._q_b_std(), dt),
            "kv_a": L.dense_init(k3, d, c.kv_lora_rank + c.qk_rope_head_dim,
                                 False, 0.02, dt),
            "kv_norm": L.rmsnorm_init(None, c.kv_lora_rank, dt),
            "kv_b": L.dense_init(
                k4, c.kv_lora_rank,
                h * (c.qk_nope_head_dim + c.v_head_dim), False, 0.02, dt),
            "out": {"kernel": L.scaled_init(
                k5, (h * c.v_head_dim, d), 0.02, self._out_depth(), dt)},
        }

    def _q_b_std(self) -> float:
        """The seeded std of the query up-projection (a block definition
        may want another, for logits a check can see)."""
        return 0.02

    def serving_params(self, params) -> Dict:
        """``params`` with every latent attention's ``q_b`` and ``kv_b``
        (the published layout: ``init``'s, a checkpoint's) replaced by
        ``q_sections``, ``w_uk`` and ``w_uv``
        (:func:`mla_serving_layout`), the one layout the sublayers read.
        Told by the tree's keys: a tree that holds no ``q_b`` comes back
        as it is, the same object.  ``init_inference`` lays the weights
        out once, as it places them; ``hidden_states_and_aux`` and
        ``_apply_paged_mixed`` pass their tree through here, so a caller
        that brings the published one pays the transposes in its own
        program, once a call and outside the layer scans."""
        c = self.config

        def lay(tree):
            if not isinstance(tree, dict):
                return tree
            if "q_b" in tree:
                q, w_uk, w_uv = mla_serving_layout(
                    tree["q_b"]["kernel"], tree["kv_b"]["kernel"],
                    c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim)
                return dict({k: v for k, v in tree.items()
                             if k not in ("q_b", "kv_b")},
                            q_sections={"kernel": q}, w_uk=w_uk, w_uv=w_uv)
            laid = {k: lay(v) for k, v in tree.items()}
            same = all(laid[k] is v for k, v in tree.items())
            return tree if same else laid
        return lay(params)

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves on one chip (no
        tensor-parallel rules yet).  Of ``params``, or of the tree
        ``serving_params`` gives."""
        if params is None:
            params = jax.eval_shape(lambda: self.serving_params(
                self.init(jax.random.PRNGKey(0))))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- the sublayers -----------------------------------------------------
    @scoped("attn_proj")
    def _q_latent(self, p, x):
        """x [B, T, h] -> the normalised (and scaled) query latent
        [B, T, r_q]; ``x`` itself where there is no query latent
        (``q_lora_rank`` None)."""
        if self.config.q_lora_rank is None:
            return x
        cq = self._norm_fn("attn_proj")(p["q_norm"],
                                        L.dense_apply(p["q_a"], x))
        if self._q_scale != 1.0:
            cq = (cq * self._q_scale).astype(x.dtype)
        return cq

    @scoped("attn_proj")
    def _mla_project(self, p, x, positions, cq=None):
        """x [B, T, h] -> (q_nope [B,T,H,dn], q_rope [B,T,H,dr] rotated,
        c [B,T,r_kv] normalised, k_rope [B,T,dr] rotated; neither rotated
        where ``mla_rotary`` is off).  ``cq``: the query latent, where the
        caller already has it."""
        c = self.config
        b, t, _ = x.shape
        norm = self._norm_fn("attn_proj")
        if cq is None:
            cq = self._q_latent(p, x)
        q = L.dense_apply(p["q_sections"], cq)
        cut = c.num_heads * c.qk_nope_head_dim
        q_nope = q[..., :cut].reshape(b, t, c.num_heads, c.qk_nope_head_dim)
        q_rope = q[..., cut:].reshape(b, t, c.num_heads, c.qk_rope_head_dim)
        kv = L.dense_apply(p["kv_a"], x)
        lat = norm(p["kv_norm"], kv[..., :c.kv_lora_rank])
        if self._kv_scale != 1.0:
            lat = (lat * self._kv_scale).astype(x.dtype)
        k_rope = kv[..., None, c.kv_lora_rank:]          # one shared head
        if not c.mla_rotary:
            return q_nope, q_rope, lat, k_rope[:, :, 0]
        q_rope = L.apply_rotary(q_rope, self._cos, self._sin, positions,
                                interleaved=False)
        k_rope = L.apply_rotary(k_rope, self._cos, self._sin, positions,
                                interleaved=False)[:, :, 0]
        return q_nope, q_rope, lat, k_rope

    @scoped("attn_proj")
    def _kv_b(self, p, dtype):
        """(W_UK [H, r, dn], W_UV [H, r, dv])."""
        return p["w_uk"].astype(dtype), p["w_uv"].astype(dtype)

    def _mla_expanded(self, p, x, positions, cq=None, chosen=None):
        """Full-sequence causal MLA in the expanded form, plain XLA —
        over every earlier position, or over ``chosen [B, T, T]`` of
        them."""
        b, t, _ = x.shape
        q_nope, q_rope, lat, k_rope = self._mla_project(p, x, positions, cq)
        w_uk, w_uv = self._kv_b(p, x.dtype)
        with jax.named_scope("attn_proj"):
            k_nope = jnp.einsum("btr,hrd->bthd", lat, w_uk)
            v = jnp.einsum("btr,hrd->bthd", lat, w_uv)
        with jax.named_scope("attn_kernel"):
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                              preferred_element_type=jnp.float32)
                 ) * self._sm_scale
            if chosen is None:
                chosen = jnp.tril(jnp.ones((t, t), bool))[None]
            s = jnp.where(chosen[:, None], s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd",
                           jax.nn.softmax(s, axis=-1).astype(x.dtype), v)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o.reshape(b, t, -1))

    # -- full sequences ----------------------------------------------------
    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        """Forward up to the final norm, expanded form, plain XLA: the
        leading layers, then ``params["blocks"]``."""
        params = self.serving_params(params)
        x = self._embed_tokens(params, input_ids)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     x.shape[:2])

        def attend(j, p, xn, _):
            return self._mla_expanded(p, xn, positions), None

        def layer(x, bp):
            return self._latent_block(self.block_transform(bp), x,
                                      attend)[0], None
        lead = self._leading_blocks(params)
        if lead is not None:
            x, _ = jax.lax.scan(layer, x, lead)
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        return (self._norm_fn("head")(params["ln_f"], x),
                jnp.zeros((), jnp.float32))

    # -- paged serving -----------------------------------------------------
    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=None, kv_bits: int = 0) -> Dict:
        """The latent pool: for each attention sublayer (sublayer ``j`` of
        layer ``l`` at index ``ATTN_SUBLAYERS * l + j``) ``num_blocks``
        pages of ``block_size`` tokens in ONE buffer, ``k``; a token's
        row is ``[c | k_rope | 0]`` in whole lane tiles (512 + 64 values
        in 640 lanes: 1,152 useful bytes a token a sublayer in bfloat16,
        1,280 held).  There is no second buffer: ``v`` is None."""
        reason = self.paged_refusal(kv_bits=kv_bits)
        if reason is not None:
            raise NotImplementedError(reason)
        c = self.config
        dtype = dtype or c.dtype
        from ..ops.transformer.paged_decode_attention import (
            latent_pool_lanes)
        lanes = latent_pool_lanes(c.kv_lora_rank, c.qk_rope_head_dim)
        return {"k": jnp.zeros((self._pool_sublayers(), num_blocks,
                                block_size, lanes), dtype),
                "v": None}

    def _pool_sublayers(self) -> int:
        """Attention sublayers that write the pool: every layer's."""
        return self.ATTN_SUBLAYERS * self.config.num_layers

    def _paged_latent_attention(self, p, xn, pool, step: MixedStep, tables,
                                null):
        """One attention sublayer of the serving step, absorbed form: the
        rows' latents and rotary keys scatter into this sublayer's pages
        (``tables``: the slots', offset to the sublayer's blocks; masked
        rows to its null block ``null``), then the decode rows and the
        chunk rows attend through the latent kernel and come back through
        ``W_UV`` and the out projection."""
        from ..ops.transformer.paged_decode_attention import (
            mla_paged_decode_attention, mla_paged_prefill_attention)
        lens, act = step.lens, step.act
        bsl, cw = step.slots, step.chunk
        t = xn.shape[1]
        q_nope, q_rope, lat, k_rope = self._mla_project(p, xn,
                                                        step.positions)
        with jax.named_scope("pool_write"):
            write, ctable = lane_pool_rows(step, tables, pool.shape[1], null)
            pool = scatter_rows(
                pool, jnp.concatenate(write),
                jnp.concatenate([lat[0], k_rope[0]], axis=-1))
        w_uk, w_uv = self._kv_b(p, xn.dtype)
        with jax.named_scope("attn_proj"):
            q_lat = jnp.einsum("thd,hrd->thr", q_nope[0], w_uk)
        with jax.named_scope("attn_kernel"):
            o_parts = [mla_paged_decode_attention(
                q_lat[:bsl], q_rope[0, :bsl], pool,
                jnp.where(act, lens + 1, 0), tables, self._sm_scale)]
            if cw:
                o_parts.append(mla_paged_prefill_attention(
                    q_lat[bsl:], q_rope[0, bsl:], pool, step.chunk_start,
                    step.chunk_len, ctable, self._sm_scale))
            o_lat = jnp.concatenate(o_parts) if cw else o_parts[0]
        with jax.named_scope("attn_proj"):
            o = jnp.einsum("thr,hrd->thd", o_lat, w_uv)
            return L.dense_apply(p["out"], o.reshape(1, t, -1)), pool

    # -- what the step's two scans carry, and what a layer is told ---------
    def _paged_probe(self, state):
        """What a check may see of a layer's attention in the carry
        (``_apply_paged_mixed(probe=True)``): nothing, for a block that
        reads every page."""
        return None

    def _layer_meta(self, step, first, count):
        """What layers ``first .. first + count`` of the stack are each
        told (a scan's ``xs``): the block offset of the layer's first
        sublayer into the pool."""
        with jax.named_scope("pool_write"):
            return (first + jnp.arange(count, dtype=step.tables.dtype)
                    ) * (self.ATTN_SUBLAYERS * step.num_blocks)

    def _paged_attend(self, params, step, off):
        """``attend`` (``_latent_block``'s contract) of the layer whose
        meta is ``off``."""
        def attend(j, p, xn, state):
            with jax.named_scope("pool_write"):
                at = off + j * step.num_blocks
                tables_at = step.tables + at
            out, pool = self._paged_latent_attention(
                p, xn, state["k"], step, tables_at, at)
            return out, dict(state, k=pool)
        return attend

    def _paged_layers(self, params, x, state, step: MixedStep, probe):
        """The leading layers' scan, then the expert layers' (sublayer
        ``j`` of layer ``l`` is the block offset ``(ATTN_SUBLAYERS * l +
        j) * num_blocks`` into the one buffer): ``counts`` the expert
        layers' ``dropless.COUNTERS``, ``seen`` every layer's
        ``_paged_probe`` of the carry, stacked."""
        row_valid = step.row_valid

        def attend_at(meta):
            return self._paged_attend(params, step, meta)

        lead, leading = self._leading_blocks(params), 0
        if lead is not None:
            leading = jax.tree_util.tree_leaves(lead)[0].shape[0]

            def lead_fn(carry, xs):
                bp, meta = xs
                y, state, _ = self._latent_block(
                    self.block_transform(bp), carry[0], attend_at(meta),
                    carry[1], row_valid)
                return (y, state), (self._paged_probe(state) if probe
                                    else None)
            (x, state), seen_lead = jax.lax.scan(
                lead_fn, (x, state),
                (lead, self._layer_meta(step, 0, leading)))

        blocks, experts = dropless.split_experts(params["blocks"])
        scanned = experts["w_up"].shape[0]

        def scan_fn(carry, xs):
            y, state, counts = carry
            bp, meta, layer = xs
            y, state, moe_counts = self._latent_block(
                self.block_transform(bp), y, attend_at(meta), state,
                row_valid, (experts, layer))
            with jax.named_scope("expert_layout"):
                counts = counts + moe_counts
            return (y, state, counts), (self._paged_probe(state) if probe
                                        else None)

        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        (x, state, counts), seen = jax.lax.scan(
            scan_fn, (x, state, zero),
            (blocks, self._layer_meta(step, leading, scanned),
             jnp.arange(scanned, dtype=jnp.int32)))
        if probe and lead is not None:
            seen = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b]), seen_lead, seen)
        return x, state, dict(zip(dropless.COUNTERS, counts)), seen

# ---------------------------------------------------------------------------
# leading dense layers, then expert layers with a shared expert
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DenseLeadMoEConfig(LatentMoEConfig):
    """``LatentMoEConfig`` for a stack of two kinds of layer:
    ``first_k_dense`` dense layers, then expert layers in each of which
    ``n_shared_experts`` shared experts run beside the routed ones."""
    first_k_dense: int = 3
    n_shared_experts: int = 1

    @property
    def scan_length(self) -> int:
        """The expert layers: what ``params["blocks"]`` stacks."""
        return self.num_layers - self.first_k_dense

    def stack_params(self, shell: int) -> int:
        """Parameters of the whole model, from those of one layer outside
        its FFN (attention and norms)."""
        d = self.d_model
        dense = shell + 3 * d * self.ff_dim
        expert = (shell + self.moe_params()
                  + 3 * d * self.n_shared_experts * self.expert_d_ff)
        return (self.first_k_dense * dense + self.scan_length * expert
                + 2 * self.vocab_size * d + d)


class DenseLeadMoELM(LatentMoELM):
    """``LatentMoELM`` for such a stack: the ``first_k_dense`` leading
    layers are ``params["dense_blocks"]`` and the expert layers
    ``params["blocks"]``; a block definition brings ``_shell_init`` (one
    layer outside its FFN) and ``_latent_block``.  The shared expert is a
    SwiGLU of ``n_shared_experts * expert_d_ff`` that every row goes
    through, whatever the router says — whole on every chip of a
    deployment, so computed here for every row and not part of
    ``experts_held``'s share."""

    PAGED_COUNTERS = LatentMoELM.PAGED_COUNTERS + ("moe_rows_shared",)

    def __init__(self, config: DenseLeadMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        if not 0 <= config.first_k_dense < config.num_layers:
            raise ValueError(
                f"first_k_dense {config.first_k_dense} leaves no expert "
                f"layer among {config.num_layers}")

    # -- init --------------------------------------------------------------
    def _shell_init(self, k) -> Dict:
        raise NotImplementedError

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

    def _paged_counters(self, step, carry, counts, walk) -> Dict[str, Any]:
        """``moe_rows_shared``: every row that carries a token goes
        through the shared expert of every expert layer."""
        return dict(super()._paged_counters(step, carry, counts, walk),
                    moe_rows_shared=jnp.sum(step.row_valid, dtype=jnp.int32)
                    * self.config.scan_length)
