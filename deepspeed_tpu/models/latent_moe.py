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

Full sequences (``apply``) run the EXPANDED form in plain XLA.  These
blocks do not train: the experts' grouped product is a forward-only
kernel (``training_refusal``).  Serving runs the ABSORBED form through
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
from ..observability.overlap import scoped
from .transformer import TransformerConfig, TransformerLM

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
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e7
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
        return (d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv
                + rkv * h * (dn + dv) + h * dv * d)

    def moe_params(self) -> int:
        """Router (and its bias) and the held experts of one layer."""
        lo, hi = self.held
        outs = self.router_outputs
        return (self.d_model * outs + (outs if self.router_bias else 0)
                + (hi - lo) * 3 * self.d_model * self.expert_d_ff)


class LatentMoELM(TransformerLM):
    """``TransformerLM`` for blocks of latent attention and routed
    experts: same ``init`` / ``apply`` / ``init_paged_cache`` /
    ``_apply_paged_mixed`` / ``partition_specs`` surface; the block
    definition brings the scanned unit."""

    #: attention sublayers in one layer of the block
    ATTN_SUBLAYERS = 1
    #: what ``_apply_paged_mixed`` counts in the program, a dispatch (the
    #: serving engine carries them out on its one result array)
    PAGED_COUNTERS = dropless.COUNTERS + ("latent_tokens_read",)

    def __init__(self, config: LatentMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        lo, hi = c.held
        if not 0 <= lo < hi <= c.n_routed_experts:
            raise ValueError(f"experts_held {c.experts_held} is not a "
                             f"range of the {c.n_routed_experts} experts")
        self._cos, self._sin = L.rotary_freqs(
            c.qk_rope_head_dim, c.qk_rope_head_dim, c.max_seq_len,
            c.rope_theta)
        self._sm_scale = 1.0 / math.sqrt(c.qk_nope_head_dim
                                         + c.qk_rope_head_dim)
        self._q_scale = (math.sqrt(c.d_model / c.q_lora_rank)
                         if c.mla_scale_q_lora else 1.0)
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

    def _extra_counters(self, row_valid) -> list:
        """What the block counts a dispatch beyond ``PAGED_COUNTERS`` of
        this class, in its own ``PAGED_COUNTERS``' order."""
        return []

    # -- refusals ----------------------------------------------------------
    _refuse_mesh = (
        "the latent-attention MoE block serves on one chip: its attention "
        "has one shared latent row a token (nothing to shard over heads "
        "in the pool) and its experts are not exchanged across chips yet "
        "(ROADMAP B6) — use serving.mesh data=1, model=1")

    def training_refusal(self) -> Optional[str]:
        return ("the latent-attention MoE block serves and does not train "
                "yet: its experts' grouped product (moe/dropless.py "
                "grouped_matmul) is a forward-only kernel and its latent "
                "attention has no training kernel (ROADMAP B8)")

    def tp_serving_view(self, model_shards, tp_axis, dp_axis):
        if model_shards > 1 or dp_axis is not None:
            raise NotImplementedError(self._refuse_mesh)
        return self

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
            return self._refuse_mesh
        if host_cache:
            return ("serving.host_cache: the host tier's block codec "
                    "encodes kv_heads x head_dim rows of k and v, not "
                    "latent rows")
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
        return {
            "q_a": L.dense_init(k1, d, c.q_lora_rank, False, 0.02, dt),
            "q_norm": L.rmsnorm_init(None, c.q_lora_rank, dt),
            "q_b": L.dense_init(
                k2, c.q_lora_rank,
                h * (c.qk_nope_head_dim + c.qk_rope_head_dim), False,
                0.02, dt),
            "kv_a": L.dense_init(k3, d, c.kv_lora_rank + c.qk_rope_head_dim,
                                 False, 0.02, dt),
            "kv_norm": L.rmsnorm_init(None, c.kv_lora_rank, dt),
            "kv_b": L.dense_init(
                k4, c.kv_lora_rank,
                h * (c.qk_nope_head_dim + c.v_head_dim), False, 0.02, dt),
            "out": {"kernel": L.scaled_init(
                k5, (h * c.v_head_dim, d), 0.02, self._out_depth(), dt)},
        }

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

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves on one chip (no
        tensor-parallel rules yet)."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- the sublayers -----------------------------------------------------
    @scoped("attn_proj")
    def _mla_project(self, p, x, positions):
        """x [B, T, h] -> (q_nope [B,T,H,dn], q_rope [B,T,H,dr] rotated,
        c [B,T,r_kv] normalised, k_rope [B,T,dr] rotated)."""
        c = self.config
        b, t, _ = x.shape
        norm = self._norm_fn("attn_proj")
        cq = norm(p["q_norm"], L.dense_apply(p["q_a"], x))
        if self._q_scale != 1.0:
            cq = (cq * self._q_scale).astype(x.dtype)
        q = L.dense_apply(p["q_b"], cq).reshape(
            b, t, c.num_heads, c.qk_nope_head_dim + c.qk_rope_head_dim)
        q_nope, q_rope = (q[..., :c.qk_nope_head_dim],
                          q[..., c.qk_nope_head_dim:])
        kv = L.dense_apply(p["kv_a"], x)
        lat = norm(p["kv_norm"], kv[..., :c.kv_lora_rank])
        if self._kv_scale != 1.0:
            lat = (lat * self._kv_scale).astype(x.dtype)
        k_rope = kv[..., None, c.kv_lora_rank:]          # one shared head
        q_rope = L.apply_rotary(q_rope, self._cos, self._sin, positions,
                                interleaved=False)
        k_rope = L.apply_rotary(k_rope, self._cos, self._sin, positions,
                                interleaved=False)[:, :, 0]
        return q_nope, q_rope, lat, k_rope

    @scoped("attn_proj")
    def _kv_b(self, p, dtype):
        """``kv_b`` as (W_UK [r, H, dn], W_UV [r, H, dv])."""
        c = self.config
        w = p["kv_b"]["kernel"].astype(dtype).reshape(
            c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _mla_expanded(self, p, x, positions):
        """Full-sequence causal MLA in the expanded form, plain XLA."""
        b, t, _ = x.shape
        q_nope, q_rope, lat, k_rope = self._mla_project(p, x, positions)
        w_uk, w_uv = self._kv_b(p, x.dtype)
        with jax.named_scope("attn_proj"):
            k_nope = jnp.einsum("btr,rhd->bthd", lat, w_uk)
            v = jnp.einsum("btr,rhd->bthd", lat, w_uv)
        with jax.named_scope("attn_kernel"):
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                              preferred_element_type=jnp.float32)
                 ) * self._sm_scale
            causal = jnp.tril(jnp.ones((t, t), bool))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd",
                           jax.nn.softmax(s, axis=-1).astype(x.dtype), v)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o.reshape(b, t, -1))

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

    # -- full sequences ----------------------------------------------------
    def hidden_states_and_aux(self, params, input_ids, rng=None, train=True,
                              token_type_ids=None):
        """Forward up to the final norm, expanded form, plain XLA: the
        leading layers, then ``params["blocks"]``."""
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
        return {"k": jnp.zeros((self.ATTN_SUBLAYERS * c.num_layers,
                                num_blocks, block_size, lanes), dtype),
                "v": None}

    def _paged_latent_attention(self, p, xn, pool, tables, lens, act,
                                chunk_slot, chunk_start, chunk_len, null,
                                positions):
        """One attention sublayer of the mixed step, absorbed form: the
        rows' latents and rotary keys scatter into this sublayer's pages
        (``tables`` already offset; masked rows to its null block), then
        the decode rows and the chunk rows attend through the latent
        kernel and come back through ``W_UV`` and the out projection."""
        from ..ops.transformer.paged_decode_attention import (
            mla_paged_decode_attention, mla_paged_prefill_attention)
        bsl = lens.shape[0]
        t = xn.shape[1]
        cw = t - bsl
        blk, npages = pool.shape[1], tables.shape[1]
        q_nope, q_rope, lat, k_rope = self._mla_project(p, xn, positions)
        with jax.named_scope("pool_write"):
            slot = jnp.arange(bsl)
            null_row = null * blk
            write = [jnp.where(
                act, tables[slot, lens // blk] * blk + lens % blk, null_row)]
            if cw:
                ci = jnp.arange(cw)
                cpos = chunk_start + ci
                ctable = tables[chunk_slot]
                write.append(jnp.where(
                    ci < chunk_len,
                    ctable[jnp.minimum(cpos // blk, npages - 1)] * blk
                    + cpos % blk, null_row))
            write = jnp.concatenate(write)
            lanes = pool.shape[2]
            rows = jnp.concatenate([lat[0], k_rope[0]], axis=-1)
            rows = jnp.pad(rows.astype(pool.dtype),
                           ((0, 0), (0, lanes - rows.shape[1])))
            pool = pool.reshape(-1, lanes).at[write].set(rows).reshape(
                pool.shape)
        w_uk, w_uv = self._kv_b(p, xn.dtype)
        with jax.named_scope("attn_proj"):
            q_lat = jnp.einsum("thd,rhd->thr", q_nope[0], w_uk)
        with jax.named_scope("attn_kernel"):
            o_parts = [mla_paged_decode_attention(
                q_lat[:bsl], q_rope[0, :bsl], pool,
                jnp.where(act, lens + 1, 0), tables, self._sm_scale)]
            if cw:
                o_parts.append(mla_paged_prefill_attention(
                    q_lat[bsl:], q_rope[0, bsl:], pool, chunk_start,
                    chunk_len, ctable, self._sm_scale))
            o_lat = jnp.concatenate(o_parts) if cw else o_parts[0]
        with jax.named_scope("attn_proj"):
            o = jnp.einsum("thr,rhd->thd", o_lat, w_uv)
            return L.dense_apply(p["out"], o.reshape(1, t, -1)), pool

    def _apply_paged_mixed(self, params, cache, dec_tokens, dec_active,
                           chunk_ids, chunk_slot, chunk_start, chunk_len,
                           spec_tokens=None, spec_active=None):
        """The mixed step of ``TransformerLM._apply_paged_mixed`` for a
        latent block: same operands, same results, the latent pool as the
        scans' carry (sublayer ``j`` of layer ``l`` is the block offset
        ``(ATTN_SUBLAYERS * l + j) * num_blocks`` into the one buffer).
        ``new_cache`` also holds ``counters`` — int32
        ``[len(PAGED_COUNTERS)]``, this dispatch's sums over the
        layers."""
        if spec_tokens is not None:
            raise NotImplementedError(self.paged_refusal(spec=True))
        if cache.get("k_scale") is not None:
            raise NotImplementedError(self.paged_refusal(kv_bits=8))
        tables, lens = cache["block_tables"], cache["lens"]
        bsl, cw = dec_tokens.shape[0], chunk_ids.shape[0]
        with jax.named_scope("embed"):
            act = dec_active > 0
            ci = jnp.arange(cw)
            positions = jnp.concatenate(
                [lens, jnp.where(ci < chunk_len, chunk_start + ci, 0)])[None]
            ids = jnp.concatenate([dec_tokens, chunk_ids])[None]
            row_valid = jnp.concatenate([act, ci < chunk_len])
        x = self._embed_tokens(params, ids)
        ns, nb = cache["k"].shape[:2]
        pool = cache["k"].reshape(ns * nb, *cache["k"].shape[2:])
        per_layer = self.ATTN_SUBLAYERS * nb

        def attend_at(off):
            def attend(j, p, xn, pool):
                with jax.named_scope("pool_write"):
                    at = off + j * nb
                    tables_at = tables + at
                return self._paged_latent_attention(
                    p, xn, pool, tables_at, lens, act, chunk_slot,
                    chunk_start, chunk_len, at, positions)
            return attend

        def offsets(first, count):
            with jax.named_scope("pool_write"):
                return (first + jnp.arange(count, dtype=tables.dtype)
                        ) * per_layer

        lead, leading = self._leading_blocks(params), 0
        if lead is not None:
            leading = jax.tree_util.tree_leaves(lead)[0].shape[0]

            def lead_fn(carry, xs):
                bp, off = xs
                y, pool, _ = self._latent_block(
                    self.block_transform(bp), carry[0], attend_at(off),
                    carry[1], row_valid)
                return (y, pool), None
            (x, pool), _ = jax.lax.scan(lead_fn, (x, pool),
                                        (lead, offsets(0, leading)))

        # the expert stack stays out of the scan's xs: sliced per layer
        # it would be copied whole, every step, to reach the kernel
        blocks = params["blocks"]
        experts = blocks["moe"]["experts"]
        blocks = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                                   if k != "experts"})
        scanned = experts["w_up"].shape[0]

        def scan_fn(carry, xs):
            y, pool, counts = carry
            bp, off, layer = xs
            y, pool, moe_counts = self._latent_block(
                self.block_transform(bp), y, attend_at(off), pool,
                row_valid, (experts, layer))
            with jax.named_scope("expert_layout"):
                counts = counts + moe_counts
            return (y, pool, counts), None

        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        (x, pool, counts), _ = jax.lax.scan(
            scan_fn, (x, pool, zero),
            (blocks, offsets(leading, scanned),
             jnp.arange(scanned, dtype=jnp.int32)))
        x = self._norm_fn("head")(params["ln_f"], x)
        with jax.named_scope("head"):
            if cw:
                last = jax.lax.dynamic_slice_in_dim(
                    x[0], bsl + jnp.maximum(chunk_len - 1, 0), 1, axis=0)
                logits = self._project(
                    params, jnp.concatenate([x[0, :bsl], last])[None])
                chunk_logits = logits[0, bsl]
            else:
                logits = self._project(params, x[0, :bsl][None])
                chunk_logits = jnp.zeros((logits.shape[-1],), logits.dtype)
            dec_logits = logits[0, :bsl]
        with jax.named_scope("pool_write"):
            # the live context the latent kernel walked, once a sublayer
            read = (jnp.sum(jnp.where(act, lens + 1, 0))
                    + jnp.where(chunk_len > 0, chunk_start + chunk_len, 0))
            new_lens = (lens + act.astype(lens.dtype)).at[chunk_slot].add(
                chunk_len, mode="drop")
            extra = [jnp.asarray(v, jnp.int32)[None]
                     for v in self._extra_counters(row_valid)]
            counters = jnp.concatenate(
                [counts, (read * ns).astype(jnp.int32)[None], *extra])
        new_cache = {
            "k": pool.reshape(ns, nb, *pool.shape[1:]), "v": None,
            "block_tables": tables, "lens": new_lens, "counters": counters}
        return dec_logits, chunk_logits, new_cache
