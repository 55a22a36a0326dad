"""The shortcut-connected latent-attention MoE block (LongCat-Flash
family), as a block definition of its own behind ``TransformerLM``'s
interfaces.

One published "layer" is two latent-attention (MLA) sublayers, two dense
SwiGLU FFNs and one MoE whose input is taken after the first attention
and whose output is added after the second FFN (the shortcut)::

    a = x + MLA_0(N_0(x));  u = N_1(a);  m = MoE(u)
    b = a + FFN_0(u);       c = b + MLA_1(N_2(b))
    y = c + FFN_1(N_3(c)) + m

RMSNorm, no bias anywhere, untied head.  ``MLA``: low-rank queries
(``q_a`` -> RMSNorm * sqrt(h / r_q) -> ``q_b``, per head 128 no-rope | 64
rotary), one shared latent ``c`` of rank 512 (RMSNorm * sqrt(h / r_kv))
that ``kv_b`` expands to per-head keys and values, and ONE rotary key head
of 64 shared by all heads.  ``MoE``: ``moe/dropless.py`` — a top-12
router over 512 routed and 256 identity experts, and this chip's share
of the routed experts (``experts_held``).

Full sequences (``apply``) run the EXPANDED form in plain XLA.  The block
does not train: the experts' grouped product is a forward-only kernel
(``training_refusal``).  Serving runs the ABSORBED form through the paged
path: the pool row of a token is ``[c (512) | k_rope (64) | 0 (64)]`` for
each of the two attention sublayers of each layer — ONE buffer ``k [2 L,
num_blocks, block, 640]`` (there is no second operand: the pool's ``v`` is
None) — and
``ops/transformer/paged_decode_attention.py``'s latent kernel attends
64 heads against one shared page.  Everything the engine, the scheduler
and the allocator do is unchanged: a block is 16 tokens whatever a row
holds.
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

#: std of the seeded selection bias, in units of the mean score 1 / (E + Z):
#: large enough to move choices, as a trained bias does
ROUTER_BIAS_SCALE = 0.25


@dataclasses.dataclass(frozen=True)
class ShortcutMoEConfig(TransformerConfig):
    """``TransformerConfig``'s sizes (``d_model``, ``num_heads``, ``d_ff``
    = the dense FFNs' width, ``num_layers``, ``vocab_size``,
    ``max_seq_len``) plus what the block adds.  The flags of the standard
    block that this one does not read are pinned by
    :func:`longcat_flash_config`."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e7
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    expert_d_ff: int = 2048
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    #: the contiguous range (lo, hi) of the routed experts held here;
    #: () = all of them
    experts_held: tuple = ()

    @classmethod
    def model_class(cls):
        return ShortcutMoELM

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    def num_params(self) -> int:
        d, h = self.d_model, self.num_heads
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        mla = (d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv
               + rkv * h * (dn + dv) + h * dv * d)
        ffn = 3 * d * self.ff_dim
        lo, hi = self.held
        router = d * (self.n_routed_experts + self.zero_expert_num) \
            + self.n_routed_experts + self.zero_expert_num
        experts = (hi - lo) * 3 * d * self.expert_d_ff
        per_layer = 2 * mla + 2 * ffn + router + experts + 4 * d
        return (self.num_layers * per_layer + 2 * self.vocab_size * d + d)


class ShortcutMoELM(TransformerLM):
    """``TransformerLM`` with the shortcut block: same ``init`` /
    ``apply`` / ``loss`` / ``init_paged_cache`` / ``_apply_paged_mixed`` /
    ``partition_specs`` surface, another scanned unit."""

    #: what ``_apply_paged_mixed`` counts in the program, a dispatch (the
    #: serving engine carries them out on its one result array)
    PAGED_COUNTERS = dropless.COUNTERS + ("latent_tokens_read",)

    def __init__(self, config: ShortcutMoEConfig, constrain=None,
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

    # -- init --------------------------------------------------------------
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
                k5, (h * c.v_head_dim, d), 0.02, 2 * c.num_layers, dt)},
        }

    def _ffn_init(self, k):
        c, dt = self.config, self.config.param_dtype
        k1, k2, k3 = jax.random.split(k, 3)
        return {"fc_gate": L.dense_init(k1, c.d_model, c.ff_dim, False,
                                        0.02, dt),
                "fc_in": L.dense_init(k2, c.d_model, c.ff_dim, False, 0.02,
                                      dt),
                "fc_out": {"kernel": L.scaled_init(
                    k3, (c.ff_dim, c.d_model), 0.02, 2 * c.num_layers, dt)}}

    def _moe_init(self, k):
        c, dt = self.config, self.config.param_dtype
        lo, hi = c.held
        outs = c.n_routed_experts + c.zero_expert_num
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "router": L.dense_init(k1, c.d_model, outs, False, 0.02, dt),
            "bias": (ROUTER_BIAS_SCALE / outs
                     * jax.random.normal(k2, (outs,))).astype(dt),
            "experts": dropless.init_experts(
                k3, hi - lo, c.d_model, c.expert_d_ff, 0.02,
                0.02 / math.sqrt(4.0 * c.num_layers), dt)}

    def init_superblock(self, k) -> Dict:
        d, dt = self.config.d_model, self.config.param_dtype
        ks = jax.random.split(k, 5)
        blk = {f"ln{i}": L.rmsnorm_init(None, d, dt) for i in range(4)}
        blk.update(attn0=self._mla_init(ks[0]), attn1=self._mla_init(ks[1]),
                   mlp0=self._ffn_init(ks[2]), mlp1=self._ffn_init(ks[3]),
                   moe=self._moe_init(ks[4]))
        return blk

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves and trains on the
        chips' data axis only (no tensor-parallel rules yet)."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- the sublayers -----------------------------------------------------
    def _mla_project(self, p, x, positions):
        """x [B, T, h] -> (q_nope [B,T,H,dn], q_rope [B,T,H,dr] rotated,
        c [B,T,r_kv] normalised, k_rope [B,T,dr] rotated)."""
        c = self.config
        b, t, _ = x.shape
        norm = self._norm_fn()
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
        k_nope = jnp.einsum("btr,rhd->bthd", lat, w_uk)
        v = jnp.einsum("btr,rhd->bthd", lat, w_uv)
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                          preferred_element_type=jnp.float32)
             ) * self._sm_scale
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd",
                       jax.nn.softmax(s, axis=-1).astype(x.dtype), v)
        return L.dense_apply(p["out"], o.reshape(b, t, -1))

    def _moe_sublayer(self, p, u, row_valid=None, stack=None):
        """u [B, T, h] -> (this chip's part of the MoE output, counters).
        ``stack = (every layer's experts, this layer's index)`` where the
        caller kept the expert stack out of its layer scan."""
        c = self.config
        b, t, h = u.shape
        flat = u.reshape(b * t, h)
        routing = dropless.route(flat, p["router"]["kernel"], p["bias"],
                                 c.moe_topk, c.routed_scaling_factor)
        experts, layer = stack or (p["experts"], None)
        y, counters = dropless.expert_share(
            experts, flat, routing, c.n_routed_experts, c.held, row_valid,
            layer=layer)
        return y.reshape(b, t, h), counters

    def _shortcut_block(self, bp, x, attend, pools=None, row_valid=None,
                        stack=None):
        """The layer above.  ``attend(j, p, x_normed, pools) -> (out,
        pools)`` is attention sublayer ``j``; ``pools`` is whatever state
        it threads (the paged path's pool, nothing for full sequences).
        Returns ``(y, pools, the MoE sublayer's counters)``."""
        norm = self._norm_fn()
        x = self.constrain(x)
        o, pools = attend(0, bp["attn0"], norm(bp["ln0"], x), pools)
        a = x + o
        u = norm(bp["ln1"], a)
        m, counters = self._moe_sublayer(bp["moe"], u, row_valid, stack)
        bb = a + self._mlp(bp["mlp0"], u)
        o, pools = attend(1, bp["attn1"], norm(bp["ln2"], bb), pools)
        cc = bb + o
        y = cc + self._mlp(bp["mlp1"], norm(bp["ln3"], cc)) + m
        return self.constrain(y), pools, counters

    def _superblock(self, sp, x, caches=None, positions=None, rng=None,
                    train=True, window=None):
        if caches is not None:
            raise NotImplementedError(
                "the latent-attention MoE block has no dense KV cache "
                "(generate()): it decodes through the paged serving path "
                "only")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                         x.shape[:2])
        y, _, _ = self._shortcut_block(
            sp, x, lambda j, p, xn, _: (self._mla_expanded(p, xn, positions),
                                        None))
        return y, None, jnp.zeros((), jnp.float32)

    def init_cache(self, batch, max_len, dtype=None):
        raise NotImplementedError(
            "the latent-attention MoE block has no dense KV cache "
            "(generate()): it decodes through the paged serving path only")

    # -- paged serving -----------------------------------------------------
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

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=None, kv_bits: int = 0) -> Dict:
        """The latent pool: for each of the ``2 L`` attention sublayers
        (sublayer ``j`` of layer ``l`` at index ``2 l + j``) ``num_blocks``
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
        return {"k": jnp.zeros((2 * c.num_layers, num_blocks, block_size,
                                lanes), dtype),
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
        slot = jnp.arange(bsl)
        null_row = null * blk
        write = [jnp.where(act, tables[slot, lens // blk] * blk + lens % blk,
                           null_row)]
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
        q_lat = jnp.einsum("thd,rhd->thr", q_nope[0], w_uk)
        o_parts = [mla_paged_decode_attention(
            q_lat[:bsl], q_rope[0, :bsl], pool,
            jnp.where(act, lens + 1, 0), tables, self._sm_scale)]
        if cw:
            o_parts.append(mla_paged_prefill_attention(
                q_lat[bsl:], q_rope[0, bsl:], pool, chunk_start, chunk_len,
                ctable, self._sm_scale))
        o_lat = jnp.concatenate(o_parts) if cw else o_parts[0]
        o = jnp.einsum("thr,rhd->thd", o_lat, w_uv)
        return L.dense_apply(p["out"], o.reshape(1, t, -1)), pool

    def _apply_paged_mixed(self, params, cache, dec_tokens, dec_active,
                           chunk_ids, chunk_slot, chunk_start, chunk_len,
                           spec_tokens=None, spec_active=None):
        """The mixed step of ``TransformerLM._apply_paged_mixed`` for this
        block: same operands, same results, the latent pool as the scan's
        carry (sublayer ``j`` of layer ``l`` is the block offset
        ``(2 l + j) * num_blocks`` into each buffer).  ``new_cache``
        also holds ``counters`` — int32 ``[len(PAGED_COUNTERS)]``, this
        dispatch's sums over the layers."""
        if spec_tokens is not None:
            raise NotImplementedError(self.paged_refusal(spec=True))
        if cache.get("k_scale") is not None:
            raise NotImplementedError(self.paged_refusal(kv_bits=8))
        c = self.config
        tables, lens = cache["block_tables"], cache["lens"]
        bsl, cw = dec_tokens.shape[0], chunk_ids.shape[0]
        act = dec_active > 0
        ci = jnp.arange(cw)
        positions = jnp.concatenate(
            [lens, jnp.where(ci < chunk_len, chunk_start + ci, 0)])[None]
        ids = jnp.concatenate([dec_tokens, chunk_ids])[None]
        row_valid = jnp.concatenate([act, ci < chunk_len])
        x = self._embed_tokens(params, ids)
        ns, nb = cache["k"].shape[:2]
        pool = cache["k"].reshape(ns * nb, *cache["k"].shape[2:])

        # the expert stack stays out of the scan's xs: sliced per layer
        # it would be copied whole, every step, to reach the kernel
        blocks = params["blocks"]
        experts = blocks["moe"]["experts"]
        blocks = dict(blocks, moe={k: v for k, v in blocks["moe"].items()
                                   if k != "experts"})

        def scan_fn(carry, xs):
            y, pool, counts = carry
            bp, off, layer = xs

            def attend(j, p, xn, pool):
                return self._paged_latent_attention(
                    p, xn, pool, tables + (off + j * nb), lens, act,
                    chunk_slot, chunk_start, chunk_len, off + j * nb,
                    positions)
            y, pool, moe_counts = self._shortcut_block(
                self.block_transform(bp), y, attend, pool, row_valid,
                (experts, layer))
            return (y, pool, counts + moe_counts), None

        offs = jnp.arange(c.num_layers, dtype=tables.dtype) * (2 * nb)
        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        (x, pool, counts), _ = jax.lax.scan(
            scan_fn, (x, pool, zero),
            (blocks, offs, jnp.arange(c.num_layers, dtype=jnp.int32)))
        x = self._norm_fn()(params["ln_f"], x)
        if cw:
            last = jax.lax.dynamic_slice_in_dim(
                x[0], bsl + jnp.maximum(chunk_len - 1, 0), 1, axis=0)
            logits = self._project(
                params, jnp.concatenate([x[0, :bsl], last])[None])
            chunk_logits = logits[0, bsl]
        else:
            logits = self._project(params, x[0, :bsl][None])
            chunk_logits = jnp.zeros((logits.shape[-1],), logits.dtype)
        # the live context the latent kernel walked, once a sublayer
        read = (jnp.sum(jnp.where(act, lens + 1, 0))
                + jnp.where(chunk_len > 0, chunk_start + chunk_len, 0))
        new_lens = (lens + act.astype(lens.dtype)).at[chunk_slot].add(
            chunk_len, mode="drop")
        new_cache = {
            "k": pool.reshape(ns, nb, *pool.shape[1:]), "v": None,
            "block_tables": tables, "lens": new_lens,
            "counters": jnp.concatenate(
                [counts, (read * ns).astype(jnp.int32)[None]])}
        return logits[0, :bsl], chunk_logits, new_cache
