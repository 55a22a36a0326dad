"""Grouped-query attention with per-head q / k norms over routed experts,
generated by DIFFUSION OVER BLOCKS (the ``sdar_moe`` family), as a block
definition behind ``TransformerLM``'s interfaces — the first block that
joins the paged k/v pool to ``moe/dropless.py``'s expert layer.

Layer, input ``x [T, d]`` (pre-norm, RMSNorm, no bias anywhere)::

    h = N1(x);  q = h Wq (H heads of D);  k = h Wk, v = h Wv (G heads of D)
    q <- rmsnorm(q; w_qn), k <- rmsnorm(k; w_kn)     a head's D channels,
                                                     one weight for all heads
    q, k <- rotary (rotate_half, all D channels, the row's position)
    x <- x + softmax(q k^T / sqrt(D), visible) v Wo  query head h reads
                                                     kv head h // (H / G)
    u = N2(x);  p = softmax(u Wg) over all E         float32
    the top k of p, weights p_e / sum of the chosen  (norm_topk_prob)
    x <- x + sum_e w_e Expert_e(u)                   Expert = SwiGLU

**Visibility by blocks**: with block length ``B``, row ``i`` sees key ``j``
iff ``j // B <= i // B`` — every earlier block whole, its own block both
ways.  The logits at position ``i`` predict the token AT position ``i``.

Generation (docs/serving.md "Generation by diffusion over blocks"): a
sequence grows a block of ``B`` positions at a time.  A block starts as
the mask token ``mask_token_id`` and is run several times: a DENOISE
forward draws a token and a confidence at every row and fills some of the
rows that still hold the mask (``inference/sampling.py::block_unmask``);
once no row holds it, a COMMIT forward runs the block's final tokens and
its keys and values are the ones kept.  Both kinds of forward write the
block's ``B`` rows of k / v at the same pool rows, ``lens .. lens + B -
1``; only a commit moves ``lens``, so whatever a denoise forward wrote is
overwritten by the next forward before anything later can see it.

The paged step is ``TransformerLM._apply_paged_mixed`` at ``B`` rows a slot
(``dec_tokens [S, B]``): every slot's current block — the BLOCK LANE, one
walk of a slot's pages for all its rows, ``ops/transformer/
paged_decode_attention.py::paged_block_attention`` — beside one
block-causal prompt chunk.  This file brings the layer
(``_paged_mixed_attention``), its one scan (``_paged_layers``) and its
counters.  There is no one-token decode lane and no draft lane.  The
experts are ONE CHIP'S SHARE (``experts_held``): the router keeps its
published width, a pick of an absent expert adds nothing.
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
from .transformer import (MixedStep, PagedMixedState, TransformerConfig,
                          TransformerLM, lane_pool_rows, scatter_rows)

#: rows of a walker of the prompt chunk's attention (whole blocks): what a
#: chunk of 512 rows x 8 query heads a kv head needs to fit VMEM
CHUNK_TILE_ROWS = 128
#: the seeded q / k norm weights are ``sqrt(QK_LOGIT_STD)`` each: a score
#: between two positions then has a standard deviation of ``QK_LOGIT_STD``
#: (with unit weights it is 1.0 and a softmax over a thousand keys is
#: nearly flat; ``models/ssd_hybrid.py`` has the argument)
QK_LOGIT_STD = 2.5

_NO_DECODE_LANE = (
    "a block-diffusion model has no one-token decode lane (a forward "
    "carries a slot's whole block): the serving engine drives "
    "_apply_paged_mixed with [slots, block_length] tokens, and generate() "
    "— a left-to-right loop over a "
    "dense cache — does not apply; serve it through serving_engine()")


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMoEConfig(TransformerConfig):
    """``TransformerConfig``'s sizes (``d_model``, ``num_heads``,
    ``num_kv_heads``, ``head_dim``, ``num_layers``, ``vocab_size``,
    ``max_seq_len``) plus the block's and the routed experts'.  ``d_ff``
    is read by nothing (every layer is an expert layer).  The flags of the
    standard block that this block does not read are pinned by the builder
    in ``models/transformer.py``."""
    #: positions a block holds: they shape the program (rows a slot a
    #: forward, the visibility rule)
    block_length: int = 4
    #: the token a row holds until it is filled: a row of the embedding
    #: like any other, never drawn
    mask_token_id: int = 0
    expert_d_ff: int = 768
    n_routed_experts: int = 128
    moe_topk: int = 8
    norm_topk_prob: bool = True
    #: the contiguous range (lo, hi) of the routed experts held here;
    #: () = all of them
    experts_held: tuple = ()

    @classmethod
    def model_class(cls):
        return BlockDiffusionMoELM

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    def num_params(self) -> int:
        d, hd = self.d_model, self.hdim
        lo, hi = self.held
        layer = (d * self.qkv_dim + self.num_heads * hd * d + 2 * hd
                 + 2 * d + d * self.n_routed_experts
                 + (hi - lo) * 3 * d * self.expert_d_ff)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return self.num_layers * layer + self.vocab_size * d + head + d


class BlockDiffusionMoELM(TransformerLM):
    """``TransformerLM`` for the block above: same ``init`` / ``apply`` /
    ``init_paged_cache`` / ``partition_specs`` surface; ``apply`` runs a
    whole sequence under the block mask."""

    #: what the serving step counts in the program, a dispatch: the
    #: expert layers' counters, then the pages the layers' walks (the block
    #: lane's and the chunk's) were handed and those of them in runs
    PAGED_COUNTERS = dropless.COUNTERS + ("kv_pages_read",
                                          "kv_pages_in_runs")

    def __init__(self, config: BlockDiffusionMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        lo, hi = c.held
        if not 0 <= lo < hi <= c.n_routed_experts:
            raise ValueError(f"experts_held {c.experts_held} is not a "
                             f"range of the {c.n_routed_experts} experts")
        if c.block_length < 1:
            raise ValueError(f"block_length must be >= 1, got "
                             f"{c.block_length}")
        if not 0 <= c.mask_token_id < c.vocab_size:
            raise ValueError(f"mask_token_id {c.mask_token_id} is not a "
                             f"row of the {c.vocab_size}-row embedding")
        if c.pos_embedding != "rotary" or c.norm_type != "rmsnorm":
            raise ValueError("the block-diffusion block is rotary and "
                             "RMS-normed: build it with sdar_moe_config")

    @property
    def block_rows(self) -> int:
        """Rows a slot rides a serving dispatch with (the engine's block
        lane)."""
        return self.config.block_length

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the block-diffusion block serves and does not train yet: "
                "its objective runs a noised and a clean copy of a sequence "
                "under one mask (a row of the noised copy sees its own "
                "block noised and every earlier block clean), which "
                "ops/transformer/flash_attention.py has no mask for "
                "(ROADMAP B12); its experts' grouped product "
                "differentiates, as models/cca_moe.py trains through it")

    def init_cache(self, batch, max_len, dtype=None):
        raise NotImplementedError(_NO_DECODE_LANE)

    def _paged_supported(self) -> Optional[str]:
        return None

    def paged_refusal(self, kv_bits: int = 0, spec: bool = False,
                      mesh_model: int = 1, mesh_data: int = 1,
                      host_cache: bool = False,
                      weight_quant: bool = False) -> Optional[str]:
        """Why the serving engine cannot be built this way around the
        block lane, or None."""
        if weight_quant:
            return ("int8 weight-only serving (quant.enabled): the expert "
                    "stack is read in place by the grouped-product kernel, "
                    "not dequantized a layer at a time")
        if spec:
            return ("the speculative lane drafts one token after another "
                    "and verifies them causally; a block-diffusion model "
                    "fills a block's rows in any order and its rows see "
                    "each other both ways: there is no draft to verify")
        if kv_bits:
            return (f"serving.kv_cache_bits={kv_bits}: the block lane "
                    f"rewrites a block's rows every forward and reads them "
                    f"back in the same program; the quantized pool's "
                    f"kernel path (scale rows a page, kv_block_size % 128) "
                    f"has no block-visibility form yet")
        if mesh_model > 1 or mesh_data > 1:
            return ("the block-diffusion MoE block serves on one chip: its "
                    "experts are not exchanged across chips yet (ROADMAP "
                    "B6) and the block lane's carried tokens are not "
                    "sharded over slots — use serving.mesh data=1, model=1")
        if host_cache:
            return ("serving.host_cache: promotions land between "
                    "dispatches planned by counts, which the block lane's "
                    "phases are; not tried with the host tier yet")
        return None

    def serving_refusal(self, block_size: int,
                        chunk_tokens: int) -> Optional[str]:
        """Why these pool and chunk sizes cannot carry whole blocks, or
        None."""
        b = self.config.block_length
        if block_size % b:
            return (f"serving.kv_block_size {block_size} is not a multiple "
                    f"of block_length {b}: a block's rows must lie in one "
                    f"page, and a full page's k/v must depend on no token "
                    f"past the page's end (what keeps the prefix cache "
                    f"sound)")
        if chunk_tokens % b:
            return (f"serving.prefill_chunk_tokens {chunk_tokens} is not a "
                    f"multiple of block_length {b}: a prompt chunk is "
                    f"whole blocks, each row seeing its own block both "
                    f"ways")
        return None

    # -- init --------------------------------------------------------------
    def init_superblock(self, k) -> Dict:
        c, dt = self.config, self.config.param_dtype
        lo, hi = c.held
        ka, kr, ke = jax.random.split(k, 3)
        blk = self._attn_block_init(ka)
        gain = jnp.full((c.hdim,), math.sqrt(QK_LOGIT_STD), dt)
        blk["attn"]["q_norm"] = {"scale": gain}
        blk["attn"]["k_norm"] = {"scale": gain}
        blk["moe"] = {
            "router": L.dense_init(kr, c.d_model, c.n_routed_experts, False,
                                   0.02, dt),
            "experts": dropless.init_experts(
                ke, hi - lo, c.d_model, c.expert_d_ff, 0.02,
                0.02 / math.sqrt(2.0 * c.num_layers), dt)}
        return blk

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves on one chip."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- the sublayers -----------------------------------------------------
    def _qkv(self, p, x, positions):
        """x [B, T, d] -> q [B, T, H, D], k / v [B, T, G, D]: the fused
        product cut by section, each head of q and of k RMS-normed over
        its own channels, THEN rotated."""
        c = self.config
        hd = c.hdim
        nq, nkv = c.num_heads * hd, c.kv_heads * hd
        qkv = self._qkv_product(p, x)
        q, k, v = (a.reshape(a.shape[:2] + (-1, hd))
                   for a in jnp.split(qkv, [nq, nq + nkv], axis=-1))
        q = L.rmsnorm_apply(p["q_norm"], q, eps=c.layernorm_eps)
        k = L.rmsnorm_apply(p["k_norm"], k, eps=c.layernorm_eps)
        cos = self._cos.astype(jnp.float32)
        sin = self._sin.astype(jnp.float32)
        q = L.apply_rotary(q, cos, sin, positions, interleaved=False)
        k = L.apply_rotary(k, cos, sin, positions, interleaved=False)
        return q, k, v

    def _block_mask(self, t: int):
        """[t, t] bool: row i sees key j iff ``j // B <= i // B``."""
        at = jnp.arange(t) // self.config.block_length
        return at[None, :] <= at[:, None]

    def _attention(self, p, x, cache_kv=None, positions=None, window=None):
        with jax.named_scope("attn_proj"):
            if positions is None:
                positions = jnp.arange(x.shape[1])[None]
            q, k, v = self._qkv(p, x, positions)
        if cache_kv is None:
            with jax.named_scope("attn_kernel"):
                o = L.gqa_attention(
                    q, k, v, causal=False,
                    mask=self._block_mask(x.shape[1])[None, None, None])
            o, pools = o.reshape(x.shape[:2] + (-1,)), None
        else:
            o, pools = self._paged_mixed_attention(q, k, v, cache_kv)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o), pools

    def _moe_sublayer(self, p, u, row_valid=None, stack=None):
        """u [1, T, d] -> (this chip's part of the routed experts' output,
        counters).  ``stack = (every layer's experts, this layer's
        index)`` where the caller kept the expert stack out of its layer
        scan."""
        c = self.config
        b, t, h = u.shape
        flat = u.reshape(b * t, h)
        routing = dropless.route(
            flat, p["router"]["kernel"], None, c.moe_topk, 1.0,
            scoring="softmax", renormalize=c.norm_topk_prob)
        experts, layer = stack or (p["experts"], None)
        y, counters = dropless.expert_share(
            experts, flat, routing, c.n_routed_experts, c.held, row_valid,
            layer=layer)
        return y.reshape(b, t, h), counters

    def _block(self, bp, x, cache_kv=None, positions=None, window=None,
               row_valid=None, stack=None):
        """One layer: ``(y, the pools as the attention left them, the
        expert layer's counters)``."""
        norm = self._norm_fn()
        a, pools = self._attention(bp["attn"], norm(bp["ln1"], x), cache_kv,
                                   positions)
        with jax.named_scope("residual"):
            x = x + a
        m, counters = self._moe_sublayer(bp["moe"], norm(bp["ln2"], x),
                                         row_valid, stack)
        with jax.named_scope("residual"):
            x = x + m
        return x, pools, counters

    # -- full sequences ----------------------------------------------------
    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        """Forward up to the final norm under the block mask, plain XLA
        attention (the expert layer takes every batch row as a row)."""
        x = self._embed_tokens(params, input_ids)

        def layer(x, bp):
            return self._block(self.block_transform(bp), x)[0], None
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        x = self._norm_fn("head")(params["ln_f"], x)
        return x, jnp.zeros((), jnp.float32)

    def apply(self, params, input_ids, cache=None, positions=None,
              token_type_ids=None):
        if cache is not None:
            raise NotImplementedError(_NO_DECODE_LANE)
        return super().apply(params, input_ids)

    # -- the paged step ----------------------------------------------------
    def _paged_mixed_attention(self, q, k, v, st: PagedMixedState):
        """One layer of the serving step.  q / k / v arrive as ``[1, S * B
        + C, heads, D]`` — slot-major, every slot's block of ``B`` rows,
        then one slot's prompt chunk; rotary already applied at each row's
        position.  Every row's k / v goes into the pool in one scatter
        (block row ``i`` of slot ``s`` at position ``lens[s] + i``, chunk
        rows at ``chunk_start + i``; an idle slot's and padding rows to
        the layer's null block), then two ``paged_attention`` calls
        attend: the block lane — all slots, ``B`` rows each, one walk of a
        slot's pages, every row seeing ``lens[s] + B`` keys — and the
        chunk, block-causal."""
        from ..ops.transformer.paged_decode_attention import (
            paged_block_attention, paged_prefill_attention)
        step, tables = st.step, st.block_tables
        _, t, nh, hd = q.shape
        slots, bk, c = step.slots, step.rows, step.chunk
        nb = slots * bk
        with jax.named_scope("pool_write"):
            write, ctable = lane_pool_rows(step, tables, st.k_pool.shape[1],
                                           st.null_block)
            write = jnp.concatenate(write)
            pk = scatter_rows(st.k_pool, write, k[0])
            pv = scatter_rows(st.v_pool, write, v[0])
        with jax.named_scope("attn_kernel"):
            o = paged_block_attention(
                q[0, :nb].reshape(slots, bk, nh, hd), pk, pv, step.lens,
                step.act, tables).reshape(nb, nh, hd)
            if c:
                tile = (CHUNK_TILE_ROWS
                        if c > CHUNK_TILE_ROWS and c % CHUNK_TILE_ROWS == 0
                        and CHUNK_TILE_ROWS % bk == 0 else None)
                o = jnp.concatenate([o, paged_prefill_attention(
                    q[0, nb:], pk, pv, step.chunk_start, step.chunk_len,
                    ctable, tile_rows=tile, block_rows=bk)])
        return o.reshape(1, t, nh * hd), (pk, pv)

    def _paged_layers(self, params, x, carry, step: MixedStep, probe):
        """One scan over the layers, the pools its carry; ``counts`` the
        expert layers' ``dropless.COUNTERS``."""
        nb = step.num_blocks
        tables = step.tables
        blocks, experts = dropless.split_experts(params["blocks"])
        nl = experts["w_up"].shape[0]

        def scan_fn(carry, xs):
            y, pools, counts = carry
            bp, off, layer = xs
            with jax.named_scope("pool_write"):
                st = PagedMixedState(*pools, tables + off, step,
                                     null_block=off)
            y, pools, moe_counts = self._block(
                self.block_transform(bp), y, st, step.positions,
                row_valid=step.row_valid, stack=(experts, layer))
            with jax.named_scope("expert_layout"):
                counts = counts + moe_counts
            return (y, pools, counts), None

        with jax.named_scope("pool_write"):
            offs = jnp.arange(nl, dtype=tables.dtype) * nb
        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        (x, pools, counts), _ = jax.lax.scan(
            scan_fn, (x, (carry["k"], carry["v"]), zero),
            (blocks, offs, jnp.arange(nl, dtype=jnp.int32)))
        return (x, dict(zip(("k", "v"), pools)),
                dict(zip(dropless.COUNTERS, counts)), None)
