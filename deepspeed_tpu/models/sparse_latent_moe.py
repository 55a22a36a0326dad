"""The latent-attention block with a LEARNED SPARSE SELECTION (GLM-5.2
family, ``glm_moe_dsa``), as a block definition behind ``TransformerLM``'s
interfaces.

Plain pre-norm, two RMSNorms a layer::

    a = x + MLA_S(N_in(x))
    y = a + F_l(N_post(a))
    F_l = SwiGLU(d_ff)                                  l < first_k_dense
        = Shared(u) + sum_{e in top-k, e held} w_e Expert_e(u)   otherwise

``MLA_S`` is latent attention over a SELECTED set ``S_t`` of the earlier
tokens only.  A layer whose ``indexer_types`` entry is ``full`` computes
the selection with its INDEXER — ``qI = W_Iq cq`` (``index_n_heads`` x
``index_head_dim``, from the query latent ``cq``), one key a token ``kI =
LayerNorm(W_Ik h)``, rotary on the first ``qk_rope_head_dim`` dims of
both, head weights ``w = W_Iw h / sqrt(heads * head_dim)``, score ``I[t,
s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t`` in float32 —
and keeps the positions of the ``index_topk`` largest (all of them while
``t < index_topk``), exactly.  A ``shared`` layer holds no indexer
weights, writes no indexer key and uses the set of the nearest ``full``
layer before it.  The router is a sigmoid gate whose picks are the top k
of ``score + bias`` (``noaux_tc``), weights renormalised and scaled.

Two kinds of layer twice over: dense and expert layers
(``latent_moe.DenseLeadMoELM``'s stack), and ``full`` and ``shared``
layers inside both scans — the indexer's weights are
``params["indexer"]``, stacked by FULL-layer number as the expert stack
is by expert-layer number, and the selection is carried from layer to
layer, across the boundary between the two scans.

The cache holds a second kind of row: beside the latent pool ``k
[layers, blocks, block, 640]`` the INDEXER pool ``v [full layers, blocks,
block, index_head_dim]`` — the place the latent pool leaves empty —
under the SAME block table, so a prefix-cache hit brings both.  The mixed
step carries both in place, scores through
``ops/transformer/sparse_latent_attention.py``'s kernel, selects without
a sort, gathers a decode row's tokens by token and walks a chunk's rows
by page under the selection as a mask.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from ..observability.overlap import scoped
from .latent_moe import (DenseLeadMoEConfig, DenseLeadMoELM,
                         head_sections)
from .transformer import lane_pool_rows, scatter_rows

_NORMS = ("ln_in", "ln_post")
_KINDS = ("full", "shared")


@dataclasses.dataclass(frozen=True)
class SparseLatentMoEConfig(DenseLeadMoEConfig):
    """``DenseLeadMoEConfig`` with the family's gate as defaults
    (:func:`models.transformer.glm_moe_dsa_config` gives the published
    sizes), the indexer's sizes and which layers compute a selection."""
    n_routed_experts: int = 256
    moe_topk: int = 8
    routed_scaling_factor: float = 2.5
    router_scoring: str = "sigmoid"
    router_bias: bool = True
    norm_topk_prob: bool = True
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: one of ``full`` | ``shared`` a layer; () = every layer ``full``
    indexer_types: tuple = ()

    @classmethod
    def model_class(cls):
        return SparseLatentMoELM

    @property
    def layer_kinds(self) -> tuple:
        return tuple(self.indexer_types) or ("full",) * self.num_layers

    @property
    def full_layers(self) -> int:
        return self.layer_kinds.count("full")

    def indexer_params(self) -> int:
        """One ``full`` layer's indexer: ``W_Iq``, ``W_Ik`` and its
        LayerNorm, ``W_Iw``."""
        j, d = self.index_n_heads, self.index_head_dim
        return (self.q_lora_rank * j * d + self.d_model * d + 2 * d
                + self.d_model * j)

    def num_params(self) -> int:
        return (self.stack_params(self.mla_params()
                                  + len(_NORMS) * self.d_model)
                + self.full_layers * self.indexer_params())


class SparseLatentMoELM(DenseLeadMoELM):
    """``DenseLeadMoELM`` with the block above."""

    ATTN_SUBLAYERS = 1
    PAGED_COUNTERS = DenseLeadMoELM.PAGED_COUNTERS + (
        "index_rows", "index_keys_scored", "sparse_tokens_read",
        "sparse_rows_reused")

    def __init__(self, config: SparseLatentMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        kinds = config.layer_kinds
        if len(kinds) != config.num_layers or set(kinds) - set(_KINDS) \
                or kinds[0] != "full":
            raise ValueError(
                f"indexer_types must name {config.num_layers} layers "
                f"'full' or 'shared', the first 'full'; got {kinds}")
        if config.index_head_dim < config.qk_rope_head_dim:
            raise ValueError(
                f"index_head_dim {config.index_head_dim} is narrower than "
                f"the rotary part {config.qk_rope_head_dim}")
        self._index_scale = 1.0 / math.sqrt(config.index_n_heads
                                            * config.index_head_dim)
        full = np.array([k == "full" for k in kinds])
        #: a layer's number among the ``full`` layers (its own if it is
        #: one, else that of the ``full`` layer before it)
        self._full_at = np.cumsum(full) - 1
        self._is_full = full

    # -- init --------------------------------------------------------------
    def _shell_init(self, k) -> Dict:
        d, dt = self.config.d_model, self.config.param_dtype
        blk = {n: L.rmsnorm_init(None, d, dt) for n in _NORMS}
        blk["attn"] = self._mla_init(k)
        return blk

    def init_indexer(self, k) -> Dict:
        """One ``full`` layer's indexer."""
        c, dt = self.config, self.config.param_dtype
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "wq": L.dense_init(k1, c.q_lora_rank,
                               c.index_n_heads * c.index_head_dim, False,
                               0.02, dt),
            "wk": L.dense_init(k2, c.d_model, c.index_head_dim, False,
                               0.02, dt),
            "k_norm": L.layernorm_init(None, c.index_head_dim, dt),
            "weights": L.dense_init(k3, c.d_model, c.index_n_heads, False,
                                    0.02, dt)}

    def init_resident(self, rng) -> Dict:
        """``DenseLeadMoELM``'s resident part and the indexers, which are
        fewer than the layers of either scan."""
        params = super().init_resident(rng)
        params["indexer"] = jax.vmap(self.init_indexer)(jax.random.split(
            jax.random.split(rng, 8)[7], self.config.full_layers))
        return params

    # -- the layer ---------------------------------------------------------
    def _latent_block(self, bp, x, attend, pools=None, row_valid=None,
                      stack=None):
        """The layer above (``LatentMoELM._latent_block``'s contract)."""
        norm = self._norm_fn()
        x = self.constrain(x)
        o, pools = attend(0, bp["attn"], norm(bp["ln_in"], x), pools)
        with jax.named_scope("residual"):
            a = x + o
        f, counters = self._ffn_sublayer(bp, norm(bp["ln_post"], a),
                                         row_valid, stack)
        with jax.named_scope("residual"):
            y = a + f
        return self.constrain(y), pools, counters

    @scoped("indexer")
    def _indexer_project(self, ip, xn, cq, positions):
        """One ``full`` layer's indexer over rows ``xn [B, T, h]`` with
        query latents ``cq``: ``(qI [B, T, J, D] rotated, kI [B, T, D]
        normalised and rotated, w [B, T, J] float32, scaled)``."""
        c = self.config
        b, t, _ = xn.shape
        q = L.dense_apply(ip["wq_sections"], cq)
        cut = c.index_n_heads * c.qk_rope_head_dim
        q_rope = q[..., :cut].reshape(b, t, c.index_n_heads,
                                      c.qk_rope_head_dim)
        q_rest = q[..., cut:].reshape(
            b, t, c.index_n_heads, c.index_head_dim - c.qk_rope_head_dim)
        k = L.layernorm_apply(ip["k_norm"], L.dense_apply(ip["wk"], xn),
                              eps=c.layernorm_eps)
        q = jnp.concatenate(
            [L.apply_rotary(q_rope, self._cos, self._sin, positions,
                            interleaved=False), q_rest], axis=-1)
        k = L.apply_rotary(k[:, :, None], self._cos, self._sin, positions,
                           interleaved=False)[:, :, 0]
        w = jnp.einsum("bth,hj->btj", xn,
                       ip["weights"]["kernel"].astype(xn.dtype),
                       preferred_element_type=jnp.float32)
        return q, k, w * self._index_scale

    def serving_params(self, params) -> Dict:
        """``LatentMoELM.serving_params``, and the indexers' ``wq`` — a
        head's rotary part and the rest are a split inside the head too —
        as ``wq_sections`` (``latent_moe.head_sections``)."""
        params = super().serving_params(params)
        ip = params["indexer"]
        if "wq" not in ip:
            return params
        c = self.config
        wq = head_sections(ip["wq"]["kernel"], c.index_n_heads,
                           c.qk_rope_head_dim)
        ip = dict({k: v for k, v in ip.items() if k != "wq"},
                  wq_sections={"kernel": wq})
        return dict(params, indexer=ip)

    def _layer_kinds(self, first, count):
        """``(is full [count] bool, full-layer number [count] int32)`` of
        layers ``first .. first + count``, as arrays a scan slices."""
        return (jnp.asarray(self._is_full[first:first + count]),
                jnp.asarray(self._full_at[first:first + count], jnp.int32))

    def _indexer_at(self, params, at):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, at, keepdims=False),
            params["indexer"])

    # -- full sequences ----------------------------------------------------
    def _select_dense(self, ip, xn, cq, positions):
        """The selection of a full sequence as a mask ``[B, T, T]``: the
        ``index_topk`` best-scored earlier positions of every row."""
        q, k, w = self._indexer_project(ip, xn, cq, positions)
        t = xn.shape[1]
        with jax.named_scope("indexer"):
            s = jnp.einsum("bqjd,bkd->bqjk", q, k,
                           preferred_element_type=jnp.float32)
            score = jnp.einsum("bqjk,bqj->bqk", jnp.maximum(s, 0.0), w)
        with jax.named_scope("select"):
            from ..ops.transformer.sparse_latent_attention import kth_largest
            causal = jnp.tril(jnp.ones((t, t), bool))[None]
            causal = jnp.broadcast_to(causal, score.shape)
            kth = kth_largest(score.reshape(-1, t), causal.reshape(-1, t),
                              self.config.index_topk).reshape(score.shape[:2])
            return causal & (score >= kth[..., None])

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None,
                              return_selection=False):
        """Forward up to the final norm, expanded form, plain XLA; the
        selection mask is carried from a ``full`` layer to the ``shared``
        layers after it.  ``return_selection``: also every layer's mask
        ``[layers, B, T, T]`` (the tests')."""
        params = self.serving_params(params)
        x = self._embed_tokens(params, input_ids)
        b, t = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))

        def layer(carry, xs):
            x, chosen = carry
            bp, is_full, full_at = xs

            def attend(j, p, xn, chosen):
                cq = self._q_latent(p, xn)
                chosen = jax.lax.cond(
                    is_full,
                    lambda: self._select_dense(
                        self._indexer_at(params, full_at), xn, cq,
                        positions),
                    lambda: chosen)
                return self._mla_expanded(p, xn, positions, cq,
                                          chosen), chosen
            y, chosen, _ = self._latent_block(self.block_transform(bp), x,
                                              attend, chosen)
            return (y, chosen), (chosen if return_selection else None)

        carry, masks = (x, jnp.zeros((b, t, t), bool)), []
        lead = self._leading_blocks(params)
        leading = 0
        if lead is not None:
            leading = jax.tree_util.tree_leaves(lead)[0].shape[0]
            carry, m = jax.lax.scan(
                layer, carry, (lead, *self._layer_kinds(0, leading)))
            masks.append(m)
        carry, m = jax.lax.scan(
            layer, carry,
            (params["blocks"],
             *self._layer_kinds(leading, self.config.scan_length)))
        masks.append(m)
        out = (self._norm_fn("head")(params["ln_f"], carry[0]),
               jnp.zeros((), jnp.float32))
        return out + (jnp.concatenate(masks),) if return_selection else out

    # -- paged serving -----------------------------------------------------
    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=None, kv_bits: int = 0) -> Dict:
        """The latent pool ``k`` (``LatentMoELM.init_paged_cache``) and
        the INDEXER pool ``v [full layers, num_blocks, block_size,
        index_head_dim]``: one key a token in every layer that computes a
        selection, in pages that follow the same block table."""
        cache = super().init_paged_cache(num_blocks, block_size, dtype,
                                         kv_bits)
        c = self.config
        cache["v"] = jnp.zeros((c.full_layers, num_blocks, block_size,
                                c.index_head_dim), dtype or c.dtype)
        return cache

    def _paged_carry(self, params, cache, step):
        """With both pools, the selection a ``full`` layer hands on: a
        decode slot's selected tokens as pool rows ``[slots, k]`` with
        their count, a chunk row's score plane, threshold and the size of
        its set; beside them ``counts``, what the layers add up of this
        dispatch's indexer and selection work (the last four of
        ``PAGED_COUNTERS``)."""
        from ..ops.transformer.sparse_latent_attention import plane_width
        c = self.config
        v = cache["v"]
        bsl, cw = step.slots, step.chunk
        sel = {"rows": jnp.zeros((bsl, c.index_topk), jnp.int32),
               "count": jnp.zeros((bsl,), jnp.int32),
               "counts": jnp.zeros((4,), jnp.int32)}
        if cw:
            sel.update(
                plane=jnp.zeros((cw, plane_width(
                    step.tables.shape[1], v.shape[2])), jnp.float32),
                floor=jnp.zeros((cw,), jnp.float32),
                chunk_count=jnp.zeros((cw,), jnp.int32))
        return dict(super()._paged_carry(params, cache, step), sel=sel)

    def _layer_meta(self, step, first, count):
        """A layer's block offset into the latent pool, whether it
        computes a selection, and its indexer's block offset into the
        indexer pool."""
        is_full, full_at = self._layer_kinds(first, count)
        with jax.named_scope("pool_write"):
            return (super()._layer_meta(step, first, count), is_full,
                    full_at, full_at.astype(step.tables.dtype)
                    * step.num_blocks)

    def _paged_select(self, ip, xn, cq, ipool, ioff, step, write, counts):
        """A ``full`` layer's part of the mixed step: the rows' indexer
        keys into the indexer pool (layer offset ``ioff``), every row's
        scores over its slot's context, and the selection in the form
        attention takes it; ``counts`` with this layer's rows and the
        context tokens it scored added."""
        from ..ops.transformer.sparse_latent_attention import (
            dsa_index_scores, kth_largest, narrowed, pool_rows_of,
            select_positions)
        c = self.config
        bsl, cw = step.slots, step.chunk
        blk = ipool.shape[1]
        q, k, w = self._indexer_project(ip, xn, cq, step.positions)
        with jax.named_scope("pool_write"):
            ipool = scatter_rows(ipool, write + ioff * blk, k[0])
        tables = step.tables + ioff
        total = jnp.where(step.act, step.lens + 1, 0)
        with jax.named_scope("indexer"):
            score = dsa_index_scores(q[0, :bsl, None], w[0, :bsl, None],
                                     ipool, total - 1, total, tables)[:, 0]
        with jax.named_scope("select"):
            pos = jnp.arange(score.shape[1], dtype=jnp.int32)[None]
            # at the plane's full width whatever the slots' lengths: behind
            # ``narrowed``'s conditional these passes took 0.5 ms more a
            # layer than they do fused with what feeds them, at any width
            picked, count = select_positions(score, pos < total[:, None],
                                             c.index_topk)
            sel = {"rows": pool_rows_of(picked, step.tables, blk),
                   "count": count}
            scored = jnp.sum(total)
        if cw:
            with jax.named_scope("indexer"):
                plane = dsa_index_scores(
                    q[:, bsl:], w[:, bsl:], ipool, step.chunk_start[None],
                    (step.chunk_start + step.chunk_len)[None],
                    tables[step.chunk_slot][None])[0]
            with jax.named_scope("select"):
                at = step.chunk_start + jnp.arange(cw, dtype=jnp.int32)
                live = jnp.arange(cw) < step.chunk_len
                seen = (pos <= at[:, None]) & live[:, None]

                def threshold(w):
                    floor = kth_largest(plane[:, :w], seen[:, :w],
                                        c.index_topk)
                    return floor, jnp.sum(
                        seen[:, :w] & (plane[:, :w] >= floor[:, None]),
                        axis=1, dtype=jnp.int32)
                floor, chunk_count = narrowed(
                    plane.shape[1], step.chunk_start + step.chunk_len,
                    threshold)
                sel.update(plane=plane, floor=floor, chunk_count=chunk_count)
                scored = scored + jnp.sum(jnp.where(live, at + 1, 0))
        with jax.named_scope("select"):
            sel["counts"] = counts + jnp.stack(
                [jnp.sum(step.row_valid, dtype=jnp.int32), scored,
                 jnp.int32(0), jnp.int32(0)])
        return ipool, sel

    def _paged_attend(self, params, step, meta):
        """``attend`` of one layer of the mixed step: latent rows into the
        latent pool, a selection computed (``full``) or taken as handed on
        (``shared``), and latent attention over it — a decode row's
        tokens gathered by token, a chunk's rows walking their slot's
        pages under the selection as a mask."""
        from ..ops.transformer.sparse_latent_attention import (
            dsa_sparse_prefill_attention, gathered_latent_attention)
        off, is_full, full_at, ioff = meta
        bsl, cw = step.slots, step.chunk

        def attend(j, p, xn, state):
            pool, ipool, sel = state["k"], state["v"], state["sel"]
            t = xn.shape[1]
            blk = pool.shape[1]
            cq = self._q_latent(p, xn)
            q_nope, q_rope, lat, k_rope = self._mla_project(
                p, xn, step.positions, cq)
            with jax.named_scope("pool_write"):
                # rows before any layer's offset; a masked row's is the
                # null block's of whichever layer adds its offset
                write, ctable = lane_pool_rows(step, step.tables,
                                               blk, 0)
                write = jnp.concatenate(write)
                pool = scatter_rows(
                    pool, write + off * blk,
                    jnp.concatenate([lat[0], k_rope[0]], axis=-1))
            # the layer that ran says so itself: a ``full`` layer adds
            # its rows and keys scored, a ``shared`` one the rows that
            # took the set as handed on
            ipool, sel = jax.lax.cond(
                is_full,
                lambda ipool, sel: self._paged_select(
                    self._indexer_at(params, full_at), xn, cq, ipool, ioff,
                    step, write, sel["counts"]),
                lambda ipool, sel: (ipool, dict(
                    sel, counts=sel["counts"].at[3].add(
                        jnp.sum(step.row_valid, dtype=jnp.int32)))),
                ipool, sel)
            with jax.named_scope("select"):
                # what this layer attends: the sets' own sizes
                read = jnp.sum(jnp.where(step.act, sel["count"], 0))
                if cw:
                    read = read + jnp.sum(sel["chunk_count"])
                sel = dict(sel, counts=sel["counts"].at[2].add(read))
            w_uk, w_uv = self._kv_b(p, xn.dtype)
            with jax.named_scope("attn_proj"):
                q_lat = jnp.einsum("thd,hrd->thr", q_nope[0], w_uk)
            with jax.named_scope("attn_kernel"):
                o_parts = [gathered_latent_attention(
                    q_lat[:bsl], q_rope[0, :bsl], pool,
                    sel["rows"] + off * blk,
                    jnp.where(step.act, sel["count"], 0), self._sm_scale)]
                if cw:
                    o_parts.append(dsa_sparse_prefill_attention(
                        q_lat[bsl:], q_rope[0, bsl:], pool, sel["plane"],
                        sel["floor"], step.chunk_start, step.chunk_len,
                        ctable + off, self._sm_scale))
                o_lat = jnp.concatenate(o_parts) if cw else o_parts[0]
            with jax.named_scope("attn_proj"):
                o = jnp.einsum("thr,hrd->thd", o_lat, w_uv)
                return (L.dense_apply(p["out"], o.reshape(1, t, -1)),
                        {"k": pool, "v": ipool, "sel": sel})
        return attend

    def _paged_counters(self, step, state, counts, walk) -> Dict[str, Any]:
        """The base's, then what the layers themselves added up
        (``_paged_select`` in a ``full`` layer, the other branch in a
        ``shared`` one, ``attend`` in both): rows x ``full`` layers that
        ran the indexer, context tokens they scored, selected tokens
        attended (the sets' own sizes, rows x layers), rows x ``shared``
        layers that took a handed-on set."""
        return dict(super()._paged_counters(step, state, counts, walk),
                    **dict(zip(self.PAGED_COUNTERS[-4:],
                               state["sel"]["counts"])))

    def _paged_probe(self, state) -> Dict:
        """What this layer attended: a decode slot's pool rows and their
        count, a chunk row's selection as a mask over the plane (before
        causality)."""
        sel = state["sel"]
        seen = {"rows": sel["rows"], "count": sel["count"]}
        if "plane" in sel:
            seen["chunk"] = sel["plane"] >= sel["floor"][:, None]
        return seen
