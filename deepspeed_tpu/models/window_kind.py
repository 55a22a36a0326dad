"""What a block takes part in whose slots hold the cache manager's
``window`` kind of page beside the ``full`` kind (docs/serving.md "The
cache manager's kinds of state"): layers that attend the newest
``sliding_window`` keys only, so a slot keeps just the pages its window
still reaches and hands the rest back while it decodes — and while it
prefills.  A mixin beside a ``TransformerLM``; two blocks are built on it,
``models/hybrid_ssm.py`` (window layers beside state-space layers) and
``models/window_moe.py`` (window layers beside full layers, over
experts).  The engine reads ``TABLE_KINDS``, ``window_pages`` and
``config.sliding_window``; the block lays the pool ``wk`` / ``wv`` into
its ``init_paged_extra`` tree and calls :meth:`_write_then_walk` from its
layers of the serving step.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .transformer import MixedStep, write_kv_rows


class WindowKind:
    #: the block tables a slot has, in the order the engine lays them
    #: side by side in its per-slot operand
    TABLE_KINDS = ("full", "window")
    #: rows of a prompt chunk to one walker of the paged kernel (what a
    #: chunk of many rows x grouped heads needs to fit VMEM)
    CHUNK_TILE_ROWS = 128
    #: why a quantized pool is refused by a block with this kind
    KV_BITS_REFUSAL = ("the window layers' walk starts inside a slot's "
                       "table, and the quantized pool's scale rows take no "
                       "first page")

    def window_pages(self, block_size: int, chunk_tokens: int
                     ) -> Tuple[int, int]:
        """Pages of a window layer a slot holds at most: while decoding
        (its window's keys), and while a chunk of ``chunk_tokens`` rows
        is in flight (the first row's window to the last row)."""
        w = self.config.sliding_window
        return ((w - 1) // block_size + 2,
                (w - 1 + chunk_tokens - 1) // block_size + 2)

    def _window_pool(self, layers: int, window_blocks: int, block_size: int,
                     dtype) -> Dict:
        """The window layers' pool: ``wk`` / ``wv`` ``[layers,
        window_blocks, block, kv_heads x head_dim]``, block 0 of a layer
        its null block."""
        c = self.config
        shape = (layers, window_blocks, block_size, c.kv_heads * c.hdim)
        return {"wk": jnp.zeros(shape, dtype), "wv": jnp.zeros(shape, dtype)}

    def _write_then_walk(self, q, k, v, pool_k, pool_v, tables, off,
                         st: MixedStep, window: Optional[int],
                         lane: Optional[str] = None):
        """One paged attention layer of the mixed step, of either kind:
        every row writes its k / v ``[S + C, lanes]`` into the layer's
        pages (``tables [S, pages]`` the kind's, ``off`` the layer's block
        offset into the pool and its null block), then the decode rows
        and the chunk attend — under a ``window`` the keys their windows
        reach, each walk from its first attended page; without one, every
        earlier key.  ``q [S + C, H, hd]``; ``lane`` names the walks'
        device scope one level inside ``attn_kernel``
        (``overlap.SCOPE_LANES``).  Returns ``(o [S + C, H, hd], pool_k,
        pool_v, the keys the two walks were handed)``."""
        from ..ops.transformer.paged_decode_attention import (
            paged_decode_attention, paged_prefill_attention)
        s = st.slots
        with jax.named_scope("pool_write"):
            tables = tables + off
            pool_k, pool_v = write_kv_rows(pool_k, pool_v, k, v, tables, st,
                                           off)
        inner = jax.named_scope(lane) if lane else contextlib.nullcontext()
        with jax.named_scope("attn_kernel"), inner:
            lengths = jnp.where(st.act, st.lens + 1, 0)
            o = [paged_decode_attention(
                q[:s], pool_k, pool_v, lengths, tables,
                sm_scale=self._sm_scale, window=window)]
            read = jnp.sum(lengths if window is None
                           else jnp.minimum(lengths, window))
            if st.chunk:
                o.append(paged_prefill_attention(
                    q[s:], pool_k, pool_v, st.chunk_start, st.chunk_len,
                    tables[st.chunk_slot], sm_scale=self._sm_scale,
                    window=window, tile_rows=self.CHUNK_TILE_ROWS))
                # the first row's window to the last row
                read += jnp.where(
                    st.chunk_len > 0, st.chunk_start + st.chunk_len
                    - (0 if window is None else
                       jnp.maximum(st.chunk_start - (window - 1), 0)), 0)
            o = jnp.concatenate(o) if st.chunk else o[0]
        return o, pool_k, pool_v, read.astype(jnp.int32)
