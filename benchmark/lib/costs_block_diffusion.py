"""Operations and bytes of attention where generation is by diffusion over
blocks, computed from shapes.  Kept with the benchmark, beside
``costs.py``: the same work whatever implements it."""
from __future__ import annotations


def block_forward_cost(context: int, block_rows: int, heads: int,
                       kv_heads: int, head_dim: int, kv_bytes: int = 2,
                       act_bytes: int = 2) -> tuple:
    """One slot's block in one layer: ``block_rows`` query rows, each
    seeing all ``context`` keys (the committed rows and the whole block,
    its own rows included).

    Operations: QK^T and PV, 2 each per (row, key, head, dim): ``4 x B x
    context x heads x head_dim``.  Bytes: the context's K and V read ONCE
    for all the block's rows, q read and the output written — a program
    that walks the pages once a row reads ``1 / B`` of this roofline."""
    flops = 4.0 * block_rows * context * heads * head_dim
    nbytes = (2.0 * context * kv_heads * head_dim * kv_bytes
              + 2.0 * block_rows * heads * head_dim * act_bytes)
    return flops, nbytes


def block_causal_chunk_cost(context: int, new_rows: int, block_rows: int,
                            heads: int, kv_heads: int, head_dim: int,
                            kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """One prompt chunk of ``new_rows`` rows (whole blocks) in one layer,
    its context ending ``context`` tokens long, the chunk included: a row
    sees everything before the chunk and, of the chunk, up to the end of
    its own block."""
    visible = (new_rows * (context - new_rows)
               + new_rows * (new_rows + block_rows) / 2)
    flops = 4.0 * visible * heads * head_dim
    nbytes = (2.0 * context * kv_heads * head_dim * kv_bytes
              + 2.0 * new_rows * heads * head_dim * act_bytes)
    return flops, nbytes
