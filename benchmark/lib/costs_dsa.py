"""Operations and bytes of a learned sparse selection over the paged
pools — the indexer's scores and latent attention over the selected
tokens — computed from shapes.  LOWER BOUNDS whatever implements the
kernels: what the algorithm needs, not what a page walk happens to move.
Kept with the benchmark, beside ``costs_latent.py``."""
from __future__ import annotations


def index_scores_cost(context: int, new_rows: int, index_heads: int,
                      index_dim: int, key_bytes: int = 2) -> tuple:
    """One sequence in one layer that computes a selection: ``new_rows``
    query positions (1 for a decode slot, the chunk length for a prefill
    chunk) score every earlier token of a context that ends ``context``
    tokens long, the new rows included.

    Operations: per visible (query position, token) pair and index head
    one dot product over ``index_dim`` values, 2 each.  Bytes: the
    context's indexer keys read once — ``index_dim`` values a token (256
    bytes in bfloat16 at 128)."""
    visible = new_rows * (context - new_rows) + new_rows * (new_rows + 1) / 2
    return (2.0 * visible * index_heads * index_dim,
            float(context) * index_dim * key_bytes)


def selected_attention_cost(context: int, new_rows: int, topk: int,
                            heads: int, latent: int, rope: int,
                            kv_bytes: int = 2) -> tuple:
    """One sequence in one attention layer: each of ``new_rows`` query
    positions attends to at most ``topk`` selected tokens of its own
    context (all of it while shorter), in the absorbed form.

    Operations: per (query position, selected token) pair and head, the
    score over ``latent + rope`` values and the value sum over ``latent``,
    2 each.  Bytes: ``latent + rope`` useful values a selected token
    (1,152 bytes in bfloat16 at 512 + 64) — for a decode row its own
    set; for a chunk no more than the LARGEST single set of the slot (a
    kernel may share fetched rows between a chunk's rows)."""
    first = context - new_rows + 1            # the first new row's context
    if first >= topk:
        pairs = float(new_rows) * topk
    else:
        below = min(new_rows, topk - first)   # rows that still see < topk
        pairs = (below * first + below * (below - 1) / 2
                 + float(new_rows - below) * topk)
    return (2.0 * pairs * heads * (latent + rope + latent),
            float(min(context, topk)) * (latent + rope) * kv_bytes)
