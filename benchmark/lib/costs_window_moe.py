"""Operations and bytes of the paged walk where window-attention layers
and full-attention layers stand in one stack, computed from shapes: the
same work whatever implements the walk.  Kept with the benchmark, beside
``costs.py``."""
from __future__ import annotations


def paged_walk_cost(kind: str, context: int, new_rows: int, heads: int,
                    kv_heads: int, head_dim: int, window: int,
                    kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """One sequence in ONE layer of ``kind``, after a dispatch that wrote
    its rows ``context - new_rows .. context - 1`` (1 for a decode slot,
    the chunk's rows for a prompt chunk); every new row attends.

    ``full`` — the row at position ``p`` sees the ``p + 1`` keys up to
    itself; the walk reads all ``context`` keys.  ``window`` — it sees
    ``min(p + 1, window)``; the walk reads the keys from the first row's
    window start to the last row: ``min(context, window + new_rows - 1)``.

    Operations: QK^T and PV, 2 each per (query row, visible key, head,
    dim).  Bytes: K and V read ONCE a (sequence, layer) over what that
    layer's walk may see (a lower bound for a chunk cut into tiles, whose
    walks overlap), q read and the output written."""
    first = context - new_rows
    if kind == "full":
        visible = new_rows * (first + 1 + context) / 2.0
        read = float(context)
    elif kind == "window":
        # rows at positions >= window - 1 see a whole window
        whole = max(0, context - max(first, window - 1))
        ramp = new_rows - whole
        visible = whole * window + ramp * (first + 1 + first + ramp) / 2.0
        read = float(context - max(0, first - (window - 1)))
    else:
        raise ValueError(f"no layer kind {kind!r}")
    flops = 4.0 * visible * heads * head_dim
    nbytes = (2.0 * read * kv_heads * head_dim * kv_bytes
              + 2.0 * new_rows * heads * head_dim * act_bytes)
    return flops, nbytes


def slot_bytes(context: int, window: int, full_layers: int,
               window_layers: int, kv_heads: int, head_dim: int,
               kv_bytes: int = 2) -> tuple:
    """What a session of ``context`` tokens holds in pages: ``(in the full
    layers, in the window layers)`` — a token is there for as long as the
    session lives in the first, while it is one of the newest ``window``
    in the second."""
    row = 2 * kv_heads * head_dim * kv_bytes
    return (context * full_layers * row,
            min(context, window) * window_layers * row)
