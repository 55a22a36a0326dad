"""Operations and bytes of a hybrid state-space block's two kernels,
computed from shapes and from what the program counted: the paged walk by
kind of layer (the layers that walk the ONE full layer's pages, and the
window layers) and the chunk's selective scan.  Kept with the benchmark,
beside ``costs.py``."""
from __future__ import annotations


def paged_walk_cost(kind: str, context: int, new_rows: int, heads: int,
                    kv_heads: int, head_dim: int, window: int,
                    kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """One sequence in ONE layer of ``kind``, after a dispatch that wrote
    its rows ``context - new_rows .. context - 1``.

    ``full`` — a layer that walks the full layer's pages.  Only the rows
    that yield a token attend there: a decode row (``new_rows`` 1), or a
    chunk's LAST row; either is one query position over all ``context``
    tokens.  ``window`` — every new row attends the ``window`` keys that
    end at itself; the walk reads the keys from the first row's window
    start to the last row, once.

    Operations: QK^T and PV, 2 each per (query row, visible key, head,
    dim).  Bytes: the keys and values read once (a lower bound for a
    chunk cut into tiles, whose windows overlap), q read and the output
    written."""
    if kind == "full":
        rows, visible, read = 1, float(context), float(context)
    elif kind == "window":
        first = context - new_rows
        rows = new_rows
        # row at position p sees min(p + 1, window) keys
        full_rows = max(0, context - max(first, window - 1))
        ramp = rows - full_rows                 # positions below window-1
        visible = (full_rows * window
                   + ramp * (first + 1 + first + ramp) / 2.0)
        read = float(context - max(0, first - (window - 1)))
    else:
        raise ValueError(f"no layer kind {kind!r}")
    flops = 4.0 * visible * heads * head_dim
    nbytes = (2.0 * read * kv_heads * head_dim * kv_bytes
              + 2.0 * rows * heads * head_dim * act_bytes)
    return flops, nbytes


def ssm_chunk_scan_cost(row_layers: float, calls: float, d_inner: int,
                        state: int) -> tuple:
    """``ssm_chunk_scan`` over ``row_layers`` (valid chunk row, state-space
    layer) pairs in ``calls`` kernel calls.  Operations a (row, channel,
    state index): the decay's product and exponential, the state's
    multiply-add, the input's product and the output's multiply-add: 7.
    Bytes: a row's ``c``, step and ``y`` in float32 (what the kernel
    moves), its ``B`` and ``C``, and a call's state in and out.  The
    kernel works on the vector unit (no matrix product in it), so against
    the chip's matrix peak the memory bound is the one that binds."""
    flops = 7.0 * row_layers * d_inner * state
    nbytes = (row_layers * (3 * d_inner + 2 * state) * 4.0
              + calls * 2.0 * d_inner * state * 4.0)
    return flops, nbytes


def state_bytes(ssm_layers: int, d_inner: int, state: int, conv: int,
                act_bytes: int = 2) -> int:
    """What one slot's recurrent state holds: a float32 state and a
    convolution tail in the activations' type, a state-space layer."""
    return ssm_layers * (d_inner * state * 4
                         + (conv - 1) * d_inner * act_bytes)
