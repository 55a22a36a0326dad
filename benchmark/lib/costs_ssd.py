"""Operations and bytes of a Mamba-2 layer's recurrence (the blocked scan
of a chunk, the decode lane's one-row update) and the bytes of the state a
slot holds, computed from shapes and from what the program counted.  The
two lanes have opposite bounds: the blocked scan is matrix products, the
decode update one pass over the whole state.  Kept with the benchmark,
beside ``costs.py``."""
from __future__ import annotations


def ssd_chunk_scan_cost(row_layers: float, calls: float, heads: int,
                        head_dim: int, state: int, block_rows: int,
                        act_bytes: int = 2) -> tuple:
    """The blocked scan over ``row_layers`` (valid chunk row, mamba layer)
    pairs in ``calls`` calls (a chunk in a layer), at ``block_rows`` rows
    a block.  Operations a row: its ``C B^T`` against its block's rows (2
    Q N), the masked product with the block's ``D o X`` (2 Q H P), what it
    adds to the state by the block's end and what the carried state gives
    it (2 H P N each).  Bytes, what the algorithm must move: a row's ``x``
    in and ``y`` out (float32), its step a head, its ``B`` and ``C``, and
    a call's state in and out; the ``[H, Q, Q]`` decay planes are the XLA
    form's own temporaries and do not count."""
    hp = heads * head_dim
    flops = row_layers * (2.0 * block_rows * state + 2.0 * block_rows * hp
                          + 4.0 * hp * state)
    nbytes = (row_layers * (hp * (act_bytes + 4) + heads * 4
                            + 2 * state * act_bytes)
              + calls * 2.0 * hp * state * 4)
    return flops, nbytes


def ssd_decode_update_cost(row_layers: float, heads: int, head_dim: int,
                           state: int, act_bytes: int = 2) -> tuple:
    """The decode lane's update over ``row_layers`` (slot handed to the
    update, mamba layer) pairs.  Operations a state element: the decay's
    product, the input's product and its add, the output's multiply-add:
    5.  Bytes: the state in and out (float32), the row's ``x`` and ``y``,
    its step, ``B`` and ``C``."""
    hp = heads * head_dim
    flops = 5.0 * row_layers * hp * state
    nbytes = row_layers * (2.0 * hp * state * 4 + hp * (act_bytes + 4)
                           + heads * 4 + 2 * state * act_bytes)
    return flops, nbytes


def state_bytes(mamba_layers: int, heads: int, head_dim: int, state: int,
                conv: int, act_bytes: int = 2) -> int:
    """What one slot's recurrent state holds: a float32 matrix state a
    head and a convolution tail (over ``x``, ``B`` and ``C``) in the
    activations' type, a mamba layer."""
    hp = heads * head_dim
    return mamba_layers * (hp * state * 4
                           + (conv - 1) * (hp + 2 * state) * act_bytes)


def state_bytes_moved(dispatches: float, chunk_dispatches: float,
                      slots: int, mamba_layers: int, heads: int,
                      head_dim: int, state: int) -> float:
    """The matrix state ``dispatches`` steps must read plus write: every
    slot of the batch in and out a mamba layer (an idle slot's too: the
    update passes over the whole buffer), and in the ``chunk_dispatches``
    of them that carry a chunk that slot's once more.  It follows from
    the step's shape, so it is what the decode lane MUST move and not
    what it moved: a copy-out shows in the lane's time, not here."""
    one = heads * head_dim * state * 4
    return mamba_layers * 2.0 * one * (slots * dispatches + chunk_dispatches)
