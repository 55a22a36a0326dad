"""Operations and bytes of a gated delta-rule (KDA) layer's recurrence —
the blocked form of a chunk, the decode lane's one-row update — and the
bytes of the state a slot holds, computed from shapes and from what the
program counted.  The two lanes have opposite bounds: the blocked form is
matrix products and a triangular solve, the decode update one pass over
the whole state.  Both count what the ALGORITHM must do, so they read the
same work whatever implements the lane: the blocked form is counted at
``BLOCK_ROWS`` rows a block, the form as the family states it, whatever
block the program under test takes.  Kept with the benchmark, beside
``costs.py``."""
from __future__ import annotations

#: rows to a block of the counted blocked form
BLOCK_ROWS = 64


def kda_chunk_scan_cost(row_layers: float, calls: float, heads: int,
                        key_dim: int, value_dim: int) -> tuple:
    """The blocked form over ``row_layers`` (valid chunk row, KDA layer)
    pairs in ``calls`` calls (a chunk in a layer), at ``BLOCK_ROWS`` rows
    a block.  Operations a row a head: its row of ``A`` and of ``P``
    against the block's earlier rows (half the block on average, 2 K each:
    2 C K), its row of the unit triangular solve over ``[V | K]`` columns
    (C (V + K)), ``W``'s correction by the incoming state (2 K V), the
    output's two products (2 K V + C V) and what it adds to the state (2 K
    V).  Bytes, what the algorithm must move: a row's ``q``, ``k``, ``v``
    and decay in and its output out (float32, as the lane is handed
    them), ``beta``, and a call's state in and out; the ``[C, C]`` planes
    are the form's own temporaries and do not count."""
    k, v, c = key_dim, value_dim, BLOCK_ROWS
    flops = row_layers * heads * (c * (3.0 * k + 2.0 * v) + 6.0 * k * v)
    nbytes = (row_layers * heads * 4.0 * (3 * k + 2 * v + 1)
              + calls * 2.0 * heads * k * v * 4)
    return flops, nbytes


def kda_decode_update_cost(row_layers: float, heads: int, key_dim: int,
                           value_dim: int) -> tuple:
    """The decode lane's update over ``row_layers`` (slot handed to the
    update, KDA layer) pairs.  Operations a state element: the decay's
    product, the two reductions against ``k`` and ``q`` (a multiply-add
    each) and the rank-1 term's multiply-add: 7.  Bytes: the state in and
    out (float32) and the row's ``q``, ``k``, ``v``, decay and output."""
    hkv = heads * key_dim * value_dim
    flops = 7.0 * row_layers * hkv
    nbytes = row_layers * (2.0 * hkv * 4
                           + heads * 4.0 * (3 * key_dim + 2 * value_dim + 1))
    return flops, nbytes


def state_bytes(kda_layers: int, heads: int, key_dim: int, value_dim: int,
                conv: int, act_bytes: int = 2) -> int:
    """What one slot's recurrent state holds: a float32 matrix state a
    head and a convolution tail (over ``q``, ``k`` and ``v``) in the
    activations' type, a KDA layer."""
    return kda_layers * (heads * key_dim * value_dim * 4 + (conv - 1)
                         * heads * (2 * key_dim + value_dim) * act_bytes)
