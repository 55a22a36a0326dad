"""Operations and bytes an algorithm needs, computed from shapes.  Kept with
the benchmark so that no PR that claims a gain can change the yardstick."""
from __future__ import annotations


def transformer_flops_per_token(num_params: int, num_layers: int,
                                d_model: int, seq_len: int) -> float:
    """Forward + backward training operations per token: 6N + 12 L d T (the
    PaLM accounting; recomputed operations do not count).  Copy of
    ``profiling/flops_profiler/profiler.py::transformer_flops_per_token``."""
    return 6.0 * num_params + 12.0 * num_layers * d_model * seq_len


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    by_flops = flops / peak["flops"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")


def paged_attention_cost(context: int, new_rows: int, heads: int,
                         kv_heads: int, head_dim: int,
                         kv_bytes: int = 2, act_bytes: int = 2) -> tuple:
    """One sequence in one layer: ``new_rows`` query rows (1 for a decode
    slot, the chunk length for a prefill chunk) attend causally to a context
    that ends ``context`` tokens long, the new rows included.

    Operations: QK^T and PV, 2 each per (query row, visible key, head, dim).
    Bytes: the context's K and V read once, q read and the output written."""
    visible = new_rows * (context - new_rows) + new_rows * (new_rows + 1) / 2
    flops = 4.0 * visible * heads * head_dim
    nbytes = (2.0 * context * kv_heads * head_dim * kv_bytes
              + 2.0 * new_rows * heads * head_dim * act_bytes)
    return flops, nbytes


def flash_attention_cost(batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, backward: bool,
                         act_bytes: int = 2) -> tuple:
    """One causal flash call over ``[batch, seq]``.  Forward: 2 matmuls over
    the causal half; backward: 5 (recomputed scores, dV, dP, dQ, dK).  Bytes:
    q, k, v, o (and for backward do, dq, dk, dv) each moved once."""
    causal_pairs = batch * heads * seq * (seq + 1) / 2
    matmuls = 5 if backward else 2
    flops = 2.0 * matmuls * causal_pairs * head_dim
    q_like = batch * seq * heads * head_dim * act_bytes
    kv_like = batch * seq * kv_heads * head_dim * act_bytes
    nbytes = (2 * q_like + 2 * kv_like) * (2 if backward else 1)
    return flops, nbytes
