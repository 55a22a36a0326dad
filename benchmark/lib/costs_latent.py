"""Operations and bytes of the latent (MLA, absorbed form) paged attention
and of the held experts' grouped product, computed from shapes and from
what the program counted.  Kept with the benchmark, beside ``costs.py``."""
from __future__ import annotations


def latent_attention_cost(context: int, new_rows: int, heads: int,
                          latent: int, rope: int,
                          kv_bytes: int = 2) -> tuple:
    """One sequence in one attention sublayer: ``new_rows`` query
    positions (1 for a decode slot, the chunk length for a prefill chunk)
    attend causally to a context that ends ``context`` tokens long, the
    new rows included.

    Operations: per visible (query position, token) pair and head, the
    score over ``latent + rope`` values and the value sum over ``latent``,
    2 each.  Bytes: the context's rows read once — ``latent + rope``
    USEFUL values a token (1,152 bytes in bfloat16 at 512 + 64; lane
    padding of the pool is the kernel's cost, not the algorithm's)."""
    visible = new_rows * (context - new_rows) + new_rows * (new_rows + 1) / 2
    flops = 2.0 * visible * heads * (latent + rope + latent)
    nbytes = float(context) * (latent + rope) * kv_bytes
    return flops, nbytes


def grouped_experts_cost(picks_held: float, experts_touched: float,
                         d_model: int, d_ff: int,
                         weight_bytes: int = 2) -> tuple:
    """The held experts' SwiGLU over the rows routed to them: per pick
    three ``d_model x d_ff`` products; per expert that received a row its
    three matrices read once (``experts_touched`` is summed over the MoE
    layers and the dispatches counted)."""
    flops = 2.0 * 3 * d_model * d_ff * picks_held
    nbytes = 3.0 * d_model * d_ff * weight_bytes * experts_touched
    return flops, nbytes
