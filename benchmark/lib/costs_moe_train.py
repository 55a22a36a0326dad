"""Operations and bytes of training one chip's share of a top-1 expert
model with grouped-query attention (``zaya1-8b``), computed from shapes and
from what the program counted.  Kept with the benchmark, beside
``costs.py``."""
from __future__ import annotations


def flops_per_token(layer_params: dict, num_layers: int, vocab: int,
                    hidden: int, heads: int, head_dim: int, seq_len: int,
                    held_share: float) -> float:
    """Forward + backward operations per token (recomputation not
    counted): 6 a parameter a token ACTIVE here — every layer's own part
    (projections, convolutions, router, norms), ONE expert at the share
    of the picks that chose a held expert (``moe_picks_held /
    moe_picks``: a pick of an absent expert costs this chip nothing), the
    tied head once — plus attention's 12 L (heads x head_dim) T: the
    scores live in the latent of ``heads x head_dim``, not the model's
    width."""
    own = sum(v for k, v in layer_params.items() if k != "expert")
    active = (num_layers * (own + held_share * layer_params["expert"])
              + vocab * hidden)
    return 6.0 * active + 12.0 * num_layers * heads * head_dim * seq_len


def grouped_product_dw_cost(picks_held: float, layer_calls: float,
                            held: int, d_model: int, d_ff: int,
                            act_bytes: int = 2) -> tuple:
    """The weights' gradient of the held experts' SwiGLU
    (``moe_grouped_matmul_dw``): per held pick three ``d_model x d_ff``
    products; bytes: the rows of both operands read once and ``[held,
    d_model, d_ff]`` float32 written once a matrix a call
    (``layer_calls``: expert layers x steps).  The forward, its
    recomputation and dx are ``costs_latent.grouped_experts_cost``, three
    times."""
    flops = 2.0 * 3 * d_model * d_ff * picks_held
    nbytes = (3.0 * picks_held * (d_model + d_ff) * act_bytes
              + 3.0 * layer_calls * held * d_model * d_ff * 4)
    return flops, nbytes
