"""Top-k gating + expert dispatch (GShard-style), TPU-native.

Behavioral counterpart of the reference's gating
(`/root/reference/deepspeed/moe/sharded_moe.py:177` top1gating, `:278`
top2gating, `:439` MOELayer.forward). Redesign notes:

  - The reference computes ``capacity`` from runtime tensor shapes and
    branches on it; here capacity is STATIC (derived from the traced token
    count), so the whole gate compiles into one XLA program with fixed
    shapes — no dynamic-shape recompiles.
  - Dispatch/combine are the same einsums as the reference
    (``sec,sm->ecm`` / ``sec,ecm->sm``); sharding constraints on the
    [E, C, M] dispatched tensor make GSPMD emit the all_to_all over the
    ``expert`` mesh axis that the reference issues by hand
    (`sharded_moe.py:89` _AllToAll autograd function).
  - Random Token Selection (`use_rts`, reference `:254`) and the RSample
    noisy gate (`:185`) take an explicit rng key — omitted key = the
    deterministic variants (drop-by-token-order), which is also what the
    reference does at eval.
  - Everything runs in fp32 regardless of the activation dtype, like the
    reference ("everything is in fp32 in this function").

Gating tensor shapes follow the GShard paper / reference notation:
S = tokens, E = experts, C = per-expert capacity, M = model dim.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    l_aux: jnp.ndarray            # scalar load-balance loss
    combine_weights: jnp.ndarray  # [S, E, C] fp32
    dispatch_mask: jnp.ndarray    # [S, E, C] bool
    exp_counts: jnp.ndarray       # [E] int32 — tokens routed per expert
                                  # (pre-drop), the reference's exp_counts


def capacity(num_tokens: int, num_experts: int, capacity_factor: float,
             min_capacity: int) -> int:
    """Static per-expert capacity (reference `_capacity`,
    `sharded_moe.py:163`)."""
    cap = int(math.ceil((num_tokens / num_experts) * capacity_factor))
    return max(cap, min_capacity)


def _rank_within_expert(mask: jnp.ndarray,
                        priority: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Position of each selected token within its expert's queue.

    ``priority`` None → token order (cumsum, the reference's non-RTS path);
    else higher priority wins a capacity slot (RTS: uniform noise).
    Returns [S, E] int32; meaningless where mask == 0."""
    if priority is None:
        return jnp.cumsum(mask, axis=0) - 1
    # Rank selected tokens by descending priority via double argsort.
    keyed = jnp.where(mask > 0, priority, -jnp.inf)
    order = jnp.argsort(-keyed, axis=0)
    return jnp.argsort(order, axis=0).astype(jnp.int32)


def _combine_tensors(gates_masked: jnp.ndarray, locations_s: jnp.ndarray,
                     cap: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    loc_sc = jax.nn.one_hot(locations_s, cap, dtype=jnp.float32)  # [S, C]
    combine = jnp.einsum("se,sc->sec", gates_masked, loc_sc)
    return combine, combine > 0


def top1_gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
                min_capacity: int = 4,
                used_token: Optional[jnp.ndarray] = None,
                noisy_gate_policy: Optional[str] = None,
                drop_tokens: bool = True, use_rts: bool = True,
                rng: Optional[jax.Array] = None) -> GateOutput:
    """Switch-style top-1 routing (reference `top1gating`,
    `sharded_moe.py:177`).

    ``drop_tokens=False`` is intentionally unsupported here: it requires a
    data-dependent capacity (runtime max of exp_counts), which XLA cannot
    compile without dynamic shapes — raise and tell the user to bound
    capacity_factor instead.
    """
    if not drop_tokens:
        raise ValueError(
            "drop_tokens=False needs data-dependent shapes under jit; raise "
            "capacity_factor (e.g. to num_experts) for the same effect")
    s, e = logits.shape
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    cap = capacity(s, e, capacity_factor, min_capacity)

    route_logits = logits
    if noisy_gate_policy == "RSample":
        if rng is None:
            raise ValueError("noisy_gate_policy='RSample' needs an rng key")
        rng, sub = jax.random.split(rng)
        route_logits = logits + jax.random.gumbel(sub, logits.shape)
        indices1 = jnp.argmax(route_logits, axis=1)
    else:
        indices1 = jnp.argmax(gates, axis=1)
    mask1 = jax.nn.one_hot(indices1, e, dtype=jnp.int32)
    if used_token is not None:
        mask1 = mask1 * used_token[:, None].astype(jnp.int32)

    exp_counts = jnp.sum(mask1, axis=0)

    # load-balance aux loss: sum(mean-prob * mean-assignment) * E
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * e

    prio = None
    if use_rts:
        if rng is None:
            prio = None   # deterministic fallback: token order
        else:
            prio = jax.random.uniform(rng, mask1.shape)
    locations1 = _rank_within_expert(mask1, prio)
    mask1 = mask1 * (locations1 < cap).astype(jnp.int32)
    if prio is not None:
        # re-pack surviving tokens contiguously into capacity slots
        locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations1_s = jnp.sum(locations1 * mask1, axis=1)

    gates_masked = gates * mask1.astype(jnp.float32)
    combine, dispatch = _combine_tensors(gates_masked, locations1_s, cap)
    # zero the slots of dropped tokens (one_hot of garbage locations is
    # already masked because gates_masked is 0 there)
    return GateOutput(l_aux, combine, dispatch, exp_counts)


def top2_gating(logits: jnp.ndarray, capacity_factor: float = 1.0,
                min_capacity: int = 4,
                rng: Optional[jax.Array] = None) -> GateOutput:
    """GShard top-2 routing (reference `top2gating`, `sharded_moe.py:278`).

    Second expert picked by gumbel-max when ``rng`` given (the reference
    always samples); deterministic second-argmax otherwise.
    """
    s, e = logits.shape
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    cap = capacity(s, e, capacity_factor * 2.0, min_capacity)

    indices1 = jnp.argmax(gates, axis=1)
    mask1 = jax.nn.one_hot(indices1, e, dtype=jnp.int32)

    logits2 = logits
    if rng is not None:
        logits2 = logits + jax.random.gumbel(rng, logits.shape)
    logits_except1 = jnp.where(mask1 > 0, -jnp.inf, logits2)
    indices2 = jnp.argmax(logits_except1, axis=1)
    mask2 = jax.nn.one_hot(indices2, e, dtype=jnp.int32)

    locations1 = jnp.cumsum(mask1, axis=0) - 1
    locations2 = jnp.cumsum(mask2, axis=0) - 1
    # second-choice tokens queue behind ALL first-choice tokens
    locations2 = locations2 + jnp.sum(mask1, axis=0, keepdims=True)

    exp_counts = jnp.sum(mask1, axis=0)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1.astype(jnp.float32), axis=0)
    l_aux = jnp.mean(me * ce) * e * e

    mask1 = mask1 * (locations1 < cap).astype(jnp.int32)
    mask2 = mask2 * (locations2 < cap).astype(jnp.int32)
    locations1_s = jnp.sum(locations1 * mask1, axis=1)
    locations2_s = jnp.sum(locations2 * mask2, axis=1)

    gates1_s = jnp.einsum("se,se->s", gates, mask1.astype(jnp.float32))
    gates2_s = jnp.einsum("se,se->s", gates, mask2.astype(jnp.float32))
    denom = jnp.maximum(gates1_s + gates2_s, jnp.finfo(jnp.float32).eps)
    gates1_s = gates1_s / denom
    gates2_s = gates2_s / denom

    combine1, _ = _combine_tensors(
        gates1_s[:, None] * mask1.astype(jnp.float32), locations1_s, cap)
    combine2, _ = _combine_tensors(
        gates2_s[:, None] * mask2.astype(jnp.float32), locations2_s, cap)
    combine = combine1 + combine2
    return GateOutput(l_aux, combine, combine > 0, exp_counts)


def gate(logits: jnp.ndarray, k: int, capacity_factor: float = 1.0,
         min_capacity: int = 4, rng: Optional[jax.Array] = None,
         noisy_gate_policy: Optional[str] = None,
         use_rts: bool = True) -> GateOutput:
    """k-dispatch front door (reference TopKGate.forward,
    `sharded_moe.py:389`; k ∈ {1, 2} like the reference)."""
    if k == 1:
        return top1_gating(logits, capacity_factor, min_capacity,
                           noisy_gate_policy=noisy_gate_policy,
                           use_rts=use_rts, rng=rng)
    if k == 2:
        return top2_gating(logits, capacity_factor, min_capacity, rng=rng)
    raise ValueError(
        f"the capacity-gated path takes top-1 and top-2 only, got k={k}; "
        f"top-k without capacity or drops is the dropless router, "
        f"moe/dropless.py (route, expert_share)")
