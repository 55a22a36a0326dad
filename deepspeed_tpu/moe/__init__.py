"""Mixture-of-Experts: top-k gating, expert-parallel dispatch, PR-MoE.

Counterpart of `/root/reference/deepspeed/moe/` re-designed for SPMD: expert
weights are a stacked [E, ...] pytree sharded over the ``expert`` mesh axis,
and the dispatch/combine all_to_alls are emitted by GSPMD from sharding
constraints instead of hand-issued collectives.
"""
from . import dropless
from .layer import MoEConfig, MoELayer, mlp_expert
from .sharded_moe import GateOutput, capacity, gate, top1_gating, top2_gating

__all__ = ["dropless", "MoEConfig", "MoELayer", "mlp_expert", "GateOutput",
           "capacity",
           "gate", "top1_gating", "top2_gating"]
