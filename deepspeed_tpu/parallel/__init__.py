"""3-axis parallel substrate: one mesh, three collective families.

The ``(pipe, model, data)`` product lives here — topology (the only
Mesh() owner in the tree, enforced by dstpu-lint MESH003), the
in-tree ``shard_map`` wrapper every manual region goes through,
and the exact-gradient collective pair (Megatron's f/g operators) the
3D training region is built from. The composition invariant: each
collective family owns one axis — ``ppermute`` moves stage-boundary
activations on ``pipe``, per-layer TP ``psum``s stay on ``model``, and
the gradient reduce(-scatter) stays on ``data`` — so no two families
ever contend for the same links.
"""
from .collectives import (REDUCE_PSUM, TP_PARTIAL_SUFFIXES, copy_to,
                          psum_tp_partials, qkv_shard_columns, reduce_from,
                          reduce_over_data)
from .shard_map_compat import shard_map
from .topology import (AXIS_ORDER, DATA_AXIS, DCN_DATA_AXIS, EXPERT_AXIS,
                       MODEL_AXIS, PIPE_AXIS, SEQUENCE_AXIS, MeshSpec,
                       PipeModelDataParallelTopology, ProcessTopology,
                       batch_sharding, build_mesh, dp_world_size,
                       mesh_topology, mp_world_size, named_sharding,
                       pp_world_size, replicated, resolve_mesh_spec)

__all__ = [
    "AXIS_ORDER", "DATA_AXIS", "DCN_DATA_AXIS", "EXPERT_AXIS",
    "MODEL_AXIS", "PIPE_AXIS", "SEQUENCE_AXIS", "MeshSpec",
    "PipeModelDataParallelTopology", "ProcessTopology", "REDUCE_PSUM",
    "TP_PARTIAL_SUFFIXES", "batch_sharding", "build_mesh", "copy_to",
    "dp_world_size", "mesh_topology", "mp_world_size", "named_sharding",
    "pp_world_size", "psum_tp_partials", "qkv_shard_columns",
    "reduce_from", "reduce_over_data", "replicated", "resolve_mesh_spec",
    "shard_map",
]
