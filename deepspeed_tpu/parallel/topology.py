"""Device mesh & process topology.

TPU-native replacement for the reference's process-group layer
(`/root/reference/deepspeed/utils/groups.py`,
`/root/reference/deepspeed/runtime/pipe/topology.py:9` ``ProcessTopology`` /
``:243`` ``PipeModelDataParallelTopology`` / ``:249``
``PipelineParallelGrid``): instead of building NCCL process groups per
parallel dimension, we build ONE `jax.sharding.Mesh` with named axes and
express every form of parallelism as sharding over those axes.

Axis names (canonical order, outermost → innermost):
    dcn_data — replicas across slices (DCN); collectives here are expensive
    pipe     — pipeline stages (ppermute ring)
    data     — data parallel / ZeRO sharding axis
    expert   — expert parallel: data-like in the training engine, which
               shards the batch over it; no engine shards experts over
               it yet (ROADMAP B6)
    sequence — context parallelism (ring attention axis)
    model    — tensor parallel; innermost so its collectives ride ICI
               neighbors
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_ORDER = ("dcn_data", "pipe", "data", "expert", "sequence", "model")

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"
SEQUENCE_AXIS = "sequence"
DCN_DATA_AXIS = "dcn_data"

#: what the pipeline and the Infinity engines say to an expert axis > 1
EXPERT_AXIS_REFUSAL = (
    "expert mesh axis > 1: nothing here shards experts over it. The "
    "expert layer is moe/dropless.py on one chip's share of the experts; "
    "its exchange across chips is ROADMAP B6. Put those devices on the "
    "data axis")


@dataclass(frozen=True)
class MeshSpec:
    """Resolved axis sizes. Product must equal device count."""
    dcn_data: int = 1
    pipe: int = 1
    data: int = 1
    expert: int = 1
    sequence: int = 1
    model: int = 1

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(getattr(self, a) for a in AXIS_ORDER)

    @property
    def world_size(self) -> int:
        return int(np.prod(self.sizes))


def resolve_mesh_spec(mesh_config, n_devices: int) -> MeshSpec:
    """Resolve -1 ("absorb remaining devices") axis sizes against n_devices."""
    sizes = {a: getattr(mesh_config, a, 1) for a in AXIS_ORDER}
    wild = [a for a, s in sizes.items() if s == -1]
    if len(wild) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wild}")
    fixed = int(np.prod([s for s in sizes.values() if s != -1]))
    if wild:
        if n_devices % fixed != 0:
            raise ValueError(
                f"Device count {n_devices} not divisible by fixed axes {fixed}")
        sizes[wild[0]] = n_devices // fixed
    spec = MeshSpec(**sizes)
    if spec.world_size != n_devices:
        raise ValueError(
            f"Mesh {sizes} covers {spec.world_size} devices, have {n_devices}")
    return spec


def build_mesh(mesh_config=None, devices: Optional[Sequence] = None) -> Mesh:
    """Build the global named mesh.

    Device order: `jax.devices()` on TPU enumerates chips so that adjacent
    indices are ICI neighbors; keeping ``model`` innermost gives TP the
    shortest links, then ``sequence``, etc. Multi-slice (dcn_data > 1) relies
    on devices being grouped by slice in the enumeration, which
    `jax.devices()` guarantees (slice-major order).
    """
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    if mesh_config is None:
        spec = MeshSpec(data=len(devices))
    else:
        spec = resolve_mesh_spec(mesh_config, len(devices))
    dev_array = devices.reshape(spec.sizes)
    return Mesh(dev_array, AXIS_ORDER)


class ProcessTopology:
    """Rank ↔ named-coordinate mapping over arbitrary axes.

    Same contract as the reference ``ProcessTopology``
    (`runtime/pipe/topology.py:9`): axes are named, ranks enumerate in
    row-major order of the axis list, and you can query coordinates, filter
    ranks by fixed coordinates, and list ranks along one axis. Used by the
    checkpoint-reshape library and the pipeline grid; at runtime the Mesh is
    authoritative.
    """

    def __init__(self, axes: List[str], dims: List[int]):
        if len(axes) != len(dims):
            raise ValueError("axes and dims must have the same length")
        self.axes = list(axes)
        self.dims = list(dims)
        self._coord_to_rank: Dict[Tuple[int, ...], int] = {}
        for rank, coord in enumerate(itertools.product(*(range(d) for d in dims))):
            self._coord_to_rank[coord] = rank
        self._rank_to_coord = {r: c for c, r in self._coord_to_rank.items()}

    @property
    def world_size(self) -> int:
        return int(np.prod(self.dims))

    def get_rank(self, **coords) -> int:
        self._check_axes(coords)
        coord = tuple(coords[a] for a in self.axes)
        return self._coord_to_rank[coord]

    def get_coord(self, rank: int):
        coord = self._rank_to_coord[rank]
        return dict(zip(self.axes, coord))

    def get_dim(self, axis: str) -> int:
        return self.dims[self.axes.index(axis)]

    def get_axis_list(self, axis: str, idx: int) -> List[int]:
        """All ranks whose coordinate on `axis` equals idx."""
        ai = self.axes.index(axis)
        return sorted(r for c, r in self._coord_to_rank.items() if c[ai] == idx)

    def get_axis_comm_lists(self, axis: str) -> List[List[int]]:
        """Groups of ranks that differ only along `axis` (the reference's
        process-group builder, `topology.py:188`)."""
        ai = self.axes.index(axis)
        groups: Dict[Tuple[int, ...], List[int]] = {}
        for coord, rank in sorted(self._coord_to_rank.items(), key=lambda kv: kv[1]):
            key = coord[:ai] + coord[ai + 1:]
            groups.setdefault(key, []).append(rank)
        return [sorted(g) for g in groups.values()]

    def filter_match(self, **filter_kwargs) -> List[int]:
        self._check_axes(filter_kwargs)
        out = []
        for coord, rank in self._coord_to_rank.items():
            d = dict(zip(self.axes, coord))
            if all(d[k] == v for k, v in filter_kwargs.items()):
                out.append(rank)
        return sorted(out)

    def _check_axes(self, coords) -> None:
        unknown = set(coords) - set(self.axes)
        if unknown:
            raise ValueError(f"Unknown axes {unknown}; have {self.axes}")

    def __str__(self):
        return f"ProcessTopology(axes={self.axes}, dims={self.dims})"


class PipeModelDataParallelTopology(ProcessTopology):
    """3D (pipe, data, model) topology — reference `topology.py:243`."""

    def __init__(self, num_pp: int, num_dp: int, num_mp: int):
        super().__init__(axes=["pipe", "data", "model"],
                         dims=[num_pp, num_dp, num_mp])


def mesh_topology(mesh: Mesh) -> ProcessTopology:
    """Derive a ProcessTopology from a Mesh (axes with size>1 only)."""
    axes = [a for a in mesh.axis_names if mesh.shape[a] > 1] or ["data"]
    dims = [mesh.shape[a] for a in axes]
    return ProcessTopology(axes, dims)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------
def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Input batches shard over every data-like axis (pipe does NOT shard the
    batch — microbatching handles it)."""
    batch_axes = tuple(a for a in (DCN_DATA_AXIS, DATA_AXIS, EXPERT_AXIS)
                       if mesh.shape.get(a, 1) > 1)
    if not batch_axes:
        return replicated(mesh)
    return NamedSharding(mesh, P(batch_axes))


def dp_world_size(mesh: Mesh) -> int:
    return (mesh.shape.get(DATA_AXIS, 1) * mesh.shape.get(DCN_DATA_AXIS, 1)
            * mesh.shape.get(EXPERT_AXIS, 1))


def mp_world_size(mesh: Mesh) -> int:
    return mesh.shape.get(MODEL_AXIS, 1)


def pp_world_size(mesh: Mesh) -> int:
    return mesh.shape.get(PIPE_AXIS, 1)
