"""ZeRO as sharding policy.

The reference implements ZeRO with ~5k LoC of imperative partitioning,
bucketing, and hook machinery (`/root/reference/deepspeed/runtime/zero/
stage_1_and_2.py:102` DeepSpeedZeroOptimizer, `stage3.py:65`
DeepSpeedZeroOptimizer_Stage3, `partition_parameters.py:539` zero.Init,
`partitioned_param_coordinator.py:44` prefetcher). On TPU under GSPMD the
same dataflow is a *declaration*: we transform the model's tensor-parallel
PartitionSpecs into specs for gradients, optimizer state, and (stage 3)
parameters over the ``data`` mesh axis.  Where the state LIVES is all a
spec tree can say; for stages 0-2 that is also enough to fix the
traffic:

  stage 0 — grads psum over data (classic DP; engine.py:1890 allreduce_gradients)
  stage 1 — optimizer state + fp32 master params sharded over data;
            XLA: grads all-reduced, update computed on the local shard,
            updated params all-gathered (reference stage_1_and_2.py step :1750)
  stage 2 — + gradient specs sharded over data → XLA reduce-scatters grads
            instead of all-reducing (reference average_tensor :942 IPG path)
  stage 3 — + parameter specs sharded over data.  A spec on a weight
            does NOT say that the weight is what travels: left to its
            cost model the partitioner keeps each shard where it is and
            moves the activations of the whole global batch to it,
            tensor-parallel style over ``data`` (an ``all-gather`` of x,
            ``all-to-all``s of the products: five times ZeRO-3's bytes
            in the four-chip cell, docs/training_perf.md).  So the policy
            states the dataflow itself, :meth:`ZeroShardingPolicy.
            gather_layer`: a scan block's compute-dtype weights are
            gathered over the data axes inside the block's rematerialised
            body, immediately before use (the reference's
            fetch_sub_module), the backward gathers them again (nothing
            gathered is saved), and their gradients leave the body
            reduced into ``grad_specs``' layout.  No prefetch: a block's
            gathers are overlapped only with that block's own compute,
            as far as XLA's scheduler manages (the reference's prefetch
            coordinator has no counterpart yet).  What is not in a scan
            block (embedding, head, final norm) is still the
            partitioner's to place.

The "partitioning" itself: for each leaf we shard the largest dimension not
already claimed by another mesh axis and divisible by the data-axis size;
leaves with no such dimension stay replicated (the analogue of the reference's
``param_persistence_threshold`` keeping small params resident).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...parallel.topology import DATA_AXIS, DCN_DATA_AXIS


def _spec_entries(spec: Optional[P], ndim: int) -> list:
    entries = list(spec) if spec is not None else []
    entries += [None] * (ndim - len(entries))
    return entries


def _is_spec(x) -> bool:
    return isinstance(x, P) or x is None


def _used_axes(entries) -> set:
    used = set()
    for e in entries:
        if e is None:
            continue
        if isinstance(e, (tuple, list)):
            used.update(e)
        else:
            used.add(e)
    return used


def shard_over_axis(spec: Optional[P], shape: Tuple[int, ...], mesh: Mesh,
                    axis: Union[str, Sequence[str]] = DATA_AXIS,
                    exclude_dims: Sequence[int] = (),
                    min_size: int = 0) -> P:
    """Add `axis` (one mesh axis name, or a sequence sharded jointly —
    the multi-axis data-parallel product, e.g. ``(dcn_data, data)``) to
    the largest free dim of `shape` divisible by the combined axis size;
    no-op if every requested axis is already used or size 1, or no dim
    qualifies (→ replicated, the small-param persistence case)."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    entries = _spec_entries(spec, len(shape))
    # an axis already claimed by `spec` (or trivial in this mesh) drops
    # out of the joint product rather than vetoing the whole shard
    axes = tuple(a for a in axes
                 if mesh.shape.get(a, 1) > 1 and a not in _used_axes(entries))
    axis_size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axis_size <= 1:
        return P(*entries)
    if int(np.prod(shape)) < min_size:
        return P(*entries)
    best, best_size = None, 0
    for d, (e, s) in enumerate(zip(entries, shape)):
        if d in exclude_dims:
            continue
        # dim may already carry other axes; require divisibility by the
        # combined factor so GSPMD tiles evenly.
        existing = 1
        if e is not None:
            names = e if isinstance(e, (tuple, list)) else (e,)
            for n in names:
                existing *= mesh.shape.get(n, 1)
        if s % (existing * axis_size) != 0:
            continue
        if s >= best_size:
            best, best_size = d, s
    if best is None:
        return P(*entries)
    e = entries[best]
    if e is None:
        entries[best] = axes if len(axes) > 1 else axes[0]
    else:
        names = tuple(e) if isinstance(e, (tuple, list)) else (e,)
        entries[best] = names + axes
    return P(*entries)


def grad_reduce_plan(region_specs, grad_specs, data_axes: Sequence[str]):
    """Per-leaf plan for reducing gradients over the data-parallel axis
    product INSIDE a manual shard_map region (the 3D pipeline engine).

    ``region_specs`` — the region's param entry specs (pipe/model view);
    ``grad_specs`` — the ZeRO policy's grad spec tree (data axes added
    for stage >= 2); ``data_axes`` — the size>1 data-parallel axes the
    region is manual over, in mesh order.

    Returns ``(plan_tree, out_spec_tree)``: plan leaves are ints
    (``collectives.REDUCE_PSUM`` = all-reduce over the product, ``d >=
    0`` = reduce-scatter along dim ``d`` — the dim the policy sharded
    over the data product, so the gradient leaves the region already in
    its ZeRO-2 layout); out specs are the region specs with the data
    axes inserted at the scatter dim.  Int leaves (not tuples) so the
    plan tree zips leaf-for-leaf against the grads tree."""
    from ...parallel.collectives import REDUCE_PSUM
    dset = set(data_axes)

    def one(rsp, gsp):
        ndim = max(len(list(gsp)) if gsp is not None else 0,
                   len(list(rsp)) if rsp is not None else 0)
        gentries = _spec_entries(gsp, ndim)
        out = _spec_entries(rsp, ndim)
        for d, e in enumerate(gentries):
            names = (tuple(e) if isinstance(e, (tuple, list))
                     else ((e,) if e is not None else ()))
            if dset & set(names):
                base = out[d]
                if base is None:
                    out[d] = (tuple(data_axes) if len(data_axes) > 1
                              else data_axes[0])
                else:
                    bnames = (tuple(base) if isinstance(base, (tuple, list))
                              else (base,))
                    out[d] = bnames + tuple(data_axes)
                return d, P(*out)
        return REDUCE_PSUM, P(*out)

    pairs = jax.tree_util.tree_map(
        one, region_specs, grad_specs,
        is_leaf=_is_spec)
    plan = jax.tree_util.tree_map(
        lambda pr: pr[0], pairs,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], P))
    out_specs = jax.tree_util.tree_map(
        lambda pr: pr[1], pairs,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], P))
    return plan, out_specs


class ZeroShardingPolicy:
    """Derives all spec trees for a ZeRO stage.

    ``scan_axis_paths`` names the params subtrees whose leading dim is a
    lax.scan layer axis (excluded from stage-3 param sharding so each scan
    step gathers only its own layer block, not the whole stack).
    """

    def __init__(self, stage: int, mesh: Mesh,
                 param_specs: Any, param_shapes: Any,
                 scan_axis_paths: Sequence[str] = ("blocks",),
                 min_partition_size: int = 0,
                 param_persistence_threshold: int = 0):
        if not 0 <= stage <= 3:
            raise ValueError(f"ZeRO stage must be 0..3, got {stage}")
        self.stage = stage
        self.mesh = mesh
        self.param_specs = param_specs
        self.param_shapes = param_shapes
        self.scan_axis_paths = tuple(scan_axis_paths)
        # stage-3: params below param_persistence_threshold elements stay
        # resident (replicated) instead of sharded+gathered per use — the
        # reference's persisted-param set (zero/config.py). Folding it into
        # min_partition_size applies it to every stage-3 spec tree (live
        # params, masters, grads, moments), which is the whole point of
        # persistence: small tensors aren't worth the collective.
        if stage >= 3:
            min_partition_size = max(min_partition_size,
                                     param_persistence_threshold)
        self.min_partition_size = min_partition_size
        self.param_persistence_threshold = param_persistence_threshold
        #: stage 3 across data-parallel devices: the engine puts
        #: :meth:`gather_layer` on the model's per-layer seam
        self.gathers_layers = stage >= 3 and any(
            mesh.shape.get(a, 1) > 1 for a in (DCN_DATA_AXIS, DATA_AXIS))

    # -- helpers -----------------------------------------------------------
    def _is_scan_path(self, path) -> bool:
        return bool(path) and getattr(path[0], "key", None) in self.scan_axis_paths

    def _sharded_tree(self, exclude_scan_dim: bool):
        def f(path, spec, shp):
            shape = tuple(getattr(shp, "shape", shp))
            excl = (0,) if (exclude_scan_dim and self._is_scan_path(path)) else ()
            # partition over the FULL data-parallel product — on a
            # multi-slice mesh `data` alone is only the intra-slice
            # replicas, and stopping there leaves a dcn_data-factor of
            # the memory saving on the table (specs come from the mesh,
            # never from jax.device_count())
            return shard_over_axis(spec, shape, self.mesh,
                                   (DCN_DATA_AXIS, DATA_AXIS),
                                   exclude_dims=excl,
                                   min_size=self.min_partition_size)
        return jax.tree_util.tree_map_with_path(
            f, self.param_specs, self.param_shapes,
            is_leaf=_is_spec)

    # -- public spec trees -------------------------------------------------
    def model_param_specs(self):
        """Specs for the live (compute-dtype) parameters."""
        if self.stage >= 3:
            return self._sharded_tree(exclude_scan_dim=True)
        return self.param_specs

    def master_param_specs(self):
        """fp32 master copies live with the optimizer state."""
        if self.stage >= 1:
            return self._sharded_tree(exclude_scan_dim=True)
        return self.param_specs

    def grad_specs(self):
        if self.stage >= 2:
            return self._sharded_tree(exclude_scan_dim=True)
        return self.param_specs

    def gather_layer(self, layer):
        """Stage 3's fetch: ``layer`` — one layer's slice of a scan-path
        subtree as the state stores it (each stacked leaf less its
        leading dim, sharded over the data axes) — in ``param_specs``'
        layout: the model-parallel / expert axes the model declared,
        the ``(dcn_data, data)`` product gathered away.  Called inside
        the block's rematerialised body it is an all-gather of the
        block's compute-dtype weights there and again in the backward,
        and its transpose reduces the block's weight gradients back
        into their shards.  A leaf the policy left whole (under
        ``param_persistence_threshold``) passes through."""
        structure = jax.tree_util.tree_structure(layer)
        stacks = self.param_specs if isinstance(self.param_specs, dict) else {}
        for name in self.scan_axis_paths:
            stack = stacks.get(name)
            if stack is not None and jax.tree_util.tree_structure(
                    stack, is_leaf=_is_spec) == structure:
                break
        else:
            raise ValueError(
                f"no subtree of {self.scan_axis_paths} has this layer's "
                f"structure: {structure}")
        with jax.named_scope("zero_comm"):
            return jax.tree_util.tree_map(
                lambda x, spec: jax.lax.with_sharding_constraint(
                    x, NamedSharding(self.mesh, P(*_spec_entries(
                        spec, x.ndim + 1)[1:]))),
                layer, stack)

    def opt_state_specs(self, opt_state_shapes):
        """Map every params-shaped subtree inside the optimizer state to
        sharded specs; scalar leaves (step counters) replicate.

        Recurses to ANY depth so wrapped optax states match too — e.g.
        ScaleByAdamState.mu/nu nested inside a chain tuple (the reference
        shards whatever tensors the optimizer holds, stage_1_and_2.py:638
        initialize_optimizer_states)."""
        moment_specs = (self._sharded_tree(exclude_scan_dim=True)
                        if self.stage >= 1 else self.param_specs)
        params_treedef = jax.tree_util.tree_structure(self.param_shapes)
        param_leaf_shapes = [
            tuple(getattr(x, "shape", ())) for x in
            jax.tree_util.tree_leaves(self.param_shapes)]
        found = [False]

        def matches(subtree) -> bool:
            try:
                if jax.tree_util.tree_structure(subtree) != params_treedef:
                    return False
                return [tuple(getattr(x, "shape", ())) for x in
                        jax.tree_util.tree_leaves(subtree)] == \
                    param_leaf_shapes
            except Exception:
                return False

        def replicate(leaf):
            return P(*([None] * len(getattr(leaf, "shape", ()))))

        # is_leaf=matches stops descent exactly at params-shaped subtrees;
        # everything else (including registered pytree nodes — dataclass
        # optimizer states etc.) is traversed by tree_map itself.
        def map_node(node):
            if matches(node):
                found[0] = True
                return moment_specs
            return replicate(node)

        specs = jax.tree_util.tree_map(map_node, opt_state_shapes,
                                       is_leaf=matches)
        has_tensor_state = any(
            len(getattr(l, "shape", ())) > 0
            for l in jax.tree_util.tree_leaves(opt_state_shapes))
        if self.stage >= 1 and not found[0] and has_tensor_state:
            from ...utils.logging import logger
            logger.warning(
                "ZeRO stage %d: no params-shaped subtree found in the "
                "optimizer state — optimizer state will be fully replicated "
                "(no memory saving). Check the optimizer's state layout.",
                self.stage)
        return specs


def to_named(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


def constrain(tree, mesh: Mesh, spec_tree):
    """with_sharding_constraint over a tree (inside jit): where ZeRO's
    gathers and scatters are placed, so under the device scope
    ``zero_comm``."""
    with jax.named_scope("zero_comm"):
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)), tree, spec_tree)
