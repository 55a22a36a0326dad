"""Exact-gradient collectives for manual shard_map regions.

The training-side tensor-parallel seam. Serving TP (inference/serving)
runs forward-only and uses raw ``lax.psum`` on block outputs; training
needs the *pair* of Megatron's conjugate operators so hand-driven
``jax.vjp`` chains (the 1F1B pipeline backward) and in-region autodiff
both produce exact gradients inside a manual region (where every
shard's loss cotangent is seeded identically and a
raw psum's transpose would over-count replicated compute by the shard
count):

  - :func:`copy_to` — Megatron's ``f``: identity forward, psum backward.
    Placed where a replicated tensor enters shard-local compute (the
    attention/MLP branch inputs, the vocab-projection input); the
    backward psum reassembles the full cotangent from per-shard partials.
  - :func:`reduce_from` — Megatron's ``g``: psum forward, identity
    backward. Placed where per-shard partial outputs rejoin the
    replicated stream (row-parallel matmul outputs, the vocab-parallel
    softmax statistics); the backward hands each shard the full
    cotangent unchanged — NOT the summed transpose a raw psum would
    apply.

Gradient calculus under this convention (validated to ~1e-7 against a
single-device reference on the 8-virtual-device CPU mesh):

  - model-sharded kernels (column/row splits, vocab-sharded embeddings)
    get EXACT shard-local gradients — no exit collective;
  - leaves consumed on the replicated stream (layernorms, positional
    embeddings applied after the embed psum) get FULL gradients on every
    shard — no exit collective;
  - replicated leaves consumed INSIDE a reduced term (the fused qkv
    kernel/bias entering via per-shard column gather, row-parallel
    output biases pre-divided by the shard count) get PARTIAL gradients
    — one exit psum over ``model`` (:func:`psum_tp_partials`) restores
    them.

The data axis composes on top: gradients leave the region through one
psum — or a ZeRO-2 reduce-scatter (:func:`reduce_over_data`) — over the
data-parallel axis product.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Sequence, Tuple, Union

import jax
import numpy as np

AxisName = Union[str, Tuple[str, ...]]

# gradient-reduce plan codes (grad_reduce_plan leaves must be pytree
# LEAVES so the plan tree zips against the grads tree): -1 = all-reduce,
# d >= 0 = reduce-scatter along dim d into the ZeRO-2 grad layout
REDUCE_PSUM = -1


@lru_cache(maxsize=None)
def copy_to(axis: AxisName):
    """Megatron ``f``: identity forward, psum-over-``axis`` backward."""

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f


@lru_cache(maxsize=None)
def reduce_from(axis: AxisName):
    """Megatron ``g``: psum-over-``axis`` forward, identity backward."""

    @jax.custom_vjp
    def f(x):
        return jax.lax.psum(x, axis)

    def fwd(x):
        return jax.lax.psum(x, axis), None

    def bwd(_, g):
        return (g,)

    f.defvjp(fwd, bwd)
    return f


# Transformer-block leaves whose gradients are PARTIAL per model shard
# under the copy_to/reduce_from convention (keyed on the trailing
# (module, weight) path pair, same addressing as the model's
# _SUFFIX_RULES): the fused qkv enters the region replicated and each
# shard gathers its own permuted columns (gradients are zero off-shard),
# and the row-parallel output biases are pre-divided by the shard count
# inside the reduced term.
TP_PARTIAL_SUFFIXES = frozenset({
    ("qkv", "kernel"), ("qkv", "bias"),
    ("out", "bias"), ("fc_out", "bias"),
})


def psum_tp_partials(tree, axis: AxisName):
    """Exit psum over the model axis for the partial-gradient leaf set."""
    def f(path, leaf):
        keys = tuple(getattr(p, "key", None) for p in path)
        if keys[-2:] in TP_PARTIAL_SUFFIXES:
            return jax.lax.psum(leaf, axis)
        return leaf
    return jax.tree_util.tree_map_with_path(f, tree)


def qkv_shard_columns(num_heads: int, num_kv_heads: int, head_dim: int,
                      model_shards: int) -> np.ndarray:
    """[model_shards, qkv_dim // model_shards] column indices: row ``s``
    is shard ``s``'s fused-qkv layout ``[q_s | k_s | v_s]`` drawn from
    the global ``[q(nh*hd) | k(nkv*hd) | v(nkv*hd)]`` packing.

    The fused qkv axis cannot tile contiguously over ``model`` (a plain
    split would hand shard 0 only q heads), so training regions take the
    kernel/bias in REPLICATED and gather these columns per shard inside
    the differentiated function — the gather's vjp scatters the partial
    gradients back into global layout, and the exit psum over ``model``
    (:func:`psum_tp_partials`) assembles them.  Same permutation math as
    serving's host-side ``_tp_qkv_perm`` prep, reshaped per shard."""
    nhl = num_heads // model_shards
    nkvl = num_kv_heads // model_shards
    rows = []
    for s in range(model_shards):
        rows.append(np.concatenate([
            np.arange(s * nhl * head_dim, (s + 1) * nhl * head_dim),
            num_heads * head_dim
            + np.arange(s * nkvl * head_dim, (s + 1) * nkvl * head_dim),
            (num_heads + num_kv_heads) * head_dim
            + np.arange(s * nkvl * head_dim, (s + 1) * nkvl * head_dim)]))
    return np.stack(rows).astype(np.int32)


def reduce_over_data(g, plan: int, data_axes: Sequence[str]):
    """Reduce one gradient leaf over the data-parallel axis product.

    ``plan`` (an int leaf from ``zero/sharding.grad_reduce_plan``):
    REDUCE_PSUM → all-reduce; ``d >= 0`` → ``psum_scatter`` along dim
    ``d``, landing the leaf directly in the ZeRO-2 sharded grad layout
    (the reference's reduce-scatter IPG path, stage_1_and_2.py:942)."""
    axes = tuple(data_axes)
    if not axes:
        return g
    if plan >= 0:
        return jax.lax.psum_scatter(g, axes, scatter_dimension=plan,
                                    tiled=True)
    return jax.lax.psum(g, axes)
