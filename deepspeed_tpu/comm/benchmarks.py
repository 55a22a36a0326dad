"""Collective benchmarks (``ds_bench`` parity).

Role-equivalent of the reference comm benchmarks
(`/root/reference/benchmarks/communication/*.py` + `bin/ds_bench`): sweep
message sizes for each collective, report latency and algorithmic bus
bandwidth. Collectives run inside jit via shard_map over the chosen mesh
axis (the only way they exist on TPU); timing uses a scalar-fetch barrier.

busbw formulas (ring algorithms, reference `communication/utils.py`):
  all_reduce:      2 * size * (n-1)/n / t
  all_gather:      size * (n-1)/n / t        (size = full gathered bytes)
  reduce_scatter:  size * (n-1)/n / t
  all_to_all:      size * (n-1)/n / t
  ppermute:        size / t
"""
from __future__ import annotations

import argparse
import json
import time
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.shard_map_compat import shard_map


def _mk_collective(name: str, mesh, axis: str) -> Callable:
    n = mesh.shape[axis]

    def wrap(body):
        return jax.jit(shard_map(
            body, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
            axis_names={axis}))

    if name == "all_reduce":
        def body(x):
            return jax.lax.psum(x, axis) / n
    elif name == "all_gather":
        def body(x):
            return jax.lax.all_gather(x, axis).reshape(x.shape[0] * n,
                                                       *x.shape[1:])[
                :x.shape[0]]
    elif name == "reduce_scatter":
        def body(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)
    elif name == "all_to_all":
        def body(x):
            return jax.lax.all_to_all(
                x.reshape(n, x.shape[0] // n, *x.shape[1:]), axis, 0, 0
            ).reshape(x.shape)
    elif name == "ppermute":
        def body(x):
            perm = [(i, (i + 1) % n) for i in range(n)]
            return jax.lax.ppermute(x, axis, perm)
    else:
        raise ValueError(f"unknown collective {name}")
    return wrap(body)


_BUSBW = {
    "all_reduce": lambda size, t, n: 2 * size * (n - 1) / n / t,
    "all_gather": lambda size, t, n: size * (n - 1) / n / t,
    "reduce_scatter": lambda size, t, n: size * (n - 1) / n / t,
    "all_to_all": lambda size, t, n: size * (n - 1) / n / t,
    "ppermute": lambda size, t, n: size / t,
}


def run_benchmark(collective: str, sizes_mb: List[float], mesh=None,
                  axis: str = "data", trials: int = 5,
                  warmups: int = 2) -> List[Dict]:
    if mesh is None:
        from ..parallel.topology import build_mesh
        mesh = build_mesh()
    n = mesh.shape[axis]
    if n < 2:
        raise ValueError(f"axis {axis!r} has size {n}; need >= 2")
    fn = _mk_collective(collective, mesh, axis)
    results = []
    for mb in sizes_mb:
        # n*n alignment: the all_to_all body re-splits the per-rank shard
        elems = max(int(mb * 2 ** 20 // 4), n * n) // (n * n) * (n * n)
        x = jnp.arange(elems, dtype=jnp.float32)
        out = fn(x)
        for _ in range(max(warmups - 1, 0)):
            out = fn(x)
        float(jnp.sum(out).ravel()[0])
        t0 = time.perf_counter()
        for _ in range(trials):
            out = fn(x)
        float(jnp.sum(out).ravel()[0])
        dt = (time.perf_counter() - t0) / trials
        # ds_bench convention: size = the PER-RANK buffer each device
        # contributes (the global array here is sharded n ways)
        size = elems * 4 // n
        results.append({
            "collective": collective, "size_bytes": size,
            "latency_ms": round(dt * 1e3, 3),
            "busbw_GBps": round(_BUSBW[collective](size, dt, n) / 1e9, 3),
        })
    return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="dstpu_bench", description="collective busbw sweep "
        "(reference bin/ds_bench)")
    p.add_argument("--collective", default="all_reduce",
                   choices=sorted(_BUSBW) + ["all"])
    p.add_argument("--axis", default="data")
    p.add_argument("--sizes-mb", default="1,4,16,64")
    p.add_argument("--trials", type=int, default=5)
    args = p.parse_args(argv)
    sizes = [float(s) for s in args.sizes_mb.split(",")]
    names = sorted(_BUSBW) if args.collective == "all" else [args.collective]
    for name in names:
        for row in run_benchmark(name, sizes, axis=args.axis,
                                 trials=args.trials):
            print(json.dumps(row))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
