"""Collective communication layer.

Role-equivalent of `deepspeed.comm` (`/root/reference/deepspeed/comm/comm.py`)
re-expressed for XLA: collectives here are **traced into jit programs** as
`jax.lax` ops and scheduled/overlapped by the XLA latency-hiding scheduler —
there are no streams, process groups, or eager NCCL calls. What survives from
the reference surface:

  - the op vocabulary (all_reduce / all_gather / reduce_scatter / all_to_all /
    broadcast / send-recv ≈ ppermute) with named mesh axes instead of process
    groups;
  - instrumentation: every wrapper records trace-time message volume to the
    CommsLogger (reference ``timed_op`` decorator, `comm/comm.py:112`) so
    `log_summary()` (`comm/comm.py:483`) works — latency comes from the
    profiler, volumes are exact at trace time;
  - `init_distributed` (`comm/comm.py:599`) becomes a thin wrapper over
    `jax.distributed.initialize` for multi-host pods.

These functions must be called inside `shard_map`/`pjit`-traced code with the
relevant axis name in scope.  Plain `jit` code using sharding constraints
normally needs none of these — XLA inserts collectives automatically; they
exist for the explicitly-scheduled paths (pipeline ring, MoE dispatch,
ZeRO grad reduction, sequence parallel) and for parity of surface.
"""
from __future__ import annotations

import os
from enum import Enum
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import trace_span
from ..utils.logging import logger
from .comms_logging import get_comms_logger


class ReduceOp(Enum):
    SUM = 0
    PRODUCT = 1
    MIN = 2
    MAX = 3
    AVG = 4


_init_mode: Optional[str] = None  # None | "noop" | "explicit" | "auto"

#: backends this stack can actually drive: collectives are traced into
#: XLA programs, so the only "backend" is XLA itself (aliases accepted
#: for porting convenience).
SUPPORTED_DIST_BACKENDS = ("xla", "jax", "tpu")


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     **_ignored) -> None:
    """Initialize multi-host JAX.

    Reference: `comm/comm.py:599` ``init_distributed`` with MPI/env discovery
    (`:664` mpi_discovery). With explicit args (or COORDINATOR_ADDRESS /
    NUM_PROCESSES / PROCESS_ID env) we pass them through; otherwise on TPU we
    attempt argless auto-detection (pod metadata), falling back to
    single-process. A later call with explicit args upgrades a no-op init.

    An unknown ``dist_backend`` is a loud ValueError, not a silent
    fall-through: a ported DeepSpeed config naming 'nccl'/'gloo'/'mpi'
    would otherwise appear to work while meaning something else entirely.
    """
    global _init_mode
    if dist_backend is None or \
            str(dist_backend).lower() not in SUPPORTED_DIST_BACKENDS:
        raise ValueError(
            f"unknown dist_backend {dist_backend!r}: this TPU-native stack "
            f"drives all collectives through XLA — supported values: "
            f"{', '.join(SUPPORTED_DIST_BACKENDS)} (DeepSpeed's "
            f"'nccl'/'gloo'/'mpi' backends have no role here)")
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS")
    env_np = os.environ.get("NUM_PROCESSES")
    env_pid = os.environ.get("PROCESS_ID")
    if num_processes is None and env_np:
        num_processes = int(env_np)
    if process_id is None and env_pid:
        process_id = int(env_pid)
    if process_id is None and os.environ.get("DSTPU_WORLD_INFO"):
        # launchers that can't template a per-host rank (pdsh over ssh)
        # ship the world-info blob instead; the rank is this hostname's
        # index in it (reference encodes world info the same way,
        # launcher/runner.py world_info_base64)
        import socket
        from ..launcher.runner import decode_world_info
        hosts = list(decode_world_info(os.environ["DSTPU_WORLD_INFO"]))
        name = socket.gethostname()
        matches = [i for i, h in enumerate(hosts)
                   if h == name or name.startswith(h + ".")
                   or h.startswith(name + ".")]
        if len(matches) == 1:
            process_id = matches[0]
        else:
            raise RuntimeError(
                f"cannot derive PROCESS_ID: hostname {name!r} matches "
                f"{len(matches)} entries of DSTPU_WORLD_INFO {hosts}")
    explicit = bool(coordinator_address or num_processes)
    if _init_mode in ("explicit", "auto"):
        return
    if _init_mode == "noop" and not explicit:
        return
    if explicit:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        _init_mode = "explicit"
        logger.info(
            f"jax.distributed initialized: process {jax.process_index()}"
            f"/{jax.process_count()}")
        return
    # Argless: auto-detect only where it can work (TPU pod runtimes).
    try:
        if jax.default_backend() == "tpu" and os.environ.get(
                "TPU_SKIP_MDS_QUERY") != "1":
            jax.distributed.initialize()
            _init_mode = "auto"
            logger.info(
                f"jax.distributed auto-initialized: process "
                f"{jax.process_index()}/{jax.process_count()}")
            return
    except Exception as e:  # single-host or no coordination service
        logger.warning(f"jax.distributed auto-init unavailable ({e}); "
                       "continuing single-process")
    _init_mode = "noop"


def is_initialized() -> bool:
    return _init_mode is not None


def get_world_size(group=None) -> int:
    """Number of *processes* (hosts). Single-controller JAX drives all local
    devices from one process, so the rank/world contract — rank in
    [0, world_size), usable for `samples[rank::world_size]` host-side data
    sharding — is process-level. Device count is `get_device_count()`."""
    return jax.process_count()


def get_rank(group=None) -> int:
    return jax.process_index()


def get_device_count() -> int:
    return jax.device_count()


def get_local_rank() -> int:
    return 0  # single-controller: one process drives all local devices


def barrier(group=None) -> None:
    """Block until all pending local device work completes; on multi-host
    pods additionally rendezvous all processes (so rank-0-writes-then-
    everyone-reads checkpoint patterns are safe)."""
    with trace_span("comm/barrier", processes=jax.process_count()):
        for d in jax.local_devices():
            try:
                jnp.zeros((), device=d).block_until_ready()
            except Exception:  # axes/platform without explicit placement
                jnp.zeros(()).block_until_ready()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                "deepspeed_tpu.comm.barrier")


# ---------------------------------------------------------------------------
# In-jit collectives (call under shard_map with the axis in scope)
# ---------------------------------------------------------------------------
def _log(op_name: str, tensor, axis_name) -> None:
    cl = get_comms_logger()
    if cl is not None and cl.enabled:
        try:
            # axis size is static at trace time — it feeds the busbw
            # correction factor in log_summary (calc_bw_factor)
            n = int(axis_size(axis_name))
        except Exception:   # axis not in scope (direct call outside trace)
            n = 0
        cl.record(op_name, int(tensor.size) * tensor.dtype.itemsize,
                  str(axis_name), n=n)


def all_reduce(tensor, op: ReduceOp = ReduceOp.SUM, axis_name: str = "data"):
    _log("all_reduce", tensor, axis_name)
    if op == ReduceOp.SUM:
        return lax.psum(tensor, axis_name)
    if op == ReduceOp.AVG:
        return lax.pmean(tensor, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(tensor, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(tensor, axis_name)
    if op == ReduceOp.PRODUCT:
        # sign-safe: |prod| via psum of log|x|, sign via parity of negatives
        magnitude = jnp.exp(lax.psum(jnp.log(jnp.abs(tensor)), axis_name))
        neg_count = lax.psum((tensor < 0).astype(jnp.int32), axis_name)
        sign = 1.0 - 2.0 * (neg_count % 2).astype(tensor.dtype)
        return sign * magnitude
    raise ValueError(f"Unsupported ReduceOp {op}")


def inference_all_reduce(tensor, axis_name: str = "model"):
    return all_reduce(tensor, ReduceOp.SUM, axis_name)


def all_gather(tensor, axis_name: str = "data", axis: int = 0,
               tiled: bool = True):
    """Gather shards along `axis` (reference all_gather_into_tensor,
    `comm/comm.py:310`). tiled=True concatenates (flat buffer semantics);
    tiled=False stacks a new leading dim."""
    _log("all_gather", tensor, axis_name)
    return lax.all_gather(tensor, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(tensor, op: ReduceOp = ReduceOp.SUM,
                   axis_name: str = "data", scatter_dimension: int = 0):
    """Reduce then scatter shards (reference reduce_scatter_tensor,
    `comm/comm.py:505`; coalesced variant
    `runtime/comm/coalesced_collectives.py:30`)."""
    _log("reduce_scatter", tensor, axis_name)
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError("reduce_scatter supports SUM/AVG")
    out = lax.psum_scatter(tensor, axis_name,
                           scatter_dimension=scatter_dimension, tiled=True)
    if op == ReduceOp.AVG:
        out = out / lax.axis_size(axis_name)
    return out


def all_to_all_single(tensor, axis_name: str = "expert", split_axis: int = 0,
                      concat_axis: int = 0):
    """MoE dispatch collective (reference `comm/comm.py:361`)."""
    _log("all_to_all", tensor, axis_name)
    return lax.all_to_all(tensor, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def broadcast(tensor, src: int = 0, axis_name: str = "data"):
    """Broadcast src's shard to all members of the axis."""
    _log("broadcast", tensor, axis_name)
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == src, tensor, jnp.zeros_like(tensor)),
                    axis_name)


def ppermute(tensor, perm: Sequence, axis_name: str = "pipe"):
    """Point-to-point ring shift — the TPU-native send/recv used by the
    pipeline engine (reference `runtime/pipe/p2p.py:49,:70`)."""
    _log("ppermute", tensor, axis_name)
    return lax.ppermute(tensor, axis_name, perm=list(perm))


def send_recv_next(tensor, n: int, axis_name: str = "pipe"):
    """Shift shards to the next stage in the ring (stage i → i+1)."""
    return ppermute(tensor, [(i, (i + 1) % n) for i in range(n)], axis_name)


def send_recv_prev(tensor, n: int, axis_name: str = "pipe"):
    """Shift shards to the previous stage (stage i → i-1)."""
    return ppermute(tensor, [(i, (i - 1) % n) for i in range(n)], axis_name)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def axis_size(axis_name: str):
    """Participant count on ``axis_name``."""
    return lax.axis_size(axis_name)


def log_summary() -> str:
    cl = get_comms_logger()
    return cl.log_summary() if cl else ""


def configure(config=None, verbose: Optional[bool] = None, **kw) -> None:
    """Enable comms logging (reference `comm/comm.py:83`)."""
    from .comms_logging import configure as _cfg
    _cfg(config=config, verbose=verbose, **kw)
