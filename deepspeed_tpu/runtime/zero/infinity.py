"""ZeRO-Infinity: train past HBM by streaming layer parameters.

Role-equivalent of the reference's ZeRO-Infinity data path —
`/root/reference/deepspeed/runtime/zero/stage3.py:480`
(_configure_tensor_swapping), `runtime/swap_tensor/partitioned_param_swapper
.py:35` (async param swap with inflight tracking) and
`pipelined_optimizer_swapper.py:55` (double-buffered optimizer state) —
redesigned for the XLA compilation model:

  The reference hooks every ``nn.Module`` pre/post-forward to fetch and
  release partitioned parameters. Here the model's OWN structure is the
  swap schedule: the transformer is a stack of identical scanned layers, so
  the training step becomes a Python-driven pipeline over THREE compiled
  programs (embed, block, head-loss) plus their VJPs. The layer loop
  streams each layer's flattened bf16 parameter vector host→device one step
  ahead of compute (double buffering via JAX async dispatch), and the
  backward walk streams bf16 gradients device→host where the native
  CPU-Adam sweep (`ops/csrc/cpu_adam.cpp` ds_adam_step_g16) folds them into
  fp32 masters held in a DRAM or NVMe ``SlotStore`` — overlapped with the
  next layer's backward on device.

Device HBM therefore holds: the resident params (embeddings, final norm,
head — fp32 masters, optimizer-stepped on host), TWO layer-parameter
buffers and one layer's VJP residuals (both independent of depth), plus
the activation stash — one [B,T,D] tensor PER layer, i.e. linear in depth
(it is the parameter/optimizer memory that goes beyond-HBM, not
activations; shrink the stash with remat or micro-batching). Host tiers:

  offload_param.device:      cpu (DRAM byte store) | nvme (file + aio)
  offload_optimizer.device:  cpu | nvme  (master|m|v slots, SlotOptimizer)

Step modes (all overlap the host work with device compute via a pool of
per-layer-ordered workers, one per host core up to 8):
  pure stream   — gas==1, no clipping: each layer's Adam update runs inside
                  the backward (no host grad accumulator at all).
  streamed gas  — gas>1, no clipping: microbatches 0..gas-2 accumulate into
                  a host fp32 store; during the LAST microbatch each
                  layer's update fires as soon as its accumulation
                  completes — the sweep still hides inside the backward.
  clip-gated    — clipping on (any gas): accumulate + record each layer's
                  exact accumulated ||g||² as it completes; the global norm
                  is ready the moment the last layer's grad lands, then the
                  sweep runs parallel across the worker pool (the update
                  must see the true norm — reference runtime/utils.py:325
                  clip_grad_norm_ — so it cannot fire earlier without
                  changing the math).

Multi-chip composition (ZeRO-3 x Infinity): on a data-parallel mesh the
flat layer vector is padded to a multiple of the dp width and sharded
``P(data)`` — each chip's HBM holds 1/D of the two layer buffers, XLA
all-gathers the vector at use inside ``block_fwd`` and reduce-scatters
``dflat`` back to shards (the GSPMD re-expression of the reference's
rank-partitioned swap, `runtime/zero/stage3.py:480`
_configure_tensor_swapping + `partitioned_param_swapper.py:35` per-rank
partition IO). Host slot stores are sized to the PROCESS-LOCAL span of the
shard axis, so on a multi-host pod each host streams only its ranks'
partitions over PCIe/NVMe while the gather rides ICI. Batches shard over
the same axis; the host Adam sweep is untouched (it just sees a shorter
vector per process).

Restrictions (all raised loudly): data-parallel-only meshes (model/pipe/
sequence/expert axes must be 1 under offload), bf16 compute (no fp16
loss scaling), the standard block, Adam/AdamW.
"""
from __future__ import annotations

import math
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from ...observability import trace_span
from ...utils.logging import logger
from ..resilience import get_fault_injector, policy_from_config, retry_call
from ..utils import host_transfer
from . import wire_codec


def _savez_retry(path: str, policy=None, **arrays) -> None:
    """One slot .npz write through the shared retry policy + the
    ``infinity.slot_write`` fault-injection site. A partial write that
    failed is simply overwritten by the retry (np.savez truncates)."""
    def _write():
        get_fault_injector().check("infinity.slot_write", path=path)
        np.savez(path, **arrays)
    retry_call(_write, policy=policy,
               what=f"infinity slot write {os.path.basename(path)}")


def _load_npz_retry(path: str, policy=None):
    """Open a slot .npz through the retry policy + the
    ``infinity.slot_read`` site. Retries cover the open; a truncated
    archive surfaces at member read and is the integrity layer's job
    (checkpoint manifest), not the retry layer's."""
    def _open():
        get_fault_injector().check("infinity.slot_read", path=path)
        return np.load(path)
    return retry_call(_open, policy=policy,
                      what=f"infinity slot read {os.path.basename(path)}")


def _flatten_info(tpl):
    """Leaves (by tree order), their shapes/sizes, offsets and total n."""
    leaves, treedef = jax.tree_util.tree_flatten(tpl)
    shapes = [tuple(l.shape) for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = np.cumsum([0] + sizes).tolist()
    return treedef, shapes, sizes, offsets, int(offsets[-1])


class InfinityStepper:
    """Layer-streamed train step with host/NVMe parameter + optimizer
    state. Owned by ``DeepSpeedEngine`` when ``offload_param`` is active."""

    def __init__(self, engine, rng):
        self.engine = engine
        model = engine.model
        cfg = engine._config
        self._validate(engine, model, cfg)
        self.model = model
        c = model.config
        self.L = c.scan_length
        self.gas = engine.gradient_accumulation_steps
        self.clip = float(cfg.gradient_clipping or 0.0)
        zc = cfg.zero_config
        op, oo = zc.offload_param, zc.offload_optimizer

        # -- optimizer hyperparams from config -----------------------------
        oc = cfg.optimizer
        name = (oc.type if oc is not None else "adamw").lower()
        params = dict(oc.params) if oc is not None else {}
        self.lr_default = params.pop("lr", 1e-3)
        betas = tuple(params.pop("betas", (0.9, 0.999)))
        eps = params.pop("eps", 1e-8)
        wd = params.pop("weight_decay", 0.0)
        adamw = params.pop("adam_w_mode", name != "adam")

        # -- layout --------------------------------------------------------
        layer_tpl = jax.eval_shape(model.init_superblock,
                                   jax.random.PRNGKey(0))
        (self._treedef, self._shapes, self._sizes, self._offsets,
         self.n_elems) = _flatten_info(layer_tpl)
        self.resident_tpl = jax.eval_shape(model.init_resident,
                                           jax.random.PRNGKey(0))
        self.total_params = (self.L * self.n_elems + sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(self.resident_tpl)))

        # -- shardings (multi-chip: dp-sharded layer vector) ---------------
        from ...parallel import topology as topo
        mesh = engine.mesh
        self.dp = topo.dp_world_size(mesh)
        # flat layer vector padded so it splits evenly into dp shards;
        # both the vector and the batch ride the data-like axes. With wire
        # compression each dp shard must also be a whole number of
        # quantization chunks so every chip encodes its shard locally.
        self.wire_bits = int(getattr(zc, "offload_wire_bits", 0) or 0)
        if self.wire_bits not in (0, 1, 4, 8):
            raise ValueError(
                f"zero_optimization.offload_wire_bits must be 0, 1, 4 or 8; "
                f"got {self.wire_bits}")
        # H2D param wire (offload_param_bits): quantized uploads + a
        # quantized device cache; see runtime/config.py for the contract
        self.param_bits = int(getattr(zc, "offload_param_bits", 0) or 0)
        if self.param_bits not in (0, 4, 8):
            raise ValueError(
                f"zero_optimization.offload_param_bits must be 0, 4 or 8; "
                f"got {self.param_bits}")
        quantum = self.dp * (wire_codec.CHUNK
                             if (self.wire_bits or self.param_bits) else 1)
        self.n_pad = -(-self.n_elems // quantum) * quantum
        # device layer-cache budget: how many streamed layers may stay
        # resident at once (2 = the minimal double-buffer; more turns the
        # backward's re-uploads into cache hits when HBM allows). The
        # config knob is in params-at-bf16; a quantized cache holds more
        # layers in the same bytes, so account in bytes.
        cache_bytes_pp = {0: 2.0, 8: 1.0, 4: 0.5}[self.param_bits]
        budget_bytes = int(zc.max_live_parameters) * 2
        per_layer_bytes = max(self.n_elems, 1) * cache_bytes_pp
        self.max_live_layers = int(np.clip(
            int(budget_bytes / per_layer_bytes), 2, self.L))
        self._flat_shard = topo.batch_sharding(mesh)
        self._batch_shard = topo.batch_sharding(mesh)
        self._repl = topo.replicated(mesh)
        # process-local span of the shard axis (multi-host: each host's
        # stores cover only its ranks' partitions)
        imap = self._flat_shard.devices_indices_map((self.n_pad,))
        spans = sorted(
            (0 if idx[0].start is None else int(idx[0].start),
             self.n_pad if idx[0].stop is None else int(idx[0].stop))
            for dev, idx in imap.items()
            if dev.process_index == jax.process_index())
        self._lo, self._hi = spans[0][0], spans[-1][1]
        uniq = sorted(set(spans))
        if any(a[1] != b[0] for a, b in zip(uniq, uniq[1:])):
            raise NotImplementedError(
                "ZeRO-Infinity needs this process's dp shards contiguous in "
                f"the flat vector; got spans {spans}")
        self.n_local = self._hi - self._lo

        # -- host stores ---------------------------------------------------
        from ..swap_tensor.slot_store import make_slot_store
        from ..swap_tensor.partitioned_optimizer_swapper import SlotOptimizer
        aio_cfg = cfg.aio
        shared_aio = None
        if "nvme" in (op.device.value, oo.device.value):
            from ...ops.aio import AsyncIOHandle
            shared_aio = AsyncIOHandle(
                block_size=aio_cfg.block_size,
                num_threads=aio_cfg.thread_count)
        self.param_store = make_slot_store(
            op.device.value, self.L, self.n_local * 2,
            nvme_path=op.nvme_path, aio=shared_aio,
            buffer_count=max(4, op.buffer_count), name="params")
        # upload pins are held by the STREAMING thread until each async H2D
        # transfer completes — give the store a way to reclaim them when
        # its ring runs dry (otherwise that thread would block waiting on
        # its own release path). Gated to the streaming thread: the
        # optimizer worker must NOT run the sweep (it would race
        # _pending_uploads and invert the store-lock/upload order) — it
        # falls through to the store's cond.wait until the streaming
        # thread sweeps.
        self._stream_thread = threading.current_thread()

        def _reclaim():
            if threading.current_thread() is self._stream_thread:
                self._sweep_uploads(block=True)
        self.param_store.reclaim = _reclaim
        # shared transient-I/O retry policy for the slot streams
        # (runtime/resilience; the host/NVMe tiers are the I/O surface a
        # multi-day run actually hits)
        self._io_policy = policy_from_config(
            getattr(cfg, "resilience", None))
        self._skip_nonfinite = bool(
            getattr(cfg, "resilience", None) is not None
            and cfg.resilience.skip_nonfinite_grad_steps)
        self.param_store.io_policy = self._io_policy
        self.opt = SlotOptimizer(
            self.L, self.n_local, device=oo.device.value,
            nvme_path=oo.nvme_path, aio=shared_aio,
            buffer_count=max(3, oo.buffer_count), lr=self.lr_default,
            betas=betas, eps=eps, weight_decay=wd, adamw_mode=adamw,
            name="optimizer")
        self.opt.store.io_policy = self._io_policy
        self._aio = shared_aio

        # collect-mode gradient accumulator, allocated lazily (fp32 [L, n])
        self._grad_accum: Optional[np.ndarray] = None

        # H2D quantized-upload encode offload: the numpy quantize pass
        # (encode_params_host) used to run inline in _ensure_layer ON the
        # streaming thread, stalling the H2D lane (and every program
        # dispatch behind it) for the duration of each layer's encode.
        # Now: (a) encoded payloads are CACHED while a layer's masters
        # are unchanged (the whole backward walk and any eval re-upload
        # re-use the forward's encode — the sweep invalidates per
        # layer), and (b) upcoming layers are encoded AHEAD on the
        # layer-pinned worker pool so the stream thread uploads a ready
        # payload. Both are gated to DRAM param stores: an NVMe store's
        # pinned ring must not be acquired from a worker while the
        # stream thread blocks on that worker's result (ring reclaim is
        # stream-thread-gated — classic lock-order deadlock), and a
        # full-model encode cache in DRAM would defeat NVMe offload.
        self._enc_lock = threading.Lock()
        self._enc_cache: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._enc_version = [0] * self.L
        self._enc_futures: Dict[int, Future] = {}
        self._enc_async = bool(self.param_bits) and \
            op.device.value == "cpu"

        # -- init ----------------------------------------------------------
        self._init_state(rng)

        # Resident tier (embeddings + norms + head) keeps masters AND Adam
        # moments on DEVICE: the resident tree is small relative to blocks
        # but its gradients are model-width x vocab — streaming them
        # device→host every step would put megabytes-per-step on the slow
        # D2H wire for no memory win. ~16 bytes/param of HBM buys zero
        # per-step resident transfers. The update is the engine's own
        # configured Optimizer (runtime/optimizers.py adam — one source of
        # the Adam math alongside the native host sweep).
        self._res_treedef = jax.tree_util.tree_structure(self.resident)
        self._res_optim = engine.optimizer
        with self.engine.mesh:
            # dstpu: ignore[TRACE003] -- one compile at init, not per step
            self.res_state = jax.jit(self._res_optim.init)(self.resident)

        # -- compiled programs (built lazily per batch-key signature) ------
        self._programs: Dict = {}
        # wire-compression RNG: one base key, folded with a monotone
        # sequence number per encoded layer-grad (deterministic, no
        # device-side RNG state to checkpoint)
        self._wire_base = jax.random.PRNGKey(0x1bad)
        self._wire_seq = 0
        # slot -> tuple of device arrays: (bf16 flat,) uncompressed, or
        # (payload, scales) under the quantized param wire
        self._dev: Dict[int, Tuple[jax.Array, ...]] = {}
        # (slot|None, device arrays, host refs kept alive for the DMA)
        self._pending_uploads: List[Tuple] = []
        # Host optimizer parallelism: one single-thread executor per worker,
        # layer i dispatched to worker i % N — per-layer ordering (accum of
        # microbatch j before j+1) is preserved while distinct layers sweep
        # on distinct cores (the native Adam + numpy accum release the GIL).
        nw = int(getattr(oo, "worker_count", 0) or 0)
        if nw <= 0:
            nw = min(os.cpu_count() or 1, 8)
        if "nvme" in (op.device.value, oo.device.value):
            # each concurrent sweep task pins one param-ring AND one
            # opt-ring buffer; bound concurrency below the smaller ring so
            # two tasks can never exhaust both rings against each other
            nw = min(nw, 2)
        self._workers = [ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"infinity-opt{k}")
            for k in range(nw)]
        try:
            from ...ops.adam.cpu_adam import _lib as adam_lib
            self._native = adam_lib()    # probed once; None → numpy paths
        except Exception:
            self._native = None
        host_gb = (self.param_store.host_bytes + self.opt.host_bytes) / 2**30
        disk_gb = (self.param_store.disk_bytes + self.opt.disk_bytes) / 2**30
        logger.info(
            f"ZeRO-Infinity: {self.total_params / 1e9:.2f}B params, "
            f"{self.L} layers x {self.n_elems / 1e6:.1f}M elems, dp="
            f"{self.dp} (local span {self.n_local / 1e6:.1f}M); host "
            f"{host_gb:.1f} GiB, nvme {disk_gb:.1f} GiB "
            f"(params={op.device.value}, optimizer={oo.device.value}); "
            f"device layer cache {self.max_live_layers}/{self.L} layers "
            f"(~{self.max_live_layers * self.n_pad * cache_bytes_pp / self.dp / 2**30:.2f}"
            f" GiB/chip — zero_optimization.max_live_parameters bounds it)"
            + (f"; D2H wire {self.wire_bits}-bit stochastic-rounded"
               if self.wire_bits else "")
            + (f"; H2D param wire {self.param_bits}-bit RTN"
               if self.param_bits else ""))

    # ------------------------------------------------------------------
    @staticmethod
    def _validate(engine, model, cfg) -> None:
        if getattr(getattr(model, "config", None), "attention_layers", ()):
            raise NotImplementedError(
                "ZeRO-Infinity streams layers through a layer-index-free "
                "block_fwd, which cannot carry the per-layer attention "
                "windows of attention_layers (GPT-Neo family); train this "
                "model with the in-HBM engine, or drop attention_layers")
        for attr in ("init_superblock", "init_resident", "_block"):
            if not hasattr(model, attr):
                raise NotImplementedError(
                    "ZeRO-Infinity needs a scan-layer model exposing "
                    "init_superblock/init_resident (TransformerLM does); "
                    f"got {type(model).__name__}")
        from ...parallel import topology as topo
        mesh = engine.mesh
        for axis in (topo.MODEL_AXIS, topo.PIPE_AXIS, topo.SEQUENCE_AXIS):
            if mesh.shape.get(axis, 1) > 1:
                raise NotImplementedError(
                    f"ZeRO-Infinity composes with data-like sharding "
                    f"only; mesh axis '{axis}' has size "
                    f"{mesh.shape[axis]} — use a data mesh under "
                    f"offload_param, or drop offload for tp/pp/sp")
        if mesh.shape.get(topo.EXPERT_AXIS, 1) > 1:
            raise NotImplementedError(topo.EXPERT_AXIS_REFUSAL)
        if engine.fp16_enabled:
            raise NotImplementedError(
                "ZeRO-Infinity requires bf16 (fp16 loss scaling is not "
                "wired into the streamed step); set bf16.enabled")
        oc = cfg.optimizer
        name = (oc.type if oc is not None else "adamw").lower()
        if name not in ("adam", "adamw", "fusedadam", "cpuadam",
                        "deepspeedcpuadam"):
            raise NotImplementedError(
                f"ZeRO-Infinity host sweep supports Adam/AdamW, got {name}")
        zc = cfg.zero_config
        if zc.offload_optimizer is None or \
                zc.offload_optimizer.device.value == "none":
            raise ValueError(
                "offload_param without offload_optimizer would keep full "
                "optimizer state in HBM, defeating the point — set "
                "offload_optimizer: {device: cpu|nvme}")

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_state(self, rng) -> None:
        """Materialize one layer at a time on device, spill to the stores.
        Layer i here is bit-identical to row i of ``model.init`` (the vmap
        over ``superblock_keys`` — parity tested).

        With ``infinity_host_init`` the layer slots are drawn host-side
        instead (same shapes/scales, different RNG) — skips the per-layer
        device→host fetch, which dominates startup on slow D2H links."""
        model = self.model
        with self.engine.mesh:
            # dstpu: ignore[TRACE003] -- one compile at init, not per step
            self.resident = jax.jit(model.init_resident,
                                    out_shardings=self._repl)(rng)
        if self.engine._config.zero_config.infinity_host_init:
            nrng = np.random.default_rng(
                int(jax.random.randint(rng, (), 0, 2**31 - 1)))
            flat = np.empty(self.n_elems, np.float32)
            stds = self._host_init_stds()
            for i in range(self.L):
                for off, size, std in zip(self._offsets, self._sizes, stds):
                    span = flat[off:off + size]
                    if std > 0.0:
                        span[:] = nrng.standard_normal(
                            size, dtype=np.float32) * std
                    else:          # biases 0 (norm scales fixed up below)
                        span[:] = 0.0
                self._set_norm_scales_one(self._unflatten_host(flat))
                self._init_slot_from_full(i, flat)
        else:
            with self.engine.mesh:
                def one_layer(k):
                    leaves = jax.tree_util.tree_leaves(
                        model.init_superblock(k))
                    flat = jnp.concatenate(
                        [l.reshape(-1).astype(jnp.float32) for l in leaves])
                    return flat

                init_fn = jax.jit(one_layer)
                keys = model.superblock_keys(rng)
                for i in range(self.L):
                    # every process computes the (identical) full vector,
                    # stores only its local span
                    self._init_slot_from_full(i, np.asarray(init_fn(keys[i])))
        self.param_store.flush()
        self.opt.flush()

    def _local_f32(self, flat_full: np.ndarray) -> np.ndarray:
        """This process's span of the padded flat vector (pad tail zeros)."""
        out = np.zeros(self.n_local, np.float32)
        hi = min(self._hi, self.n_elems)
        if hi > self._lo:
            out[:hi - self._lo] = flat_full[self._lo:hi]
        return out

    def _init_slot_from_full(self, i: int, flat_full: np.ndarray) -> None:
        loc = self._local_f32(flat_full)
        self.opt.init_slot(i, loc)
        buf = self.param_store.acquire(i)
        buf[:self.n_local * 2].view(np.uint16)[:] = (
            loc.astype(ml_dtypes.bfloat16).view(np.uint16))
        self.param_store.release(i, dirty=True)
        self._invalidate_encoded(i)

    def _host_init_stds(self) -> List[float]:
        """Per-leaf init stddev matching model init (models/transformer.py
        _block_init): 0.02 for kernels, 0.02/sqrt(2*num_layers) for the
        residual-branch projections (scaled_init), 0 for 1-d leaves."""
        layer_tpl = jax.eval_shape(self.model.init_superblock,
                                   jax.random.PRNGKey(0))
        nl = self.model.config.num_layers

        def std_for(path, leaf):
            keys = tuple(str(getattr(p, "key", "")) for p in path)
            if len(leaf.shape) < 2:
                return 0.0
            if keys[-2:] in (("out", "kernel"), ("fc_out", "kernel")):
                return 0.02 / math.sqrt(2.0 * nl)
            return 0.02
        tree = jax.tree_util.tree_map_with_path(std_for, layer_tpl)
        return jax.tree_util.tree_leaves(tree)

    def _unflatten_host(self, flat: np.ndarray):
        """Host-side views of a flat slot, shaped as the layer tree."""
        leaves = [flat[o:o + s].reshape(sh)
                  for o, s, sh in zip(self._offsets, self._sizes,
                                      self._shapes)]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _set_norm_scales_one(self, layer_tree) -> None:
        """Host init: norm 'scale' leaves → 1.0 (views mutate the slot)."""
        def visit(path, leaf):
            keys = [getattr(p, "key", "") for p in path]
            if any(str(k).startswith("ln") for k in keys) and \
                    "scale" in [str(k) for k in keys]:
                leaf[...] = 1.0
            return leaf
        jax.tree_util.tree_map_with_path(visit, layer_tree)

    # ------------------------------------------------------------------
    # device layer cache
    # ------------------------------------------------------------------
    def _sweep_uploads(self, block: bool = False) -> None:
        """Release param-store pins whose H2D transfer has completed. The
        pin must outlive the transfer: ``device_put`` is async and reads the
        pinned host buffer when the DMA runs — releasing immediately would
        let the NVMe ring recycle the buffer under the transfer."""
        still = []
        for slot, arrs, refs in self._pending_uploads:
            if block:
                for a in arrs:
                    host_transfer(a, block=True)  # join the H2D DMA
            if all(a.is_ready() for a in arrs):
                if slot is not None:
                    self.param_store.release(slot, dirty=False)
            else:
                still.append((slot, arrs, refs))
        self._pending_uploads = still

    def _put_vec(self, host_local: np.ndarray, total: int) -> jax.Array:
        """Upload the process-local span of a P(data)-sharded 1-D vector of
        ``total`` elements (every wire vector's length divides evenly over
        the dp axis by n_pad construction). Single-process: one sharded
        device_put (JAX slices per device). Multi-host: each process
        contributes only its addressable shards."""
        if jax.process_count() == 1:
            return jax.device_put(host_local, self._flat_shard)
        lo0 = self._lo * total // self.n_pad   # local span start, scaled
        shards = []
        imap = self._flat_shard.addressable_devices_indices_map((total,))
        for dev, idx in imap.items():
            sl = idx[0]
            lo = 0 if sl.start is None else int(sl.start)
            hi = total if sl.stop is None else int(sl.stop)
            shards.append(jax.device_put(
                host_local[lo - lo0:hi - lo0], dev))
        return jax.make_array_from_single_device_arrays(
            (total,), self._flat_shard, shards)

    def _put_flat(self, host_bf16_local: np.ndarray) -> jax.Array:
        return self._put_vec(host_bf16_local, self.n_pad)

    def _fetch_flat(self, arr: jax.Array) -> np.ndarray:
        """bf16 device vector → host, process-local span only (the D2H wire
        carries each host's partition, reference partitioned_param_swapper
        per-rank IO). Deliberate sync — this IS the offload wire."""
        if jax.process_count() == 1:
            return host_transfer(arr)
        out = np.empty(self.n_local, ml_dtypes.bfloat16)
        for sh in arr.addressable_shards:
            sl = sh.index[0]
            lo = 0 if sl.start is None else int(sl.start)
            out[lo - self._lo:lo - self._lo + sh.data.shape[0]] = (
                host_transfer(sh.data))
        return out

    def _fetch_span(self, arr: jax.Array) -> np.ndarray:
        """Process-local span of any P(data)-sharded 1-D vector (wire
        payload / scales — lengths proportional to n_pad). Deliberate
        sync — the compressed-wire half of the offload stream."""
        if jax.process_count() == 1:
            return host_transfer(arr)
        shards = sorted(((0 if sh.index[0].start is None
                          else int(sh.index[0].start), sh.data)
                         for sh in arr.addressable_shards))
        return np.concatenate([host_transfer(d) for _, d in shards])

    def _decode_wire(self, wire, out: np.ndarray,
                     accumulate: bool) -> None:
        """Host side of the compressed grad wire: fetch payload + scales
        (process-local spans) and decode into the fp32 vector."""
        payload = self._fetch_span(wire[0])
        scales = self._fetch_span(wire[1])
        wire_codec.decode_into(out, payload, scales, self.wire_bits,
                               accumulate=accumulate)

    def _ensure_layer(self, i: int, keep) -> Tuple[jax.Array, ...]:
        """Device copy of layer i's sharded param vector — (bf16 flat,) or
        (payload, scales) under the quantized param wire — uploading from
        the host store on miss. Eviction honours
        ``zero_optimization.max_live_parameters`` (reference stage3
        max_live_parameters budget): layers stay resident up to the budget
        so the backward walk re-uses the forward's uploads instead of
        re-crossing the H2D wire — oldest-uploaded evicted first (on a
        forward sweep that keeps exactly the layers the backward needs
        first)."""
        if i in self._dev:
            return self._dev[i]
        while len(self._dev) >= self.max_live_layers:
            victim = next((k for k in self._dev if k not in keep), None)
            if victim is None:
                break
            del self._dev[victim]
        self._sweep_uploads()
        if self.param_bits:
            # quantized upload: the encoded payload comes from the cache,
            # an ahead-of-need worker encode, or (NVMe store / cold
            # start) an inline pass; the async DMA reads the ENCODED
            # arrays — no slot pin outlives this call (refs keep the
            # payload alive instead)
            payload, scales = self._encoded_params(i)
            pay_total = {8: self.n_pad, 4: self.n_pad // 2}[self.param_bits]
            arrs = (self._put_vec(payload, pay_total),
                    self._put_vec(scales, self.n_pad // wire_codec.CHUNK))
            self._pending_uploads.append((None, arrs, (payload, scales)))
        else:
            buf = self.param_store.acquire(i)
            host = buf[:self.n_local * 2].view(ml_dtypes.bfloat16)
            arrs = (self._put_flat(host),)
            # pin held until transfer done
            self._pending_uploads.append((i, arrs, ()))
        self._dev[i] = arrs
        return arrs

    # -- H2D encode cache / worker offload (param_bits only) ------------
    def _invalidate_encoded(self, i: int) -> None:
        """Layer i's masters changed (host Adam sweep, checkpoint load,
        init): any cached or in-flight encoded payload is stale."""
        if not self.param_bits:
            return
        with self._enc_lock:
            self._enc_version[i] += 1
            self._enc_cache.pop(i, None)
            self._enc_futures.pop(i, None)

    def _encode_slot(self, i: int, version: int):
        """Worker-pool task: pinned slot -> (payload, scales) encode.
        Runs on layer i's OWN pinned worker, so it serializes after any
        queued sweep of the same layer (whose slot write would have
        bumped ``version`` and made this result dead on arrival)."""
        buf = self.param_store.acquire(i)
        try:
            host = buf[:self.n_local * 2].view(ml_dtypes.bfloat16)
            enc = wire_codec.encode_params_host(host, self.param_bits)
        finally:
            self.param_store.release(i, dirty=False)
        with self._enc_lock:
            if self._enc_version[i] == version:
                self._enc_cache[i] = enc
        return version, enc

    def _prefetch_encode(self, i: int) -> None:
        """Queue layer i's quantize pass ahead of need so the streaming
        thread uploads a ready payload instead of stalling the H2D lane
        on the numpy encode (the forward walk prefetches i+2 while
        uploading i+1 and computing i; the backward mirrors it)."""
        if not self._enc_async or not 0 <= i < self.L or i in self._dev:
            return
        with self._enc_lock:
            if i in self._enc_cache or i in self._enc_futures:
                return
            fut = self._submit(i, self._encode_slot, self._enc_version[i])
            self._enc_futures[i] = fut

    def _encoded_params(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        """Encoded (payload, scales) for layer i: unchanged-master cache
        hit -> in-flight worker prefetch -> inline encode."""
        if self._enc_async:
            with self._enc_lock:
                enc = self._enc_cache.get(i)
                fut = self._enc_futures.pop(i, None)
            if enc is not None:
                return enc
            if fut is not None:
                version, enc = fut.result()
                with self._enc_lock:
                    if self._enc_version[i] == version:
                        return enc
        with self._enc_lock:
            v0 = self._enc_version[i]
        buf = self.param_store.acquire(i)
        try:
            host = buf[:self.n_local * 2].view(ml_dtypes.bfloat16)
            enc = wire_codec.encode_params_host(host, self.param_bits)
        finally:
            self.param_store.release(i, dirty=False)
        if self._enc_async:
            with self._enc_lock:
                if self._enc_version[i] == v0:
                    self._enc_cache[i] = enc
        return enc

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _unflatten(self, flat: jax.Array):
        leaves = [jax.lax.slice(flat, (o,), (o + s,)).reshape(sh)
                  for o, s, sh in zip(self._offsets, self._sizes,
                                      self._shapes)]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _build_programs(self, has_labels: bool, has_mask: bool):
        key = (has_labels, has_mask)
        if key in self._programs:
            return self._programs[key]
        model, c = self.model, self.model.config
        from ...models import layers as Lx
        norm = (Lx.layernorm_apply if c.norm_type == "layernorm"
                else Lx.rmsnorm_apply)
        eps = c.layernorm_eps

        def cast_res(res):
            return jax.tree_util.tree_map(
                lambda p: p.astype(c.dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, res)

        def embed_fwd(res, ids, tt):
            # Delegate to the model's shared embedding path so the offload
            # forward math matches the in-HBM path exactly — including the
            # token-type add (BERT) and embedding layernorm (BLOOM) that
            # init_resident stores (models/transformer.py _embed_tokens).
            return model._embed_tokens(
                cast_res(res), ids,
                token_type_ids=(tt if c.token_type_vocab else None))

        def flat_fwd(flat, x):
            return model._block(self._unflatten(flat), x)[0]

        pb = self.param_bits

        if pb:
            # quantized layer cache: each program takes (payload, scales)
            # and fuses the dequant into the layer compute. block_vjp
            # differentiates w.r.t. the DEQUANTIZED flat — that gradient
            # is what the host sweep applies to the exact f32 masters
            # (straight-through: d(dequant)/d(master) treated as identity,
            # the standard QAT estimator; the quantization error is
            # re-derived from the masters at every upload, never carried).
            def block_fwd(payload, scales, x):
                flat = wire_codec.decode_params(payload, scales, pb)
                return flat_fwd(flat, x)

            def block_vjp(payload, scales, x, dy):
                flat = wire_codec.decode_params(payload, scales, pb)
                dflat, dx = jax.vjp(flat_fwd, flat, x)[1](dy)
                sq = jnp.sum(jnp.square(dflat.astype(jnp.float32)))
                return dflat, dx, sq
        else:
            block_fwd = flat_fwd

        def head_loss(res, xL, ids, labels, mask):
            # mirrors model.loss's label/mask/chunk semantics
            # (models/transformer.py loss) with the resident subtree as
            # the param source
            if not has_labels:
                labels = jnp.concatenate(
                    [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
                last = jnp.ones_like(ids, jnp.float32).at[:, -1].set(0.0)
                mask = last if not has_mask else mask * last
            elif not has_mask:
                mask = jnp.ones_like(labels, jnp.float32)
            res = cast_res(res)
            x = norm(res["ln_f"], xL, eps=eps)
            t = labels.shape[1]
            chunk = c.loss_chunk
            if chunk and t > chunk and t % chunk == 0:
                n_chunks = t // chunk

                def to_chunks(a):
                    return a.reshape(a.shape[0], n_chunks, chunk,
                                     *a.shape[2:]).swapaxes(0, 1)

                @jax.checkpoint
                def chunk_nll(xc, yc, mc):
                    logits = model._project(res, xc)
                    lse = jax.scipy.special.logsumexp(logits, axis=-1)
                    tgt = jnp.take_along_axis(logits, yc[..., None],
                                              axis=-1)[..., 0]
                    return jnp.sum((lse - tgt) * mc), jnp.sum(mc)

                def body(carry, xs):
                    s, n = chunk_nll(*xs)
                    return (carry[0] + s, carry[1] + n), None
                (tot, cnt), _ = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)),
                    (to_chunks(x), to_chunks(labels),
                     to_chunks(mask.astype(jnp.float32))))
                return tot / jnp.maximum(cnt, 1.0)
            logits = model._project(res, x)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, labels[..., None],
                                      axis=-1)[..., 0]
            nll = (lse - tgt) * mask.astype(jnp.float32)
            return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)

        def head_vjp(res, xL, ids, labels, mask):
            loss, grads = jax.value_and_grad(head_loss, argnums=(0, 1))(
                res, xL, ids, labels, mask)
            return loss, grads[0], grads[1]

        if not pb:
            def block_vjp(flat, x, dy):
                dflat, dx = jax.vjp(flat_fwd, flat, x)[1](dy)
                sq = jnp.sum(jnp.square(dflat.astype(jnp.float32)))
                return dflat, dx, sq

        def embed_vjp(res, ids, tt, dx):
            _, vjp = jax.vjp(lambda r: embed_fwd(r, ids, tt), res)
            return vjp(dx)[0]

        def res_combine(a, b):
            summed = jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) + y.astype(jnp.float32),
                a, b)
            sq = sum(jnp.sum(jnp.square(l))
                     for l in jax.tree_util.tree_leaves(summed))
            return summed, sq

        # out_shardings pin the ZeRO contract: activations ride the batch
        # axis, dflat is reduce-scattered back to dp shards (XLA emits the
        # psum-fused scatter), resident grads and scalars replicate
        with self.engine.mesh:
            progs = dict(
                embed_fwd=jax.jit(embed_fwd,
                                  out_shardings=self._batch_shard),
                block_fwd=jax.jit(block_fwd,
                                  out_shardings=self._batch_shard),
                head_vjp=jax.jit(head_vjp, out_shardings=(
                    self._repl, self._repl, self._batch_shard)),
                block_vjp=jax.jit(block_vjp, out_shardings=(
                    self._flat_shard, self._batch_shard, self._repl)),
                embed_vjp=jax.jit(embed_vjp, out_shardings=self._repl),
                res_combine=jax.jit(res_combine, out_shardings=(
                    self._repl, self._repl)),
                encode_grad=(jax.jit(
                    lambda dflat, k: wire_codec.encode(
                        dflat, self.wire_bits, k),
                    out_shardings=(self._flat_shard, self._flat_shard))
                    if self.wire_bits else None),
                eval_loss=jax.jit(
                    lambda res, xL, ids, labels, mask:
                    head_loss(res, xL, ids, labels, mask),
                    out_shardings=self._repl),
            )
        self._programs[key] = progs
        return progs

    # ------------------------------------------------------------------
    # micro fwd/bwd
    # ------------------------------------------------------------------
    def _prep_batch(self, batch):
        ids = np.asarray(batch["input_ids"])  # dstpu: ignore[SYNC003] -- host batch data
        gas = self.gas
        if ids.ndim == 2:
            b = ids.shape[0]
            if b % gas:
                raise ValueError(f"batch {b} not divisible by gas {gas}")
            ids = ids.reshape(gas, b // gas, *ids.shape[1:])
        if ids.shape[1] % self.dp:
            raise ValueError(
                f"micro-batch {ids.shape[1]} not divisible by the "
                f"data-parallel width {self.dp} (Infinity shards the batch "
                f"over the dp axis)")
        labels = batch.get("labels")
        mask = batch.get("loss_mask")
        tt = batch.get("token_type_ids")

        def reshape_like(a):
            a = np.asarray(a)  # dstpu: ignore[SYNC003] -- host batch data
            return (a.reshape(gas, a.shape[0] // gas, *a.shape[1:])
                    if a.ndim == 2 else a)
        return (ids,
                reshape_like(labels) if labels is not None else None,
                reshape_like(mask) if mask is not None else None,
                reshape_like(tt) if tt is not None else None)

    def _forward_stream(self, progs, ids_dev, tt_dev, stash: bool = True):
        """Streamed forward → (activation stash | None, final hidden)."""
        L = self.L
        x = progs["embed_fwd"](self.resident, ids_dev, tt_dev)
        acts: List[Any] = [None] * L if stash else None
        self._ensure_layer(0, {0})
        self._prefetch_encode(1)
        for i in range(L):
            if i + 1 < L:
                self._ensure_layer(i + 1, {i, i + 1})
            self._prefetch_encode(i + 2)
            if stash:
                acts[i] = x
            x = progs["block_fwd"](*self._dev[i], x)
        return acts, x

    def _tt_dev(self, tt, ids):
        """Token-type ids on device. Models without a type vocab get a
        (1,1) dummy (the jitted program drops the unused arg); models with
        one default to all-zero types, matching ``_embed_tokens``."""
        if not self.model.config.token_type_vocab:
            return jnp.zeros((1, 1), jnp.int32)
        if tt is None:
            tt = np.zeros_like(np.asarray(ids))  # dstpu: ignore[SYNC003] -- host batch data
        # dstpu: ignore[SYNC003] -- host batch data, upload is async
        return jax.device_put(np.asarray(tt), self._batch_shard)

    def _micro_fwd_bwd(self, progs, ids, labels, mask, tt,
                       on_layer_grad: Callable[[int, Any], None]):
        """One microbatch forward+backward, streaming layer grads into
        ``on_layer_grad``. Returns (loss, resident_grad_tree_dev,
        res_sq_dev, total_sq_dev); total_sq's block-grad terms are
        PRE-quantization when the wire codec is active (the decoded norm
        is recomputed host-side in that case)."""
        zero_i = jnp.zeros((1, 1), jnp.int32)
        # dstpu: ignore[SYNC003] -- host batch data, uploads are async
        ids_dev = jax.device_put(np.asarray(ids), self._batch_shard)
        # dstpu: ignore[SYNC003] -- host batch data
        labels_dev = (jax.device_put(np.asarray(labels), self._batch_shard)
                      if labels is not None else zero_i)
        # dstpu: ignore[SYNC003] -- host batch data
        mask_dev = (jax.device_put(np.asarray(mask, np.float32),
                                   self._batch_shard)
                    if mask is not None
                    else jnp.zeros((1, 1), jnp.float32))
        tt_dev = self._tt_dev(tt, ids)
        acts, xL = self._forward_stream(progs, ids_dev, tt_dev)
        loss, d_res_head, dy = progs["head_vjp"](
            self.resident, xL, ids_dev, labels_dev, mask_dev)
        sqs = []
        for i in reversed(range(self.L)):
            if i - 1 >= 0:
                self._ensure_layer(i - 1, {i, i - 1})
            self._prefetch_encode(i - 2)
            dflat, dy, sq = progs["block_vjp"](*self._dev[i], acts[i], dy)
            acts[i] = None
            if self.wire_bits:
                # quantize on device; only the packed payload + per-chunk
                # scales cross the D2H wire (wire_codec: unbiased
                # stochastic rounding, no persistent error state)
                self._wire_seq += 1
                wire = progs["encode_grad"](
                    dflat, jax.random.fold_in(self._wire_base,
                                              self._wire_seq))
            else:
                wire = dflat
            for part in (wire if isinstance(wire, tuple) else (wire,)):
                try:
                    part.copy_to_host_async()
                except Exception:
                    pass
            sqs.append(sq)
            on_layer_grad(i, wire)
        d_res_embed = progs["embed_vjp"](self.resident, ids_dev, tt_dev, dy)
        d_res, res_sq = progs["res_combine"](d_res_head, d_res_embed)
        total_sq = res_sq + sum(sqs)
        return loss, d_res, res_sq, total_sq

    # ------------------------------------------------------------------
    # optimizer application
    # ------------------------------------------------------------------
    def _step_layer(self, i: int, wire, lr: float,
                    grad_scale: float) -> None:
        """Worker-thread task: D2H-complete grad → native Adam sweep →
        bf16 emit into the param store slot (stream mode)."""
        with trace_span("infinity/opt_layer", layer=i, mode="stream"):
            if self.wire_bits:
                g32 = np.empty(self.n_local, np.float32)
                self._decode_wire(wire, g32, accumulate=False)
                # the reported grad_norm must describe the grads actually
                # APPLIED — the stochastically-rounded decode, not the
                # pre-quantization device values (advisor r4, low)
                self._layer_sq[i] = float(np.dot(g32, g32))
                g = g32
            else:
                g = self._fetch_flat(wire).view(np.uint16)  # bf16 wire
            self.opt.prefetch(i)
            pbuf = self.param_store.acquire(i)
            out16 = pbuf[:self.n_local * 2].view(np.uint16)
            self.opt.step_slot(i, g, lr=lr,
                               grad_scale=grad_scale, out_bf16=out16)
            self.param_store.release(i, dirty=True)
            self._invalidate_encoded(i)

    def _submit(self, i: int, fn, *args):
        """Dispatch a layer task to its pinned worker (i % N) — preserves
        per-layer ordering, parallelizes across layers."""
        return self._workers[i % len(self._workers)].submit(fn, i, *args)

    def _accum_layer(self, i: int, wire) -> None:
        """Worker-thread task: accumulate the wire grad into the fp32 host
        store (collect mode). ``_grad_accum`` is allocated by the main
        thread before any submission (lazy alloc here would race across
        workers)."""
        if self.wire_bits:
            self._decode_wire(wire, self._grad_accum[i], accumulate=True)
            return
        g = self._fetch_flat(wire).view(np.uint16)
        if self._native is not None:
            from ...ops.adam.cpu_adam import _C_F32, _C_U16, _ptr
            self._native.ds_accum_g16(self.n_local,
                                      _ptr(self._grad_accum[i], _C_F32),
                                      _ptr(np.ascontiguousarray(g), _C_U16))
        else:
            self._grad_accum[i] += g.view(ml_dtypes.bfloat16).astype(
                np.float32)

    def _apply_layer_from_accum(self, i: int, lr: float,
                                grad_scale: float) -> None:
        """Worker-thread task: Adam over the accumulated fp32 grad row →
        bf16 emit into the param store slot; zero the row for next step."""
        with trace_span("infinity/opt_layer", layer=i, mode="accum"):
            self.opt.prefetch(i)
            pbuf = self.param_store.acquire(i)
            out16 = pbuf[:self.n_local * 2].view(np.uint16)
            self.opt.step_slot(i, self._grad_accum[i], lr=lr,
                               grad_scale=grad_scale, out_bf16=out16)
            self.param_store.release(i, dirty=True)
            self._invalidate_encoded(i)
            self._grad_accum[i] = 0.0

    def _finish_layer(self, i: int, dflat, lr: float,
                      apply_scale: Optional[float]) -> None:
        """Worker-thread task for the LAST microbatch of a layer:
        accumulate, record the layer's exact accumulated ||g||², and — when
        no clipping gates the update (``apply_scale`` set) — run the Adam
        sweep for this layer immediately, overlapped with the backward of
        the layers below it (streamed update under gradient accumulation)."""
        self._accum_layer(i, dflat)
        row = self._grad_accum[i]
        self._layer_sq[i] = float(np.dot(row, row))
        if apply_scale is not None:
            self._apply_layer_from_accum(i, lr, apply_scale)

    def _step_resident(self, grads_dev, lr: float,
                       grad_scale: float) -> None:
        """Device-resident optimizer step over the summed resident grad
        tree (the engine's configured Optimizer; grad_scale folds
        microbatch count x clip factor, like the native sweep)."""
        if getattr(self, "_res_apply", None) is None:
            opt = self._res_optim

            def apply(res, st, g, lr_, scale):
                g = jax.tree_util.tree_map(lambda x: x / scale, g)
                return opt.apply(g, st, res, lr_)
            with self.engine.mesh:
                self._res_apply = jax.jit(apply, out_shardings=self._repl)
        self.resident, self.res_state = self._res_apply(
            self.resident, self.res_state, grads_dev,
            jnp.asarray(lr, jnp.float32),
            jnp.asarray(grad_scale, jnp.float32))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def train_step(self, batch) -> Dict:
        t0 = time.perf_counter()
        self._stream_thread = threading.current_thread()
        engine = self.engine
        ids, labels, mask, tt = self._prep_batch(batch)
        progs = self._build_programs(labels is not None, mask is not None)
        step_i = int(engine.state["step"])
        # one deliberate sync: lr feeds the host Adam sweep's arguments
        lr = float(host_transfer(engine.lr_schedule(jnp.asarray(step_i))))
        gas = self.gas
        # pure stream: grads are final on arrival, no norm gate — the Adam
        # sweep rides inside the backward with no accumulator at all
        pure_stream = (gas == 1 and self.clip == 0.0)
        self.opt.begin_step()

        futures = []
        micro_stats: List[Tuple] = []   # (loss, res_sq, sq) device scalars
        res_acc = None
        self._dev.clear()
        if not pure_stream and self._grad_accum is None:
            self._grad_accum = np.zeros((self.L, self.n_local), np.float32)
        self._layer_sq = np.zeros(self.L, np.float64)
        if getattr(self, "_res_add", None) is None:
            with self.engine.mesh:
                self._res_add = jax.jit(lambda a, b: jax.tree_util.tree_map(
                    jnp.add, a, b), out_shardings=self._repl)
                self._res_sq = jax.jit(lambda t: sum(
                    jnp.sum(jnp.square(l))
                    for l in jax.tree_util.tree_leaves(t)),
                    out_shardings=self._repl)
        for j in range(gas):
            last = (j == gas - 1)
            if pure_stream:
                def on_grad(i, dflat):
                    futures.append(self._submit(
                        i, self._step_layer, dflat, lr, 1.0))
            elif last:
                # streamed finish: clip==0 applies Adam per layer as its
                # accumulated grad completes, overlapped with the ongoing
                # backward; clip>0 only records the exact per-layer ||g||²
                # (the update must wait for the global norm)
                apply_scale = float(gas) if self.clip == 0.0 else None

                def on_grad(i, dflat, s=apply_scale):
                    futures.append(self._submit(
                        i, self._finish_layer, dflat, lr, s))
            else:
                def on_grad(i, dflat):
                    futures.append(self._submit(i, self._accum_layer, dflat))
            loss, d_res, res_sq, sq = self._micro_fwd_bwd(
                progs, ids[j],
                labels[j] if labels is not None else None,
                mask[j] if mask is not None else None,
                tt[j] if tt is not None else None, on_grad)
            # keep the per-microbatch scalars LAZY: float() here would
            # block the stream thread on microbatch j's full backward
            # before it may dispatch j+1 — gas-1 needless pipeline stalls
            # per step (dstpu-lint SYNC002 caught it). Converted after
            # the worker join below, when they are ready for free.
            micro_stats.append((loss, res_sq, sq))
            res_acc = d_res if res_acc is None else self._res_add(res_acc,
                                                                 d_res)
        # Release every upload pin BEFORE blocking on the workers: once
        # this thread parks in result(), nobody else may reclaim them
        # (slot_store.reclaim is gated to the stream thread), and a worker
        # needing a param-ring buffer would starve against our own pins.
        self._sweep_uploads(block=True)
        with trace_span("infinity/worker_join", tasks=len(futures)):
            for f in futures:
                f.result()   # surface worker exceptions, join the sweep
        loss_total = sum(float(host_transfer(ls)) for ls, _, _ in
                         micro_stats)
        res_sq_total = sum(float(host_transfer(rs)) for _, rs, _ in
                           micro_stats)
        sq_total = sum(float(host_transfer(s)) for _, _, s in micro_stats)

        grad_scale = float(gas)
        if pure_stream:
            if self.wire_bits:
                # the applied grads are the stochastically-rounded wire
                # decode: report THEIR norm (recorded per layer by
                # _step_layer), not the pre-quantization device values
                block_sq = float(np.sum(self._layer_sq))
                if jax.process_count() > 1:
                    from jax.experimental import multihost_utils
                    block_sq = float(np.sum(
                        multihost_utils.process_allgather(
                            np.float32(block_sq))))
                gnorm = math.sqrt(res_sq_total + block_sq)
            else:
                # gas==1: Σ per-layer ||g||² IS the exact squared norm
                gnorm = math.sqrt(sq_total)
        else:
            # exact norm of the ACCUMULATED grads (clipping must see the
            # true norm — reference runtime/utils.py:325 clip_grad_norm_);
            # per-layer terms were recorded by _finish_layer as each
            # layer's accumulation completed
            sq = float(host_transfer(self._res_sq(res_acc)))
            block_sq = float(np.sum(self._layer_sq))
            if jax.process_count() > 1:
                # each host holds a disjoint span of the block grads —
                # sum the partial squared norms across processes
                from jax.experimental import multihost_utils
                block_sq = float(np.sum(multihost_utils.process_allgather(
                    np.float32(block_sq))))
            sq += block_sq
            gnorm = math.sqrt(sq) / gas
            if self.clip > 0.0:
                if not np.isfinite(gnorm) and self._skip_nonfinite:
                    # clip-gated mode is the one Infinity mode where the
                    # sweep has NOT run yet when the norm is known — a
                    # poisoned step can still be skipped outright
                    # (resilience.skip_nonfinite_grad_steps)
                    logger.warning(
                        f"non-finite global grad norm ({gnorm}) — skipping "
                        f"the optimizer sweep for this step")
                    self.opt.step_count -= 1   # undo begin_step
                    self._grad_accum[:] = 0.0
                    engine.state["skipped"] = engine.state["skipped"] + 1
                    self._dev.clear()
                    self._sweep_uploads(block=True)
                    self.param_store.flush()
                    self.opt.flush()
                    metrics = {"loss": loss_total / gas, "grad_norm": gnorm,
                               "lr": lr, "overflow": 1, "loss_scale": 1.0,
                               "step_time": time.perf_counter() - t0}
                    self._last_metrics = metrics
                    return metrics
                if np.isfinite(gnorm) and gnorm > self.clip:
                    grad_scale *= gnorm / self.clip
                # clip-gated sweep, parallel across layers/cores
                with trace_span("infinity/clip_sweep", layers=self.L):
                    sweep = [self._submit(i, self._apply_layer_from_accum,
                                          lr, grad_scale)
                             for i in range(self.L)]
                    for f in sweep:
                        f.result()
        self._step_resident(res_acc, lr, grad_scale)
        self._dev.clear()   # device copies are stale after the sweep
        self._sweep_uploads(block=True)
        self.param_store.flush()
        self.opt.flush()

        engine.state["step"] = engine.state["step"] + 1
        metrics = {"loss": loss_total / gas, "grad_norm": gnorm, "lr": lr,
                   "overflow": 0, "loss_scale": 1.0,
                   "step_time": time.perf_counter() - t0}
        self._last_metrics = metrics
        return metrics

    def eval_loss(self, batch) -> float:
        """Eval takes the batch whole (no gas split — eval batches need not
        match the training batch triple), streamed forward without an
        activation stash."""
        ids = np.asarray(batch["input_ids"])  # dstpu: ignore[SYNC003] -- host batch data
        labels = batch.get("labels")
        mask = batch.get("loss_mask")
        progs = self._build_programs(labels is not None, mask is not None)
        self._stream_thread = threading.current_thread()
        self._dev.clear()
        if ids.shape[0] % self.dp:
            raise ValueError(
                f"eval batch {ids.shape[0]} not divisible by dp {self.dp}")
        ids_dev = jax.device_put(ids, self._batch_shard)
        zero_i = jnp.zeros((1, 1), jnp.int32)
        tt_dev = self._tt_dev(batch.get("token_type_ids"), ids)
        _, xL = self._forward_stream(progs, ids_dev, tt_dev, stash=False)
        out = float(host_transfer(progs["eval_loss"](
            self.resident, xL, ids_dev,
            # dstpu: ignore[SYNC003] -- host batch data
            jax.device_put(np.asarray(labels), self._batch_shard)
            if labels is not None else zero_i,
            # dstpu: ignore[SYNC003] -- host batch data
            jax.device_put(np.asarray(mask, np.float32), self._batch_shard)
            if mask is not None
            else jnp.zeros((1, 1), jnp.float32))))
        self._sweep_uploads(block=True)
        return out

    def _require_single_process(self, what: str) -> None:
        if jax.process_count() > 1:
            raise NotImplementedError(
                f"{what} on a multi-host pod needs a cross-process gather "
                f"of the partitioned host slots — run it from a "
                f"single-process restore, or use per-host save dirs")

    def gather_params(self):
        """Full (unstacked→stacked) param tree as host numpy — the
        zero_to_fp32 equivalent for tests/export. Masters (fp32)."""
        self._require_single_process("gather_params")
        blocks_flat = np.stack([self.opt.master(i)[:self.n_elems]
                                for i in range(self.L)])
        leaves = []
        for o, s, sh in zip(self._offsets, self._sizes, self._shapes):
            leaves.append(blocks_flat[:, o:o + s].reshape((self.L,) + sh))
        blocks = jax.tree_util.tree_unflatten(self._treedef, leaves)
        res = jax.device_get(self.resident)
        res = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32), res)
        res["blocks"] = blocks
        return res

    # -- checkpoint --------------------------------------------------------
    def save_to_dir(self, path: str) -> None:
        """Stream the full host state (fp32 masters + moments + resident
        optimizer) to ``path``, one slot at a time — constant memory, any
        model size. Called by the checkpoint engine
        (runtime/checkpoint_engine/engine.py) for infinity-mode saves."""
        import json
        import os
        self._require_single_process("Infinity checkpoint save")
        os.makedirs(path, exist_ok=True)
        for i in range(self.L):
            p, m, v = self.opt.state(i)
            # logical (unpadded) vectors — checkpoints are mesh-independent,
            # a D=1 save restores onto a D=8 mesh and vice versa
            n = self.n_elems
            _savez_retry(os.path.join(path, f"slot_{i:05d}.npz"),
                         self._io_policy, p=p[:n], m=m[:n], v=v[:n])
        res = self._resident_state_host()
        _savez_retry(os.path.join(path, "resident.npz"), self._io_policy,
                     **{f"{k}_{j}": a for k, arrs in res.items()
                        for j, a in enumerate(arrs)})

        def path_str(p):
            return "/".join(str(getattr(x, "key", x)) for x in p)
        # shape-only templates from __init__ — no device transfers here
        layer_tpl = jax.eval_shape(self.model.init_superblock,
                                   jax.random.PRNGKey(0))
        layer_leaves = [
            {"path": path_str(p), "shape": list(l.shape)}
            for p, l in jax.tree_util.tree_flatten_with_path(layer_tpl)[0]]
        res_leaves = [
            {"path": path_str(p), "shape": list(l.shape)}
            for p, l in jax.tree_util.tree_flatten_with_path(
                self.resident_tpl)[0]]
        with open(os.path.join(path, "meta.json"), "w") as f:
            json.dump({"L": self.L, "n_elems": self.n_elems,
                       "step_count": self.opt.step_count,
                       "res_step_count": self.res_step_count,
                       "n_res_leaves": len(res["master"]),
                       # leaf layout: lets offline tools (universal
                       # checkpoint export) rebuild the full fp32 tree
                       # from the flat slots without a live engine
                       "layer_leaves": layer_leaves,
                       "res_leaves": res_leaves}, f)

    @property
    def res_step_count(self) -> int:
        return int(self.res_state["step"])

    def _resident_state_host(self) -> Dict[str, List[np.ndarray]]:
        """Device-resident optimizer state → host leaf lists."""
        return {
            "master": [np.asarray(x, np.float32) for x in
                       jax.tree_util.tree_leaves(
                           jax.device_get(self.resident))],
            "m": [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(self.res_state["m"]))],
            "v": [np.asarray(x) for x in jax.tree_util.tree_leaves(
                jax.device_get(self.res_state["v"]))],
        }

    def _load_resident_state(self, res: Dict[str, List[np.ndarray]],
                             step_count: int) -> None:
        def put(leaves):
            return jax.device_put(jax.tree_util.tree_unflatten(
                self._res_treedef,
                [np.asarray(a, np.float32) for a in leaves]), self._repl)
        self.resident = put(res["master"])
        self.res_state = {"step": jnp.asarray(int(step_count), jnp.int32),
                          "m": put(res["m"]), "v": put(res["v"])}

    def load_from_dir(self, path: str, load_optimizer_states: bool = True
                      ) -> None:
        import json
        import os
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta["L"] != self.L or meta["n_elems"] != self.n_elems:
            raise ValueError(
                f"checkpoint layout (L={meta['L']}, n={meta['n_elems']}) "
                f"does not match this model (L={self.L}, n={self.n_elems})")
        zl = np.zeros(self.n_local, np.float32)
        for i in range(self.L):
            with _load_npz_retry(os.path.join(path, f"slot_{i:05d}.npz"),
                                 self._io_policy) as z:
                p = self._local_f32(z["p"])
                m = self._local_f32(z["m"]) if load_optimizer_states else zl
                v = self._local_f32(z["v"]) if load_optimizer_states else zl
                self.opt.load_state(i, p, m, v)
                buf = self.param_store.acquire(i)
                buf[:self.n_local * 2].view(np.uint16)[:] = (
                    p.astype(ml_dtypes.bfloat16).view(np.uint16))
                self.param_store.release(i, dirty=True)
                self._invalidate_encoded(i)
        with _load_npz_retry(os.path.join(path, "resident.npz"),
                             self._io_policy) as z:
            n = meta["n_res_leaves"]
            res = {k: [z[f"{k}_{j}"] for j in range(n)]
                   for k in ("master", "m", "v")}
        if not load_optimizer_states:
            res = {k: (arrs if k == "master"
                       else [np.zeros_like(a) for a in arrs])
                   for k, arrs in res.items()}
        self._load_resident_state(
            res, meta["res_step_count"] if load_optimizer_states else 0)
        self.opt.step_count = (int(meta["step_count"])
                               if load_optimizer_states else 0)
        self.param_store.flush()
        self.opt.flush()

    def state_dict(self) -> Dict:
        self._require_single_process("Infinity state_dict")
        n = self.n_elems
        return {
            "step_count": self.opt.step_count,
            "slots": [tuple(a[:n] for a in self.opt.state(i))
                      for i in range(self.L)],
            "resident": self._resident_state_host(),
            "res_step_count": self.res_step_count,
        }

    def load_state_dict(self, sd: Dict) -> None:
        self.opt.step_count = int(sd["step_count"])
        for i, (p, m, v) in enumerate(sd["slots"]):
            p, m, v = (self._local_f32(np.asarray(a)) for a in (p, m, v))
            self.opt.load_state(i, p, m, v)
            buf = self.param_store.acquire(i)
            buf[:self.n_local * 2].view(np.uint16)[:] = (
                p.astype(ml_dtypes.bfloat16).view(np.uint16))
            self.param_store.release(i, dirty=True)
            self._invalidate_encoded(i)
        self._load_resident_state(sd["resident"], sd["res_step_count"])
        self.param_store.flush()
        self.opt.flush()

    def close(self) -> None:
        for w in self._workers:
            w.shutdown(wait=True)
        self.param_store.close()
        self.opt.close()
        if self._aio is not None:
            self._aio.close()
