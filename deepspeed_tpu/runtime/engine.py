"""Training engine.

Role-equivalent of the reference ``DeepSpeedEngine``
(`/root/reference/deepspeed/runtime/engine.py:189`), redesigned for XLA's
compilation model. The reference is an nn.Module wrapper whose
forward/backward/step each run eagerly with hand-scheduled collectives; here
the whole training step — gradient accumulation loop, mixed precision,
ZeRO collectives, gradient clipping, optimizer update, loss-scale state
machine — is ONE jitted program over a named-axis mesh. DeepSpeed's runtime
machinery maps as:

  _configure_distributed_model (engine.py:1120) → mesh build + param init
      directly into their target shardings (no broadcast needed: same program,
      same rng → identical replicated values; sharded values materialize only
      their shard)
  allreduce_gradients bucketing (engine.py:1890,2336) → grad sharding
      constraints; XLA chooses bucketing/overlap
  GAS boundary logic (engine.py:1740 scale, is_gradient_accumulation_boundary)
      → lax.scan over the microbatch axis inside the step
  FP16_Optimizer / BF16_Optimizer wrappers (engine.py:1424,1478) → fp32 master
      params in the state + cast-on-forward + loss-scale state transitions
  ZeRO stage selection (engine.py:1498) → ZeroShardingPolicy spec trees

The legacy ``forward()/backward()/step()`` triple is kept as a compatibility
surface (each call is its own jitted program, grads accumulate in a donated
device buffer); ``train_batch()``/``train_step()`` is the native path.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import (get_overlap_profiler, get_registry,
                             trace_span)
from ..parallel import topology as topo
from ..parallel.shard_map_compat import shard_map
from ..utils.logging import logger
from . import lr_schedules
from .config import DeepSpeedConfig
from .fp16 import DynamicLossScaler, static_loss_scaler
from .optimizers import Optimizer, get_optimizer, wrap_optax
from .resilience import Heartbeat
from .utils import host_transfer
from .zero.sharding import ZeroShardingPolicy, constrain, to_named

MEM_EFFICIENT_LINEAR_DEFAULT = True


def _count_jit_build(fn: Callable) -> None:
    """Recompile watermark: every jit program the engine constructs bumps
    this counter — a rising value mid-run means a retrace bomb.  ``fn``
    is the function being wrapped in ``jax.jit``: the overlap profiler's
    build records under its name read ``own`` and say WHICH program was
    built, when, and what it cost (``builds()``)."""
    get_registry().counter("dstpu_jit_programs_built_total").inc()
    get_overlap_profiler().own_program(fn.__name__)


def _tree_zeros_f32(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


class DeepSpeedEngine:
    """Single-controller SPMD training engine over a named mesh."""

    def __init__(self,
                 model,
                 config: Any = None,
                 mesh: Optional[Mesh] = None,
                 optimizer: Any = None,
                 lr_scheduler: Any = None,
                 loss_fn: Optional[Callable] = None,
                 param_specs: Any = None,
                 rng: Optional[jax.Array] = None,
                 dont_init: bool = False):
        self._config = (config if isinstance(config, DeepSpeedConfig)
                        else DeepSpeedConfig(config or {}))
        # the ``training`` block carries model-side hot-path knobs
        # (remat policy, fused loss head, loss chunking) — apply them by
        # rebuilding the model BEFORE anything binds model.loss, so a
        # tuned config JSON alone changes the compiled step program
        model = self._apply_training_overrides(model)
        self.model = model
        if self._config.resilience.fault_injection:
            # config-driven fault plans arm the process-global injector
            # (runtime/resilience; env DSTPU_FAULTS plans merge on top)
            from .resilience import get_fault_injector
            get_fault_injector().add_plans_from_config(
                self._config.resilience.fault_injection)
        # worker side of the elastic agent's hung-worker watchdog: beat
        # the DSTPU_HEARTBEAT_FILE the agent assigned us once per
        # interval at every train step (no-op when launched standalone)
        self._heartbeat = Heartbeat(
            interval_s=self._config.resilience.heartbeat_interval_s)
        self.mesh = mesh if mesh is not None else topo.build_mesh(
            self._config.mesh)
        self.dp_world_size = topo.dp_world_size(self.mesh)
        self.mp_world_size = topo.mp_world_size(self.mesh)
        self._config.resolve_batch_sizes(self.dp_world_size)

        self.zero_stage = self._config.zero_optimization_stage
        self.fp16_enabled = self._config.fp16.enabled
        self.bf16_enabled = self._config.bf16.enabled
        self.compute_dtype = {
            "bfloat16": jnp.bfloat16, "float16": jnp.float16,
            "float32": jnp.float32}[self._config.precision_dtype]
        self.gradient_accumulation_steps = (
            self._config.gradient_accumulation_steps or 1)
        self.train_micro_batch_size_per_gpu = \
            self._config.train_micro_batch_size_per_gpu
        self.train_batch_size = self._config.train_batch_size

        self._loss_fn = loss_fn or (
            model.loss if hasattr(model, "loss") else None)
        if self._loss_fn is None:
            raise ValueError("Need model.loss or an explicit loss_fn")
        if hasattr(model, "bind_mesh"):
            model.bind_mesh(self.mesh)

        # -- optimizer -----------------------------------------------------
        self.optimizer = self._configure_optimizer(optimizer)
        self.lr_schedule = self._configure_lr_schedule(lr_scheduler)

        # -- loss scaling --------------------------------------------------
        fp16c = self._config.fp16
        if self.fp16_enabled:
            if fp16c.dynamic:
                self.loss_scaler = DynamicLossScaler(
                    initial_scale_power=fp16c.initial_scale_power,
                    scale_window=fp16c.loss_scale_window,
                    min_scale=fp16c.min_loss_scale,
                    hysteresis=fp16c.hysteresis)
            else:
                self.loss_scaler = static_loss_scaler(fp16c.loss_scale)
        else:
            self.loss_scaler = None

        # -- sharding policy ----------------------------------------------
        if param_specs is None and hasattr(model, "partition_specs"):
            param_specs = model.partition_specs()
        self._param_shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0)))
        if param_specs is None:
            param_specs = jax.tree_util.tree_map(
                lambda s: P(*([None] * len(s.shape))), self._param_shapes)
        self.zero_policy = ZeroShardingPolicy(
            self.zero_stage, self.mesh, param_specs, self._param_shapes,
            min_partition_size=0,
            param_persistence_threshold=(
                self._config.zero_config.param_persistence_threshold
                if self.zero_stage >= 3 else 0))
        self._install_layer_gather(model)
        self.master_specs = self.zero_policy.master_param_specs()
        self.grad_specs = self.zero_policy.grad_specs()
        opt_shapes = jax.eval_shape(self.optimizer.init, self._param_shapes)
        self.opt_specs = self.zero_policy.opt_state_specs(opt_shapes)

        # batch leaves are [gas, global_batch, ...]; expert-parallel ranks
        # are also data ranks (reference _create_expert_and_data_parallel,
        # utils/groups.py:109), so the batch shards over 'expert' too
        batch_axes = tuple(a for a in (topo.DCN_DATA_AXIS, topo.DATA_AXIS,
                                       topo.EXPERT_AXIS)
                           if self.mesh.shape.get(a, 1) > 1)
        self._batch_dim_spec = batch_axes if batch_axes else None

        self.global_steps = 0
        self.micro_steps = 0
        self._step_times: list = []

        # -- observability (reference MonitorMaster at engine.py:287,
        #    ThroughputTimer/EngineTimers at engine.py:149; span tracer +
        #    metrics registry are TPU-native — deepspeed_tpu/observability)
        from ..monitor.monitor import MonitorMaster
        from ..observability import configure as _obs_configure
        from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
        self.monitor = MonitorMaster(self._config.monitor)
        seq_len = getattr(getattr(model, "config", None), "max_seq_len", 0)
        self.tput_timer = ThroughputTimer(self.train_batch_size, seq_len)
        self.timers = SynchronizedWallClockTimer()
        self._analytic_flops_per_step = None
        self._tracer, self._obs = _obs_configure(
            self._config.observability, rank=jax.process_index())
        from ..observability import get_flight_recorder
        self._flight = get_flight_recorder()
        # host/device overlap profiler: splits the fused step into
        # enqueue vs device-wait from timestamps the step path already
        # takes (observability/overlap.py); disabled = attribute check
        self._ovl = get_overlap_profiler()
        self._skip_burst = 0
        if self._obs.enabled:
            # derived gauges refreshed at export time (plain host reads —
            # memory_stats and the comms log never sync the device)
            self._obs.set_collector("engine", self._obs_collect)

        # -- ZeRO-Offload tiers (host DRAM optimizer / Infinity streaming) -
        from .zero.offload import validate_offload_config
        offload_mode = validate_offload_config(self._config)
        self.offload_enabled = offload_mode == "optimizer"
        self.infinity_enabled = offload_mode == "infinity"
        self._host_opt = None
        self._host_scaler = None
        self._infinity = None
        if offload_mode != "none" and optimizer is not None:
            raise ValueError(
                "offload needs a config-named optimizer (Adam/AdamW/"
                "Adagrad) — the host step runs in native code, not "
                "through a user optimizer object")

        # -- state init (sharded at materialization) -----------------------
        if not dont_init:
            with self._ovl.setup_span("setup/state_init"):
                self.state = self.init_state(rng if rng is not None
                                             else jax.random.PRNGKey(0))
        self._train_step_fn = None
        self._grad_fn = None
        self._apply_fn = None
        self._grad_acc = None
        self._grad_acc_count = 0
        self._last_lr = float(self.optimizer.hyperparams.get("lr", 0.0))

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def _configure_optimizer(self, optimizer) -> Optimizer:
        """Reference `engine.py:1253` _configure_optimizer /
        `:1307` _configure_basic_optimizer (name-dispatch from config)."""
        if isinstance(optimizer, Optimizer):
            return optimizer
        if optimizer is not None:  # assume optax transformation
            return wrap_optax(optimizer)
        oc = self._config.optimizer
        if oc is None:
            return get_optimizer("adamw")
        return get_optimizer(oc.type, **dict(oc.params))

    def _configure_lr_schedule(self, lr_scheduler):
        sc = self._config.scheduler
        if self.optimizer.hyperparams.get("external_lr"):
            if sc is not None or callable(lr_scheduler):
                raise ValueError(
                    "an optax optimizer carries its own schedule; remove the "
                    "engine scheduler (put optax.scale_by_schedule in the "
                    "chain instead)")
            return lr_schedules.constant_lr(0.0)  # reported lr is N/A
        if callable(lr_scheduler):
            return lr_scheduler
        if sc is None:
            return lr_schedules.constant_lr(
                self.optimizer.hyperparams.get("lr", 1e-3))
        return lr_schedules.get_lr_schedule(sc.type, dict(sc.params))

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def state_specs(self) -> Dict:
        if self.infinity_enabled:
            return {"step": P(), "skipped": P()}
        if self.offload_enabled:
            # device state is ONLY compute-dtype params — masters/moments
            # live on the host (runtime/zero/offload.py)
            return {"step": P(), "skipped": P(),
                    "params": self.zero_policy.model_param_specs()}
        specs = {"step": P(), "skipped": P(), "params": self.master_specs,
                 "opt": self.opt_specs}
        if self.loss_scaler is not None:
            specs["scaler"] = jax.tree_util.tree_map(lambda _: P(),
                                                     self.loss_scaler.init())
        return specs

    def state_shardings(self) -> Dict:
        return to_named(self.mesh, self.state_specs())

    def _cached_program(self, key: str, build: Callable):
        """Engine-lifetime cache for jitted programs (the TRACE003
        discipline: never construct ``jax.jit(...)`` per call — the
        compile cache is keyed on the callable object, so a fresh wrap
        retraces every time).  ``build`` runs once per ``key``."""
        if not hasattr(self, "_programs_misc"):
            self._programs_misc = {}
        if key not in self._programs_misc:
            self._programs_misc[key] = build()
        return self._programs_misc[key]

    def init_state(self, rng) -> Dict:
        """Build the train state directly into its target shardings — the
        jitted init materializes only each device's shard (replaces the
        reference's init-then-broadcast `engine.py:1083` and zero.Init
        partition-at-construction `partition_parameters.py:539`)."""
        if self.infinity_enabled:
            # ZeRO-Infinity: params/optimizer live in host stores owned by
            # the stepper; engine state carries only the counters
            from .zero.infinity import InfinityStepper
            self._infinity = InfinityStepper(self, rng)
            return {"step": jnp.zeros((), jnp.int32),
                    "skipped": jnp.zeros((), jnp.int32)}
        if self.offload_enabled:
            return self._init_state_offload(rng)

        def _init(rng):
            params = self.model.init(rng)
            if not self._config.bf16.master_weights and self.bf16_enabled:
                params = jax.tree_util.tree_map(
                    lambda p: p.astype(jnp.bfloat16), params)
            state = {"step": jnp.zeros((), jnp.int32),
                     "skipped": jnp.zeros((), jnp.int32), "params": params,
                     "opt": self.optimizer.init(params)}
            if self.loss_scaler is not None:
                state["scaler"] = self.loss_scaler.init()
            return state

        init_fn = self._cached_program(
            "init_state",
            lambda: jax.jit(_init, out_shardings=self.state_shardings()))
        with self.mesh:
            return init_fn(rng)

    def _init_state_offload(self, rng) -> Dict:
        """Offload init: fp32 params materialize sharded on device, move to
        host (masters for the CPU optimizer), device keeps the compute-dtype
        copy in the model shardings."""
        from .zero.offload import HostLossScaler, ZeroOffloadHostOptimizer
        f32_shardings = to_named(self.mesh, self.master_specs)
        init_fn = self._cached_program(
            "init_offload_f32",
            lambda: jax.jit(self.model.init, out_shardings=f32_shardings))
        with self.mesh:
            f32_params = init_fn(rng)
        host_tree = jax.device_get(f32_params)
        self._host_opt = ZeroOffloadHostOptimizer(self, host_tree)
        if self.loss_scaler is not None:
            self._host_scaler = HostLossScaler(self.loss_scaler)
        logger.info(
            f"ZeRO-Offload: {self._host_opt.host_bytes / 2**30:.2f} GiB "
            f"optimizer state in host DRAM; device holds "
            f"{'bf16' if self.compute_dtype == jnp.bfloat16 else str(self.compute_dtype)} params only")
        param_shardings = to_named(self.mesh,
                                   self.zero_policy.model_param_specs())
        # cached for the per-step upload (constant for the engine lifetime)
        self._offload_shardings = jax.tree_util.tree_leaves(
            param_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        cast = jax.jit(self._cast_for_compute, out_shardings=param_shardings)
        with self.mesh:
            dev_params = cast(f32_params)
        return {"step": jnp.zeros((), jnp.int32),
                "skipped": jnp.zeros((), jnp.int32), "params": dev_params}

    def _accumulate_micro_grads(self, state, batch, scale):
        """Shared GAS loop: scan the microbatch axis, sum f32 grads +
        scaled losses. Single source of the accumulation semantics for the
        fused train step AND the offload grad function.  Third result:
        the counters of a loss that returns ``(loss, counters)``, summed
        over the microbatches ({} for a loss that returns a scalar)."""
        gas = self.gradient_accumulation_steps

        def micro(carry, mb):
            gsum, lsum = carry
            (loss, counters), grads = jax.value_and_grad(
                self._micro_loss, has_aux=True)(state["params"], mb, scale)
            with jax.named_scope("optimizer"):
                grads = constrain(
                    jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                           grads),
                    self.mesh, self.grad_specs)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            return (gsum, lsum + loss), counters

        zeros = _tree_zeros_f32(state["params"])
        if gas == 1:
            sq = jax.tree_util.tree_map(lambda x: x[0], batch)
            (gsum, lsum), counters = micro(
                (zeros, jnp.zeros((), jnp.float32)), sq)
        else:
            (gsum, lsum), counters = jax.lax.scan(
                micro, (zeros, jnp.zeros((), jnp.float32)), batch)
            counters = jax.tree_util.tree_map(lambda c: c.sum(0), counters)
        return gsum, lsum, counters

    def _build_offload_grad_fn(self):
        """The jitted grads-for-offload program. With
        ``zero_optimization.offload_wire_bits`` set, the gradient leaves
        are concatenated into ONE flat vector and stochastic-rounding
        encoded ON DEVICE (runtime/zero/wire_codec.py, the same codec and
        layout ZeRO-Infinity streams per layer — chunk scales span leaf
        boundaries there too) so the D2H wire carries n/8..n bytes instead
        of 4n in a single transfer — the r4 tier-1 bottleneck was exactly
        this wire, and per-leaf transfers would pay ~n_leaves round trips
        on it. Clipping/overflow use the device-side pre-quantization
        norm: the clip factor rides the host sweep's single grad multiply
        either way, and E[decode(encode(g))] = g."""
        from .zero import wire_codec
        bits = self._offload_wire_bits

        def offload_grad_fn(state, batch, scale, key):
            gsum, lsum, _ = self._accumulate_micro_grads(state, batch, scale)
            gnorm = global_norm(gsum)
            if not bits:
                return lsum, gsum, gnorm
            # ONE flat vector, ONE encode, ONE D2H transfer: on a
            # high-latency wire ~100 per-leaf fetches pay ~100 round
            # trips; the concatenated form is also exactly the layout
            # Infinity streams per layer, chunk scales spanning leaf
            # boundaries and all
            flat = jnp.concatenate(
                [g.reshape(-1) for g in jax.tree_util.tree_leaves(gsum)])
            pad = (-flat.shape[0]) % wire_codec.CHUNK
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return lsum, wire_codec.encode(flat, bits, key), gnorm

        with self.mesh:
            self._offload_grad_fn = jax.jit(offload_grad_fn)
        _count_jit_build(offload_grad_fn)
        return self._offload_grad_fn

    @property
    def _offload_wire_bits(self) -> int:
        return int(getattr(self._config.zero_config, "offload_wire_bits",
                           0) or 0)

    def _upload_split_fn(self, dtype):
        """One-flat-H2D upload: jitted split of the concatenated param
        vector back into master-shaped leaves (single-device fast path)."""
        key = ("upload_split", np.dtype(dtype).name)
        if not hasattr(self, "_programs_misc"):
            self._programs_misc = {}
        if key not in self._programs_misc:
            masters = self._host_opt.opt.master
            offs = np.cumsum([0] + [m.size for m in masters])
            shapes = [m.shape for m in masters]

            def split(flat):
                return [flat[offs[i]:offs[i + 1]].reshape(shapes[i])
                        for i in range(len(shapes))]
            self._programs_misc[key] = jax.jit(split)
        return self._programs_misc[key]

    def _wire_fetch_fn(self, enc):
        """Host side of the offload wire: ONE D2H of the concatenated
        payload, then chunk-aligned INCREMENTAL decode per leaf — under
        step_pipelined the decode of bucket i+1's span overlaps bucket
        i's sweep (the fetch lane's work), instead of one monolithic
        decode emptying the overlap (advisor r5)."""
        from .zero import wire_codec
        bits = self._offload_wire_bits
        masters = self._host_opt.opt.master
        payload, scales = enc
        CH = wire_codec.CHUNK
        total = sum(m.size for m in masters)
        n_chunks = -(-total // CH)
        pay_per_chunk = {8: CH, 4: CH // 2, 1: CH // 8}[bits]
        offs = np.cumsum([0] + [m.size for m in masters])
        state = {"wm": 0}                 # decoded-chunk watermark

        def fetch(k):
            if "buf" not in state:
                # persistent decode buffer: sized to the full master set,
                # allocated once per engine (a fresh multi-GB np.empty per
                # step would be recurring allocator cost on the hot path)
                if getattr(self, "_wire_buf", None) is None or \
                        self._wire_buf.shape[0] != n_chunks * CH:
                    self._wire_buf = np.empty(n_chunks * CH, np.float32)
                state["buf"] = self._wire_buf
                state["payload"] = np.asarray(payload)        # one D2H
                state["scales"] = np.asarray(scales)
            need = -(-int(offs[k + 1]) // CH)
            wm = state["wm"]
            if need > wm:
                wire_codec.decode_into(
                    state["buf"][wm * CH:need * CH],
                    state["payload"][wm * pay_per_chunk:
                                     need * pay_per_chunk],
                    state["scales"][wm:need], bits)
                state["wm"] = need
            return state["buf"][offs[k]:offs[k + 1]].reshape(
                masters[k].shape)
        return fetch

    def _offload_train_step(self, batch: Dict) -> Dict:
        """grads on device → host C++ optimizer sweep → params back.
        Reference: the cpu_offload step path of stage_1_and_2.py (grads to
        pinned host buffers, DeepSpeedCPUAdam.step, param copy-back)."""
        cfg = self._config
        if getattr(self, "_offload_grad_fn", None) is None:
            self._build_offload_grad_fn()
        gas = self.gradient_accumulation_steps
        scale = self._host_scaler.scale if self._host_scaler else 1.0
        wcb = cfg.wall_clock_breakdown
        step_i = int(self.state["step"])
        if wcb:
            self.timers("offload/grads").start()
        with trace_span("offload/grads", gas=gas):
            lsum, grads, gnorm_raw = self._offload_grad_fn(
                self.state, batch, jnp.asarray(scale, jnp.float32),
                jax.random.PRNGKey(step_i))

        # the host sweep needs loss/gnorm/lr on the host anyway — this
        # IS the step's sync boundary, so move all three over in ONE
        # batched host_transfer instead of three scattered float()
        # round trips (each a full device round trip on its own)
        stats = jnp.stack([lsum, gnorm_raw,
                           self.lr_schedule(jnp.asarray(step_i))])
        lsum_h, gnorm_h, lr_h = host_transfer(stats)
        denom = scale * gas
        gnorm = float(gnorm_h) / denom
        lr = float(lr_h)
        if wcb:
            self.timers("offload/grads").stop()  # the transfer synced
        # a non-finite norm skips the host sweep either because the fp16
        # scaler says so or because resilience hygiene does (bf16 offload
        # runs have no scaler but the same poisoned-masters failure mode)
        overflow = (not math.isfinite(gnorm)) and \
            ((self._host_scaler is not None
              and self._host_scaler.detect_overflow)
             or cfg.resilience.skip_nonfinite_grad_steps)
        if overflow:
            self.state["skipped"] = self.state["skipped"] + 1
        else:
            factor = 1.0
            if cfg.gradient_clipping and cfg.gradient_clipping > 0 \
                    and math.isfinite(gnorm):
                factor = min(1.0, cfg.gradient_clipping / max(gnorm, 1e-6))
            # overlapped sweep: bucket i+1 D2H || bucket i native Adam ||
            # bucket i-1 H2D (reference PipelinedOptimizerSwapper:55)
            fetch_fn = None
            if self._offload_wire_bits:
                grad_dev = grads                      # (payload, scales)
                fetch_fn = self._wire_fetch_fn(grads)
            else:
                grad_dev = jax.tree_util.tree_leaves(grads)
            for g in jax.tree_util.tree_leaves(grad_dev):
                try:
                    g.copy_to_host_async()
                except Exception:
                    pass
            emit_bf16 = self.compute_dtype == jnp.bfloat16
            up_dtype = (np.float16 if self.compute_dtype == jnp.float16
                        else None)
            if fetch_fn is not None and self.mesh.size == 1:
                # compressed wire + one chip: sweep everything and upload
                # ONE flat vector, split back to leaves on device, in place
                # of ~n_leaves per-leaf H2D uploads; multi-chip keeps the
                # pipelined per-bucket path.  Whether the split still pays
                # on the chip's host link is ROADMAP C6 — unmeasured.
                n_leaves = len(self._host_opt.opt.master)
                if wcb:
                    self.timers("offload/sweep").start()
                with trace_span("offload/host_sweep", bucketed=False):
                    outs = self._host_opt.step(
                        [fetch_fn(k) for k in range(n_leaves)], lr=lr,
                        grad_scale=denom / factor, emit_bf16=emit_bf16)
                if wcb:
                    self.timers("offload/sweep").stop()
                flat = np.concatenate(
                    [host_transfer(o).reshape(-1) for o in outs])
                if up_dtype is not None:
                    flat = flat.astype(up_dtype)
                with trace_span("offload/upload"):
                    new_leaves = self._upload_split_fn(flat.dtype)(flat)
            else:
                if wcb:
                    self.timers("offload/sweep").start()
                with trace_span("offload/host_sweep", bucketed=True):
                    new_leaves = self._host_opt.step_pipelined(
                        grad_dev, self._offload_shardings, lr=lr,
                        grad_scale=denom / factor,
                        emit_bf16=emit_bf16, upload_dtype=up_dtype,
                        fetch_fn=fetch_fn)
                if wcb:
                    self.timers("offload/sweep").stop()
            self.state["params"] = jax.tree_util.tree_unflatten(
                self._host_opt.treedef, new_leaves)
            self.state["step"] = self.state["step"] + 1
        if self._host_scaler is not None:
            self._host_scaler.update(overflow)

        metrics = {
            "loss": float(lsum_h) / denom,
            "grad_norm": gnorm,
            "lr": lr,
            "overflow": int(overflow),
            "loss_scale": scale,
        }
        self._last_metrics = metrics
        return metrics

    # ------------------------------------------------------------------
    # core step math (shared by fused train_step and compat step())
    # ------------------------------------------------------------------
    def _cast_for_compute(self, params):
        if self.compute_dtype == jnp.float32:
            return params
        with jax.named_scope("zero_comm"):
            return jax.tree_util.tree_map(
                lambda p: p.astype(self.compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

    def _current_scale(self, state):
        """The live loss scale as a traced f32 scalar (1.0 when no scaler)."""
        if self.loss_scaler is not None:
            return state["scaler"].scale
        return jnp.asarray(1.0, jnp.float32)

    def _loss_and_counters(self, params, micro_batch):
        """The loss function's scalar, and the counters of one that
        returns ``(loss, {name: scalar})`` — what the model counted in the
        program (a block that routes experts: ``models/cca_moe.py``); the
        fused step hands them back in ``train_step``'s result."""
        out = self._loss_fn(self._cast_for_compute(params), micro_batch)
        return out if isinstance(out, tuple) else (out, {})

    def _micro_loss(self, params, micro_batch, scale):
        loss, counters = self._loss_and_counters(params, micro_batch)
        return loss * scale, counters

    def _batch_spec_tree(self, batch):
        def spec(x):
            nd = np.ndim(x)
            entries = [None] * nd
            if nd >= 2:
                entries[1] = self._batch_dim_spec
            return P(*entries)
        return jax.tree_util.tree_map(spec, batch)

    def _apply_grads(self, state, grads, n_micro: float, overflow=None):
        """Unscaled summed grads → clipped update → new state.

        Mirrors reference step path: CheckOverflow (`runtime/utils.py:170`),
        clip_grad_norm_ (`runtime/utils.py:325`), optimizer.step, loss-scale
        update, skip-on-overflow (`fp16/fused_optimizer.py`)."""
        cfg = self._config
        scale = self._current_scale(state)
        denom = scale * n_micro
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / denom, grads)
        grads = constrain(grads, self.mesh, self.grad_specs)

        if overflow is None:
            if self.loss_scaler is not None and \
                    self.loss_scaler.detect_overflow:
                overflow = DynamicLossScaler.has_overflow(grads)
            else:
                overflow = jnp.asarray(False)

        gnorm = global_norm(grads)
        if cfg.resilience.skip_nonfinite_grad_steps:
            # a NaN/Inf global norm means the update would poison params
            # AND optimizer moments — skip the step and count it in
            # state['skipped'] (the fp16 scaler catches this only when a
            # scaler exists; bf16/fp32 runs need the same protection)
            overflow = jnp.logical_or(jnp.asarray(overflow),
                                      jnp.logical_not(jnp.isfinite(gnorm)))
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            clip = jnp.asarray(cfg.gradient_clipping, jnp.float32)
            factor = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-6))
            grads = jax.tree_util.tree_map(lambda g: g * factor, grads)

        lr = self.lr_schedule(state["step"])
        new_params, new_opt = self.optimizer.apply(
            grads, state["opt"], state["params"], lr)
        new_params = constrain(new_params, self.mesh, self.master_specs)

        # skip update on overflow (fp16): keep old params/opt, still advance
        # the loss-scale state machine.
        def select(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
        new_params = select(new_params, state["params"])
        new_opt = select(new_opt, state["opt"])

        new_state = {"step": state["step"] + jnp.where(overflow, 0, 1),
                     "skipped": state.get(
                         "skipped", jnp.zeros((), jnp.int32))
                     + overflow.astype(jnp.int32),
                     "params": new_params, "opt": new_opt}
        if self.loss_scaler is not None:
            new_state["scaler"] = self.loss_scaler.update(
                state["scaler"], overflow)
        metrics = {"grad_norm": gnorm, "lr": lr,
                   "overflow": overflow.astype(jnp.int32),
                   "loss_scale": scale}
        return new_state, metrics

    def _build_train_step(self):
        # the step's construction; its compile comes with the first
        # ``train_step`` and is found by its ``own`` build record
        with self._ovl.setup_span("setup/build_train_step"):
            if self.optimizer.hyperparams.get("onebit"):
                return self._build_onebit_train_step()
            return self._build_train_step_traced()

    def _build_train_step_traced(self):
        gas = self.gradient_accumulation_steps

        def train_step(state, batch):
            scale = self._current_scale(state)
            gsum, lsum, counters = self._accumulate_micro_grads(
                state, batch, scale)
            with jax.named_scope("optimizer"):
                new_state, metrics = self._apply_grads(state, gsum,
                                                       float(gas))
            metrics.update(counters)
            metrics["loss"] = lsum / (scale * gas)
            return new_state, metrics

        # Donated-buffer audit (ISSUE 11): state in / state out aliases the
        # params + opt leaves — always safe and always donated (the step
        # would otherwise hold 2x model state live across the update).
        # The BATCH is only donatable when the caller feeds fresh device
        # buffers every step; bench/autotune loops re-feed one batch, so
        # it is opt-in via training.donate_batch. The offload grad fn
        # (_build_offload_grad_fn) donates NOTHING: its state stays live
        # for the host optimizer sweep and its batch is reused.
        donate = (0, 1) if self._config.training.donate_batch else (0,)
        with self.mesh:
            self._train_step_fn = jax.jit(train_step, donate_argnums=donate)
        _count_jit_build(train_step)
        return self._train_step_fn

    def _install_layer_gather(self, model):
        """Stage 3 over more than one data-parallel device: the policy's
        ``gather_layer`` goes on the model's per-layer seam
        (``block_transform``, called on a layer's slice inside the
        rematerialised scan body by every block family), ahead of the
        transform the model was built with.  Any other engine leaves —
        or puts back — the model's own."""
        own = getattr(model, "block_transform", None)
        if own is None:
            return
        own = getattr(own, "model_transform", own)
        if not self.zero_policy.gathers_layers:
            model.block_transform = own
            return

        policy = self.zero_policy    # not self: the model outlives engines

        def gather_then(layer):
            return own(policy.gather_layer(layer))
        gather_then.model_transform = own
        model.block_transform = gather_then

    def _apply_training_overrides(self, model):
        """Rebuild ``model`` with the ``training`` block's model-side
        overrides (remat / fused_loss_head / loss_chunk). Mirrors
        Autotuner.apply_best: dataclass-config models are reconstructed
        via dataclasses.replace; models without one reject overrides
        loudly instead of silently ignoring a tuned config."""
        overrides = self._config.training.model_overrides()
        if not overrides:
            return model
        import dataclasses as _dc
        mcfg = getattr(model, "config", None)
        if mcfg is None or not _dc.is_dataclass(mcfg):
            raise ValueError(
                f"config has training overrides {sorted(overrides)} but "
                f"{type(model).__name__} has no dataclass .config to "
                f"rebuild from")
        applicable = {k: v for k, v in overrides.items()
                      if hasattr(mcfg, k)}
        missing = set(overrides) - set(applicable)
        if missing:
            raise ValueError(
                f"training overrides {sorted(missing)} have no matching "
                f"field on {type(mcfg).__name__}")
        if all(getattr(mcfg, k) == v for k, v in applicable.items()):
            return model
        return type(model)(_dc.replace(mcfg, **applicable),
                           getattr(model, "constrain", None))

    # ------------------------------------------------------------------
    # 1-bit Adam: shard_map'd step over the compression axis
    # ------------------------------------------------------------------
    def _onebit_program_key(self) -> str:
        """Phase key for the step ABOUT to run (1-based step index).
        OnebitAdam/Lamb: warmup|compress at the freeze boundary; 0/1-Adam:
        var|comp|local|sync from its host schedule."""
        opt = self.optimizer
        t = self.global_steps + 1
        if getattr(opt, "program_key", None) is not None:
            return opt.program_key(t)
        return "warmup" if t <= opt.freeze_step else "compress"

    def _build_onebit_train_step(self, key: Optional[str] = None):
        """Compiled step for 1-bit optimizers. Grads stay LOCAL to each
        ``comm_axis`` replica (partial-manual shard_map; other axes remain
        GSPMD-auto); the optimizer owns the cross-replica reduction —
        full-precision pmean in warmup/var phases, error-compensated 1-bit
        collectives elsewhere (reference fp16/onebit/{adam,zoadam,lamb}.py;
        nothing reduces grads twice). ONE program per phase key, cached —
        phase switches are host decisions between steps."""
        from jax.sharding import PartitionSpec as P
        opt = self.optimizer
        axis = opt.comm_axis
        gas = self.gradient_accumulation_steps
        w = self.mesh.shape.get(axis, 1)
        if self._config.gradient_clipping:
            logger.warning(
                "gradient_clipping is ignored by the 1-bit optimizer "
                "(momentum, not gradients, is communicated — same "
                "restriction as the reference)")
        if key is None:
            key = self._onebit_program_key()
        self._onebit_key = key
        if getattr(self, "_onebit_errors", None) is None:
            def espec(leaf):
                return P(axis, *([None] * (leaf.ndim - 1)))
            err_init = self._cached_program(
                "onebit_init_errors",
                lambda: jax.jit(
                    lambda: opt.init_errors(self._param_shapes, w)))
            with self.mesh:
                errs = err_init()
            shardings = jax.tree_util.tree_map(
                lambda l: NamedSharding(self.mesh, espec(l)), errs)
            self._onebit_errors = jax.device_put(errs, shardings)
        if getattr(self, "_onebit_compiled", None) is None:
            self._onebit_compiled = {}

        if key not in self._onebit_compiled:
            programs = getattr(opt, "programs", None) or {
                "warmup": (opt.apply, False),
                "compress": (opt.compression_apply, True)}
            apply_fn, uses_errors = programs[key]

            def core(state, errors, batch):
                # fp16 x 1-bit (reference fp16/onebit/adam.py under
                # FP16_Optimizer): scale the loss, unscale the local
                # grads, skip-on-overflow EVERYWHERE (the apply is a
                # collective, so overflow anywhere must skip all
                # replicas), advance the loss-scale state machine.
                scale = self._current_scale(state)
                gsum, lsum, _ = self._accumulate_micro_grads(
                    state, batch, scale)
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) / (gas * scale), gsum)
                if self.loss_scaler is not None and \
                        self.loss_scaler.detect_overflow:
                    local_over = DynamicLossScaler.has_overflow(grads)
                    overflow = jax.lax.pmax(
                        local_over.astype(jnp.int32), axis) > 0
                else:
                    overflow = jnp.asarray(False)
                lr = self.lr_schedule(state["step"])
                if uses_errors:
                    new_params, new_opt, new_errors = apply_fn(
                        grads, state["opt"], state["params"], lr, errors)
                else:
                    new_params, new_opt = apply_fn(
                        grads, state["opt"], state["params"], lr)
                    new_errors = errors

                def select(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(overflow, o, n), new, old)
                new_params = select(new_params, state["params"])
                new_opt = select(new_opt, state["opt"])
                new_errors = select(new_errors, errors)
                new_state = {"step": state["step"]
                             + jnp.where(overflow, 0, 1),
                             "skipped": state["skipped"]
                             + overflow.astype(jnp.int32),
                             "params": new_params, "opt": new_opt}
                if self.loss_scaler is not None:
                    new_state["scaler"] = self.loss_scaler.update(
                        state["scaler"], overflow)
                loss = jax.lax.pmean(lsum, axis) / (gas * scale)
                # observability must not reintroduce the traffic 1-bit
                # removes: report the mean of per-replica local norms (one
                # scalar on the wire) — an upper bound on the norm of the
                # averaged gradient, documented as such.
                gnorm = jax.lax.pmean(global_norm(grads), axis)
                return new_state, new_errors, {
                    "loss": loss, "grad_norm": gnorm, "lr": lr,
                    "overflow": overflow.astype(jnp.int32),
                    "loss_scale": scale}

            state_specs = jax.tree_util.tree_map(lambda _: P(),
                                                 self.state_specs())
            err_in = jax.tree_util.tree_map(
                lambda l: P(axis), self._onebit_errors)

            def onebit_train_step(state, errors, batch):
                bspec = jax.tree_util.tree_map(lambda _: P(None, axis),
                                               batch)
                sharded = shard_map(
                    core, mesh=self.mesh,
                    in_specs=(state_specs, err_in, bspec),
                    out_specs=(state_specs, err_in,
                               jax.tree_util.tree_map(
                                   lambda _: P(),
                                   {"loss": 0, "grad_norm": 0, "lr": 0,
                                    "overflow": 0, "loss_scale": 0})),
                    axis_names={axis})
                return sharded(state, errors, batch)

            with self.mesh:
                self._onebit_compiled[key] = jax.jit(onebit_train_step,
                                                     donate_argnums=(0, 1))
            _count_jit_build(onebit_train_step)

        # error buffers re-zero when a reset-marked phase first activates
        # (reference reinitial_error_buffer, zoadam.py:324)
        if key in getattr(opt, "reset_errors_on", ()) and \
                not getattr(self, "_onebit_errors_reset", False):
            zero_fn = self._cached_program(
                "onebit_zero_errors",
                lambda: jax.jit(
                    lambda e: jax.tree_util.tree_map(jnp.zeros_like, e),
                    donate_argnums=(0,)))
            with self.mesh:
                self._onebit_errors = zero_fn(self._onebit_errors)
            self._onebit_errors_reset = True

        compiled = self._onebit_compiled[key]

        def run(state, batch):
            new_state, self._onebit_errors, metrics = compiled(
                state, self._onebit_errors, batch)
            return new_state, metrics

        self._train_step_fn = run
        return self._train_step_fn

    # ------------------------------------------------------------------
    # native API
    # ------------------------------------------------------------------
    def shard_batch(self, batch: Dict) -> Dict:
        """Host numpy batch [gas*micro*dp, ...] or [gas, B, ...] →
        device arrays sharded over the data axes."""
        gas = self.gradient_accumulation_steps
        global_b = self.train_batch_size
        # multi-host: each process supplies its LOCAL slice of the global
        # batch (launcher/dataloader contract, reference deepspeed.runtime
        # dataloader sharding)
        nproc = jax.process_count()
        local_b = global_b // nproc if nproc > 1 else global_b

        def prep(x):
            # deliberate host materialization: batches normally arrive
            # as host arrays (train_step only calls shard_batch when the
            # leaves are NOT jax.Array), so this is a coercion, not a
            # device round trip — and when a caller DOES hand a device
            # leaf, the sync is the documented contract of this helper
            x = host_transfer(x)
            if x.ndim >= 1 and x.shape[0] == local_b:
                return x.reshape((gas, local_b // gas) + x.shape[1:])
            if x.ndim >= 2 and x.shape[0] == gas:
                return x  # already [gas, micro*dp(_local), ...]
            raise ValueError(
                f"batch leading dim {x.shape[0]} matches neither the "
                f"process-local batch ({local_b}"
                f"{f' = {global_b}/{nproc} procs' if nproc > 1 else ''}) "
                f"nor [gas={gas}, ...] layout")
        batch = {k: prep(v) for k, v in batch.items()}
        shardings = to_named(self.mesh, self._batch_spec_tree(batch))
        if nproc > 1:
            # assemble global arrays from per-process shards — device_put
            # cannot write non-addressable shards
            def to_global(x, sharding):
                x = np.asarray(x)
                spec = sharding.spec
                gshape = list(x.shape)
                if len(spec) > 1 and spec[1] is not None:
                    gshape[1] = gshape[1] * nproc
                return jax.make_array_from_process_local_data(
                    sharding, x, tuple(gshape))
            return jax.tree_util.tree_map(to_global, batch, shardings)
        return jax.device_put(batch, shardings)

    def train_step(self, batch: Dict) -> Dict:
        """One full optimizer step (gas microbatches). Returns metrics dict
        of device scalars."""
        self._heartbeat.maybe_beat()
        if self.infinity_enabled:
            self.tput_timer.start()
            with trace_span("engine/train_step", mode="infinity",
                            step=self.global_steps):
                metrics = self._infinity.train_step(batch)
            self.tput_timer.stop()  # streamed step is synchronous
            self.global_steps += 1
            self.micro_steps += self.gradient_accumulation_steps
            if self._config.wall_clock_breakdown:
                self._step_times.append(metrics["step_time"])
            # on the ENGINE (the stepper keeps its own copy) — this is
            # what get_global_grad_norm() reads
            self._last_metrics = metrics
            self._post_step_observe(metrics, batch)
            return metrics
        if self.offload_enabled:
            if any(not isinstance(v, jax.Array) for v in
                   jax.tree_util.tree_leaves(batch)):
                with trace_span("engine/shard_batch"):
                    batch = self.shard_batch(batch)
            t0 = time.perf_counter()
            self.tput_timer.start()
            with trace_span("engine/train_step", mode="offload",
                            step=self.global_steps):
                metrics = self._offload_train_step(batch)
            self.tput_timer.stop()  # host step is synchronous already
            self.global_steps += 1
            self.micro_steps += self.gradient_accumulation_steps
            if self._config.wall_clock_breakdown:
                self._step_times.append(time.perf_counter() - t0)
            self._post_step_observe(metrics, batch)
            return metrics
        if self.optimizer.hyperparams.get("onebit"):
            key = self._onebit_program_key()
            if key != getattr(self, "_onebit_key", None) or \
                    self._train_step_fn is None:
                self._build_onebit_train_step(key)
        if self._train_step_fn is None:
            self._build_train_step()
        if any(not isinstance(v, jax.Array) for v in
               jax.tree_util.tree_leaves(batch)):
            with trace_span("engine/shard_batch"):
                batch = self.shard_batch(batch)
        else:
            gas = self.gradient_accumulation_steps
            for leaf in jax.tree_util.tree_leaves(batch):
                if leaf.ndim < 2 or leaf.shape[0] != gas:
                    raise ValueError(
                        f"device batch leaves must be [gas={gas}, "
                        f"micro*dp, ...]; got {leaf.shape} — pass host "
                        f"arrays or use engine.shard_batch()")
        t0 = time.perf_counter()
        self.tput_timer.start()
        # the fused step is ONE jitted program — fwd/bwd/allreduce/clip/
        # optimizer phases live inside XLA (the device profiler's job);
        # host-side the span pair splits enqueue from device wait
        with trace_span("engine/train_step", mode="fused",
                        step=self.global_steps):
            self.state, metrics = self._train_step_fn(self.state, batch)
        ovl_on = self._ovl.enabled
        # step_fn returned = async dispatch enqueued; the overlap
        # profiler's enqueue/device-wait boundary (no extra sync — the
        # wait end reuses the step_sync join below)
        t_enq = time.perf_counter() if ovl_on else 0.0
        self.global_steps += 1
        self.micro_steps += self.gradient_accumulation_steps
        # sync whenever anything CONSUMES the timing (monitor, breakdown,
        # metrics registry, or the periodic print) — unsynced stop() would
        # time async-dispatch enqueue, inflating tok/s and MFU by orders
        # of magnitude
        sync = (self.monitor.enabled or self._config.wall_clock_breakdown
                or bool(self._config.steps_per_print) or self._obs.enabled
                or self._flight.enabled or self._ovl.enabled)
        if sync:
            with trace_span("engine/step_sync", step=self.global_steps):
                self.tput_timer.stop(sync=metrics["loss"])
            if ovl_on:
                # total = t0 -> after the sync join; wait = enqueue
                # boundary -> join.  Recorded only on synced steps — an
                # unsynced step has no join to measure against and the
                # profiler never adds one
                t_end = time.perf_counter()
                self._ovl.observe("train", total_s=t_end - t0,
                                  enqueue_s=t_enq - t0,
                                  wait_s=t_end - t_enq)
        else:
            self.tput_timer.stop()
        if self._config.wall_clock_breakdown:
            host_transfer(metrics["loss"], block=True)
            self._step_times.append(time.perf_counter() - t0)
        # keep get_global_grad_norm() current: the compat step() path and
        # the offload/infinity paths set this too
        self._last_metrics = metrics
        self._post_step_observe(metrics, batch)
        return metrics

    def _post_step_observe(self, metrics: Dict, batch) -> None:
        """Monitor events at the GAS boundary + periodic log line
        (reference engine.py:1938 loss writes, :2270 _write_monitor).
        Also the metrics-registry feed point: the step already synced
        (train_step's sync flag includes the registry), so the float()
        materializations below are cheap."""
        cfg = self._config
        do_print = cfg.steps_per_print and \
            self.global_steps % cfg.steps_per_print == 0
        obs = self._obs
        fr = self._flight
        if not (do_print or self.monitor.enabled or obs.enabled
                or fr.enabled):
            return
        m = {k: float(v) for k, v in metrics.items()}
        step = self.global_steps
        if fr.enabled:
            # black-box snapshot per optimizer step; a burst of
            # consecutive overflow-skipped steps dumps a post-mortem
            # bundle (the run is diverging or the scale is thrashing —
            # capture the evidence while the ring still holds it)
            fr.record({
                "kind": "train_step", "step": step, "t": time.time(),
                "loss": m.get("loss"), "grad_norm": m.get("grad_norm"),
                "loss_scale": m.get("loss_scale"),
                "overflow": bool(m.get("overflow")),
            })
            if m.get("overflow"):
                self._skip_burst += 1
                if self._skip_burst >= fr.skip_burst_steps:
                    fr.dump("skipped_step_burst",
                            f"{self._skip_burst} consecutive skipped "
                            f"steps ending at step {step}",
                            extra={"loss_scale": m.get("loss_scale"),
                                   "grad_norm": m.get("grad_norm")})
                    self._skip_burst = 0
            else:
                self._skip_burst = 0
        if obs.enabled:
            obs.counter("dstpu_train_steps_total").inc()
            if m.get("overflow"):
                obs.counter("dstpu_train_skipped_steps_total").inc()
            dt = self.tput_timer.last_step_time
            if dt is not None:
                obs.histogram("dstpu_step_time_seconds").observe(dt)
        if self.monitor.enabled:
            events = [("Train/loss", m["loss"], step),
                      ("Train/lr", m["lr"], step),
                      ("Train/grad_norm", m["grad_norm"], step),
                      ("Train/loss_scale", m.get("loss_scale", 1.0), step)]
            if self.tput_timer.timed_steps > 0:
                events.append(("Train/samples_per_sec",
                               self.tput_timer.samples_per_sec, step))
                if self.tput_timer.seq_length:
                    events.append(("Train/tokens_per_sec",
                                   self.tput_timer.tokens_per_sec, step))
                mfu = self._try_mfu(batch)
                if mfu is not None:
                    events.append(("Train/mfu", mfu, step))
            if obs.enabled:
                # registry scalars ride the existing fan-out — TB/CSV/W&B
                # get every counter/gauge/histogram-mean for free
                obs.collect()
                events.extend(obs.to_events(step))
            self.monitor.write_events(events)
            self.monitor.flush()
        if obs.enabled:
            from ..observability import (export_interval_steps,
                                         export_metrics)
            ivl = export_interval_steps()
            if ivl and step % ivl == 0:
                export_metrics()
        if do_print:
            extra = ""
            if self.tput_timer.timed_steps > 0:
                extra = f" tok/s={self.tput_timer.tokens_per_sec:,.0f}"
                mfu = self._try_mfu(batch)
                if mfu is not None:
                    extra += f" mfu={100 * mfu:.1f}%"
            logger.info(
                f"step={self.global_steps} loss={m['loss']:.4f} "
                f"lr={m['lr']:.3e} grad_norm={m['grad_norm']:.3f} "
                f"loss_scale={m.get('loss_scale', 1.0):.0f}{extra}")
            if cfg.wall_clock_breakdown and self.timers.timers:
                # named-timer breakdown; memory_breakdown (the config key)
                # appends the device/host memory snapshot to the line
                self.timers.log(sorted(self.timers.timers),
                                memory_breakdown=cfg.memory_breakdown)

    def _obs_collect(self) -> None:
        """Export-time refresh of derived gauges: device-memory watermark
        and comms wire volume. Host-side reads only — ``memory_stats``
        and the trace-time comms log never block on the device."""
        obs = self._obs
        try:
            stats = jax.devices()[0].memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                obs.gauge("dstpu_device_peak_memory_bytes").set(float(peak))
        except Exception:
            pass
        from ..comm.comms_logging import get_comms_logger
        from ..observability import sanitize_name
        cl = get_comms_logger()
        if cl is not None:
            for op_name, sizes in cl.comms_dict.items():
                vol = sum(rec["volume"] for rec in sizes.values())
                obs.gauge(
                    f"dstpu_comm_volume_bytes_{sanitize_name(op_name)}",
                    help="trace-time comms payload volume (CommsLogger)",
                ).set(float(vol))

    def flush_observability(self, sync: bool = True):
        """Flush the span trace and metric exports
        (docs/observability.md). ``sync=True`` first joins the last
        step's loss via ``host_transfer(block=True)`` — the explicit
        flush-boundary device sync, so the trace covers fully-executed
        work. Returns the list of files written."""
        from ..observability import flush_all
        val = None
        if sync:
            last = getattr(self, "_last_metrics", None)
            if last:
                val = last.get("loss")
        return flush_all(sync=val)

    def _try_mfu(self, batch) -> Optional[float]:
        """Engine-reported MFU from ANALYTIC flops (6N + attention) — the
        bench script no longer owns this number (VERDICT missing #7).
        Deliberately not XLA cost analysis here: that would lower+compile a
        second copy of the train step mid-loop; the explicit FlopsProfiler
        API is where users pay that cost knowingly."""
        del batch
        if self.offload_enabled or self.infinity_enabled:
            return None  # offload step is host-bound; MFU is not the metric
        if self.tput_timer.timed_steps == 0:
            return None
        if self._analytic_flops_per_step is None:
            from ..profiling.flops_profiler.profiler import (
                chip_peak_flops, transformer_flops_per_token)
            mcfg = getattr(self.model, "config", None)
            if mcfg is None or not hasattr(mcfg, "d_model"):
                return None
            try:
                peak = chip_peak_flops()
            except ValueError as e:
                # a device without a published peak (the CPU mesh) has
                # no MFU: not measured, rather than a number
                logger.debug(f"mfu unavailable: {e}")
                return None
            seq = self.tput_timer.seq_length or mcfg.max_seq_len
            self._analytic_flops_per_step = (
                self.train_batch_size * seq *
                transformer_flops_per_token(
                    self.num_parameters(), mcfg.num_layers,
                    mcfg.d_model, seq))
            self._peak_flops = peak * max(jax.device_count(), 1)
        return (self._analytic_flops_per_step /
                self.tput_timer.avg_step_time / self._peak_flops)

    def train_batch(self, data_iter: Optional[Iterable] = None,
                    batch: Optional[Dict] = None) -> Dict:
        """Reference `PipelineEngine.train_batch`-style surface for plain DP:
        pull one global batch from the iterator and step."""
        if batch is None:
            if not hasattr(data_iter, "__next__"):
                # cache the iterator per loader so successive calls advance
                # through the data instead of restarting at batch 0
                if getattr(self, "_data_iter_src", None) is not data_iter:
                    self._data_iter_src = data_iter
                    self._data_iter = iter(data_iter)
                try:
                    batch = next(self._data_iter)
                except StopIteration:
                    self._data_iter = iter(data_iter)  # next epoch
                    batch = next(self._data_iter)
            else:
                batch = next(data_iter)
        return self.train_step(batch)

    def eval_loss(self, batch: Dict) -> jnp.ndarray:
        if self.infinity_enabled:
            return jnp.asarray(self._infinity.eval_loss(batch))
        if any(not isinstance(v, jax.Array)
               for v in jax.tree_util.tree_leaves(batch)):
            batch = self.shard_batch(batch)
        sq = jax.tree_util.tree_map(lambda x: x.reshape((-1,) + x.shape[2:]),
                                    batch)
        if not hasattr(self, "_eval_fn"):
            def eval_loss_fn(params, mb):
                return self._loss_and_counters(params, mb)[0]
            with self.mesh:
                self._eval_fn = jax.jit(eval_loss_fn)
            _count_jit_build(eval_loss_fn)
        return self._eval_fn(self.state["params"], sq)

    # ------------------------------------------------------------------
    # compat API: forward / backward / step  (reference engine.py:1761,
    # 1910, 2121). Each call is an independent jitted program.
    # ------------------------------------------------------------------
    def forward(self, batch: Dict) -> jnp.ndarray:
        if self.offload_enabled or self.infinity_enabled:
            raise NotImplementedError(
                "the compat forward/backward/step surface is not wired for "
                "offload — use train_step()/train_batch()")
        self._last_batch = batch if isinstance(
            next(iter(jax.tree_util.tree_leaves(batch))), jax.Array) \
            else jax.device_put(batch, to_named(
                self.mesh, jax.tree_util.tree_map(
                    lambda x: P(self._batch_dim_spec,), batch)))
        if self._grad_fn is None:
            def forward_grads(params, mb, scale):
                (loss, _), grads = jax.value_and_grad(
                    self._micro_loss, has_aux=True)(params, mb, scale)
                return loss, grads
            with self.mesh:
                self._grad_fn = jax.jit(forward_grads)
            _count_jit_build(forward_grads)
        scale = (self.state["scaler"].scale
                 if self.loss_scaler is not None else 1.0)
        with trace_span("engine/forward", micro_step=self.micro_steps):
            self._last_loss, self._last_grads = self._grad_fn(
                self.state["params"], self._last_batch, scale)
        return self._last_loss / scale if self.fp16_enabled else self._last_loss

    def backward(self, loss=None) -> None:
        """Accumulate the grads of the last forward into the GAS buffer."""
        del loss  # grads were produced alongside forward (jit has no tape)
        with trace_span("engine/backward", micro_step=self.micro_steps):
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                           self._last_grads)
            if self._grad_acc is None:
                self._grad_acc = grads
            else:
                # cache the jitted adder: jax.jit keys its compile cache on
                # the callable object, so a fresh lambda here meant a fresh
                # trace+compile EVERY microbatch (dstpu-lint TRACE003)
                if getattr(self, "_grad_acc_add_fn", None) is None:
                    def grad_acc_add(a, b):
                        return jax.tree_util.tree_map(jnp.add, a, b)
                    with self.mesh:
                        self._grad_acc_add_fn = jax.jit(
                            grad_acc_add, donate_argnums=(0,))
                    _count_jit_build(grad_acc_add)
                with self.mesh:
                    self._grad_acc = self._grad_acc_add_fn(self._grad_acc,
                                                           grads)
        self._grad_acc_count += 1
        self.micro_steps += 1

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._grad_acc_count >= self.gradient_accumulation_steps

    def step(self) -> None:
        self._heartbeat.maybe_beat()
        if self._grad_acc is None:
            return
        if self._apply_fn is None:
            def apply_grads(state, grads, n_micro):
                return self._apply_grads(state, grads, n_micro)
            with self.mesh:
                self._apply_fn = jax.jit(apply_grads,
                                         donate_argnums=(0, 1))
            _count_jit_build(apply_grads)
        with trace_span("engine/optimizer_step", step=self.global_steps):
            self.state, metrics = self._apply_fn(
                self.state, self._grad_acc,
                jnp.asarray(float(self._grad_acc_count), jnp.float32))
        self._grad_acc = None
        self._grad_acc_count = 0
        self.global_steps += 1
        self._last_metrics = metrics

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def get_lr(self) -> float:
        return float(self.lr_schedule(self.state["step"]))

    def get_global_grad_norm(self) -> Optional[float]:
        m = getattr(self, "_last_metrics", None)
        return float(m["grad_norm"]) if m else None

    @property
    def skipped_steps(self) -> int:
        return int(self.state.get("skipped", 0))

    @property
    def loss_scale(self) -> float:
        if self._host_scaler is not None:
            return self._host_scaler.scale
        if self.loss_scaler is None:
            return 1.0
        return float(self.state["scaler"].scale)

    def num_parameters(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self._param_shapes))

    # checkpointing lives in runtime/checkpoint_engine (wired by __init__.py)
    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        from .checkpoint_engine.engine import save_checkpoint as _save
        # a multi-GB checkpoint write is the longest legitimate gap
        # between train steps — bracket it with beats so the elastic
        # agent's watchdog doesn't read it as a hang
        self._heartbeat.beat_now()
        try:
            return _save(self, save_dir, tag=tag,
                         client_state=client_state or {})
        finally:
            self._heartbeat.beat_now()

    def load_checkpoint(self, load_dir, tag=None, **kw):
        from .checkpoint_engine.engine import load_checkpoint as _load
        self._heartbeat.maybe_beat()
        return _load(self, load_dir, tag=tag, **kw)
