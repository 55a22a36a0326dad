"""Master configuration.

One JSON/dict config is the spine of the framework, exactly as in the
reference (`/root/reference/deepspeed/runtime/config.py:810`
``_initialize_params``): every subsystem hangs its sub-config off this object.
The schema accepts DeepSpeed-style JSON so existing configs port over, plus
TPU-native blocks (``mesh``, ``sequence_parallel``) that have no reference
equivalent.

Batch-size reconciliation follows the reference's triple rule
(`runtime/config.py:921-980`):
    train_batch_size == micro_batch_per_device * gradient_accumulation_steps
                        * data_parallel_world_size
Given any two, the third is inferred; all three given must agree.
"""
from __future__ import annotations

import json
from enum import Enum
from typing import Any, Dict, Optional, Union

from pydantic import Field, model_validator

from .config_utils import ConfigModel, dict_raise_error_on_duplicate_keys
from . import constants as C


# ---------------------------------------------------------------------------
# Precision
# ---------------------------------------------------------------------------
class FP16Config(ConfigModel):
    """fp16 block — dynamic loss scaling semantics follow the reference
    DynamicLossScaler (`runtime/fp16/loss_scaler.py:77`)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = C.FP16_LOSS_SCALE_DEFAULT  # 0 => dynamic
    initial_scale_power: int = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.FP16_HYSTERESIS_DEFAULT
    min_loss_scale: float = C.FP16_MIN_LOSS_SCALE_DEFAULT

    @property
    def dynamic(self) -> bool:
        return self.enabled and self.loss_scale == 0


class BF16Config(ConfigModel):
    """bf16 block. On TPU bf16 is the native matmul dtype; fp32 master params
    are kept like the reference BF16_Optimizer (`runtime/bf16_optimizer.py:38`)."""
    enabled: bool = False
    # Keep a full-precision master copy of params (rarely worth disabling).
    master_weights: bool = True


# ---------------------------------------------------------------------------
# ZeRO
# ---------------------------------------------------------------------------
class OffloadDeviceEnum(str, Enum):
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class DeepSpeedZeroOffloadParamConfig(ConfigModel):
    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = int(1e8)
    max_in_cpu: int = int(1e9)
    pin_memory: bool = False


class DeepSpeedZeroOffloadOptimizerConfig(ConfigModel):
    device: OffloadDeviceEnum = OffloadDeviceEnum.none
    nvme_path: Optional[str] = None
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    # host optimizer-sweep parallelism; 0 = one worker per host core
    # (capped at 8) — the reference's AVX sweep is single-threaded per
    # sub-group but a TPU-VM host has dozens of cores to put behind it
    worker_count: int = 0

    @property
    def pipeline(self) -> bool:
        return self.pipeline_read or self.pipeline_write


class ZeroConfig(ConfigModel):
    """zero_optimization block (reference: `runtime/zero/config.py`).

    TPU interpretation: stages are sharding policies over the ``data`` mesh
    axis, applied as `jax.sharding` annotations rather than runtime hooks.
      stage 0 — pure DP: params/grads/opt-state replicated, grads psum'd.
      stage 1 — optimizer state sharded over data axis.
      stage 2 — + gradients reduce-scattered (psum_scatter) over data axis.
      stage 3 — + parameters sharded (FSDP); XLA inserts just-in-time
                 all-gathers, scheduled per layer block.
    """
    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_partitions: bool = True
    allgather_bucket_size: int = int(5e8)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[DeepSpeedZeroOffloadParamConfig] = None
    offload_optimizer: Optional[DeepSpeedZeroOffloadOptimizerConfig] = None
    sub_group_size: int = int(1e9)
    cpu_offload: Optional[bool] = None  # deprecated alias
    cpu_offload_params: Optional[bool] = None  # deprecated alias
    prefetch_bucket_size: int = int(5e7)
    param_persistence_threshold: int = int(1e5)
    model_persistence_threshold: int = int(1e14)  # pydantic int bounds: keep finite
    max_live_parameters: int = int(1e9)
    max_reuse_distance: int = int(1e9)
    gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = 1
    # TPU-native: how many layer blocks to scan over for stage-3 gather
    # scheduling (0 = let XLA decide; >0 = lax.scan over stacked blocks).
    stage3_scan_layers: int = 0
    # ZeRO-Infinity: initialize layer slots host-side (numpy RNG) instead of
    # materializing each layer on device and fetching it. The values differ
    # from model.init's (different RNG), so use only for from-scratch runs
    # where init distribution, not init bits, matters — it removes a
    # 4-bytes/param device→host fetch at startup, which dominates init time
    # on hosts with slow D2H links.
    infinity_host_init: bool = False
    # ZeRO-Infinity D2H gradient-wire compression: 0 = off (bf16 wire),
    # 8/4/1 = grouped stochastic-rounding quantization to that many bits
    # before the device->host fetch (runtime/zero/wire_codec.py). The role
    # the reference's 1-bit error-feedback compression plays on the
    # network wire (runtime/comm/nccl.py:52), re-derived for a host
    # offload wire where persistent device error state would cost HBM
    # linear in total params: stochastic rounding is unbiased WITHOUT
    # error memory.
    offload_wire_bits: int = 0
    # ZeRO-Infinity H2D parameter-wire compression: 0 = off (bf16 uploads),
    # 8/4 = block-quantized parameter uploads (deterministic round-to-
    # nearest, per-chunk max-abs scales; runtime/zero/wire_codec.py
    # encode_params_host/decode_params). The streamed forward re-uploads
    # every layer each step (the host sweep changed them), so on slow H2D
    # links the upload wire bounds the step exactly like the reference's
    # NVMe read path bounds its stage-3 prefetch
    # (zero/partitioned_param_swapper). 8-bit halves upload bytes vs bf16
    # AND doubles the device layer cache (the cache stores the quantized
    # payload; dequant is fused into each layer's compiled program, an
    # HBM-cheap read at 1 byte/param). The forward/backward compute sees
    # the quantized weights; the f32 masters on the host stay exact.
    offload_param_bits: int = 0

    @model_validator(mode="after")
    def _resolve_deprecated(self):
        if self.cpu_offload and self.offload_optimizer is None:
            self.offload_optimizer = DeepSpeedZeroOffloadOptimizerConfig(
                device=OffloadDeviceEnum.cpu)
        if self.cpu_offload_params and self.offload_param is None:
            self.offload_param = DeepSpeedZeroOffloadParamConfig(
                device=OffloadDeviceEnum.cpu)
        if not 0 <= self.stage <= 3:
            raise ValueError(f"zero_optimization.stage must be 0..3, got {self.stage}")
        # wire-codec bit widths fail at PARSE time on every engine path
        # (offload_bench's tier-1 path consumes offload_wire_bits without
        # ever building an InfinityStepper, whose own checks these mirror)
        if self.offload_param_bits not in (0, 4, 8):
            raise ValueError(
                f"zero_optimization.offload_param_bits must be 0, 4 or 8; "
                f"got {self.offload_param_bits}")
        if self.offload_wire_bits not in (0, 1, 4, 8):
            raise ValueError(
                f"zero_optimization.offload_wire_bits must be 0, 1, 4 or "
                f"8; got {self.offload_wire_bits}")
        return self


# ---------------------------------------------------------------------------
# Optimizer / scheduler blocks
# ---------------------------------------------------------------------------
class OptimizerConfig(ConfigModel):
    type: str = "AdamW"
    params: Dict[str, Any] = Field(default_factory=dict)
    legacy_fusion: bool = False


class SchedulerConfig(ConfigModel):
    type: str = "WarmupLR"
    params: Dict[str, Any] = Field(default_factory=dict)


# ---------------------------------------------------------------------------
# Mesh (TPU-native block; replaces reference groups.py / mpu plumbing)
# ---------------------------------------------------------------------------
class MeshConfig(ConfigModel):
    """Named-axis device mesh over ICI/DCN.

    Replaces the reference's process-group topology
    (`deepspeed/utils/groups.py`, `runtime/pipe/topology.py:243`) with a
    declarative `jax.sharding.Mesh` spec. Axis sizes of -1 mean "absorb the
    remaining devices" (at most one axis may be -1; ``data`` defaults to -1).
    Axis order is outermost→innermost placement on the device torus; keep
    ``model``/``sequence`` innermost so their collectives ride ICI.
    """
    data: int = -1
    model: int = 1      # tensor parallel
    pipe: int = 1       # pipeline stages
    expert: int = 1     # MoE expert parallel (folded into data at runtime)
    sequence: int = 1   # context/sequence parallel
    # devices per host axis for multi-slice: "dcn_data" replicas over DCN
    dcn_data: int = 1


class PipelineConfig(ConfigModel):
    """pipeline block (reference: PipelineEngine knobs on the engine config).

    ``stages`` — "auto" (stage count = the mesh's ``pipe`` axis) or an
    explicit int the engine cross-checks against the mesh: a tuned config
    exported for one topology fails loudly on another instead of silently
    training a different 3D shape. ``micro_batches`` is an alias for the
    microbatch count M (reconciled into the batch triple as
    gradient_accumulation_steps — the reference's train_batch =
    micro * M * dp identity)."""
    stages: Union[int, str] = C.PIPE_STAGES_DEFAULT

    @model_validator(mode="after")
    def _check_stages(self):
        s = self.stages
        if isinstance(s, str) and s != "auto":
            if not s.isdigit():
                raise ValueError(
                    f"pipeline.stages must be 'auto' or a positive int, "
                    f"got {s!r}")
            self.stages = int(s)
        if isinstance(self.stages, int) and self.stages < 1:
            raise ValueError(
                f"pipeline.stages must be >= 1, got {self.stages}")
        return self

    @model_validator(mode="before")
    @classmethod
    def _one_schedule(cls, data):
        if isinstance(data, dict) and "schedule" in data:
            raise ValueError(
                "pipeline.schedule is not an option: the pipeline engine "
                "compiles one schedule, 1F1B. The second one carried the "
                "auxiliary loss of the capacity-gated expert layer, which "
                "is gone; the expert layer is moe/dropless.py")
        return data
    partition: str = "parameters"  # parameters | uniform | type:regex
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    micro_batches: Optional[int] = None


class SequenceParallelConfig(ConfigModel):
    """TPU-native capability absent from the reference (SURVEY §5.7)."""
    enabled: bool = False
    mode: str = "ring"  # ring | ulysses
    axis: str = "sequence"


class TensorParallelConfig(ConfigModel):
    enabled: bool = False
    tp_size: int = 1
    # auto-TP: shard any Dense whose name matches these patterns
    autotp_size: int = 0


# ---------------------------------------------------------------------------
# Aux subsystem blocks
# ---------------------------------------------------------------------------
class ActivationCheckpointingConfig(ConfigModel):
    """Maps to jax.checkpoint/remat policies rather than the reference's
    manual activation stash (`runtime/activation_checkpointing/`)."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # remat policy name: none|full|dots_saveable|nothing_saveable|custom
    policy: str = "full"


class AioConfig(ConfigModel):
    """aio block (reference `runtime/swap_tensor/aio_config.py`)."""
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


class FlopsProfilerConfig(ConfigModel):
    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"


class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJobName"


class MonitorConfig(ConfigModel):
    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)

    @property
    def enabled(self) -> bool:
        return (self.tensorboard.enabled or self.wandb.enabled
                or self.csv_monitor.enabled)


class CheckpointConfig(ConfigModel):
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    # async checkpointing via a background committer thread
    async_save: bool = False


class CommsConfig(ConfigModel):
    verbose: bool = False
    prof_all: bool = False
    debug: bool = False
    prof_ops: list = Field(default_factory=list)


class ResilienceConfig(ConfigModel):
    """``resilience`` block (runtime/resilience/, docs/resilience.md).

    Governs checkpoint integrity (manifest + atomic commit + last-good
    fallback), the shared I/O retry policy, non-finite-gradient step
    skipping, worker liveness, and deterministic fault injection."""
    # -- checkpoint integrity --
    checkpoint_integrity: bool = C.RESILIENCE_CHECKPOINT_INTEGRITY_DEFAULT
    # re-read and re-fingerprint every artifact right after commit; the
    # paranoid mode that catches a lying write cache at save time
    verify_on_save: bool = C.RESILIENCE_VERIFY_ON_SAVE_DEFAULT
    # on a corrupt/partial tag at load, fall back to the newest tag that
    # still verifies instead of raising
    fallback_to_last_good: bool = C.RESILIENCE_FALLBACK_DEFAULT
    # -- retriable I/O (runtime/resilience/retry.py) --
    io_retry_attempts: int = C.RESILIENCE_IO_RETRY_ATTEMPTS_DEFAULT
    io_retry_base_delay_s: float = C.RESILIENCE_IO_RETRY_BASE_DELAY_DEFAULT
    io_retry_max_delay_s: float = C.RESILIENCE_IO_RETRY_MAX_DELAY_DEFAULT
    io_retry_jitter: float = C.RESILIENCE_IO_RETRY_JITTER_DEFAULT
    # -- training-step hygiene --
    # skip the optimizer update (and count it in state['skipped']) when
    # the global grad norm is non-finite, instead of poisoning opt state
    skip_nonfinite_grad_steps: bool = C.RESILIENCE_SKIP_NONFINITE_DEFAULT
    # -- liveness (elasticity/elastic_agent.py watchdog) --
    heartbeat_interval_s: float = C.RESILIENCE_HEARTBEAT_INTERVAL_DEFAULT
    watchdog_timeout_s: float = C.RESILIENCE_WATCHDOG_TIMEOUT_DEFAULT  # 0=off
    # -- fault injection (runtime/resilience/fault_injection.py) --
    # {"site": {"kind": "fail|fatal|truncate|delay|kill",
    #           "at": 1, "count": 1, "arg": 0}}
    # sites cover checkpoint/slot-store I/O AND the serving stack
    # (serving.allocate / append_block / admission / dispatch — see
    # docs/serving.md "Failure handling & overload")
    fault_injection: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="after")
    def _validate(self):
        if self.io_retry_attempts < 1:
            raise ValueError(
                f"resilience.io_retry_attempts must be >= 1, got "
                f"{self.io_retry_attempts}")
        if self.io_retry_base_delay_s < 0 or \
                self.io_retry_max_delay_s < self.io_retry_base_delay_s:
            raise ValueError(
                "resilience: need 0 <= io_retry_base_delay_s <= "
                f"io_retry_max_delay_s, got {self.io_retry_base_delay_s}/"
                f"{self.io_retry_max_delay_s}")
        if not 0.0 <= self.io_retry_jitter <= 1.0:
            raise ValueError(
                f"resilience.io_retry_jitter must be in [0, 1], got "
                f"{self.io_retry_jitter}")
        if self.watchdog_timeout_s < 0 or self.heartbeat_interval_s <= 0:
            raise ValueError(
                "resilience: watchdog_timeout_s must be >= 0 (0 disables) "
                "and heartbeat_interval_s > 0")
        if self.watchdog_timeout_s and \
                self.watchdog_timeout_s < 2 * self.heartbeat_interval_s:
            raise ValueError(
                f"resilience.watchdog_timeout_s "
                f"({self.watchdog_timeout_s}) must be at least twice "
                f"heartbeat_interval_s ({self.heartbeat_interval_s}) or a "
                f"healthy worker one beat behind gets killed")
        return self


class TracingConfig(ConfigModel):
    """``observability.tracing`` — host-side span tracer
    (deepspeed_tpu/observability/tracer.py). Spans record into a
    preallocated ring buffer and export as Chrome trace-event JSON
    (Perfetto-loadable); device syncs happen only at explicit flush
    boundaries via ``host_transfer()``."""
    enabled: bool = C.OBSERVABILITY_TRACING_ENABLED_DEFAULT
    # ring capacity in spans; oldest spans are overwritten on wraparound
    buffer_size: int = C.OBSERVABILITY_TRACE_BUFFER_DEFAULT
    # directory for per-process trace_rank<r>.json files
    output_dir: str = C.OBSERVABILITY_TRACE_DIR_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.buffer_size < 1:
            raise ValueError(
                f"observability.tracing.buffer_size must be >= 1, got "
                f"{self.buffer_size}")
        return self


class ObsMetricsConfig(ConfigModel):
    """``observability.metrics`` — counter/gauge/histogram registry with
    Prometheus-textfile and JSON exporters
    (deepspeed_tpu/observability/metrics.py). Scalars also flow into the
    MonitorMaster fan-out (TB/CSV/W&B) when a monitor is enabled."""
    enabled: bool = C.OBSERVABILITY_METRICS_ENABLED_DEFAULT
    # node_exporter textfile-collector directory (dstpu_rank<r>.prom)
    prometheus_dir: Optional[str] = C.OBSERVABILITY_PROMETHEUS_DIR_DEFAULT
    # JSON snapshot path
    json_path: Optional[str] = C.OBSERVABILITY_JSON_PATH_DEFAULT
    # export every N steps (0 = only at flush/close/atexit)
    export_interval_steps: int = C.OBSERVABILITY_EXPORT_INTERVAL_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.export_interval_steps < 0:
            raise ValueError(
                f"observability.metrics.export_interval_steps must be "
                f">= 0, got {self.export_interval_steps}")
        return self


class RequestTracingConfig(ConfigModel):
    """``observability.request_tracing`` — per-request serving timelines
    (deepspeed_tpu/observability/request_trace.py). Every request gets a
    trace id at submit; lifecycle sites stamp segments that export as a
    Perfetto waterfall track per request inside the span tracer's
    ``trace_rank<r>.json``. Requires ``tracing.enabled`` (the export
    rides the same flush)."""
    enabled: bool = C.OBSERVABILITY_REQUEST_TRACE_ENABLED_DEFAULT
    # retained request timelines; oldest completed evicted first
    capacity: int = C.OBSERVABILITY_REQUEST_TRACE_CAPACITY_DEFAULT
    # stamped segments per request before drops are counted
    max_segments: int = C.OBSERVABILITY_REQUEST_TRACE_SEGMENTS_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.capacity < 1 or self.max_segments < 1:
            raise ValueError(
                "observability.request_tracing: capacity and max_segments "
                f"must be >= 1, got {self.capacity}/{self.max_segments}")
        return self


class SloConfig(ConfigModel):
    """``observability.slo`` — per-tenant multi-window burn-rate alerting
    over the TTFT / inter-token SLOs declared in ``TenantSpec``
    (deepspeed_tpu/observability/slo.py). An alert fires when the error
    budget (``1 - objective``) burns ``burn_threshold``x faster than
    sustainable in BOTH the fast and slow windows."""
    enabled: bool = C.OBSERVABILITY_SLO_ENABLED_DEFAULT
    objective: float = C.OBSERVABILITY_SLO_OBJECTIVE_DEFAULT
    fast_window_s: float = C.OBSERVABILITY_SLO_FAST_WINDOW_DEFAULT
    slow_window_s: float = C.OBSERVABILITY_SLO_SLOW_WINDOW_DEFAULT
    burn_threshold: float = C.OBSERVABILITY_SLO_BURN_THRESHOLD_DEFAULT
    # firing -> resolved once fast burn < threshold * resolve_fraction
    resolve_fraction: float = C.OBSERVABILITY_SLO_RESOLVE_FRACTION_DEFAULT
    # fast-window observations required before an alert may fire
    min_samples: int = C.OBSERVABILITY_SLO_MIN_SAMPLES_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if not 0.0 < self.objective < 1.0:
            raise ValueError(
                f"observability.slo.objective must be in (0, 1), got "
                f"{self.objective}")
        if self.fast_window_s <= 0 or \
                self.slow_window_s < self.fast_window_s:
            raise ValueError(
                "observability.slo: need 0 < fast_window_s <= "
                f"slow_window_s, got {self.fast_window_s}/"
                f"{self.slow_window_s}")
        if self.burn_threshold <= 0 or self.min_samples < 1:
            raise ValueError(
                "observability.slo: burn_threshold must be > 0 and "
                f"min_samples >= 1, got {self.burn_threshold}/"
                f"{self.min_samples}")
        if not 0.0 <= self.resolve_fraction <= 1.0:
            raise ValueError(
                f"observability.slo.resolve_fraction must be in [0, 1], "
                f"got {self.resolve_fraction}")
        return self


class FlightRecorderConfig(ConfigModel):
    """``observability.flight`` — black-box flight recorder
    (deepspeed_tpu/observability/flight_recorder.py): a bounded ring of
    per-iteration engine snapshots dumped as an atomic, manifest-sealed
    post-mortem bundle on ServingError / watchdog trip / skipped-step
    burst."""
    enabled: bool = C.OBSERVABILITY_FLIGHT_ENABLED_DEFAULT
    capacity: int = C.OBSERVABILITY_FLIGHT_CAPACITY_DEFAULT
    output_dir: str = C.OBSERVABILITY_FLIGHT_DIR_DEFAULT
    max_terminal_events: int = C.OBSERVABILITY_FLIGHT_TERMINALS_DEFAULT
    # consecutive skipped train steps that trip a post-mortem dump
    skip_burst_steps: int = C.OBSERVABILITY_FLIGHT_SKIP_BURST_DEFAULT
    max_bundles: int = C.OBSERVABILITY_FLIGHT_MAX_BUNDLES_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.capacity < 1 or self.max_terminal_events < 1 \
                or self.max_bundles < 1:
            raise ValueError(
                "observability.flight: capacity, max_terminal_events and "
                "max_bundles must be >= 1, got "
                f"{self.capacity}/{self.max_terminal_events}/"
                f"{self.max_bundles}")
        if self.skip_burst_steps < 1:
            raise ValueError(
                f"observability.flight.skip_burst_steps must be >= 1, got "
                f"{self.skip_burst_steps}")
        return self


class OverlapConfig(ConfigModel):
    """``observability.overlap`` — host/device overlap profiler
    (deepspeed_tpu/observability/overlap.py): splits each serving
    iteration into its five phases (plan, operands, enqueue, device
    wait, apply) and each synced training step into host-plan, enqueue
    and device-wait (no new device syncs), counts dispatches and rows,
    keeps terminal requests' stamps, and exports gauges+histograms and a
    per-iteration trace track."""
    enabled: bool = C.OBSERVABILITY_OVERLAP_ENABLED_DEFAULT
    # records retained in each ring (iterations, terminal requests)
    capacity: int = C.OBSERVABILITY_OVERLAP_CAPACITY_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.capacity < 1:
            raise ValueError(
                f"observability.overlap.capacity must be >= 1, got "
                f"{self.capacity}")
        return self


class ObservabilityConfig(ConfigModel):
    """``observability`` block (deepspeed_tpu/observability/,
    docs/observability.md)."""
    tracing: TracingConfig = Field(default_factory=TracingConfig)
    metrics: ObsMetricsConfig = Field(default_factory=ObsMetricsConfig)
    request_tracing: RequestTracingConfig = Field(
        default_factory=RequestTracingConfig)
    slo: SloConfig = Field(default_factory=SloConfig)
    flight: FlightRecorderConfig = Field(
        default_factory=FlightRecorderConfig)
    overlap: OverlapConfig = Field(default_factory=OverlapConfig)

    @model_validator(mode="after")
    def _validate(self):
        if self.request_tracing.enabled and not self.tracing.enabled:
            raise ValueError(
                "observability.request_tracing.enabled requires "
                "observability.tracing.enabled — the per-request "
                "waterfall exports inside the span tracer's Chrome trace")
        return self

    @property
    def enabled(self) -> bool:
        return (self.tracing.enabled or self.metrics.enabled
                or self.request_tracing.enabled or self.slo.enabled
                or self.flight.enabled or self.overlap.enabled)


#: remat policies the model's ``_remat`` accepts (models/transformer.py);
#: kept here so the config rejects a typo'd policy at parse time, before
#: the engine rebuilds the model with it
TRAINING_REMAT_POLICIES = ("none", "full", "dots_saveable",
                           "dots_no_batch", "nothing_saveable",
                           "host_offload")


class TrainingConfig(ConfigModel):
    """``training`` block (docs/training_perf.md).

    Overrides of the model-side hot-path knobs the autotuner searches.
    Every field defaulting to None means "keep the model config's
    setting"; a non-None value makes the ENGINE rebuild the model with
    that knob at initialize time, so a tuned best-config JSON is
    self-contained — no caller-side model surgery needed to apply it."""
    # jax.checkpoint policy applied per transformer block
    remat: Optional[str] = C.TRAINING_REMAT_DEFAULT
    # analytic custom-VJP loss head (ops/transformer/fused_loss.py):
    # backward recomputes chunk logits and forms softmax−onehot in-VJP
    # instead of materializing [B,T,V] logit cotangents
    fused_loss_head: Optional[bool] = C.TRAINING_FUSED_LOSS_HEAD_DEFAULT
    # tokens per loss chunk (model config ``loss_chunk``); 0 = dense
    loss_chunk: Optional[int] = C.TRAINING_LOSS_CHUNK_DEFAULT
    # donate batch buffers into the jitted step alongside engine state
    # (runtime/engine.py _build_train_step). Off by default: bench and
    # autotune loops re-feed the same device batch, which donation
    # would invalidate.
    donate_batch: bool = C.TRAINING_DONATE_BATCH_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.remat is not None and \
                self.remat not in TRAINING_REMAT_POLICIES:
            raise ValueError(
                f"training.remat must be one of "
                f"{list(TRAINING_REMAT_POLICIES)}, got {self.remat!r}")
        if self.loss_chunk is not None and self.loss_chunk < 0:
            raise ValueError(
                f"training.loss_chunk must be >= 0 (0 = dense), got "
                f"{self.loss_chunk}")
        return self

    def model_overrides(self) -> Dict[str, Any]:
        """The non-None model-config overrides this block carries."""
        out: Dict[str, Any] = {}
        for key in ("remat", "fused_loss_head", "loss_chunk"):
            val = getattr(self, key)
            if val is not None:
                out[key] = val
        return out


# ---------------------------------------------------------------------------
# Master config
# ---------------------------------------------------------------------------
class DeepSpeedConfig:
    """Parses the master dict/JSON; exposes typed sub-configs.

    Mirrors the surface of the reference `DeepSpeedConfig`
    (`runtime/config.py:679`): scalar engine knobs as attributes, each
    subsystem a typed config object.
    """

    def __init__(self, config: Any, world_size: Optional[int] = None):
        if isinstance(config, str):
            with open(config, "r") as f:
                self._param_dict = json.load(
                    f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise ValueError(
                f"Expected a dict or a json path, got {type(config)}")
        self._world_size = world_size
        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._do_sanity_check()

    # -- parsing ----------------------------------------------------------
    def _initialize_params(self, pd: dict) -> None:
        g = pd.get
        self.train_batch_size = g(C.TRAIN_BATCH_SIZE,
                                  C.TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = g(
            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = g(
            C.GRADIENT_ACCUMULATION_STEPS,
            C.GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self.steps_per_print = g(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = g(C.DUMP_STATE, False)
        self.gradient_clipping = g(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT)
        # legacy DeepSpeed alias: top-level max_grad_norm == gradient_clipping
        # (previously accepted and silently IGNORED — dstpu-lint CFG001)
        mgn = g(C.MAX_GRAD_NORM)
        if mgn is not None:
            if C.GRADIENT_CLIPPING in pd and pd[C.GRADIENT_CLIPPING] != mgn:
                raise ValueError(
                    f"both {C.GRADIENT_CLIPPING} "
                    f"({pd[C.GRADIENT_CLIPPING]}) and its legacy alias "
                    f"{C.MAX_GRAD_NORM} ({mgn}) are set and disagree")
            self.gradient_clipping = mgn
        # amp is apex/CUDA mixed precision; a config that asks for it must
        # not silently train in fp32 (previously ignored — dstpu-lint CFG001)
        amp = g(C.AMP) or {}
        amp_on = (amp.get("enabled", False) if isinstance(amp, dict)
                  else bool(amp))    # "amp": true shorthand
        if amp_on:
            raise NotImplementedError(
                "amp (apex) is CUDA-specific and not supported on TPU — "
                "use bf16: {enabled: true} (native) or fp16 with dynamic "
                "loss scaling instead")
        self.prescale_gradients = g(C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = g(C.GRADIENT_PREDIVIDE_FACTOR,
                                           C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = g(C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT)
        self.wall_clock_breakdown = g(C.WALL_CLOCK_BREAKDOWN,
                                      C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.communication_data_type = g(C.COMMUNICATION_DATA_TYPE)
        self.disable_allgather = g(C.DISABLE_ALLGATHER, False)
        self.memory_breakdown = g(C.MEMORY_BREAKDOWN, False)

        self.fp16 = FP16Config(**g(C.FP16, {}))
        self.bf16 = BF16Config(**g(C.BF16, {}))
        self.zero_config = ZeroConfig(**g(C.ZERO_OPTIMIZATION, {}))
        self.optimizer = (OptimizerConfig(**pd[C.OPTIMIZER])
                          if C.OPTIMIZER in pd else None)
        self.scheduler = (SchedulerConfig(**pd[C.SCHEDULER])
                          if C.SCHEDULER in pd else None)
        self.mesh = MeshConfig(**g(C.MESH, {}))
        self.pipeline = PipelineConfig(**g(C.PIPELINE, {}))
        self.sequence_parallel = SequenceParallelConfig(**g(C.SEQUENCE_PARALLEL, {}))
        self.tensor_parallel = TensorParallelConfig(**g(C.TENSOR_PARALLEL, {}))
        self.activation_checkpointing = ActivationCheckpointingConfig(
            **g(C.ACTIVATION_CHECKPOINTING, {}))
        self.aio = AioConfig(**g(C.AIO, {}))
        self.flops_profiler = FlopsProfilerConfig(**g(C.FLOPS_PROFILER, {}))
        self.monitor = MonitorConfig(
            tensorboard=TensorBoardConfig(**g(C.MONITOR_TENSORBOARD, {})),
            wandb=WandbConfig(**g(C.MONITOR_WANDB, {})),
            csv_monitor=CSVConfig(**g(C.MONITOR_CSV, {})),
        )
        self.checkpoint_config = CheckpointConfig(**g(C.CHECKPOINT, {}))
        self.comms_config = CommsConfig(**g(C.COMMS_LOGGER, {}))
        self.resilience = ResilienceConfig(**g(C.RESILIENCE, {}))
        self.observability = ObservabilityConfig(**g(C.OBSERVABILITY, {}))
        self.training = TrainingConfig(**g(C.TRAINING, {}))

        # Late imports to avoid cycles; these blocks are parsed by their
        # subsystems on first use.
        self.elasticity_dict = g(C.ELASTICITY)
        self.autotuning_dict = g(C.AUTOTUNING)
        self.compression_dict = g(C.COMPRESSION_TRAINING)
        self.data_efficiency_dict = g(C.DATA_EFFICIENCY)
        self.curriculum_learning_legacy = g(C.CURRICULUM_LEARNING_LEGACY)

    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def precision_dtype(self) -> str:
        if self.bf16.enabled:
            return "bfloat16"
        if self.fp16.enabled:
            return "float16"
        return "float32"

    # -- batch reconciliation (reference config.py:921-980) ---------------
    def _configure_train_batch_size(self) -> None:
        if not hasattr(self, "_user_batch_triple"):
            self._user_batch_triple = (self.train_batch_size,
                                       self.train_micro_batch_size_per_gpu,
                                       self.gradient_accumulation_steps)
        tb, mb, gas = self._user_batch_triple
        ws = self._world_size  # data-parallel world size; may be None pre-mesh

        def _exact_div(num, den, what):
            if num % den != 0:
                raise ValueError(
                    f"train_batch_size ({num}) is not divisible by {what} "
                    f"({den}); the triple train_batch = micro_batch * "
                    f"gradient_accumulation_steps * dp_world must hold exactly")
            return num // den

        if ws is not None:
            if tb is not None and mb is not None and gas is not None:
                if tb != mb * gas * ws:
                    raise ValueError(
                        f"train_batch_size ({tb}) != micro_batch ({mb}) * "
                        f"gradient_accumulation_steps ({gas}) * dp_world ({ws})")
            elif tb is not None and mb is not None:
                gas = _exact_div(tb, mb * ws, "micro_batch * dp_world")
            elif tb is not None and gas is not None:
                mb = _exact_div(tb, gas * ws, "gradient_accumulation_steps * dp_world")
            elif mb is not None and gas is not None:
                tb = mb * gas * ws
            elif tb is not None:
                gas = 1
                mb = _exact_div(tb, ws, "dp_world")
            elif mb is not None:
                gas = 1
                tb = mb * ws
            else:
                raise ValueError(
                    "Need at least train_batch_size or "
                    "train_micro_batch_size_per_gpu in config")
        else:
            if gas is None:
                gas = 1
            if mb is None and tb is not None:
                mb = tb  # resolved later once mesh known
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas

    def resolve_batch_sizes(self, dp_world: int) -> None:
        """Re-run the triple reconciliation once the mesh is built."""
        self._world_size = dp_world
        self._configure_train_batch_size()
        self._do_sanity_check()

    def _do_sanity_check(self) -> None:
        if self.fp16.enabled and self.bf16.enabled:
            raise ValueError("fp16 and bf16 cannot both be enabled")
        for v, name in ((self.train_batch_size, C.TRAIN_BATCH_SIZE),
                        (self.train_micro_batch_size_per_gpu,
                         C.TRAIN_MICRO_BATCH_SIZE_PER_GPU),
                        (self.gradient_accumulation_steps,
                         C.GRADIENT_ACCUMULATION_STEPS)):
            if v is not None and v <= 0:
                raise ValueError(f"{name} must be positive, got {v}")
        z = self.zero_config
        if z.stage < 3 and z.offload_param is not None and \
                z.offload_param.device != OffloadDeviceEnum.none:
            raise ValueError("offload_param requires ZeRO stage 3")

    def print_config(self) -> str:
        return json.dumps(self._param_dict, indent=2, sort_keys=True, default=str)
