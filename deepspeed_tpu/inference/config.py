"""Inference config.

Mirrors the reference ``DeepSpeedInferenceConfig``
(`/root/reference/deepspeed/inference/config.py`, 276 LoC): dtype,
tensor_parallel, max_out_tokens, kernel injection and quantization
blocks — minus the CUDA-graph knob (jit + donated buffers give the same
replay-without-dispatch behavior for free) and plus TPU mesh controls.
"""
from __future__ import annotations

from typing import Any, Optional

from pydantic import Field, model_validator

from ..runtime import constants as C
from ..runtime.config import ObservabilityConfig
from ..runtime.config_utils import ConfigModel


class ServingMeshConfig(ConfigModel):
    """``serving.mesh`` block — the (data, model) submesh the mixed
    decode+prefill program shards over (docs/serving.md
    "Tensor-parallel serving").

    ``model`` splits attention heads, the paged KV pool (values AND the
    int8/int4 scale planes) and the MLP column/row-wise, so each chip
    holds ``kv_heads / model`` of every block — per-chip pool HBM drops
    by the same factor.  ``data`` partitions the decode slots, so
    ``data * model`` chips serve ``data`` x the concurrent slots.  Block
    ids, the allocator, prefix-cache digests and the scheduler stay
    replicated host-side and unchanged.  ``1 x 1`` (the default) keeps
    the single-device program byte-identical to the pre-TP path."""
    data: int = C.SERVING_MESH_DATA_DEFAULT
    model: int = C.SERVING_MESH_MODEL_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.data < 1:
            raise ValueError(
                f"serving.mesh.data must be >= 1, got {self.data}")
        if self.model < 1:
            raise ValueError(
                f"serving.mesh.model must be >= 1, got {self.model}")
        return self


class HostCacheConfig(ConfigModel):
    """``serving.host_cache`` block — the tiered host prefix cache
    (docs/serving.md "Tiered prefix cache").

    With ``enabled``, refcount-0 blocks the pool LRU evicts are
    DEMOTED instead of forgotten: encoded through the quantizer wire
    codec into a host DRAM slot store (first ``dram_budget_bytes``),
    overflowing to an NVMe-backed store (``nvme_budget_bytes`` at
    ``nvme_path``), keyed by the same chained content digest as the
    device radix index.  A prefix hit on a spilled chain claims pool
    blocks immediately and streams the payloads back during the
    admission/prefill window (at most ``promote_parallelism`` block
    scatters per engine step) — warm TTFT at host-copy cost instead of
    recompute cost."""
    enabled: bool = C.SERVING_HOST_CACHE_ENABLED_DEFAULT
    dram_budget_bytes: int = C.SERVING_HOST_CACHE_DRAM_BUDGET_BYTES_DEFAULT
    nvme_budget_bytes: int = C.SERVING_HOST_CACHE_NVME_BUDGET_BYTES_DEFAULT
    nvme_path: Optional[str] = C.SERVING_HOST_CACHE_NVME_PATH_DEFAULT
    promote_parallelism: int = \
        C.SERVING_HOST_CACHE_PROMOTE_PARALLELISM_DEFAULT
    wire_bits: int = C.SERVING_HOST_CACHE_WIRE_BITS_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.dram_budget_bytes < 0 or self.nvme_budget_bytes < 0:
            raise ValueError(
                "serving.host_cache budgets must be >= 0 (0 = tier off)")
        if self.enabled and not (self.dram_budget_bytes
                                 or self.nvme_budget_bytes):
            raise ValueError(
                "serving.host_cache.enabled needs dram_budget_bytes "
                "and/or nvme_budget_bytes > 0")
        if self.nvme_budget_bytes and not self.nvme_path:
            raise ValueError(
                "serving.host_cache.nvme_budget_bytes > 0 requires "
                "nvme_path (directory for the backing file)")
        if self.promote_parallelism < 1:
            raise ValueError(
                f"serving.host_cache.promote_parallelism must be >= 1, "
                f"got {self.promote_parallelism}")
        if self.wire_bits not in (0, 4, 8):
            raise ValueError(
                f"serving.host_cache.wire_bits must be one of 0 (raw "
                f"dtype bytes), 8 (int8) or 4 (packed int4), got "
                f"{self.wire_bits}")
        return self


class FleetConfig(ConfigModel):
    """``serving.fleet`` block — the resilient serving fleet
    (`inference/serving/fleet/`, docs/serving.md "Fleet serving &
    failover").

    With ``enabled``, ``replicas`` independent ``ServingEngine``s sit
    behind a ``FleetRouter`` that places each request on the replica
    whose radix/host-tier digests cover the longest prompt prefix,
    traded against queue depth.  A replica that raises ``ServingError``,
    hits an injected fatal, or (threaded) misses heartbeats past
    ``heartbeat_timeout_s`` is declared DEAD and every in-flight request
    is replayed on a healthy replica with its original fold_in key —
    the resumed stream is bit-identical and the router's high-water
    deduplicator delivers each token exactly once."""
    enabled: bool = C.SERVING_FLEET_ENABLED_DEFAULT
    replicas: int = C.SERVING_FLEET_REPLICAS_DEFAULT
    heartbeat_interval_s: float = \
        C.SERVING_FLEET_HEARTBEAT_INTERVAL_S_DEFAULT
    heartbeat_timeout_s: float = \
        C.SERVING_FLEET_HEARTBEAT_TIMEOUT_S_DEFAULT
    affinity_weight: float = C.SERVING_FLEET_AFFINITY_WEIGHT_DEFAULT
    max_failovers: int = C.SERVING_FLEET_MAX_FAILOVERS_DEFAULT
    retry_base_delay_s: float = C.SERVING_FLEET_RETRY_BASE_DELAY_S_DEFAULT
    retry_max_delay_s: float = C.SERVING_FLEET_RETRY_MAX_DELAY_S_DEFAULT
    #: disaggregated fleet: first K replicas prefill-only publishers,
    #: rest decode (0 = uniform); requires the host-tier KV fabric
    prefill_replicas: int = C.SERVING_FLEET_PREFILL_REPLICAS_DEFAULT
    #: affinity credit for fabric-resident vs device-resident prefix
    promote_discount: float = C.SERVING_FLEET_PROMOTE_DISCOUNT_DEFAULT
    # autoscaler policy knobs (fleet/autoscaler.py)
    chip_budget: int = C.SERVING_FLEET_CHIP_BUDGET_DEFAULT
    scale_up_cooldown_s: float = \
        C.SERVING_FLEET_SCALE_UP_COOLDOWN_S_DEFAULT
    scale_down_cooldown_s: float = \
        C.SERVING_FLEET_SCALE_DOWN_COOLDOWN_S_DEFAULT
    queue_high: float = C.SERVING_FLEET_QUEUE_HIGH_DEFAULT
    queue_low: float = C.SERVING_FLEET_QUEUE_LOW_DEFAULT
    quiet_s: float = C.SERVING_FLEET_QUIET_S_DEFAULT

    @model_validator(mode="after")
    def _validate(self):
        if self.replicas < 1:
            raise ValueError(
                f"serving.fleet.replicas must be >= 1, got "
                f"{self.replicas}")
        if self.heartbeat_interval_s <= 0:
            raise ValueError(
                f"serving.fleet.heartbeat_interval_s must be > 0, got "
                f"{self.heartbeat_interval_s}")
        if (self.heartbeat_timeout_s
                and self.heartbeat_timeout_s
                < 2 * self.heartbeat_interval_s):
            # same rule as the training watchdog: a timeout tighter than
            # two beats declares healthy replicas dead
            raise ValueError(
                f"serving.fleet.heartbeat_timeout_s must be 0 or >= 2x "
                f"heartbeat_interval_s, got {self.heartbeat_timeout_s}")
        if self.affinity_weight < 0:
            raise ValueError(
                f"serving.fleet.affinity_weight must be >= 0, got "
                f"{self.affinity_weight}")
        if self.max_failovers < 0:
            raise ValueError(
                f"serving.fleet.max_failovers must be >= 0, got "
                f"{self.max_failovers}")
        if self.retry_base_delay_s <= 0 \
                or self.retry_max_delay_s < self.retry_base_delay_s:
            raise ValueError(
                "serving.fleet retry delays must satisfy "
                "0 < retry_base_delay_s <= retry_max_delay_s")
        if not 0 <= self.prefill_replicas < self.replicas:
            # a disaggregated split must leave >= 1 decode replica —
            # a fleet of pure publishers can never stream a token
            raise ValueError(
                f"serving.fleet.prefill_replicas must be in "
                f"[0, replicas), got {self.prefill_replicas} of "
                f"{self.replicas}")
        if not 0.0 <= self.promote_discount <= 1.0:
            raise ValueError(
                f"serving.fleet.promote_discount must be in [0, 1], "
                f"got {self.promote_discount}")
        if self.chip_budget < 1:
            raise ValueError(
                f"serving.fleet.chip_budget must be >= 1, got "
                f"{self.chip_budget}")
        if self.scale_up_cooldown_s <= 0 or self.scale_down_cooldown_s <= 0:
            raise ValueError(
                "serving.fleet scale cooldowns must be > 0 — a zero "
                "cooldown lets an alert storm scale at tick rate")
        if self.queue_low > self.queue_high:
            raise ValueError(
                f"serving.fleet.queue_low ({self.queue_low}) must be <= "
                f"queue_high ({self.queue_high})")
        if self.quiet_s < 0:
            raise ValueError(
                f"serving.fleet.quiet_s must be >= 0, got {self.quiet_s}")
        return self


class ServingConfig(ConfigModel):
    """``serving`` block — continuous-batching inference
    (`inference/serving/`, docs/serving.md).

    The KV workspace becomes one shared pool of ``num_kv_blocks`` fixed
    ``kv_block_size``-token blocks (block 0 reserved as the null
    block), and the decode step becomes a single compiled program over
    ``max_batch_slots`` slots that requests join and leave between
    iterations.  Pool sizing rule of thumb: concurrent tokens =
    (num_kv_blocks - 1) * kv_block_size must cover the target batch's
    prompts + generations or the scheduler will (correctly) queue and
    preempt."""
    enabled: bool = C.SERVING_ENABLED_DEFAULT
    kv_block_size: int = C.SERVING_KV_BLOCK_SIZE_DEFAULT
    num_kv_blocks: int = C.SERVING_NUM_KV_BLOCKS_DEFAULT
    max_batch_slots: int = C.SERVING_MAX_BATCH_SLOTS_DEFAULT
    # chunked prefill: prompt tokens processed per iteration alongside
    # the live decode slots (also the mixed program's compiled chunk
    # width — bigger chunks prefill faster but add VMEM pressure and
    # lengthen the iterations they ride, raising inter-token latency)
    prefill_chunk_tokens: int = C.SERVING_PREFILL_CHUNK_TOKENS_DEFAULT
    # content-addressed prefix caching (RadixAttention-style): shared or
    # resubmitted prefixes reuse pool blocks instead of re-prefilling
    prefix_cache: bool = C.SERVING_PREFIX_CACHE_DEFAULT
    # quantized KV cache: 0 = engine dtype (byte-identical legacy path),
    # 8 = int8, 4 = packed int4 — per-row per-head scales stored
    # alongside, dequant fused into the paged attention kernels; the
    # same pool HBM budget holds ~2x / ~3.8x the tokens and decode
    # moves proportionally fewer bytes (docs/serving.md "Quantized KV
    # cache")
    kv_cache_bits: int = C.SERVING_KV_CACHE_BITS_DEFAULT
    # -- robustness / overload control (docs/serving.md "Failure
    # handling & overload") --
    # bounded backpressure: submit() beyond this many WAITING requests
    # returns the request terminal with status SHED instead of queueing
    # it (0 = unbounded)
    max_queue_depth: int = C.SERVING_MAX_QUEUE_DEPTH_DEFAULT
    # preemption-thrash guard: after this many preemptions a request is
    # pinned (never a victim again); if the pool then cannot grow at
    # all, the growing request fails loudly (0 = no cap)
    max_preemptions: int = C.SERVING_MAX_PREEMPTIONS_DEFAULT
    # no-progress watchdog: consecutive zero-progress iterations (while
    # work remains) before step() raises ServingError with scheduler
    # diagnostics (0 = disabled)
    no_progress_steps: int = C.SERVING_NO_PROGRESS_STEPS_DEFAULT
    # default request TTL in seconds, swept each step() for WAITING and
    # RUNNING requests (terminal status TIMED_OUT); 0 = none;
    # submit(deadline_s=...) overrides per request
    default_deadline_s: float = C.SERVING_DEFAULT_DEADLINE_S_DEFAULT
    # speculative decoding draft depth: tokens the draft model proposes
    # per slot per iteration when a draft model is armed
    # (serving_engine(draft_model=...)); ignored without a draft.  The
    # verified round emits 1..spec_k+1 tokens per iteration with EXACT
    # token equivalence to plain decode under the same key
    # (docs/serving.md "Speculative decoding")
    spec_k: int = C.SERVING_SPEC_K_DEFAULT
    # generation by diffusion over blocks (docs/serving.md; read only for
    # a model whose blocks are generated that way — block_length and the
    # mask token are the MODEL's): denoise forwards a block of all-mask
    # rows takes before its commit (1 .. block_length), which of the
    # rows that still hold the mask a forward fills, and the confidence
    # above which the dynamic rule fills a row early.  The published
    # sampler's defaults.
    denoising_steps: int = C.SERVING_DENOISING_STEPS_DEFAULT
    remasking_strategy: str = C.SERVING_REMASKING_STRATEGY_DEFAULT
    confidence_threshold: float = C.SERVING_CONFIDENCE_THRESHOLD_DEFAULT
    # (data, model) serving submesh — see ServingMeshConfig; shape
    # constraints the model config imposes (model | kv_heads,
    # data | max_batch_slots) are checked at ServingEngine build, where
    # the model is known
    mesh: ServingMeshConfig = Field(default_factory=ServingMeshConfig)
    # tiered host prefix cache: spill LRU-evicted blocks to host
    # DRAM/NVMe and promote on hit — see HostCacheConfig
    host_cache: HostCacheConfig = Field(default_factory=HostCacheConfig)
    # resilient replica fleet: router + health-checked replicas with
    # token-exact failover — see FleetConfig
    fleet: FleetConfig = Field(default_factory=FleetConfig)

    @model_validator(mode="after")
    def _validate(self):
        if self.kv_block_size < 1:
            raise ValueError(
                f"serving.kv_block_size must be >= 1, got "
                f"{self.kv_block_size}")
        if self.num_kv_blocks < 2:
            raise ValueError(
                f"serving.num_kv_blocks must be >= 2 (block 0 is the "
                f"reserved null block), got {self.num_kv_blocks}")
        if self.max_batch_slots < 1:
            raise ValueError(
                f"serving.max_batch_slots must be >= 1, got "
                f"{self.max_batch_slots}")
        if self.prefill_chunk_tokens < 1:
            raise ValueError(
                f"serving.prefill_chunk_tokens must be >= 1, got "
                f"{self.prefill_chunk_tokens}")
        if self.kv_cache_bits not in (0, 4, 8):
            raise ValueError(
                f"serving.kv_cache_bits must be one of 0 (engine "
                f"dtype), 8 (int8) or 4 (packed int4), got "
                f"{self.kv_cache_bits}")
        if self.max_queue_depth < 0:
            raise ValueError(
                f"serving.max_queue_depth must be >= 0 (0 = unbounded), "
                f"got {self.max_queue_depth}")
        if self.max_preemptions < 0:
            raise ValueError(
                f"serving.max_preemptions must be >= 0 (0 = no cap), "
                f"got {self.max_preemptions}")
        if self.no_progress_steps < 0:
            raise ValueError(
                f"serving.no_progress_steps must be >= 0 (0 = disabled), "
                f"got {self.no_progress_steps}")
        if self.spec_k < 1:
            raise ValueError(
                f"serving.spec_k must be >= 1 (only read when a draft "
                f"model is armed), got {self.spec_k}")
        if self.denoising_steps < 1:
            raise ValueError(
                f"serving.denoising_steps must be >= 1, got "
                f"{self.denoising_steps}")
        if self.remasking_strategy not in (
                "low_confidence_static", "low_confidence_dynamic",
                "sequential"):
            raise ValueError(
                f"serving.remasking_strategy must be low_confidence_static"
                f", low_confidence_dynamic or sequential, got "
                f"{self.remasking_strategy!r}")
        if not 0.0 < self.confidence_threshold <= 1.0:
            raise ValueError(
                f"serving.confidence_threshold must be in (0, 1], got "
                f"{self.confidence_threshold}")
        if self.default_deadline_s < 0:
            raise ValueError(
                f"serving.default_deadline_s must be >= 0 (0 = none), "
                f"got {self.default_deadline_s}")
        if self.max_batch_slots % self.mesh.data:
            raise ValueError(
                f"serving.mesh.data ({self.mesh.data}) must divide "
                f"serving.max_batch_slots ({self.max_batch_slots}) — "
                f"decode slots partition evenly over the data axis")
        return self


class TensorParallelConfig(ConfigModel):
    """`inference/config.py` DeepSpeedTPConfig (tp_size there)."""
    enabled: bool = True
    tp_size: int = 1


class QuantConfig(ConfigModel):
    """Weight quantization for serving (reference quant block: qkv/mlp int8).
    ``bits`` 0 disables. ``quantize_embeddings`` widens the scope to the
    embedding tables / lm_head — the reference GroupQuantizer
    (`module_inject/replace_module.py:150`) restricts itself to the
    attention/MLP projections, and int8 embeddings carry a
    disproportionate quality cost, so the default matches that scope."""
    enabled: bool = False
    bits: int = 8
    quantize_embeddings: bool = False


class DeepSpeedInferenceConfig(ConfigModel):
    dtype: str = "bfloat16"              # serving dtype for weights/compute
    tensor_parallel: TensorParallelConfig = Field(
        default_factory=TensorParallelConfig)
    quant: QuantConfig = Field(default_factory=QuantConfig)
    # continuous-batching serving layer (inference/serving/,
    # docs/serving.md): paged KV pool + iteration-level scheduler
    serving: ServingConfig = Field(default_factory=ServingConfig)
    # KV workspace sizing (reference inference_context.h: max_out_tokens
    # bounds the preallocated cache)
    max_out_tokens: int = 1024
    max_batch_size: int = 16
    # Serving shape policy: prompts are right-padded up to a multiple of
    # this bucket so varied prompt lengths reuse ONE compiled program per
    # bucket instead of recompiling per exact length (the true length is a
    # dynamic argument). 0 = exact shapes (compile per length).
    prompt_bucket: int = 64
    # kernel injection (reference replace_with_kernel_inject): use the
    # Pallas decode kernel on the token-at-a-time path
    replace_with_kernel_inject: bool = True
    # profiling device syncs (profile_model_time) run under this timeout
    # so a wedged device becomes a logged error, not a hang
    # (runtime/resilience run_with_timeout); <= 0 disables the guard
    profile_sync_timeout_s: float = 60.0
    # checkpoint to load params from (a deepspeed_tpu training checkpoint
    # dir, or None when the caller passes params directly)
    checkpoint: Optional[str] = None
    checkpoint_tag: Optional[str] = None
    # sampling defaults for generate()
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    # observability block (same schema as training's
    # DeepSpeedConfig.observability — runtime/config.py
    # ObservabilityConfig: tracing/metrics/request_tracing/slo/flight/
    # overlap).  None (the default) leaves the process-global telemetry
    # singletons EXACTLY as they are — a serving engine must be able to
    # join a process whose tracer/registry another engine (or the test
    # harness) already armed; an explicit block reconfigures them at
    # engine build, newest-engine-wins like the training path.
    observability: Optional[ObservabilityConfig] = None

    def compute_dtype(self):
        import jax.numpy as jnp
        return {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                "float32": jnp.float32, "bf16": jnp.bfloat16,
                "fp16": jnp.float16, "fp32": jnp.float32}[self.dtype]
