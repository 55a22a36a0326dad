"""Continuous-batching serving engine: device half of the subsystem.

Couples the host-side policy (``scheduler.py`` + ``block_allocator.py``)
to ONE step compiled in TWO shapes:

  * **mixed step**: every iteration it takes one decode token for each
    live slot AND up to ``prefill_chunk_tokens`` tokens of a single
    prompt chunk, scattering the chunk's KV into the slot's pool blocks
    and sampling a first token when the chunk completes a prefix
    (Sarathi-Serve-style chunked prefill).  Slot liveness and chunk
    placement travel as data (length vectors, block tables, scalars),
    so the program shape is independent of the prompt-length
    distribution — no per-padded-length prefill family, no retrace as
    requests join and leave.
  * **decode-only shape** of the same step: a dispatch whose plan has
    no chunk (``next_prefill_chunk`` answered ``None``) sends the chunk
    vector's head alone, so the program it runs has no chunk lane — no
    chunk rows through the matmuls, no chunk kernel call, no first-token
    sample.  The plan chooses, nothing else does.  Both shapes are built
    by the FIRST dispatch, whichever it needs (the other runs once with
    every slot inactive), so ``decode_builds`` reads 2 from then on and
    a server never compiles under traffic — the acceptance test pins
    the build counter.
  * **prefix caching** (RadixAttention-style): admission takes
    content-hash hits against the paged pool, so shared-prefix and
    preempted-then-resubmitted requests skip straight to their uncached
    tail; the allocator parks freed-but-registered blocks in an LRU
    until capacity pressure evicts them.
  * pools are donated back into each dispatch, so on TPU the serving
    loop re-dispatches one compiled program over the same HBM buffers —
    the iteration-level-scheduling analogue of the CUDA-graph replay
    the reference gets from `inference/engine.py:493`.
  * **one iteration in flight** (docs/serving.md "The dispatch in
    flight"): ``step()`` plans and enqueues iteration k+1 from the state
    *as dispatched* while k is on the device, and only then reads and
    applies k's result, so the host's whole share of an iteration runs
    under a device program.  The newest token of every slot stays on the
    device — the step takes the previous dispatch's result array as an
    operand and a per-slot source column says whether a row's input
    token is the host's or that array's.  Whatever cannot be planned
    from counts alone (a draft armed, a preemption, a promotion, a
    ``prefill_only`` hand-off) lands the dispatch in flight first: the
    synchronous order is the drained case of the same loop.

Observability (PR-3 layer): queue-depth / batch-occupancy / blocks-in-
use / cached-blocks gauges, TTFT + inter-token-latency histograms,
token + preemption + prefix-cache hit/evict counters — all under
``dstpu_serving_*`` (docs/serving.md lists them).

Robustness (docs/serving.md "Failure handling & overload"): terminal
request statuses (OK / CANCELLED / TIMED_OUT / FAILED / SHED) with
``cancel()`` + per-request deadlines swept each step; bounded submit
backpressure (``max_queue_depth``) and a preemption-thrash pin-or-fail
guard; per-slot finite-flag quarantine computed INSIDE the one compiled
program (a poisoned request fails alone, its KV never reaches the
prefix cache, the batch continues); a no-progress watchdog and
fault-injection sites (``serving.allocate`` / ``serving.append_block``
/ ``serving.admission`` / ``serving.dispatch``) that keep those failure
paths tested in CI.
"""
from __future__ import annotations

import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...observability import (get_flight_recorder, get_overlap_profiler,
                              get_registry, get_request_tracer, overlap,
                              trace_span)
from ...parallel import topology as topo
from ...parallel.shard_map_compat import shard_map
from ...runtime.resilience.errors import (FatalIOError, ServingError,
                                          TransientIOError)
from ...runtime.resilience.fault_injection import get_fault_injector
from ...runtime.resilience.heartbeat import Heartbeat
from ...runtime.resilience.retry import retry_call
from ...utils.logging import logger
from ..sampling import block_unmask, fold_in_keys, sample_tokens_per_row
from .block_allocator import PagedBlockAllocator, window_pool_blocks
from .host_cache import BlockCodec, HostTierCache
from .frontend.streaming import TokenEvent
from .scheduler import (ContinuousBatchingScheduler, Request,
                        RequestState, RequestStatus,
                        estimate_retry_after_s)


# ---------------------------------------------------------------------------
# what crosses between host and device in one dispatch
# ---------------------------------------------------------------------------
# Two int32 arrays in, one int32 array out (docs/serving.md "What a
# dispatch carries"), and that array back in as a device operand of the
# next dispatch, where a decoding row whose ``_TOKEN_SRC`` says so takes
# its input token from.  A transfer costs the same few hundred
# microseconds whether it carries 4 bytes or 4 KB, so the per-slot state
# is ONE ``[num_slots, _SLOT_COLS + max_pages]`` array, the chunk's ONE
# ``[_CHUNK_HEAD + chunk_tokens]`` vector (its ``_CHUNK_HEAD`` alone when
# no chunk rides: the decode-only shape) and the results ONE
# ``[num_slots, _R_SPEC (+ 2 + spec_k + 1)]`` array.  float32 and uint32
# lanes travel by their bits (``ndarray.view`` on the host,
# ``lax.bitcast_convert_type`` in the program): not one bit of a
# temperature, a ``top_p`` or a key changes on the way.
# (a key is two columns wide)
(_LENS, _DEC_TOKEN, _DEC_ACTIVE, _SPEC_ACTIVE, _TOP_K, _OUT_IDX, _KEY, _,
 _TEMP, _TOP_P, _TOKEN_SRC, _SLOT_COLS) = range(12)   # then the block table
#: ``_TOKEN_SRC``: the row's input token is the host's ``_DEC_TOKEN`` /
#: the slot's newest token in the previous dispatch's result array
SRC_HOST, SRC_DEVICE = 0, 1
(_C_SLOT, _C_START, _C_LEN, _C_TOP_K, _C_OUT_IDX, _C_KEY, _, _C_TEMP,
 _C_TOP_P, _CHUNK_HEAD) = range(10)               # then the chunk's ids
# ``_R_NEXT`` is the slot's NEWEST token: the one this dispatch sampled
# for a decoding row, the chunk's first token in the slot whose prompt it
# completed, else the one the slot came in with — so the next dispatch
# finds every slot's input token there, whichever lane produced it.  The
# chunk's two scalars ride in every row; with the draft armed the row
# goes on: n_emit, spec_finite, the spec_k + 1 target samples
(_R_NEXT, _R_DEC_FINITE, _R_FIRST, _R_CHUNK_FINITE, _R_SPEC) = range(5)
# Generation by diffusion over blocks (docs/serving.md; ``block_rows`` > 0,
# which refuses the draft): a slot rides a dispatch with its whole block of
# ``block_rows`` tokens.  ``_DEC_ACTIVE`` then names the forward's PHASE,
# the draft's column carries the rows a denoise forward may fill, the
# host's copy of a block (one just opened) rides as the LAST ``block_rows``
# columns of the per-slot operand, after the tables, and the result array
# carries every slot's block as the forward left it from ``_R_SPEC`` on —
# where the next dispatch finds it (``_TOKEN_SRC``), as it finds
# ``_R_NEXT`` for a one-token model.
PHASE_DENOISE, PHASE_COMMIT = 1, 2
_BLOCK_FILL = _SPEC_ACTIVE


def _bits(x: jax.Array, dtype) -> jax.Array:
    return lax.bitcast_convert_type(x, dtype)


class _SlotState(NamedTuple):
    """The per-slot operand array, sliced apart inside the program."""
    tables: jax.Array
    lens: jax.Array
    dec_tokens: jax.Array
    dec_active: jax.Array
    spec_active: jax.Array
    temp: jax.Array
    top_k: jax.Array
    top_p: jax.Array
    keys: jax.Array
    out_idx: jax.Array
    token_src: jax.Array

    @classmethod
    def unpack(cls, slots: jax.Array) -> "_SlotState":
        return cls(
            tables=slots[:, _SLOT_COLS:], lens=slots[:, _LENS],
            dec_tokens=slots[:, _DEC_TOKEN],
            dec_active=slots[:, _DEC_ACTIVE],
            spec_active=slots[:, _SPEC_ACTIVE],
            temp=_bits(slots[:, _TEMP], jnp.float32),
            top_k=slots[:, _TOP_K],
            top_p=_bits(slots[:, _TOP_P], jnp.float32),
            keys=_bits(slots[:, _KEY:_KEY + 2], jnp.uint32),
            out_idx=slots[:, _OUT_IDX],
            token_src=slots[:, _TOKEN_SRC])


class _ChunkState(NamedTuple):
    """The chunk operand vector, sliced apart inside the program."""
    ids: jax.Array
    slot: jax.Array
    start: jax.Array
    len: jax.Array
    temp: jax.Array
    top_k: jax.Array
    top_p: jax.Array
    key: jax.Array
    out_idx: jax.Array

    @classmethod
    def unpack(cls, chunk: jax.Array) -> "_ChunkState":
        return cls(
            ids=chunk[_CHUNK_HEAD:], slot=chunk[_C_SLOT],
            start=chunk[_C_START], len=chunk[_C_LEN],
            temp=_bits(chunk[_C_TEMP], jnp.float32),
            top_k=chunk[_C_TOP_K],
            top_p=_bits(chunk[_C_TOP_P], jnp.float32),
            key=_bits(chunk[_C_KEY:_C_KEY + 2], jnp.uint32),
            out_idx=chunk[_C_OUT_IDX])


class _Flight(NamedTuple):
    """One dispatch between its enqueue and the read of its result."""
    result: jax.Array
    dec: List[Tuple[int, Request]]
    chunk: Optional[Tuple[int, Request, int, int]]
    spec: List[Tuple[int, Request]]
    #: its chunk completes a prompt: the chunk's row samples a token
    ends_prefill: bool
    #: enqueued before its predecessor's result was read
    ahead: bool
    t0: float
    #: ``count_dispatch``'s share known at enqueue (profiler on)
    counts: Optional[Dict[str, int]]
    #: the block lane's plan, a slot of ``dec``
    block: Optional[Dict[int, "_BlockPlan"]] = None


class _BlockPlan(NamedTuple):
    """One slot's forward in the block lane (generation by diffusion over
    blocks), as planned."""
    phase: int                  # PHASE_DENOISE | PHASE_COMMIT
    fill: int                   # rows a denoise forward may fill
    #: the block's tokens if this forward opens it (the host's to send),
    #: else None: they are on the device
    opened: Optional[List[int]]
    start: int                  # the block's first position
    new_tokens: int             # tokens its commit makes visible
    #: rows still masked once this forward has landed, by counts (-1:
    #: the block is closed)
    masked_after: int
    #: denoise forwards the block had taken before this one
    denoised: int


def _pack_results(nxt, dec_finite, first, chunk_finite, *spec_cols,
                  samples=None, counters=None) -> jax.Array:
    """Everything the host reads after a dispatch as one int32 array,
    a row a slot (the ``_R_*`` columns).  ``counters`` — what the model's
    program counted this dispatch (``TransformerLM.PAGED_COUNTERS``) —
    ride as the LAST columns, the same in every row."""
    rows = nxt.shape[0]
    cols = [nxt, dec_finite, jnp.broadcast_to(first, (rows,)),
            jnp.broadcast_to(chunk_finite, (rows,)), *spec_cols]
    out = jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)
    if samples is not None:
        out = jnp.concatenate([out, samples.astype(jnp.int32)], axis=1)
    if counters is not None:
        out = jnp.concatenate([out, jnp.broadcast_to(
            counters.astype(jnp.int32)[None], (rows, counters.shape[0]))],
            axis=1)
    return out


def _tp_qkv_perm(nh: int, nkv: int, hd: int, mp: int) -> np.ndarray:
    """Column permutation carrying the fused global qkv layout
    ``[q(nh*hd) | k(nkv*hd) | v(nkv*hd)]`` into ``mp`` contiguous
    per-shard fused layouts ``[q_s | k_s | v_s]``.

    A plain tile of the fused axis over ``model`` would hand shard 0
    the first ``qkv_dim/mp`` columns — mostly q heads, no k/v — so the
    qkv kernel (and bias, and its per-channel quant scales) is permuted
    ONCE at engine prep; after the shuffle each shard's contiguous
    chunk is exactly its own heads in the fused order the model's
    reshape-split expects.  Applied host-side to the last axis of
    ``qkv.kernel`` / ``qkv.bias`` (and the kernel's channel scales)."""
    nhl, nkvl = nh // mp, nkv // mp
    cols = []
    for s in range(mp):
        cols.append(np.arange(s * nhl * hd, (s + 1) * nhl * hd))
        cols.append(nh * hd + np.arange(s * nkvl * hd, (s + 1) * nkvl * hd))
        cols.append((nh + nkv) * hd
                    + np.arange(s * nkvl * hd, (s + 1) * nkvl * hd))
    return np.concatenate(cols)


class ServingEngine:
    """Continuous-batching front end over an ``InferenceEngine``.

    Usage::

        eng = deepspeed_tpu.init_inference(model, config={
            "serving": {"enabled": True, "kv_block_size": 16,
                        "num_kv_blocks": 512, "max_batch_slots": 8,
                        "prefill_chunk_tokens": 256}})
        srv = eng.serving_engine()
        reqs = [srv.submit(p, max_new_tokens=64) for p in prompts]
        srv.run()                      # drain
        streams = [r.output for r in reqs]

    Sampling is PER REQUEST and IN PROGRAM: ``submit()`` takes
    ``temperature``/``top_k``/``top_p``/``seed`` (defaulting to the
    inference config), and every slot's params + PRNG key ride the ONE
    compiled mixed step as data — any mix of sampling configs shares
    its two shapes (``decode_builds == 2``).  Output token j of a request
    is always drawn with ``fold_in(request_key, j)``, so a stream is
    reproducible across batch composition, admission order, preemption,
    and mesh shape, and token-identical to ``generate()`` under the
    same key (temperature 0 is bit-exact greedy).  ``submit(on_token=
    ...)`` streams tokens at iteration boundaries (see
    ``frontend/streaming.py``); a draft model passed at construction
    arms the speculative third lane (docs/serving.md "Speculative
    decoding") with exact token equivalence to the non-speculative
    sampler.
    """

    def __init__(self, engine, rng: Optional[jax.Array] = None,
                 draft_model=None, draft_params=None,
                 shared_host_cache: Optional[HostTierCache] = None,
                 role: str = "mixed"):
        cfg = engine.config.serving
        model = engine.module
        # disaggregated fleet replica class (docs/serving.md
        # "Disaggregated fleet & autoscaling"): a "prefill" engine runs
        # chunked prefill only and publishes finished chains to the KV
        # fabric; "decode"/"mixed" engines serve full requests ("decode"
        # is a routing preference, not an engine-side restriction, so a
        # degraded fleet can still fall back to any replica)
        if role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"serving role must be 'mixed', 'prefill' or 'decode', "
                f"got {role!r}")
        if role == "prefill" and not cfg.host_cache.enabled:
            raise ValueError(
                "role='prefill' requires serving.host_cache.enabled — "
                "the host tier IS the KV fabric prefill workers publish "
                "finished chains into")
        self.role = role
        #: fabric identity for published entries (orphan reaping is
        #: publisher-scoped); the fleet router overwrites this with the
        #: replica id at construction
        self.publisher_id = f"engine-{id(self):x}"
        reason = model._paged_supported()
        if reason is not None:
            raise NotImplementedError(
                f"continuous-batching serving cannot run this model: "
                f"{reason}")
        reason = model.paged_refusal(
            kv_bits=cfg.kv_cache_bits, spec=draft_model is not None,
            mesh_model=cfg.mesh.model, mesh_data=cfg.mesh.data,
            host_cache=cfg.host_cache.enabled,
            weight_quant=getattr(engine, "_quantized", False))
        if reason is not None:
            raise NotImplementedError(
                f"continuous-batching serving cannot be built this way "
                f"for this model: {reason}")
        self.engine = engine
        self.model = model
        self.block_size = cfg.kv_block_size
        self.num_slots = cfg.max_batch_slots
        self.chunk_tokens = cfg.prefill_chunk_tokens
        self.max_pages = max(
            1, -(-engine.config.max_out_tokens // self.block_size))
        #: rows a slot rides a dispatch with where generation is by
        #: diffusion over blocks (docs/serving.md); 0: one token a step
        self.block_rows = model.block_rows
        if self.block_rows:
            self._init_block_lane(cfg, engine.config.max_out_tokens)
        # a block whose per-sequence state cannot be found again at a
        # block boundary serves with the prefix cache off, and says why
        no_hits = model.prefix_cache_refusal()
        self.prefix_cache = cfg.prefix_cache and no_hits is None
        if cfg.prefix_cache and no_hits is not None:
            logger.info(f"serving: prefix cache off: {no_hits}")
        self.allocator = PagedBlockAllocator(
            cfg.num_kv_blocks, self.block_size,
            enable_prefix_cache=self.prefix_cache)
        #: the block tables a slot has (the allocator's layer kinds that
        #: are pages), side by side in the per-slot operand
        self.table_kinds = model.TABLE_KINDS
        self.window_blocks = 0
        if "window" in self.table_kinds:
            # every slot's bound at once, in the groups the kind hands
            # out: all but one decoding, one with a chunk in flight (it is
            # trimmed as soon as the chunk is enqueued), the null block
            self.window_blocks = window_pool_blocks(
                self.num_slots, *model.window_pages(self.block_size,
                                                    self.chunk_tokens))
            self.allocator.add_window_kind(self.window_blocks,
                                           model.config.sliding_window)
        # a prefill worker publishes to the fabric but never claims from
        # it: claiming would steal the very entries the decode class is
        # about to promote
        self.allocator.allow_claims = role != "prefill"
        self.scheduler = ContinuousBatchingScheduler(
            self.num_slots, self.allocator, self.max_pages,
            max_queue_depth=cfg.max_queue_depth,
            max_preemptions=cfg.max_preemptions)
        # SHED terminals advertise a drain-rate-derived Retry-After
        # (docs/serving.md "Fleet serving & failover")
        self.scheduler.retry_after_hint = self._estimate_retry_after
        self.scheduler.step_rows = max(1, self.block_rows)
        self.no_progress_steps = cfg.no_progress_steps
        self.default_deadline_s = cfg.default_deadline_s
        #: KV-cache width: 0 = engine dtype, 8 = int8, 4 = packed int4
        #: (``serving.kv_cache_bits``, docs/serving.md "Quantized KV
        #: cache")
        self.kv_bits = cfg.kv_cache_bits
        #: consecutive zero-progress iterations (the serving watchdog)
        self._no_progress = 0
        # request-trace recorder + flight recorder (observability/):
        # process-global singletons; every hot-path site below guards on
        # ``.enabled`` so the disabled default is one attribute check
        self._rt = get_request_tracer()
        self._fr = get_flight_recorder()
        # host/device overlap profiler (observability/overlap.py): the
        # iteration bracket, the phase marks and the dispatch counters
        # below all guard on ``.enabled`` — disabled is one attribute
        # check
        self._ovl = get_overlap_profiler()
        # -- (data, model) serving submesh (docs/serving.md
        # "Tensor-parallel serving"): model shards heads + KV pool +
        # MLP, data shards the decode slots.  The step ALWAYS runs under
        # shard_map over this submesh, 1x1 included: the paged kernel
        # is a Mosaic call XLA cannot partition, and the engine's own
        # mesh spans every device of the host ----------------------------
        self.tp_data_size = cfg.mesh.data
        self.tp_model_size = cfg.mesh.model
        self._init_tp_mesh()
        # every device buffer the step carries beside the weights: the
        # pools, their scale planes, the model's own per-slot tree
        with self._ovl.setup_span("setup/pools"):
            with trace_span("serving/kv_quantize", bits=self.kv_bits,
                            blocks=cfg.num_kv_blocks):
                pools = model.init_paged_cache(cfg.num_kv_blocks,
                                               self.block_size,
                                               dtype=engine.dtype,
                                               kv_bits=self.kv_bits)
            # the pools live in the sharding the step returns them in —
            # sharding is part of the jit cache key, so anything else would
            # retrace the program on the second dispatch.  They shard on
            # the kv-head lanes over `model` (scale planes on their kv-head
            # axis) and REPLICATE over `data`: each chip holds
            # kv_heads/model of every block (kv_pool_bytes)
            self._pool_sh = NamedSharding(self.tp_mesh, self._pool_spec)
            self._pscale_sh = NamedSharding(self.tp_mesh, self._pscale_spec)
            # a pool of ONE buffer (a latent pool: key and value are one
            # row) has no "v": the operand is None, like the scale planes of
            # an unquantized pool
            self._pool_k = jax.device_put(pools["k"], self._pool_sh)
            self._pool_v = (None if pools["v"] is None else
                            jax.device_put(pools["v"], self._pool_sh))
            self._pool_ks = self._pool_vs = None
            if self.kv_bits:
                self._pool_ks = jax.device_put(pools["k_scale"],
                                               self._pscale_sh)
                self._pool_vs = jax.device_put(pools["v_scale"],
                                               self._pscale_sh)
            # what a slot keeps besides the pool's pages (a window kind's
            # pool, per-slot recurrent state): the model's own tree, carried
            # by the step like the pools; None for a block that has none
            # (made where it will live: at gigabytes of state a copy on the
            # way in would not fit beside the weights)
            make_extra = jax.jit(
                lambda: model.init_paged_extra(
                    self.num_slots, self.block_size, self.window_blocks,
                    dtype=engine.dtype),
                out_shardings=NamedSharding(self.tp_mesh, P()))
            self._pool_x = make_extra()
            if self._pool_x is not None and model.SLOT_STATE:
                self.allocator.add_state_kind(self.num_slots)
        self._prep_tp_params()
        logger.info(
            f"serving: paged KV pool {cfg.num_kv_blocks} x "
            f"{self.block_size}-token blocks "
            f"({self.kv_pool_bytes / 2**20:.1f} MiB"
            f"{f', int{self.kv_bits} + f32 scales' if self.kv_bits else ''}"
            f"), {self.num_slots} decode "
            f"slots, {self.max_pages} pages/seq, prefill chunk "
            f"{self.chunk_tokens} tokens, prefix cache "
            f"{'on' if self.prefix_cache else 'off'}")

        # donation keeps the pools in-place on TPU; the CPU backend
        # does not implement donation and would warn every dispatch
        self._donate = jax.default_backend() != "cpu"

        # -- tiered host prefix cache (docs/serving.md "Tiered prefix
        # cache"): LRU-evicted registered blocks demote into host
        # DRAM/NVMe through the wire codec; hits on spilled chains
        # promote back during the admission/prefill window ---------------
        self.host_cache: Optional[HostTierCache] = None
        self._hc_codec: Optional[BlockCodec] = None
        self._gather_block = self._scatter_block = None
        self._promote_k = cfg.host_cache.promote_parallelism
        #: plain-int mirrors of the host-tier counters, read by
        #: tests/unit/test_host_cache.py without the registry
        self.host_counts = {"promoted_blocks": 0, "promote_failures": 0,
                            "spill_failures": 0}
        #: KV-fabric mirrors (disaggregated fleet): chain blocks this
        #: engine published, publishes degraded to decode-side
        #: recompute, and prefill-only requests completed; read by
        #: fleet/replica.py's snapshot and tests/unit/test_disagg_fleet.py
        self.fabric_counts = {"published_blocks": 0,
                              "publish_failures": 0,
                              "prefill_only_completed": 0}
        if cfg.host_cache.enabled:
            if not self.prefix_cache:
                raise ValueError(
                    "serving.host_cache.enabled requires "
                    "serving.prefix_cache — the host tier is keyed by "
                    "the radix index's content digests")
            # ``shared_host_cache`` is the fleet's cross-replica warm
            # tier: the store is content-addressed and device-agnostic,
            # so replicas sharing one instance hit prefixes their
            # siblings spilled — and a joining replica starts warm
            # (docs/serving.md "Fleet serving & failover")
            self._init_host_cache(cfg.host_cache,
                                  shared=shared_host_cache)

        self.temperature = engine.config.temperature
        self.top_k = engine.config.top_k
        self.top_p = engine.config.top_p
        #: raw uint32 base key: a submit() without an explicit seed
        #: samples with this key — the same default ``generate()``
        #: uses, so unseeded serving matches unseeded generate
        base = rng if rng is not None else jax.random.PRNGKey(0)
        self._base_key = tuple(int(x) for x in np.asarray(base))

        #: draft-model speculative decoding (Leviathan et al., ICML
        #: '23): ``serving.spec_k`` proposals per slot per iteration,
        #: verified by the target in the mixed step's third lane
        self.spec_k = cfg.spec_k
        self._draft_model = draft_model
        self._draft_params = draft_params
        self._tp_draft = None
        self._dpool_k = self._dpool_v = None
        if draft_model is not None:
            self._init_draft(draft_model, draft_params)
        #: rows the target runs in a decode-only dispatch, whatever
        #: rides: a decode row per slot and, with a draft, its
        #: spec_k + 1 verify rows; the mixed shape adds the chunk lane
        self._decode_rows_per_dispatch = self.num_slots * (
            self.block_rows
            or 1 + (self.spec_k + 1 if draft_model is not None else 0))
        # -- the dispatch in flight (docs/serving.md) ---------------------
        #: the iteration on the device: its dispatches, oldest first,
        #: enqueued and not yet read (empty between a drain and the next
        #: ``step()``)
        self._flight: List[_Flight] = []
        #: the last dispatch's result array, the next one's operand: all
        #: zeros before the first (no row reads it then), in the
        #: sharding the step returns it in — sharding is part of the jit
        #: cache key
        self._prev_result = jax.device_put(
            np.zeros((self.num_slots, _R_SPEC + len(model.PAGED_COUNTERS)
                      + self.block_rows
                      + (2 + self.spec_k + 1 if draft_model is not None
                         else 0)), np.int32),
            NamedSharding(self.tp_mesh, P(topo.DATA_AXIS, None)))
        #: when the last result reached the host (the ITL histogram's
        #: clock: the gap between two successive results)
        self._last_result_t = 0.0
        #: plain-int mirror of the overlap profiler's two counters
        self.flight_counts = {"dispatches": 0, "ahead_dispatches": 0,
                              "void_rows": 0}

        #: incremented at TRACE time inside the step — the "the serving
        #: loop compiles the step's two shapes with its first dispatch
        #: and nothing after, whatever the prompt-length distribution"
        #: acceptance pin
        self.decode_builds = 0
        self._step_fn = None
        # -- streaming (frontend/streaming.py): token/terminal events
        # buffer inside an iteration and flush at its boundary; engine-
        # level hooks are the frontend's fairness + metrics taps -------
        self.token_hooks: List[Callable] = []
        self.lifecycle_hooks: List[Callable] = []
        self._event_buf: List[TokenEvent] = []

        #: liveness beat stamped at every iteration boundary so a
        #: serving process under the elastic agent (or a fleet replica
        #: thread) never looks hung while it is making progress.
        #: Defaults to the agent's ``DSTPU_HEARTBEAT_FILE`` env
        #: contract — a no-op outside an agent; the fleet's
        #: ``ReplicaHandle`` swaps in a per-replica file.
        self.heartbeat = Heartbeat(
            interval_s=cfg.fleet.heartbeat_interval_s)
        # drain-rate EMA feeding the SHED retry_after_s hint: seconds
        # per finished request, updated at each iteration boundary
        self._drain_rate_ema: Optional[float] = None
        self._last_finish_t: Optional[float] = None

        reg = get_registry()
        self._m_queue = reg.gauge(
            "dstpu_serving_queue_depth", "requests waiting for a decode slot")
        self._m_active = reg.gauge(
            "dstpu_serving_active_slots",
            "decode-slot occupancy (continuous batch size)")
        self._m_blocks = reg.gauge(
            "dstpu_serving_kv_blocks_in_use", "paged KV pool blocks held")
        self._m_cached = reg.gauge(
            "dstpu_serving_cached_kv_blocks",
            "refcount-0 pool blocks parked in the prefix-cache LRU")
        # static pool-footprint gauges (set once: the pool is
        # preallocated) — the compressed pool must be VISIBLE, not
        # inferred from config
        reg.gauge(
            "dstpu_serving_kv_pool_bytes",
            "device HBM held by the paged KV pool (values + dequant "
            "scales)").set(self.kv_pool_bytes)
        reg.gauge(
            "dstpu_serving_kv_bits",
            "KV-cache width: 0 = engine dtype, 8 = int8, 4 = packed "
            "int4").set(self.kv_bits)
        # serving-mesh shape gauges: per-chip numbers above (pool bytes)
        # only read honestly next to the mesh they were measured on
        reg.gauge(
            "dstpu_mesh_data_size",
            "serving mesh data-axis size (decode-slot sharding)"
            ).set(self.tp_data_size)
        reg.gauge(
            "dstpu_mesh_model_size",
            "serving mesh model-axis size (tensor parallelism)"
            ).set(self.tp_model_size)
        # per-token per-layer model-axis psum payload (bytes): one psum
        # on attention+MLP outputs for parallel-residual blocks, two for
        # serial/post-norm (docs/serving.md "Tensor-parallel serving")
        mc = model.config
        npsums = 1 if mc.parallel_residual else 2
        self.tp_psum_bytes_per_token_layer = (
            0 if self.tp_model_size == 1
            else mc.d_model * jnp.dtype(mc.dtype).itemsize * npsums)
        self._m_ttft = reg.histogram(
            "dstpu_serving_ttft_seconds",
            "submit -> first token (includes queueing + chunked prefill)")
        self._m_itl = reg.histogram(
            "dstpu_serving_inter_token_seconds",
            "time between two successive dispatch results reaching the "
            "host (per-token latency of every active stream)")
        #: extra histograms that mirror every TTFT/ITL observation —
        #: fleet replica handles register their per-replica ground-truth
        #: series here (observability/fleet_metrics.py merges them
        #: bucket-wise into the fleet view)
        self.mirror_hists: Dict[str, List[Any]] = {}
        self._m_tokens = reg.counter(
            "dstpu_serving_tokens_total", "tokens generated by serving")
        self._m_preempt = reg.counter(
            "dstpu_serving_preemptions_total",
            "sequences evicted on KV-pool pressure (tail recompute on "
            "re-admission)")
        self._m_hit_tokens = reg.counter(
            "dstpu_serving_prefix_cache_hit_tokens_total",
            "prompt tokens served from cached KV blocks (prefill skipped)")
        self._m_prefill_tokens = reg.counter(
            "dstpu_serving_prefill_tokens_total",
            "prompt tokens actually computed by chunked prefill "
            "(the prefix-cache miss side)")
        self._m_evictions = reg.counter(
            "dstpu_serving_prefix_cache_evictions_total",
            "cached blocks evicted from the LRU under capacity pressure")
        # lifecycle terminals (docs/serving.md "Failure handling &
        # overload"): every non-OK terminal increments exactly one of
        # cancelled/timed_out/shed/failed; quarantines additionally
        # increment the quarantined counter (they are FAILED requests
        # whose KV was discarded)
        self._m_cancelled = reg.counter(
            "dstpu_serving_cancelled_total", "requests cancelled by caller")
        self._m_timed_out = reg.counter(
            "dstpu_serving_timed_out_total",
            "requests expired by the per-request deadline sweep")
        self._m_shed = reg.counter(
            "dstpu_serving_shed_total",
            "requests rejected at submit by max_queue_depth backpressure")
        self._m_failed = reg.counter(
            "dstpu_serving_failed_total",
            "requests failed (quarantine, thrash pin-or-fail, fatal fault)")
        self._m_quarantined = reg.counter(
            "dstpu_serving_quarantined_total",
            "requests quarantined on non-finite logits (KV discarded, "
            "batch unaffected)")
        #: plain-int mirror of the lifecycle counters, read by
        #: fleet/replica.py's snapshot and the serving tests
        self.lifecycle_counts = {"cancelled": 0, "timed_out": 0,
                                 "shed": 0, "failed": 0, "quarantined": 0}
        # speculative-decoding acceptance (docs/serving.md "Speculative
        # decoding"): rate = accepted / proposed
        self._m_spec_proposed = reg.counter(
            "dstpu_serving_spec_proposed_tokens_total",
            "draft tokens proposed to the speculative verify lane")
        self._m_spec_accepted = reg.counter(
            "dstpu_serving_spec_accepted_tokens_total",
            "draft tokens accepted by the target's verify step")
        reg.gauge(
            "dstpu_serving_spec_k",
            "draft proposals per slot per iteration (0 = speculative "
            "decoding off)").set(self.spec_k if draft_model is not None
                                 else 0)
        #: plain-int mirror read by tests/unit/test_frontend.py and
        #: test_serving_chaos.py (acceptance rate = accepted / proposed)
        self.spec_counts = {"proposed": 0, "accepted": 0}
        # generation by diffusion over blocks (docs/serving.md): slot
        # forwards of either phase, rows of live slots dispatched, tokens
        # made visible by commits, and denoise forwards a committed block
        # took (what the dynamic rule saves)
        self._m_block_denoise = reg.counter(
            "dstpu_serving_block_forwards_denoise_total",
            "block-lane slot forwards that filled masked rows (their k/v "
            "are overwritten by the next forward)")
        self._m_block_commit = reg.counter(
            "dstpu_serving_block_forwards_commit_total",
            "block-lane slot forwards over a block's final tokens (their "
            "k/v are kept; the block becomes visible)")
        self._m_block_rows = reg.counter(
            "dstpu_serving_block_rows_total",
            "rows of live slots dispatched in the block lane "
            "(block_length a slot a forward)")
        self._m_block_tokens = reg.counter(
            "dstpu_serving_block_tokens_total",
            "tokens made visible by block commits")
        self._m_block_steps = reg.histogram(
            "dstpu_serving_block_denoise_forwards",
            "denoise forwards a committed block took",
            buckets=[1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64])
        #: plain-int mirror of the block lane's counters
        self.block_counts = {"denoise": 0, "commit": 0, "rows": 0,
                             "tokens": 0}
        # tiered host cache metrics (docs/serving.md "Tiered prefix
        # cache"): per-tier hit/spill/evict counters, resident-bytes and
        # promote-queue-depth gauges
        self._m_host_spills = reg.counter(
            "dstpu_serving_host_spills_total",
            "evicted KV blocks demoted into the host tier (vs forgotten)")
        self._m_host_dram_hits = reg.counter(
            "dstpu_serving_host_dram_hits_total",
            "prefix-hit blocks claimed out of the host DRAM tier")
        self._m_host_nvme_hits = reg.counter(
            "dstpu_serving_host_nvme_hits_total",
            "prefix-hit blocks claimed out of the host NVMe tier")
        self._m_host_demotions = reg.counter(
            "dstpu_serving_host_demotions_total",
            "entries pushed DRAM -> NVMe under host-tier pressure")
        self._m_host_evictions = reg.counter(
            "dstpu_serving_host_evictions_total",
            "entries aged out of the host tier entirely")
        self._m_host_hit_tokens = reg.counter(
            "dstpu_serving_host_hit_tokens_total",
            "prompt tokens served by host-tier promotion instead of "
            "recompute")
        self._m_promoted = reg.counter(
            "dstpu_serving_promoted_blocks_total",
            "host-tier payloads landed back into the device pool")
        self._m_promote_failures = reg.counter(
            "dstpu_serving_promote_failures_total",
            "promotions dropped to recompute (fatal fault / bad payload)")
        self._m_spill_failures = reg.counter(
            "dstpu_serving_spill_failures_total",
            "spills degraded to plain eviction (host store fault)")
        # KV-fabric metrics (docs/serving.md "Disaggregated fleet &
        # autoscaling"): prefill-side publishes and their degradations
        self._m_fabric_published = reg.counter(
            "dstpu_serving_fabric_published_total",
            "finished-chain blocks published into the KV fabric")
        self._m_fabric_publish_failures = reg.counter(
            "dstpu_serving_fabric_publish_failures_total",
            "fabric publishes degraded to decode-side recompute")
        self._m_host_dram_bytes = reg.gauge(
            "dstpu_serving_host_dram_bytes",
            "encoded KV bytes resident in the host DRAM tier")
        self._m_host_nvme_bytes = reg.gauge(
            "dstpu_serving_host_nvme_bytes",
            "encoded KV bytes resident in the host NVMe tier")
        self._m_promote_depth = reg.gauge(
            "dstpu_serving_promote_queue_depth",
            "claimed host payloads waiting to land in the pool")
        # counter deltas are polled off the (jax-free) allocator's
        # cumulative ints
        self._hits_polled = 0
        self._evictions_polled = 0
        self._host_polled = {"spills": 0, "dram_hits": 0, "nvme_hits": 0,
                             "demotions": 0, "evictions": 0,
                             "hit_tokens": 0}

    # ------------------------------------------------------------------
    # generation by diffusion over blocks (docs/serving.md)
    # ------------------------------------------------------------------
    def _init_block_lane(self, cfg, max_out_tokens: int) -> None:
        """The block lane's settings, each checked against the model's
        block: ``serving.denoising_steps`` (1 .. block_length), the rule
        and its threshold; whole blocks in a page, a chunk and the served
        positions."""
        model, rows = self.model, self.block_rows
        reason = model.serving_refusal(self.block_size, self.chunk_tokens)
        if reason is None and max_out_tokens % rows:
            reason = (f"max_out_tokens {max_out_tokens} is not a multiple "
                      f"of block_length {rows}: a sequence's last block "
                      f"must lie inside the served positions")
        if reason is None and not 1 <= cfg.denoising_steps <= rows:
            reason = (f"serving.denoising_steps {cfg.denoising_steps} is "
                      f"not in 1 .. block_length {rows}: every denoise "
                      f"forward fills at least one row")
        if reason is not None:
            raise NotImplementedError(
                f"continuous-batching serving cannot be built this way "
                f"for this model: {reason}")
        self.denoising_steps = cfg.denoising_steps
        self.unmask_rule = cfg.remasking_strategy
        self.confidence_threshold = cfg.confidence_threshold
        #: rows a denoise forward fills, a step (the published
        #: ``get_num_transfer_tokens``)
        base, more = divmod(rows, self.denoising_steps)
        self._fill_schedule = [base + (s < more)
                               for s in range(self.denoising_steps)]
        logger.info(
            f"serving: block lane: blocks of {rows} rows in "
            f"{self.denoising_steps} denoise steps + commit, rule "
            f"{self.unmask_rule}"
            f"{f' (threshold {self.confidence_threshold})' if self.unmask_rule == 'low_confidence_dynamic' else ''}")

    # ------------------------------------------------------------------
    # tensor-parallel serving (docs/serving.md "Tensor-parallel serving")
    # ------------------------------------------------------------------
    @property
    def _pool_spec(self) -> P:
        """KV pools [L, blocks, block, kv_heads * d]: the kv-head lanes
        over `model`, replicated over `data` (every data shard applies
        every slot's writes — see the model's gather_rows)."""
        return P(None, None, None, topo.MODEL_AXIS)

    @property
    def _pscale_spec(self) -> P:
        """Quant scale planes [L, blocks, kv_heads, 1, block] ride the
        pools' kv_heads sharding."""
        return P(None, None, topo.MODEL_AXIS, None, None)

    def _init_tp_mesh(self) -> None:
        """Validate the (data, model) request against the model shapes,
        build the serving submesh over the first data*model devices, and
        derive the per-shard model view."""
        dp, mp = self.tp_data_size, self.tp_model_size
        c = self.model.config
        if mp > 1:
            for name, dim in (("kv_heads", c.kv_heads),
                              ("num_heads", c.num_heads),
                              ("d_ff", c.ff_dim),
                              ("vocab_size", c.vocab_size)):
                if dim % mp:
                    raise ValueError(
                        f"serving.mesh.model ({mp}) must divide "
                        f"{name} ({dim}) — heads/MLP columns/vocab "
                        f"partition evenly over the model axis")
        devices = jax.devices()
        if len(devices) < dp * mp:
            raise ValueError(
                f"serving.mesh (data={dp}, model={mp}) needs "
                f"{dp * mp} devices, have {len(devices)}")
        from ...runtime.config import MeshConfig
        self.tp_mesh = topo.build_mesh(MeshConfig(data=dp, model=mp),
                                       devices=devices[:dp * mp])
        self._tp_model = self.model.tp_serving_view(
            mp, topo.MODEL_AXIS,
            topo.DATA_AXIS if dp > 1 else None)
        if mp > 1 and getattr(self.engine, "_quantized", False) and \
                self.engine._qmode != "channel":
            raise NotImplementedError(
                "tensor-parallel serving over quantized weights needs "
                "per-output-channel scales (grouped scales cross shard "
                "boundaries) — the engine selects channel mode when "
                "serving.mesh.model > 1 at init_inference time; rebuild "
                "the engine with the serving mesh in its config")

    def _prep_tp_params(self) -> None:
        """One-time weight prep for the sharded step: permute the fused
        qkv columns (kernel + bias + per-channel quant scales) into
        per-shard-contiguous order, pre-divide the row-parallel out /
        fc_out biases by the model shard count (the per-layer psum then
        restores them exactly), and commit everything to the serving
        submesh under the model's Megatron partition specs."""
        engine, model = self.engine, self.model
        c = model.config
        mp_size = self.tp_model_size
        params = engine.params
        specs = model.partition_specs(params)
        scales = getattr(engine, "_scales", None)
        flags = getattr(engine, "_qflags", None)
        if mp_size > 1:
            perm = jnp.asarray(
                _tp_qkv_perm(c.num_heads, c.kv_heads, c.hdim, mp_size))

            def tail_of(path):
                return tuple(str(getattr(p, "key", "")) for p in path)[-2:]

            def prep(path, leaf):
                tail = tail_of(path)
                if tail in (("qkv", "kernel"), ("qkv", "bias")):
                    return jnp.take(leaf, perm, axis=-1)
                if tail in (("out", "bias"), ("fc_out", "bias")):
                    return leaf / mp_size
                return leaf
            params = jax.tree_util.tree_map_with_path(prep, params)
            if scales is not None:
                def prep_s(path, s, f):
                    if f and tail_of(path) == ("qkv", "kernel"):
                        return jnp.take(s, perm, axis=-1)
                    return s
                scales = jax.tree_util.tree_map_with_path(
                    prep_s, scales, flags)

        def put(tree, spec_tree):
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.tp_mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))
            return jax.device_put(tree, shardings)

        self._tp_param_specs = specs
        self._tp_params = put(params, specs)
        self._tp_scales = self._tp_scale_specs = None
        if scales is not None:
            # per-output-CHANNEL scale vectors shard like their kernel's
            # last axis (shard-local dequant); placeholder leaves for
            # unquantized params replicate
            def sspec(pspec, f, s):
                nd = len(s.shape)
                if not f or nd == 0:
                    return P(*([None] * nd))
                last = pspec[-1] if len(pspec) else None
                return P(*([None] * (nd - 1)), last)
            self._tp_scale_specs = jax.tree_util.tree_map(
                sspec, specs, flags, scales,
                is_leaf=lambda x: isinstance(x, P))
            self._tp_scales = put(scales, self._tp_scale_specs)

    @property
    def kv_pool_bytes(self) -> int:
        """PER-CHIP device HBM held by the paged KV pool — values plus
        the dequant scale planes when quantized (the
        ``dstpu_serving_kv_pool_bytes`` gauge).  Under a model-sharded
        mesh each chip holds ``kv_heads / model`` of every block, so
        this is 1/model of the global pool (data shards replicate the
        pool; they add capacity in SLOTS, not bytes)."""
        total = self._pool_k.nbytes
        if self._pool_v is not None:
            total += self._pool_v.nbytes
        if self._pool_ks is not None:
            total += self._pool_ks.nbytes + self._pool_vs.nbytes
        total += sum(a.nbytes for a in
                     jax.tree_util.tree_leaves(self._pool_x))
        return total // self.tp_model_size

    @property
    def kv_row_width(self) -> int:
        """Lanes of one token's row in a pool buffer (``kv_heads x
        head_dim``; a latent pool's padded row)."""
        return int(self._pool_k.shape[-1])

    # ------------------------------------------------------------------
    # tiered host prefix cache (docs/serving.md "Tiered prefix cache")
    # ------------------------------------------------------------------
    def _init_host_cache(self, hc, shared=None) -> None:
        """Build the host tier from the pool geometry and wire it into
        the allocator: eviction becomes demotion (``_spill_block``),
        and the allocate hit walk extends into the host store.  The
        gather/scatter helper programs are compiled HERE, off the
        serving clock, by round-tripping the null block — the mixed
        step stays the one step (``decode_builds`` untouched).
        ``shared`` injects an already-built (fleet-shared) store
        instead: entry geometry must match, budgets were sized by
        whoever built it."""
        c = self.model.config
        self._hc_codec = BlockCodec(
            c.num_layers, self.block_size, c.kv_heads, c.hdim,
            kv_bits=self.kv_bits, wire_bits=hc.wire_bits,
            dtype=np.dtype(self._pool_k.dtype) if not self.kv_bits
            else np.int8)
        entry = self._hc_codec.nbytes
        if shared is not None:
            if shared.entry_nbytes != entry:
                raise ValueError(
                    f"shared host cache entry size "
                    f"{shared.entry_nbytes} != this replica's codec "
                    f"{entry} bytes — fleet replicas must share pool "
                    f"geometry (block size, kv heads, bits)")
            self.host_cache = shared
            self.allocator.attach_host_tier(self.host_cache,
                                            self._spill_block)
            self._build_block_dma()
            return
        dram_slots = hc.dram_budget_bytes // entry
        nvme_slots = hc.nvme_budget_bytes // entry
        if dram_slots == 0 and nvme_slots == 0:
            raise ValueError(
                f"serving.host_cache budgets admit zero entries — one "
                f"encoded block is {entry} bytes ({c.num_layers} layers "
                f"x {self.block_size} tokens x {c.kv_heads} kv heads at "
                f"{self._hc_codec.at_rest_bits or 'raw'} bits)")
        self.host_cache = HostTierCache(
            entry, dram_slots, nvme_slots=nvme_slots,
            nvme_path=hc.nvme_path,
            buffer_count=max(4, self._promote_k))
        self.allocator.attach_host_tier(self.host_cache,
                                        self._spill_block)
        self._build_block_dma()
        logger.info(
            f"serving: tiered host cache on — entry {entry / 2**10:.1f} "
            f"KiB at {self._hc_codec.at_rest_bits or 'raw'}-bit, "
            f"dram {dram_slots} entries"
            f"{f', nvme {nvme_slots} entries' if nvme_slots else ''}, "
            f"promote parallelism {self._promote_k}")

    def _build_block_dma(self) -> None:
        # block-granular DMA helpers: tiny jitted gather/scatter over
        # the pools (NOT the mixed step — these run in the admission
        # window, never per decode token).  They translate between the
        # pool layout ([L, nb, blk, kvh * De] values, [L, nb, kvh, 1,
        # blk] scales) and the codec's per-block [L, blk, kvh, De] /
        # [L, blk, kvh]; the scatter returns the pools in their own
        # shardings so the mixed step never sees a new input layout
        kvh = self.model.config.kv_heads

        def take(pool, b):
            blk = pool[:, b]
            return blk.reshape(*blk.shape[:2], kvh, -1)

        def put(pool, b, blk):
            return pool.at[:, b].set(blk.reshape(*blk.shape[:2], -1))

        def take_s(scale, b):
            return scale[:, b, :, 0].swapaxes(1, 2)

        def put_s(scale, b, rows):
            return scale.at[:, b, :, 0].set(rows.swapaxes(1, 2))
        if self.kv_bits:
            self._gather_block = jax.jit(
                lambda pk, pv, pks, pvs, b:
                (take(pk, b), take(pv, b), take_s(pks, b), take_s(pvs, b)))
            self._scatter_block = jax.jit(
                lambda pk, pv, pks, pvs, b, k, v, ks, vs:
                (put(pk, b, k), put(pv, b, v), put_s(pks, b, ks),
                 put_s(pvs, b, vs)),
                out_shardings=(self._pool_sh, self._pool_sh,
                               self._pscale_sh, self._pscale_sh),
                donate_argnums=(0, 1, 2, 3) if self._donate else ())
        else:
            self._gather_block = jax.jit(
                lambda pk, pv, b: (take(pk, b), take(pv, b)))
            self._scatter_block = jax.jit(
                lambda pk, pv, b, k, v: (put(pk, b, k), put(pv, b, v)),
                out_shardings=(self._pool_sh, self._pool_sh),
                donate_argnums=(0, 1) if self._donate else ())
        # compile warmup: scatter the null block's own content back into
        # itself — a semantic no-op that traces both programs now
        b0 = jnp.asarray(0, jnp.int32)
        if self.kv_bits:
            k, v, ks, vs = self._gather_block(
                self._pool_k, self._pool_v, self._pool_ks,
                self._pool_vs, b0)
            (self._pool_k, self._pool_v, self._pool_ks,
             self._pool_vs) = self._scatter_block(
                self._pool_k, self._pool_v, self._pool_ks,
                self._pool_vs, b0, k, v, ks, vs)
        else:
            k, v = self._gather_block(self._pool_k, self._pool_v, b0)
            self._pool_k, self._pool_v = self._scatter_block(
                self._pool_k, self._pool_v, b0, k, v)

    def _spill_block(self, block: int, digest: bytes) -> None:
        """Allocator eviction callback: encode the dying block and park
        it in the host tier under its chain digest.  NEVER raises — the
        ``serving.spill`` fault site (transient faults retried under
        the resilience backoff) degrades any terminal failure to a
        plain eviction, so a sick host store costs warmth, not
        correctness, and never a wrong block."""
        try:
            with trace_span("serving/spill", block=block):
                bi = jnp.asarray(block, jnp.int32)
                if self.kv_bits:
                    k, v, ks, vs = self._gather_block(
                        self._pool_k, self._pool_v, self._pool_ks,
                        self._pool_vs, bi)
                    payload = self._hc_codec.encode(
                        np.asarray(k), np.asarray(v),
                        np.asarray(ks), np.asarray(vs))
                else:
                    k, v = self._gather_block(self._pool_k,
                                              self._pool_v, bi)
                    payload = self._hc_codec.encode(np.asarray(k),
                                                    np.asarray(v))

                def _put():
                    get_fault_injector().check("serving.spill")
                    self.host_cache.put(digest, payload)
                retry_call(_put, what=f"host-tier spill of block {block}")
        except Exception as e:   # noqa: BLE001 — degrade, never raise
            self.host_counts["spill_failures"] += 1
            self._m_spill_failures.inc()
            logger.warning(
                f"serving: spill of block {block} failed ({e!r}) — "
                f"degraded to plain eviction")

    def _publish_block(self, block: int, digest: bytes) -> bool:
        """Push one finished-chain block into the KV fabric (same
        gather + wire-codec path as :meth:`_spill_block`, but through
        :meth:`HostTierCache.publish` so the entry carries a crc32 and
        this engine's publisher id).  NEVER raises: the
        ``serving.fabric.publish`` site fires inside ``publish`` before
        any fabric mutation, transient faults retry under the
        resilience backoff, and any terminal failure degrades to
        decode-side recompute — a handoff miss, never a wrong token."""
        try:
            with trace_span("serving/fabric_publish", block=block):
                bi = jnp.asarray(block, jnp.int32)
                if self.kv_bits:
                    k, v, ks, vs = self._gather_block(
                        self._pool_k, self._pool_v, self._pool_ks,
                        self._pool_vs, bi)
                    payload = self._hc_codec.encode(
                        np.asarray(k), np.asarray(v),
                        np.asarray(ks), np.asarray(vs))
                else:
                    k, v = self._gather_block(self._pool_k,
                                              self._pool_v, bi)
                    payload = self._hc_codec.encode(np.asarray(k),
                                                    np.asarray(v))

                def _pub():
                    self.host_cache.publish(digest, payload,
                                            publisher=self.publisher_id)
                retry_call(_pub,
                           what=f"fabric publish of block {block}")
            self.fabric_counts["published_blocks"] += 1
            self._m_fabric_published.inc()
            return True
        except Exception as e:   # noqa: BLE001 — degrade, never raise
            self.fabric_counts["publish_failures"] += 1
            self._m_fabric_publish_failures.inc()
            logger.warning(
                f"serving: fabric publish of block {block} failed "
                f"({e!r}) — decode leg will recompute")
            return False

    def _publish_chain(self, req) -> int:
        """Publish every committed full block of ``req``'s chain, in
        block order, stopping at the first failure so published chains
        stay prefix-contiguous (the decode-side hit walk stops at its
        first miss — a gap would strand the tail as unclaimable
        orphans).  Returns blocks published."""
        if self.host_cache is None:
            return 0
        alloc = self.allocator
        table = alloc.block_table(req.req_id)
        published = 0
        for digest, block in zip(alloc.seq_chain(req.req_id), table):
            if not self._publish_block(block, digest):
                break
            published += 1
        return published

    def _finish_prefill_only(self, slot: int, req) -> None:
        """A ``prefill_only`` request's target landed: publish the
        finished chain to the fabric, OK-finish the slot with its
        blocks unregistered (the digests now live fabric-side only),
        and close the stream with a tokenless OK terminal event — the
        router's handoff trigger.  No token is ever sampled or emitted
        on the prefill leg; the decode leg starts its stream at output
        index 0 with the pinned key."""
        if self._rt.enabled:
            # fabric_publish is a fleet flow-arrow anchor: the merged
            # fleet trace binds the prefill->decode handoff arrow inside
            # this X segment (observability/fleet_trace.py)
            t0p = time.perf_counter()
            published = self._publish_chain(req)
            self._rt.on_segment(
                req, "fabric_publish", t0p, time.perf_counter() - t0p,
                blocks=published,
                publisher=getattr(self, "publisher_id", None))
        else:
            self._publish_chain(req)
        self.fabric_counts["prefill_only_completed"] += 1
        self.scheduler.finish_prefill(slot)
        now = time.perf_counter()
        self._event_buf.append(TokenEvent(
            request=req, token=None, index=0, status=req.status,
            final=True, tenant=req.tenant, time_s=now,
            prev_time_s=None))

    def _service_promotions(self) -> int:
        """Land up to ``promote_parallelism`` queued host->pool block
        promotions (admission-window work: the scheduler holds the
        owning requests in the PROMOTING phase until their blocks
        land).  Transient ``serving.promote`` faults that outlive the
        in-call retry budget leave the job queued for next step; a
        fatal fault drops the job AND its registration and rolls every
        holder back to recompute (``promotion_failed``) — stale or
        mismatched KV is never served.  Returns blocks landed (counts
        as watchdog progress)."""
        alloc = self.allocator
        if self.host_cache is None or not alloc.num_pending:
            return 0
        sched = self.scheduler
        promoting = [r for r in sched.running.values()
                     if sched.promoting(r)]
        t0 = time.perf_counter()
        landed = 0
        for job in alloc.pending_jobs()[:self._promote_k]:
            try:
                with trace_span("serving/promote", block=job.block):
                    def _land():
                        # the fault site fires BEFORE the scatter, so a
                        # fault leaves the pool untouched; the scatter
                        # itself is idempotent under retry
                        get_fault_injector().check("serving.promote")
                        self._land_promotion(job)
                    retry_call(_land,
                               what=f"host-tier promote of block "
                                    f"{job.block}")
            except TransientIOError as e:
                # retry budget exhausted but the fault is transient:
                # the job stays queued and retries next step (the
                # request stays PROMOTING — delayed, never corrupted)
                logger.warning(
                    f"serving: promote of block {job.block} still "
                    f"transient after retries — queued for next step: "
                    f"{e}")
                continue
            except Exception as e:   # noqa: BLE001 — fatal: recompute
                affected = alloc.promotion_failed(job.digest)
                self.host_counts["promote_failures"] += 1
                self._m_promote_failures.inc()
                for seq_id, block_index in affected:
                    for req in sched.running.values():
                        if req.req_id == seq_id:
                            # roll back to the last row BEFORE the dead
                            # block: prefill recomputes from there
                            # (rewriting identical content, so the
                            # chain record stays valid)
                            req.cached_tokens = min(
                                req.cached_tokens,
                                block_index * self.block_size)
                logger.warning(
                    f"serving: promote of block {job.block} failed "
                    f"fatally ({e!r}) — host entry dropped, "
                    f"{len(affected)} holder(s) fall back to recompute")
                continue
            alloc.promotion_landed(job.digest)
            landed += 1
            self.host_counts["promoted_blocks"] += 1
            self._m_promoted.inc()
        dur = time.perf_counter() - t0
        if landed and self._rt.enabled:
            self._rt.on_promote(promoting, t0, dur, landed)
        return landed

    def _land_promotion(self, job) -> None:
        """Decode one claimed payload and scatter it into the pool at
        its claimed block."""
        k, v, ks, vs = self._hc_codec.decode(job.payload)
        bi = np.int32(job.block)
        if self.kv_bits:
            (self._pool_k, self._pool_v, self._pool_ks,
             self._pool_vs) = self._scatter_block(
                self._pool_k, self._pool_v, self._pool_ks,
                self._pool_vs, bi, k, v, ks, vs)
        else:
            self._pool_k, self._pool_v = self._scatter_block(
                self._pool_k, self._pool_v, bi, k, v)

    # ------------------------------------------------------------------
    # speculative decoding (draft lane)
    # ------------------------------------------------------------------
    def _init_draft(self, draft, params) -> None:
        """Validate the draft model against the target and build its
        OWN paged pools (same geometry, same block tables/lens as the
        target's — the draft pool moves in lockstep, so preemption,
        prefix hits, and slot churn all stay valid for speculation).
        The draft pool is never quantized: it is small by construction
        and its logits drive acceptance, not output."""
        cfg = self.engine.config.serving
        reason = draft._paged_supported()
        if reason is not None:
            raise NotImplementedError(
                f"speculative draft model cannot run the paged path: "
                f"{reason}")
        if draft.config.vocab_size != self.model.config.vocab_size:
            raise ValueError(
                f"draft vocab_size ({draft.config.vocab_size}) must "
                f"match the target's ({self.model.config.vocab_size}) "
                f"— proposals are token ids")
        if draft.config.max_seq_len < self.engine.config.max_out_tokens:
            raise ValueError(
                f"draft max_seq_len ({draft.config.max_seq_len}) is "
                f"shorter than max_out_tokens "
                f"({self.engine.config.max_out_tokens}) — the draft "
                f"must reach every position the target serves")
        if params is None:
            # fresh-init drafts are only useful for plumbing tests:
            # acceptance will be ~chance.  Real deployments pass a
            # trained (typically distilled) draft checkpoint.
            logger.warning(
                "serving: no draft_params given — initializing an "
                "UNTRAINED draft (near-zero acceptance; pass a trained "
                "draft checkpoint for real speedups)")
            params = draft.init(jax.random.PRNGKey(1))
        self._draft_params = params
        with self._ovl.setup_span("setup/pools"):
            with trace_span("serving/draft_pool", blocks=cfg.num_kv_blocks):
                dpools = draft.init_paged_cache(
                    cfg.num_kv_blocks, self.block_size,
                    dtype=self.engine.dtype, kv_bits=0)
            # the draft replicates over BOTH mesh axes (it is small); its
            # view arms only the data axis so the slot-sharded lens/tables
            # it shares with the target stay correct
            self._tp_draft = draft.tp_serving_view(
                1, None, topo.DATA_AXIS if self.tp_data_size > 1 else None)
            rep = NamedSharding(self.tp_mesh, P())
            self._dpool_k = jax.device_put(dpools["k"], rep)
            self._dpool_v = jax.device_put(dpools["v"], rep)
            self._draft_params = jax.device_put(self._draft_params, rep)
        logger.info(
            f"serving: speculative decoding armed — draft "
            f"{draft.config.num_layers}L/{draft.config.d_model}d, "
            f"k={self.spec_k} proposals/slot/iteration")

    # ------------------------------------------------------------------
    # token streaming (frontend/streaming.py)
    # ------------------------------------------------------------------
    def _emit_token(self, req: Request, token: int) -> None:
        """Buffer one emitted token (status/final resolved at flush —
        the request may reach a terminal state later in the same
        iteration)."""
        now = time.perf_counter()
        self._event_buf.append(TokenEvent(
            request=req, token=token, index=len(req.output) - 1,
            status=None, final=False, tenant=req.tenant, time_s=now,
            prev_time_s=req.last_token_time))
        req.last_token_time = now

    def _flush_events(self) -> None:
        """Deliver buffered token/terminal events at the iteration
        boundary: engine-level hooks first (frontend fairness +
        metrics), then the request's own ``on_token``.  A callback
        exception disables that request's stream — logged once, the
        request and the batch keep running."""
        if not self._event_buf:
            return
        events, self._event_buf = self._event_buf, []
        last_of = {id(ev.request): i for i, ev in enumerate(events)}
        for i, ev in enumerate(events):
            req = ev.request
            if req.state is RequestState.FINISHED and \
                    last_of[id(req)] == i:
                ev = ev._replace(status=req.status, final=True)
            for hook in self.token_hooks:
                try:
                    hook(ev)
                except Exception as e:     # hook bugs must not stall serving
                    logger.warning(f"serving: token hook failed: {e!r}")
            cb = req.on_token
            if cb is None:
                continue
            try:
                cb(ev)
            except Exception as e:
                req.on_token = None
                logger.warning(
                    f"serving: {req.req_id} on_token callback raised "
                    f"{e!r} — stream disabled, request continues")

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               on_token: Optional[Callable] = None,
               tenant: str = "default",
               prefill_only: bool = False,
               trace_id: Optional[str] = None,
               record_blocks: bool = False) -> Request:
        """Queue a request.  ``deadline_s`` is a TTL from submit, swept
        every ``step()`` whether the request is still WAITING or already
        RUNNING (defaults to ``serving.default_deadline_s``; 0 = none).
        Under overload (``serving.max_queue_depth`` waiting requests)
        the request is returned TERMINAL with ``status ==
        RequestStatus.SHED`` and an empty stream — check ``req.status``,
        this is backpressure, not an exception.

        ``temperature``/``top_k``/``top_p`` default to the inference
        config; ``seed`` derives the request's PRNG key (None = the
        engine's base key, matching an unseeded ``generate()``) —
        output token j is always sampled with ``fold_in(key, j)``, so
        the stream is reproducible regardless of batching.
        ``on_token`` receives a :class:`TokenEvent` per emitted token
        at iteration boundaries.  ``tenant`` tags the request for the
        multi-tenant frontend's fairness accounting.

        ``prefill_only`` runs the prefill leg of a disaggregated
        handoff: the prompt's KV is computed (and published to the KV
        fabric when the host tier is attached), NO token is emitted,
        and the stream closes with a tokenless OK terminal event the
        moment the prefill target lands.

        ``trace_id`` carries a fleet-wide trace context into this
        engine: when set, the request tracer adopts it instead of
        minting a fresh per-process id, so prefill, decode and failover
        legs of one disaggregated request share ONE trace id in the
        merged fleet trace (observability/fleet_trace.py).

        ``record_blocks`` (generation by diffusion over blocks) keeps the
        request's trajectory in ``Request.block_steps``: the block's
        tokens after every forward it rode."""
        if prefill_only and self.host_cache is None:
            raise ValueError(
                "prefill_only requires the host-tier KV fabric "
                "(serving.host_cache.enabled) — there is nowhere to "
                "publish the finished chain")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        total = len(prompt) + max_new_tokens
        if total > self.engine.config.max_out_tokens:
            raise ValueError(
                f"prompt+new = {total} exceeds max_out_tokens "
                f"({self.engine.config.max_out_tokens})")
        if deadline_s is not None and deadline_s < 0:
            raise ValueError(
                f"deadline_s must be >= 0 (0 = no deadline), got "
                f"{deadline_s}")
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        temperature = (self.temperature if temperature is None
                       else float(temperature))
        top_k = self.top_k if top_k is None else int(top_k)
        top_p = self.top_p if top_p is None else float(top_p)
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got "
                             f"{temperature}")
        if self.block_rows and temperature > 0:
            raise NotImplementedError(
                f"temperature {temperature}: the block lane draws greedily "
                f"(a row's token and its confidence come from one argmax "
                f"over the vocabulary; a per-row categorical draw over "
                f"block_length x slots rows is not built) — submit with "
                f"temperature=0, or put \"temperature\": 0.0 in the engine "
                f"config")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got "
                             f"{top_k}")
        if not 0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        key = (self._base_key if seed is None else tuple(
            int(x) for x in np.asarray(jax.random.PRNGKey(seed))))
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id,
                      deadline_s=deadline_s if deadline_s else None,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      prng_key=key, on_token=on_token, tenant=tenant,
                      prefill_only=prefill_only,
                      block_steps=[] if record_blocks and self.block_rows
                      else None)
        if trace_id is not None:
            # fleet-minted trace context: set BEFORE scheduler.submit so
            # the request tracer's on_submit adopts it as-is
            req.trace_id = trace_id
        self.scheduler.submit(req)
        self._drain_terminal_events()
        self._m_queue.set(self.scheduler.queue_depth)
        self._flush_events()
        return req

    def cancel(self, req: Request) -> bool:
        """Cancel a request; returns True if it transitioned to
        CANCELLED, False if it was already terminal (idempotent).  Safe
        at any point between two ``step()`` calls (the serving loop is
        single-threaded, so caller code always runs at an iteration
        boundary): a RUNNING request's APPLIED blocks are commit-cached
        first — exactly like preemption — then freed, so a cancelled
        request's prefix stays warm for shared-prefix siblings.  A row
        the iteration in flight still carries for it is void: its result
        is ignored when it lands (``_apply``)."""
        with trace_span("serving/cancel", req=req.req_id):
            ok = self.scheduler.cancel(req)
        self._drain_terminal_events()
        self._update_gauges()
        self._flush_events()
        return ok

    def _drain_terminal_events(self) -> int:
        """Fold the scheduler's non-OK terminal transitions into the
        lifecycle counters (each event counted exactly once, whichever
        path initiated it)."""
        events = self.scheduler.terminal_events
        if not events:
            return 0
        self.scheduler.terminal_events = []
        by_status = {RequestStatus.CANCELLED: ("cancelled",
                                               self._m_cancelled),
                     RequestStatus.TIMED_OUT: ("timed_out",
                                               self._m_timed_out),
                     RequestStatus.SHED: ("shed", self._m_shed),
                     RequestStatus.FAILED: ("failed", self._m_failed)}
        for req in events:
            key, counter = by_status[req.status]
            counter.inc()
            self.lifecycle_counts[key] += 1
            logger.warning(f"serving: {req.req_id} -> {req.status.value}"
                           f"{': ' + req.error if req.error else ''}")
            # the stream must END even when no token ever flowed: a
            # tokenless terminal event closes it with the status
            self._event_buf.append(TokenEvent(
                request=req, token=None, index=len(req.output),
                status=req.status, final=True, tenant=req.tenant,
                time_s=time.perf_counter(),
                prev_time_s=req.last_token_time))
            for hook in self.lifecycle_hooks:
                try:
                    hook(req)
                except Exception as e:
                    logger.warning(
                        f"serving: lifecycle hook failed: {e!r}")
        return len(events)

    # ------------------------------------------------------------------
    # the one compiled step, in its two shapes
    # ------------------------------------------------------------------
    def _build_step(self):
        # the TP view shares weights/rotary/block_transform with the
        # plain model; its per-shard head counts + armed axis names are
        # what make the SAME body below shard-correct inside shard_map
        engine, model = self.engine, self._tp_model
        draft = self._tp_draft
        spec_on = self._draft_model is not None
        S = self.spec_k + 1 if spec_on else 0

        def built():
            # trace-time side effect: counts program BUILDS, not calls —
            # one a shape, and continuous batching must never retrace
            # either
            self.decode_builds += 1
            get_registry().counter("dstpu_jit_programs_built_total").inc()

        def chunk_results(chunk_logits, ch: _ChunkState):
            """``first`` and ``chunk_finite`` of the result array."""
            if not ch.ids.shape[0]:
                # the decode-only shape: no chunk, so nothing to sample
                # from and nothing that could be non-finite — constants
                # in the columns the host reads only when a chunk rode
                return jnp.zeros((), jnp.int32), jnp.ones((), jnp.bool_)
            # the chunk's first token: output index ch.out_idx of the
            # prefilling request, drawn with ITS key — identical to the
            # token a decode iteration would have produced, which is
            # what makes preempt-recompute and prefix-hit resumes
            # token-exact
            first = sample_tokens_per_row(
                chunk_logits[None],
                fold_in_keys(ch.key[None], ch.out_idx[None]),
                ch.temp[None], ch.top_k[None], ch.top_p[None])[0]
            with jax.named_scope("head"):
                return first, jnp.all(jnp.isfinite(chunk_logits))

        def input_tokens(sl: _SlotState, prev):
            """Every decoding row's input token: the host's, or — for a
            row planned while the dispatch that sampled it was still on
            the device — the slot's newest token in that dispatch's
            result array.  One select over ``[num_slots]`` integers."""
            return jnp.where(sl.token_src == SRC_DEVICE, prev[:, _R_NEXT],
                             sl.dec_tokens)

        def newest_tokens(prev, active, nxt, first, ch: _ChunkState):
            """The ``_R_NEXT`` column: what each slot's next decode row
            takes as input — sampled by this dispatch's decode lane or
            its chunk lane, else carried over from the previous
            dispatch's, so a dispatch that carries no row for a slot (a
            chunk remainder's) hands its token on."""
            newest = jnp.where(active > 0, nxt, prev[:, _R_NEXT])
            if not ch.ids.shape[0]:
                return newest
            rows = newest.shape[0]       # this shard's slots, of all
            row = jnp.arange(rows) + (
                lax.axis_index(topo.DATA_AXIS) * rows
                if self.tp_data_size > 1 else 0)
            return jnp.where((row == ch.slot) & (ch.len > 0), first, newest)

        def serving_step(params, scales, pool_k, pool_v, pool_ks, pool_vs,
                         pool_x, prev, slots, chunk):
            built()
            # slices of an operand are free: the two host arrays come
            # apart first thing
            sl, ch = _SlotState.unpack(slots), _ChunkState.unpack(chunk)
            dec_tokens = input_tokens(sl, prev)
            mp = engine._model_params(params, scales)
            cache = {"k": pool_k, "v": pool_v, "k_scale": pool_ks,
                     "v_scale": pool_vs, "block_tables": sl.tables,
                     "lens": sl.lens}
            if pool_x is not None:
                cache["extra"] = pool_x
            dec_logits, chunk_logits, cache = model._apply_paged_mixed(
                mp, cache, dec_tokens, sl.dec_active, ch.ids, ch.slot,
                ch.start, ch.len)
            # in-program per-slot sampling: output token j of a request
            # is ALWAYS drawn with fold_in(request_key, j) — batch-,
            # order- and preemption-independent (docs/serving.md
            # "Sampling, streaming & multi-tenant SLOs")
            nxt = sample_tokens_per_row(
                dec_logits, fold_in_keys(sl.keys, sl.out_idx), sl.temp,
                sl.top_k, sl.top_p)
            first, chunk_finite = chunk_results(chunk_logits, ch)
            # per-slot finite flags, computed IN-PROGRAM (no extra
            # dispatch, no retrace — decode_builds stays 2): a slot
            # whose logits go non-finite is quarantined host-side
            # instead of silently streaming garbage or poisoning the
            # prefix cache
            with jax.named_scope("head"):
                dec_finite = jnp.all(jnp.isfinite(dec_logits), axis=-1)
            with jax.named_scope("sample"):
                packed = _pack_results(
                    newest_tokens(prev, sl.dec_active, nxt, first, ch),
                    dec_finite, first, chunk_finite,
                    counters=cache.get("counters"))
            return (packed, cache["k"], cache["v"],
                    cache.get("k_scale"), cache.get("v_scale"),
                    cache.get("extra"))

        B = self.block_rows
        pages = self.max_pages * len(self.table_kinds) if B else 0

        def serving_block_step(params, scales, pool_k, pool_v, pool_ks,
                               pool_vs, pool_x, prev, slots, chunk):
            """Generation by diffusion over blocks: every live slot's
            block through one forward — a denoise step that fills some
            of its masked rows, or the commit of its final tokens; the
            program is the same, the host's phase column says which —
            beside one prompt chunk.  The blocks live on the device from
            one dispatch to the next, in the result array."""
            built()
            sl = _SlotState.unpack(slots[:, :_SLOT_COLS + pages])
            ch = _ChunkState.unpack(chunk)
            carried = prev[:, _R_SPEC:_R_SPEC + B]
            block = jnp.where((sl.token_src == SRC_DEVICE)[:, None],
                              carried, slots[:, _SLOT_COLS + pages:])
            mp = engine._model_params(params, scales)
            cache = {"k": pool_k, "v": pool_v, "k_scale": pool_ks,
                     "v_scale": pool_vs, "block_tables": sl.tables,
                     "lens": sl.lens}
            # the one step, at B rows a slot (nothing samples from the
            # chunk of such a model: no head over it)
            logits, _, cache = model._apply_paged_mixed(
                mp, cache, block, sl.dec_active, ch.ids, ch.slot, ch.start,
                ch.len)
            # confidence, ranking, transfer: on the device
            filled = block_unmask(
                logits, block,
                jnp.where(sl.dec_active == PHASE_DENOISE,
                          slots[:, _BLOCK_FILL], 0),
                mask_id=model.config.mask_token_id, rule=self.unmask_rule,
                threshold=self.confidence_threshold)
            with jax.named_scope("head"):
                finite = jnp.all(jnp.isfinite(logits), axis=(-2, -1))
            with jax.named_scope("sample"):
                # a slot that rode nothing (a chunk remainder's dispatch)
                # hands its block on
                block = jnp.where((sl.dec_active > 0)[:, None], filled,
                                  carried)
                packed = _pack_results(
                    block[:, 0], finite, jnp.zeros((), jnp.int32),
                    jnp.ones((), jnp.bool_), samples=block,
                    counters=cache.get("counters"))
            return (packed, cache["k"], cache["v"], None, None, pool_x)

        def serving_spec_step(params, scales, dparams, pool_k, pool_v,
                              pool_ks, pool_vs, pool_x, dpool_k, dpool_v,
                              prev, slots, chunk):
            built()
            sl = _SlotState.unpack(slots)
            (tables, lens, _host_tokens, dec_active, spec_active, temp,
             top_k, top_p, keys, out_idx, _src) = sl
            # the same operand and the same select as the plain step; a
            # speculating slot's lengths are the device's to say
            # (n_emit), so the loop plans nothing past this dispatch and
            # every row's source is the host
            dec_tokens = input_tokens(sl, prev)
            ch = _ChunkState.unpack(chunk)
            mp = engine._model_params(params, scales)
            empty = jnp.zeros((0,), jnp.int32)
            zero = jnp.asarray(0, jnp.int32)
            zeros_b = jnp.zeros_like(dec_tokens)
            # --- draft lane (Leviathan et al.): spec_k proposals per
            # speculating slot + one KV-only step, inside the ONE
            # program.  The draft pool moves in LOCKSTEP with the
            # target pool: feed 0 also writes every PLAIN-decoding
            # slot's token, and the chunk mirror replays the prefill
            # chunk (it goes with the chunk: the decode-only shape has
            # none) — so every committed / prefix-cached block is valid
            # in BOTH pools and speculation survives preemption, prefix
            # hits, and slot churn.
            dcache = {"k": dpool_k, "v": dpool_v,
                      "block_tables": tables, "lens": lens}
            if ch.ids.shape[0]:
                _dl, _cl, dcache = draft._apply_paged_mixed(
                    dparams, dcache, zeros_b, zeros_b, ch.ids, ch.slot,
                    ch.start, ch.len)
            any_active = ((dec_active > 0)
                          | (spec_active > 0)).astype(jnp.int32)
            cur = dec_tokens
            toks = [cur]
            for i in range(S):     # feeds: x, d_1 .. d_{k-1}, then d_k
                dcache = dict(dcache, lens=lens + i)
                dlg, _cl, dcache = draft._apply_paged_mixed(
                    dparams, dcache, cur,
                    any_active if i == 0 else spec_active,
                    empty, zero, zero, zero)
                if i < S - 1:
                    # the draft draws with the SAME deterministic key
                    # the target uses at that position: when the
                    # distributions agree so do the samples, and the
                    # exact-match verify below accepts
                    cur = sample_tokens_per_row(
                        dlg, fold_in_keys(keys, out_idx + i), temp,
                        top_k, top_p)
                    toks.append(cur)
            spec_tokens = jnp.stack(toks, axis=1)            # [B, S]
            cache = {"k": pool_k, "v": pool_v, "k_scale": pool_ks,
                     "v_scale": pool_vs, "block_tables": tables,
                     "lens": lens}
            dec_logits, spec_logits, chunk_logits, cache = \
                model._apply_paged_mixed(
                    mp, cache, dec_tokens, dec_active, ch.ids, ch.slot,
                    ch.start, ch.len,
                    spec_tokens=spec_tokens, spec_active=spec_active)
            nxt = sample_tokens_per_row(
                dec_logits, fold_in_keys(keys, out_idx), temp, top_k,
                top_p)
            # the target samples s_i at every draft position with that
            # position's own key; accept d_i while d_i == s_{i-1}.
            # Every accepted position therefore saw EXACTLY the context
            # and key the sequential sampler would have — token
            # equivalence by construction, not merely in distribution —
            # and the EMITTED tokens are always the target's samples.
            s = jnp.stack(
                [sample_tokens_per_row(
                    spec_logits[:, i], fold_in_keys(keys, out_idx + i),
                    temp, top_k, top_p) for i in range(S)], axis=1)
            matches = (spec_tokens[:, 1:] == s[:, :-1]).astype(jnp.int32)
            n_emit = 1 + jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
            first, chunk_finite = chunk_results(chunk_logits, ch)
            dec_finite = jnp.all(jnp.isfinite(dec_logits), axis=-1)
            spec_finite = jnp.all(jnp.isfinite(spec_logits),
                                  axis=(-2, -1))
            return (_pack_results(
                        newest_tokens(prev, dec_active, nxt, first, ch),
                        dec_finite, first, chunk_finite, n_emit,
                        spec_finite, samples=s),
                    cache["k"], cache["v"], cache.get("k_scale"),
                    cache.get("v_scale"), pool_x, dcache["k"],
                    dcache["v"])

        # the quantized pool's scale planes are donated with it (they
        # are rewritten at every scatter, exactly like the values); the
        # draft pools donate alongside the target's
        if spec_on:
            fn = serving_spec_step
            donate = (3, 4, 8, 9) + ((5, 6) if self.kv_bits else ())
        else:
            fn = serving_block_step if B else serving_step
            donate = (2, 3) + ((4, 5) if self.kv_bits else ()) + (
                (6,) if self._pool_x is not None else ())
        # the body runs shard_mapped over the (data, model) serving
        # submesh.  Pools/params shard over 'model' (kv-head lanes /
        # column-row tiles); the per-slot operands and results (this
        # dispatch's and the previous one's) — a row a slot — over
        # 'data'; the chunk vector stays replicated, and the
        # draft (params + pools) replicates over both axes, so every
        # shard traces the one identical program a shape
        # (decode_builds == 2 regardless of mesh)
        d = topo.DATA_AXIS
        pool_sp = self._pool_spec
        pscale_sp = self._pscale_spec if self.kv_bits else P()
        scale_sp = (self._tp_scale_specs
                    if self._tp_scales is not None else P())
        pools_sp = (pool_sp, pool_sp if self._pool_v is not None else P(),
                    pscale_sp, pscale_sp, P())
        # the previous result comes back as it left: a row a slot
        host_in = (P(d, None), P(d, None), P())
        if spec_on:
            in_specs = ((self._tp_param_specs, scale_sp, P()) + pools_sp
                        + (P(), P()) + host_in)
            out_specs = (P(d, None),) + pools_sp + (P(), P())
        else:
            in_specs = (self._tp_param_specs, scale_sp) + pools_sp + host_in
            out_specs = (P(d, None),) + pools_sp
        # manual over EVERY axis of the submesh (the rest are size 1):
        # a Mosaic call refuses to lower while any mesh axis is auto
        sharded = shard_map(fn, mesh=self.tp_mesh, in_specs=in_specs,
                            out_specs=out_specs)
        # the profiler's build records under this name read ``own``
        get_overlap_profiler().own_program(fn.__name__)
        with self.tp_mesh:
            return jax.jit(
                sharded, donate_argnums=donate if self._donate else ())

    # ------------------------------------------------------------------
    # one scheduler iteration
    # ------------------------------------------------------------------
    def _quarantine(self, slot: int, req: Request, where: str) -> None:
        """Non-finite logits detected in ``slot``: the request FAILS and
        its blocks are DISCARDED (freed without commit, registrations
        dropped — suspect KV must never serve a prefix-cache hit), and
        the batch continues; every other stream is untouched."""
        msg = (f"non-finite logits at {where} (slot {slot}) after "
               f"{len(req.output)} tokens — request quarantined, KV "
               f"blocks discarded")
        if self._rt.enabled:
            self._rt.mark(req, "quarantine", where=where, slot=slot)
        with trace_span("serving/quarantine", req=req.req_id, slot=slot):
            self.scheduler.terminate_slot(slot, RequestStatus.FAILED,
                                          msg, discard=True)
        self._m_quarantined.inc()
        self.lifecycle_counts["quarantined"] += 1
        logger.error(f"serving: {req.req_id}: {msg}")

    def _step_operands(self, dec: List[Tuple[int, Request]],
                       chunk: Optional[Tuple[int, Request, int, int]],
                       spec: List[Tuple[int, Request]] = (),
                       block: Optional[Dict[int, "_BlockPlan"]] = None
                       ) -> tuple:
        """The step's positional operands for one dispatch (see
        ``_build_step``): weights and the pools, which live on the
        device, then the TWO host arrays of the ``_SLOT_COLS`` /
        ``_CHUNK_HEAD`` layout — per-slot state and block tables, and
        the prompt chunk with its sampling state — filled from the
        scheduler's request records AS DISPATCHED (applied counts plus
        what the dispatch in flight adds).  A decoding row whose newest
        token is still on the device says so in ``_TOKEN_SRC`` and
        carries no token.  ``chunk is None`` IS the choice of
        shape: the chunk vector is then its head alone, and the program
        that takes it has no chunk lane.  Both are built fresh: the
        program reads them asynchronously on the chip, and the CPU
        backend may alias a host buffer outright.  No device program is
        launched here."""
        slots = np.zeros((self.num_slots, self._slot_cols), np.int32)
        slots_f, slots_u = slots.view(np.float32), slots.view(np.uint32)
        slots_f[:, _TOP_P] = 1.0
        for slot, req in self.scheduler.running.items():
            table = self.allocator.block_table(req.req_id)
            slots[slot, _SLOT_COLS:_SLOT_COLS + len(table)] = table
            slots[slot, _LENS] = req.planned_cached
        if self.window_blocks:
            self._window_operands(slots, dec, chunk)
        if block is not None:
            # the block lane: a forward's phase, the rows it may fill,
            # and — for a block just opened — its tokens; an open
            # block's are on the device
            for slot, _req in dec:
                bp = block[slot]
                slots[slot, _DEC_ACTIVE] = bp.phase
                slots[slot, _BLOCK_FILL] = bp.fill
                if bp.opened is None:
                    slots[slot, _TOKEN_SRC] = SRC_DEVICE
                else:
                    slots[slot, -self.block_rows:] = bp.opened
            dec = ()
        for slot, req in list(dec) + list(spec):
            if req.flight_tokens:
                slots[slot, _TOKEN_SRC] = SRC_DEVICE
            else:
                slots[slot, _DEC_TOKEN] = req.output[-1]
            slots[slot, _TOP_K] = req.top_k
            slots[slot, _OUT_IDX] = req.planned_output
            slots_u[slot, _KEY:_KEY + 2] = req.prng_key
            slots_f[slot, _TEMP] = req.temperature
            slots_f[slot, _TOP_P] = req.top_p
        for slot, _req in dec:
            slots[slot, _DEC_ACTIVE] = 1
        for slot, _req in spec:
            slots[slot, _SPEC_ACTIVE] = 1
        chunk_vec = np.zeros(
            (_CHUNK_HEAD + (0 if chunk is None else self.chunk_tokens),),
            np.int32)
        chunk_f, chunk_u = (chunk_vec.view(np.float32),
                            chunk_vec.view(np.uint32))
        chunk_f[_C_TOP_P] = 1.0
        if chunk is not None:
            c_slot, req, c_start, c_len = chunk
            chunk_vec[_C_SLOT:_C_KEY] = (c_slot, c_start, c_len, req.top_k,
                                         req.planned_output)
            chunk_u[_C_KEY:_C_KEY + 2] = req.prng_key
            chunk_f[_C_TEMP] = req.temperature
            chunk_f[_C_TOP_P] = req.top_p
            chunk_vec[_CHUNK_HEAD:_CHUNK_HEAD + c_len] = \
                req.prefix[c_start:c_start + c_len]
        return self._device_operands() + (slots, chunk_vec)

    @property
    def _slot_cols(self) -> int:
        """Columns of the per-slot operand: the ``_SLOT_COLS`` scalars,
        then a block table a kind of ``table_kinds``."""
        return (_SLOT_COLS + self.max_pages * len(self.table_kinds)
                + self.block_rows)

    def _window_operands(self, slots: np.ndarray, dec, chunk) -> None:
        """The window kind's part of one dispatch's plan: every row the
        dispatch writes gets its page and gives back the pages its
        window has left (``window_reserve``), then the tables go in
        beside the full kind's: the pages a slot still holds alone (the
        ones it gave back read the null block, which the zeroed operand
        already says)."""
        alloc = self.allocator
        for _slot, req in dec:
            at = req.planned_cached
            alloc.window_reserve(req.req_id, at, at + 1)
        if chunk is not None:
            _c_slot, req, c_start, c_len = chunk
            alloc.window_reserve(req.req_id, c_start, c_start + c_len,
                                 "chunk")
        at = _SLOT_COLS + self.max_pages
        for slot, req in self.scheduler.running.items():
            first, held = alloc.window_pages_held(req.req_id)
            slots[slot, at + first:at + first + len(held)] = held

    def _device_operands(self) -> tuple:
        """The operands that live on the device: weights, then the pools
        and the result array as the last dispatch returned them."""
        pools = (self._pool_k, self._pool_v, self._pool_ks, self._pool_vs,
                 self._pool_x)
        if self._draft_model is not None:
            pools = (self._draft_params,) + pools + (self._dpool_k,
                                                     self._dpool_v)
        return (self._tp_params, self._tp_scales) + pools + (
            self._prev_result,)

    def _launch(self, operands: tuple) -> jax.Array:
        """Enqueue the step on ``operands`` — their chunk vector's
        length selects the shape — keep the pools it returns (the ones
        passed in are donated) and hand back the one array the host
        reads, which is also the next dispatch's operand."""
        result, *pools = self._step_fn(*operands)
        (self._pool_k, self._pool_v, self._pool_ks, self._pool_vs,
         self._pool_x) = pools[:5]
        if self._draft_model is not None:
            self._dpool_k, self._dpool_v = pools[5:]
        self._prev_result = result
        return result

    def _build_both_shapes(self, chunk) -> None:
        """The first dispatch builds BOTH shapes of the step, so that a
        server never compiles under traffic however late its first
        chunk-less (or first chunk-carrying) dispatch comes: the shape
        ``chunk`` does NOT take runs here, once, with every slot
        inactive — it writes only null-block rows — and the dispatch
        that called builds its own by running."""
        with self._ovl.setup_span("setup/build_step"):
            self._step_fn = self._build_step()
            self._launch(self._idle_operands(chunk_lane=chunk is None))

    def _idle_operands(self, chunk_lane: bool) -> tuple:
        """The step's operands with nothing riding — every slot
        inactive, no chunk — in either shape: with the (empty) chunk
        lane or without it."""
        return self._device_operands() + (
            np.zeros((self.num_slots, self._slot_cols), np.int32),
            np.zeros((_CHUNK_HEAD + (self.chunk_tokens if chunk_lane
                                     else 0),), np.int32))

    def _sampler_rows(self, slots: np.ndarray,
                      chunk_vec: np.ndarray) -> Dict[str, int]:
        """What the rows of one dispatch ask the in-program sampler for,
        from the operands its own predicates read
        (``sample_tokens_per_row``): rows at temperature > 0, and those
        of them with a top-k or a top-p set.  A speculating slot is
        ``spec_k + 1`` rows, a riding chunk one."""
        slots_f, chunk_f = slots.view(np.float32), chunk_vec.view(np.float32)
        samples = slots_f[:, _TEMP] > 0
        chunk_samples = bool(chunk_f[_C_TEMP] > 0)
        if not (chunk_samples or samples.any()):
            return {}           # every row greedy: both counters add 0
        filters = samples & ((slots[:, _TOP_K] > 0)
                             | (slots_f[:, _TOP_P] < 1))
        chunk_filters = chunk_samples and bool(
            chunk_vec[_C_TOP_K] > 0 or chunk_f[_C_TOP_P] < 1)
        rows = 1 + self.spec_k * slots[:, _SPEC_ACTIVE]
        return {"sampled_rows": int(rows[samples].sum()) + chunk_samples,
                "filtered_rows": int(rows[filters].sum()) + chunk_filters}

    def _enqueue(self, dec: List[Tuple[int, Request]],
                 chunk: Optional[Tuple[int, Request, int, int]],
                 spec: List[Tuple[int, Request]] = (),
                 ahead: bool = False) -> bool:
        """Enqueue one dispatch of the mixed program — a decode token
        for every slot in ``dec``, a draft+verify round for every slot
        in ``spec`` (draft armed only), plus (optionally) one prompt
        chunk — and note on the request records what it will add when it
        lands (``flight_rows`` / ``flight_tokens``: the state as
        dispatched).  Nothing is read here; :meth:`_apply` reads.
        Returns False when a transient fault at the dispatch site
        skipped the dispatch: the caller abandons the iteration (no
        budget charged, the same work retries NEXT step; streams are
        delayed, never corrupted).  A fatal fault raises
        :class:`ServingError`."""
        try:
            get_fault_injector().check("serving.dispatch")
        except TransientIOError as e:
            logger.warning(f"serving: transient dispatch fault — "
                           f"iteration skipped, will retry: {e}")
            return False
        except FatalIOError as e:
            raise ServingError(
                f"fatal fault at serving dispatch: {e}") from e
        c_len = chunk[3] if chunk is not None else 0
        window_freed = self.allocator.window_freed_total
        ovl = self._ovl
        ovl_on = ovl.enabled
        if ovl_on:
            ovl.mark(overlap.OPERANDS)
        if self._step_fn is None:
            self._build_both_shapes(chunk)
        block = self._plan_blocks(dec) if self.block_rows else None
        operands = self._step_operands(
            dec, chunk, spec, **({} if block is None else {"block": block}))
        rows = self._decode_rows_per_dispatch + (
            0 if chunk is None else self.chunk_tokens)
        if ovl_on:
            ovl.mark(overlap.ENQUEUE)
        t0 = time.perf_counter()
        with trace_span("serving/dispatch", decode=len(dec),
                        chunk_tokens=c_len, spec=len(spec), rows=rows,
                        tp=self.tp_mesh.size, ahead=int(ahead),
                        moe=int("moe_picks" in self.model.PAGED_COUNTERS),
                        sparse=int("sparse_tokens_read"
                                   in self.model.PAGED_COUNTERS),
                        kinds=len(self.allocator.kinds)):
            result = self._launch(operands)
            # queued behind the program now, not requested once the host
            # has noticed that it ended
            result.copy_to_host_async()
        more_counts = {}
        if self.window_blocks:
            # the chunk's slot keeps what its NEXT row's window reaches:
            # whoever is handed the rest writes it in a later program
            if chunk is not None:
                self.allocator.window_trim(chunk[1].req_id,
                                           chunk[2] + c_len)
            more_counts["window_blocks_freed"] = (
                self.allocator.window_freed_total - window_freed)
        # the state as dispatched.  A speculating slot's row count is
        # the device's to say: its dispatch lands before anything else is
        # planned (_plan_iteration), so it carries none
        if block is not None:
            self._dispatched_blocks(dec, block)
            more_counts.update(
                block_rows=len(dec) * self.block_rows,
                block_commits=sum(bp.phase == PHASE_COMMIT
                                  for bp in block.values()),
                block_tokens=sum(bp.new_tokens for bp in block.values()))
        else:
            for _slot, req in dec:
                req.flight_rows += 1
                req.flight_tokens += 1
        ends_prefill = False
        if chunk is not None:
            req = chunk[1]
            req.flight_rows += c_len
            # (a block model's chunk samples nothing: what is left of the
            # prompt rides the first block)
            ends_prefill = (req.planned_cached >= req.prefill_target
                            and not req.prefill_only
                            and not self.block_rows)
            req.flight_tokens += ends_prefill
        self._flight.append(_Flight(
            result, dec, chunk, spec, ends_prefill, ahead, t0,
            dict(decode_rows=(len(dec) * max(1, self.block_rows)
                              + len(spec) * (self.spec_k + 1)),
                 chunk_rows=c_len, rows_computed=rows,
                 host_arrays_in=sum(isinstance(a, np.ndarray)
                                    for a in operands),
                 host_reads_out=1, ahead_dispatches=int(ahead),
                 **more_counts, **self._sampler_rows(*operands[-2:]))
            if ovl_on else None, block))
        return True

    def _plan_blocks(self, dec: List[Tuple[int, Request]]
                     ) -> Dict[int, "_BlockPlan"]:
        """The block lane's plan for one dispatch, a slot of ``dec``, from
        counts alone and changing nothing.  A block opens at the slot's
        committed rows with what is left of its prompt in place and the
        mask token everywhere else; it is denoised while it holds masked
        rows — by the static and the sequential rule ``_fill_schedule``
        says how many remain after each forward; by the dynamic rule only
        the result does, and ``_apply`` writes the count back before the
        next plan (``_sees_ahead``) — and committed by the forward
        after."""
        rows, mask = self.block_rows, self.model.config.mask_token_id
        plan = {}
        for slot, req in dec:
            start, opened = req.planned_cached, None
            masked, step = req.block_masked, req.block_step
            if masked < 0:
                # (only a request's first block after its admission finds
                # prompt tokens past its committed rows)
                left = (req.prefix[start:]
                        if start < len(req.prompt) + len(req.output) else [])
                masked, step = rows - len(left), 0
                opened = left + [mask] * masked
            if masked:
                fill = self._fill_schedule[step]
                plan[slot] = _BlockPlan(PHASE_DENOISE, fill, opened, start,
                                        0, max(0, masked - fill), step)
            else:
                have = req.planned_output
                new = min(start + rows - len(req.prompt) - have,
                          req.max_new_tokens - have)
                plan[slot] = _BlockPlan(PHASE_COMMIT, 0, opened, start, new,
                                        -1, step)
        return plan

    def _dispatched_blocks(self, dec, plan: Dict[int, "_BlockPlan"]) -> None:
        """Note on the request records what the block lane's dispatch
        will have done when it lands: the state as dispatched."""
        for slot, req in dec:
            bp = plan[slot]
            req.block_masked = bp.masked_after
            if bp.phase == PHASE_COMMIT:
                req.block_step = 0
                req.flight_rows += self.block_rows
                req.flight_tokens += bp.new_tokens
            else:
                req.block_step = bp.denoised + 1

    def _apply(self, fl: _Flight) -> int:
        """Read one dispatch's result — the one place the host waits for
        the device — and apply it to the scheduler's request records.
        A row whose request ended after the dispatch was enqueued (eos,
        a quarantine, a cancel, a deadline: news that arrives one
        dispatch late) is VOID: its result is ignored and nothing of it
        is committed; the blocks it wrote into were the request's own
        when it was planned, and whoever holds them next writes them in
        a later program before reading them.  Returns the progress made
        (decode tokens emitted + prefill tokens landed) — the serving
        watchdog's heartbeat."""
        sched = self.scheduler
        dec, chunk, spec = fl.dec, fl.chunk, fl.spec
        ovl = self._ovl
        ovl_on = ovl.enabled
        if ovl_on:
            # from here to the one read below the host waits on the
            # device — for THIS dispatch, with the next one queued behind
            # it when the loop ran ahead
            ovl.mark(overlap.DEVICE_WAIT)
        res = np.asarray(fl.result)
        # ITL = the gap between two successive results (this dispatch's
        # own enqueue-to-read when the device had stood idle before it),
        # captured BEFORE the host-side bookkeeping below (commit
        # hashing, finishes, quarantines) so the histogram stays
        # comparable across PRs
        now = time.perf_counter()
        t0 = max(fl.t0, self._last_result_t)
        dispatch_dt = now - t0
        self._last_result_t = now
        if ovl_on:
            ovl.mark(overlap.APPLY)
        nxt, dec_fin = res[:, _R_NEXT], res[:, _R_DEC_FINITE]
        first, chunk_fin = res[0, _R_FIRST], res[0, _R_CHUNK_FINITE]
        if spec:
            n_emit, spec_fin = res[:, _R_SPEC], res[:, _R_SPEC + 1]
            emitted = res[:, _R_SPEC + 2:]
        live = [(slot, req) for slot, req in dec
                if sched.running.get(slot) is req]
        void = len(dec) - len(live)
        if self._rt.enabled and live:
            # request-track segments reuse t0/dispatch_dt — no extra
            # clock reads on the hot path
            self._rt.on_decode([r for _, r in live], t0, dispatch_dt,
                               len(live))
        progress = 0
        if fl.block is not None:
            progress += self._apply_blocks(fl, live, res)
            live = ()
        for slot, req in live:
            req.flight_rows -= 1
            req.flight_tokens -= 1
            if not bool(dec_fin[slot]):
                # quarantine BEFORE any commit: the row(s) this dispatch
                # wrote are suspect and must not register in the cache
                self._quarantine(slot, req, "decode")
                continue
            req.cached_tokens += 1
            tok = int(nxt[slot])
            req.output.append(tok)
            self._emit_token(req, tok)
            progress += 1
            if req.cached_tokens % self.block_size == 0:
                # a decode-filled block just completed: register it so a
                # preemption (or an identical resubmission) stays warm
                self.allocator.commit_cached(req.req_id, req.prefix,
                                             req.cached_tokens)
            if req.done:
                sched.finish(slot)
        for slot, req in spec:      # landed at once: never void
            if not bool(spec_fin[slot]):
                self._quarantine(slot, req, "spec decode")
                continue
            # the KV rollback is the length vector: positions past
            # lens + appended were written by rejected draft rows but
            # are never attended (and are rewritten before they can be)
            take = min(int(n_emit[slot]),
                       req.max_new_tokens - len(req.output))
            appended = 0
            for j in range(take):
                tok = int(emitted[slot, j])
                req.output.append(tok)
                self._emit_token(req, tok)
                appended += 1
                if req.done:
                    break
            old = req.cached_tokens
            req.cached_tokens += appended
            progress += appended
            if self._rt.enabled:
                self._rt.on_spec([req], t0, dispatch_dt, self.spec_k,
                                 max(0, appended - 1))
            self.spec_counts["proposed"] += self.spec_k
            self._m_spec_proposed.inc(self.spec_k)
            if appended > 1:
                self.spec_counts["accepted"] += appended - 1
                self._m_spec_accepted.inc(appended - 1)
            if req.cached_tokens // self.block_size \
                    > old // self.block_size:
                self.allocator.commit_cached(req.req_id, req.prefix,
                                             req.cached_tokens)
            if req.done:
                sched.finish(slot)
        if dec or spec:
            # exemplar: any batch participant experienced this dispatch
            # latency; None while request tracing is off (no-op)
            self._m_itl.observe(dispatch_dt,
                                exemplar=(dec[0][1].trace_id if dec
                                          else spec[0][1].trace_id))
            for h in self.mirror_hists.get("itl", ()):
                h.observe(dispatch_dt)
            if progress and fl.block is None:
                self._m_tokens.inc(progress)
        if chunk is not None:
            c_slot, req, c_start, c_len = chunk
            if sched.running.get(c_slot) is not req:
                void += c_len
            elif not bool(chunk_fin):
                self._quarantine(c_slot, req, "prefill chunk")
            else:
                req.flight_rows -= c_len
                req.flight_tokens -= fl.ends_prefill
                req.cached_tokens += c_len
                progress += c_len
                self._m_prefill_tokens.inc(c_len)
                self.allocator.commit_cached(req.req_id, req.prefix,
                                             req.cached_tokens)
                if self._rt.enabled:
                    self._rt.on_prefill_chunk(
                        req, t0, dispatch_dt, c_start, c_len,
                        done=req.cached_tokens >= req.prefill_target)
                if (req.cached_tokens >= req.prefill_target
                        and req.prefill_only):
                    # prefill leg of a disaggregated handoff: publish
                    # the chain, finish OK, emit no token — the decode
                    # leg samples output index 0 with the same pinned
                    # key, so the stream is identical to a one-replica
                    # run
                    self._finish_prefill_only(c_slot, req)
                elif (req.cached_tokens >= req.prefill_target
                      and not self.block_rows):
                    # the chunk that completed the prefix carries the
                    # first token (sampled from its last valid position
                    # with the request's own key at output index 0 —
                    # identical to what a decode step would emit)
                    tok = int(first)
                    req.output.append(tok)
                    self._emit_token(req, tok)
                    self._m_tokens.inc()
                    self._observe_first_token(req)
                    if req.done:
                        sched.finish(c_slot)
        self.flight_counts["dispatches"] += 1
        self.flight_counts["ahead_dispatches"] += fl.ahead
        self.flight_counts["void_rows"] += void
        if ovl_on and fl.counts is not None:
            counted = self.model.PAGED_COUNTERS
            ovl.count_dispatch(
                **fl.counts, void_rows=void,
                # what the program counted: the row's last columns
                **(dict(zip(counted, map(int, res[0, -len(counted):])))
                   if counted else {}))
        return progress

    def _observe_first_token(self, req: Request) -> None:
        """Stamp a request's first visible token (once) and observe its
        TTFT."""
        if req.first_token_time is not None:
            return
        req.first_token_time = time.perf_counter()
        ttft = req.first_token_time - req.submit_time
        self._m_ttft.observe(ttft, exemplar=req.trace_id)
        for h in self.mirror_hists.get("ttft", ()):
            h.observe(ttft)

    def _apply_blocks(self, fl: _Flight, live, res: np.ndarray) -> int:
        """The block lane's part of :meth:`_apply`: every live slot's
        block as its forward left it.  A denoise forward moves nothing
        but the block (its rows' k/v are overwritten by the next
        forward); a commit makes the block's new tokens visible at once,
        moves the request's committed rows by a whole block and registers
        the pages it completed.  Returns the progress made (tokens made
        visible, and one for each denoise forward: it moved state)."""
        sched, rows = self.scheduler, self.block_rows
        mask = self.model.config.mask_token_id
        finite = res[:, _R_DEC_FINITE]
        blocks = res[:, _R_SPEC:_R_SPEC + rows]
        denoise = commits = tokens = 0
        for slot, req in live:
            bp = fl.block[slot]
            commit = bp.phase == PHASE_COMMIT
            if commit:
                req.flight_rows -= rows
                req.flight_tokens -= bp.new_tokens
            if not bool(finite[slot]):
                self._quarantine(slot, req, "block forward")
                continue
            block = [int(t) for t in blocks[slot]]
            req.block_forwards += 1
            if req.block_steps is not None:
                req.block_steps.append(
                    (bp.start, "commit" if commit else "denoise", block))
            if not commit:
                denoise += 1
                if self.unmask_rule == "low_confidence_dynamic":
                    # the device's to say: read before the next plan
                    req.block_masked = block.count(mask)
                continue
            commits += 1
            self._m_block_steps.observe(bp.denoised)
            # the block's rows past what the prompt left in it, as far as
            # the request asked for (the rest of its last block is dropped)
            at = len(req.prompt) + len(req.output) - bp.start
            new = block[at:at + bp.new_tokens]
            if req.eos_token_id is not None and req.eos_token_id in new:
                new = new[:new.index(req.eos_token_id) + 1]
            req.cached_tokens += rows
            for tok in new:
                req.output.append(tok)
                self._emit_token(req, tok)
            tokens += len(new)
            if new:
                self._observe_first_token(req)
            if req.cached_tokens % self.block_size == 0:
                self.allocator.commit_cached(req.req_id, req.prefix,
                                             req.cached_tokens)
            if req.done:
                sched.finish(slot)
        # the registry and its plain-int mirror: applied forwards only
        for key, counter, n in (
                ("denoise", self._m_block_denoise, denoise),
                ("commit", self._m_block_commit, commits),
                ("rows", self._m_block_rows, rows * (denoise + commits)),
                ("tokens", self._m_block_tokens, tokens)):
            self.block_counts[key] += n
            counter.inc(n)
        self._m_tokens.inc(tokens)
        return denoise + tokens

    def _land(self, n: Optional[int] = None) -> int:
        """Read and apply the ``n`` oldest dispatches in flight (all of
        them by default), oldest first; returns the progress they
        made."""
        if n is None:
            n = len(self._flight)
        landing, self._flight = self._flight[:n], self._flight[n:]
        return sum(self._apply(fl) for fl in landing)

    def _dispatch(self, dec: List[Tuple[int, Request]],
                  chunk: Optional[Tuple[int, Request, int, int]],
                  spec: List[Tuple[int, Request]] = ()
                  ) -> Optional[int]:
        """One dispatch in the synchronous order: enqueue it, then read
        and apply it (and whatever was in flight before it).  Returns the
        progress made, or ``None`` when a transient fault skipped the
        dispatch."""
        if not self._enqueue(dec, chunk, spec):
            return None
        return self._land()

    def step(self) -> bool:
        """One continuous-batching iteration: sweep deadlines, admit
        (taking prefix-cache hits), guarantee KV capacity, then dispatch
        the mixed program — one decode token for every live slot riding
        alongside up to ``prefill_chunk_tokens`` of prompt chunks.
        Each call applies exactly ONE iteration's results; the iteration
        it plans and enqueues is the one AFTER the one it applies
        whenever that can be planned from counts alone (``_sees_ahead``),
        so the device always has its next program queued — a call on an
        idle engine enqueues two iterations and applies the first.
        Returns True while work remains or an iteration is in flight.

        Robustness (docs/serving.md "Failure handling & overload"):
        expired deadlines terminate WAITING and RUNNING requests at this
        boundary; non-finite slots are quarantined inside the dispatch;
        and the no-progress watchdog raises :class:`ServingError` with
        scheduler diagnostics after ``serving.no_progress_steps``
        consecutive iterations that moved nothing (no tokens, no prefill
        chunks, no terminal transitions) while work remained."""
        try:
            result = self._step_impl()
            # iteration boundary reached with the loop alive: stamp the
            # liveness beat the elastic agent / fleet watchdog reads
            # (rate-limited inside maybe_beat)
            self.heartbeat.maybe_beat()
            return result
        except ServingError as e:
            # black-box flight recorder: seal the post-mortem bundle
            # (snapshot ring + terminals + metrics + trace) before the
            # error propagates — dump() never raises and never masks
            # the original failure
            if self._fr.enabled:
                self._fr.dump("serving_error", str(e), extra={
                    "diagnose": self._diagnose("engine state at failure")})
            raise

    def _sees_ahead(self) -> bool:
        """Whether the iteration after the one on the device can be
        planned NOW, from counts the host already holds.  Decided by what
        the engine can observe, never by an option; when it cannot, the
        dispatch in flight lands first and the loop runs in the
        synchronous order:

          * a draft armed — a speculating slot grows by ``n_emit`` rows,
            which only the result says;
          * a host->pool promotion pending — it lands with nothing in
            flight (a block SPILL needs no rule: its gather reads the
            pool as the dispatch in flight returns it, so it waits for
            that dispatch by itself);
          * a ``prefill_only`` request aboard — its hand-off publishes
            the chain from the pool at apply time;
          * the decoding slots' next rows need more blocks than the pool
            has free — ``ensure_decode_capacity`` would preempt, and a
            victim's recompute restarts from applied counts."""
        sched = self.scheduler
        if self.block_rows and self.unmask_rule == "low_confidence_dynamic":
            # the device decides when a block is full: the host reads it
            # before it plans that slot's next forward
            return False
        return (self._draft_model is None
                and not self.allocator.num_pending
                and not any(r.prefill_only for r in sched.running.values())
                and sched.decode_growth_blocks() <= self.allocator.num_free)

    def _plan_iteration(self, ahead: bool = False) -> int:
        """Plan one iteration and enqueue its dispatches: sweep
        deadlines, guarantee KV capacity, admit (taking prefix-cache
        hits), land promotions, then one dispatch — a decode token for
        every live slot beside up to ``prefill_chunk_tokens`` of a
        prompt chunk — and one more for each chunk remainder the budget
        still covers.  Every count read here is the state AS DISPATCHED,
        so with ``ahead`` (the previous iteration still on the device)
        the plan is the one the synchronous loop would make after that
        iteration landed in full.  Returns the progress made on the way
        (blocks promoted; a speculative dispatch's tokens — it lands
        before the next is planned)."""
        sched = self.scheduler
        ovl = self._ovl
        if ovl.enabled:
            ovl.mark(overlap.PLAN)
        sched.sweep_deadlines()
        # capacity BEFORE admission: running sequences claim their next
        # block first, so a fresh admission is never immediately chosen
        # as the preemption victim (which would discard the prefill
        # it just paid for)
        for req in sched.ensure_decode_capacity():
            self._m_preempt.inc()
            logger.info(f"serving: preempted {req.req_id} on KV pressure "
                        f"({req.preemptions} time(s))")
        sched.schedule_admissions()
        if ahead and not self._sees_ahead():
            # an admission brought a promotion or a hand-off: they are
            # served once the iteration on the device has landed
            return 0
        # land queued host->pool promotions in the admission window:
        # PROMOTING requests are held out of next_prefill_chunk until
        # their claimed blocks carry real KV again
        promoted = self._service_promotions()
        self._drain_terminal_events()
        self._update_gauges()

        # a landed promotion MOVED state (the request it unblocks may
        # only prefill next iteration) — count it as progress so a
        # promote-only iteration never trips the watchdog
        progress = promoted
        budget = self.chunk_tokens
        include_decode = True
        while True:
            chunk = sched.next_prefill_chunk(budget)
            dec = sched.decoding_slots() if include_decode else []
            spec: List[Tuple[int, Request]] = []
            if dec and self._draft_model is not None:
                # speculate on every decoding slot that (a) still wants
                # >= 2 tokens (one round must be able to beat plain
                # decode), (b) fits spec_k + 1 more positions inside the
                # sequence bound, and (c) can grow its block table to
                # cover the draft rows WITHOUT preempting anyone
                # (try_grow never preempts — under KV pressure slots
                # just fall back to plain decode)
                S = self.spec_k + 1
                limit = min(self.engine.config.max_out_tokens,
                            sched.max_tokens_per_seq())
                kept = []
                for slot, req in dec:
                    if (req.max_new_tokens - len(req.output) >= 2
                            and req.cached_tokens + S <= limit
                            and sched.try_grow(slot, S)):
                        spec.append((slot, req))
                    elif req.state is RequestState.RUNNING:
                        # try_grow can fail a request fatally; only
                        # still-running slots keep their decode seat
                        kept.append((slot, req))
                dec = kept
            if not dec and not spec and chunk is None:
                break
            if spec:
                # what a speculating slot leaves behind is the device's
                # to say: it lands before anything is planned past it
                landed = self._dispatch(dec, chunk, spec)
                enqueued = landed is not None
                progress += landed or 0
            else:
                enqueued = self._enqueue(dec, chunk, ahead=ahead)
            if not enqueued:
                # transient dispatch fault: abandon the iteration — the
                # chunk budget was NOT charged and the same decode/chunk
                # work retries next step
                break
            include_decode = False
            if chunk is None:
                break
            budget -= chunk[3]
            if budget <= 0:
                break
            if ovl.enabled:
                ovl.mark(overlap.PLAN)       # a second dispatch follows
        return progress

    def _step_impl(self) -> bool:
        sched = self.scheduler
        ovl = self._ovl
        if ovl.enabled:
            ovl.begin()
        finished_before = len(sched.finished)
        progress = 0
        if not self._flight:
            # nothing on the device: the synchronous order's first half
            progress += self._plan_iteration()
        landing = len(self._flight)
        if landing and self._sees_ahead():
            # one iteration ahead: the device finds it queued when the
            # one it is running ends, and everything below — the read,
            # the apply, the caller's own work between two step()s —
            # runs under a device program
            progress += self._plan_iteration(ahead=True)
        # each call applies exactly one iteration's results
        progress += self._land(landing)
        if ovl.enabled:
            ovl.mark(overlap.APPLY)
        self._drain_terminal_events()
        self._update_gauges()
        # one flush per iteration boundary: every token emitted above
        # and every terminal transition reaches its stream callbacks
        # here, on the serving thread, in emission order
        self._flush_events()
        if self._fr.enabled:
            # all plain host-side ints — no device interaction
            self._fr.record(self._flight_snapshot())
        # terminal transitions count as progress: a sweep that expires
        # requests, a quarantine, or a thrash-fail all MOVED state.
        # Preemptions deliberately do not — a preemption-only iteration
        # is exactly the livelock signature the watchdog exists for.
        progress += len(sched.finished) - finished_before
        self._update_drain_rate(len(sched.finished) - finished_before)
        working = sched.has_work or bool(self._flight)
        if progress or not working:
            self._no_progress = 0
        else:
            self._no_progress += 1
            if self.no_progress_steps and \
                    self._no_progress >= self.no_progress_steps:
                raise ServingError(self._diagnose(
                    f"serving made no progress for {self._no_progress} "
                    f"consecutive iterations (zero tokens, zero prefill, "
                    f"zero terminal transitions) — scheduler wedged or "
                    f"every dispatch faulted"))
        if ovl.enabled:
            ovl.end("serving")
        return working

    def _update_drain_rate(self, n_finished: int) -> None:
        """EMA of wall seconds per FINISHED request, fed by every
        iteration boundary — the drain rate behind the SHED
        ``retry_after_s`` hint."""
        if n_finished <= 0:
            return
        now = time.perf_counter()
        if self._last_finish_t is not None:
            per = (now - self._last_finish_t) / n_finished
            self._drain_rate_ema = per if self._drain_rate_ema is None \
                else 0.7 * self._drain_rate_ema + 0.3 * per
        self._last_finish_t = now

    def _estimate_retry_after(self) -> float:
        return estimate_retry_after_s(self._drain_rate_ema)

    def _flight_snapshot(self) -> dict:
        """One flight-recorder frame: the engine state an operator needs
        to reconstruct the final iterations after a crash."""
        sched, alloc = self.scheduler, self.allocator
        return {
            "t": time.perf_counter(),
            "queue_depth": sched.queue_depth,
            "active_slots": sched.active_slots,
            "pool_used": alloc.num_used,
            "pool_free": alloc.num_free,
            "pool_cached": alloc.num_cached,
            "preemptions": sched.preemption_count,
            "pinned": sum(1 for r in sched.running.values()
                          if sched.pinned(r)),
            "no_progress": self._no_progress,
            "lifecycle": dict(self.lifecycle_counts),
            "spec": dict(self.spec_counts),
            "decode_builds": self.decode_builds,
            "in_flight": len(self._flight),
            "flight": dict(self.flight_counts),
            "host_pending": alloc.num_pending,
            "host": dict(self.host_counts),
        }

    def _diagnose(self, headline: str) -> str:
        """Scheduler + pool state snapshot for loud errors (watchdog,
        non-drain): enough to see WHICH request is stuck and why."""
        sched, alloc = self.scheduler, self.allocator
        lines = [headline,
                 f"  queue_depth={sched.queue_depth} "
                 f"active_slots={sched.active_slots}/{self.num_slots} "
                 f"pool used={alloc.num_used} free={alloc.num_free} "
                 f"cached={alloc.num_cached} of {alloc.usable_blocks}"]
        for slot, req in sorted(sched.running.items()):
            lines.append(
                f"  slot {slot}: {req.req_id} cached={req.cached_tokens}"
                f"/{req.prefill_target} out={len(req.output)}"
                f"/{req.max_new_tokens} preemptions={req.preemptions}"
                f"{' PINNED' if sched.pinned(req) else ''}")
        for req in list(sched.waiting)[:8]:
            lines.append(f"  waiting: {req.req_id} "
                         f"prompt={len(req.prompt)} "
                         f"preemptions={req.preemptions}")
        if sched.queue_depth > 8:
            lines.append(f"  ... and {sched.queue_depth - 8} more waiting")
        return "\n".join(lines)

    def _update_gauges(self) -> None:
        self._m_queue.set(self.scheduler.queue_depth)
        self._m_active.set(self.scheduler.active_slots)
        self._m_blocks.set(self.allocator.num_used)
        self._m_cached.set(self.allocator.num_cached)
        d = self.allocator.hit_tokens_total - self._hits_polled
        if d:
            self._m_hit_tokens.inc(d)
            self._hits_polled += d
        d = self.allocator.evictions_total - self._evictions_polled
        if d:
            self._m_evictions.inc(d)
            self._evictions_polled += d
        hc = self.host_cache
        if hc is None:
            return
        hp = self._host_polled
        for key, counter, cur in (
                ("spills", self._m_host_spills, hc.spills_total),
                ("demotions", self._m_host_demotions, hc.demotions_total),
                ("evictions", self._m_host_evictions, hc.evictions_total),
                ("dram_hits", self._m_host_dram_hits,
                 hc.hits_total.get("dram", 0)),
                ("nvme_hits", self._m_host_nvme_hits,
                 hc.hits_total.get("nvme", 0)),
                ("hit_tokens", self._m_host_hit_tokens,
                 self.allocator.host_hit_tokens_total)):
            d = cur - hp[key]
            if d:
                counter.inc(d)
                hp[key] += d
        tiers = hc.tier_names
        if "dram" in tiers:
            self._m_host_dram_bytes.set(hc.resident_bytes("dram"))
        if "nvme" in tiers:
            self._m_host_nvme_bytes.set(hc.resident_bytes("nvme"))
        self._m_promote_depth.set(self.allocator.num_pending)

    def _default_max_steps(self) -> int:
        """A generous drain bound computed from the queued work: enough
        iterations to prefill and decode every request SERIALLY, times a
        preemption-recompute allowance, plus slack for admission-only
        and fault-skipped iterations.  Far above any healthy drain, so
        hitting it means a scheduler bug — which is the point: ``run()``
        without an explicit ``max_steps`` must never spin forever."""
        sched = self.scheduler
        work = list(sched.waiting) + list(sched.running.values())
        if not work:
            return 1
        steps = 0
        for r in work:
            # worst-case prefix at a late re-admission includes every
            # token the request may ever generate
            prefix = len(r.prompt) + r.max_new_tokens
            forwards = r.max_new_tokens
            if self.block_rows:
                # denoising_steps + 1 forwards a block, the first and the
                # last block partly filled
                forwards = ((r.max_new_tokens // self.block_rows + 2)
                            * (self.denoising_steps + 1))
            steps += -(-prefix // self.chunk_tokens) + forwards + 2
            if self.host_cache is not None:
                # a fully host-warm prefix promotes promote_parallelism
                # blocks per iteration while the request waits PROMOTING
                steps += -(-prefix // self.block_size)
        allowance = (sched.max_preemptions or 8) + 1
        return steps * allowance + 64

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain the queue; returns every terminal request — natural
        completions (``status OK``) and cancelled / timed-out / shed /
        failed ones alike (check ``req.status``).  ``max_steps`` bounds
        the drain; ``None`` computes a generous bound from the queued
        work (tokens, chunks, preemption allowance), so a scheduler bug
        or a preemption livelock is a loud :class:`ServingError` with
        diagnostics, never a silent spin."""
        if max_steps is None:
            max_steps = self._default_max_steps()
        steps = 0
        while self.step():
            steps += 1
            if steps >= max_steps:
                msg = self._diagnose(
                    f"serving did not drain within {max_steps} steps")
                if self._fr.enabled:
                    self._fr.dump("serving_error", msg)
                raise ServingError(msg)
        # a drained pool must hold zero sequence-referenced blocks
        # (cached-LRU blocks may remain — they are reclaimable capacity,
        # not leaks) — leak check
        self.allocator.assert_consistent()
        if self.allocator.num_used:
            from .block_allocator import BlockPoolError
            raise BlockPoolError(
                f"{self.allocator.num_used} KV blocks still held after "
                f"drain — scheduler leak")
        return list(self.scheduler.finished)
