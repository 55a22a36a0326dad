"""Inference engine: TP-sliced serving with a compiled decode loop.

Role-equivalent of the reference ``InferenceEngine``
(`/root/reference/deepspeed/inference/engine.py:33`). Mapping of its moving
parts onto the TPU design:

  _create_model_parallel_group (engine.py:196)  → a {'model': tp, 'data': n}
      mesh; TP layout comes from the model's partition_specs (declarative
      auto-TP — `module_inject/auto_tp.py` heuristic when the model has none)
  _load_checkpoint / meta-tensor path (:387,:287) → orbax restore of the
      params subtree DIRECTLY into the TP NamedShardings: every chip
      materializes only its slice, whatever topology saved the checkpoint
      (the reference needs per-architecture checkpoint loaders + mp-resharding
      code, `module_inject/load_checkpoint.py`, `state_dict_factory.py`)
  dtype conversion (:457)                        → cast on load
  CUDA-graph capture/replay (:474,:493)          → jit: the decode step is one
      compiled program re-dispatched with donated cache buffers — replay
      without per-op launch overhead is the default execution model
  forward (:515) / _generate (:544)              → forward() logits;
      generate() = prefill + lax.scan decode loop, fully compiled, with
      greedy/temperature/top-k/top-p sampling and EOS masking
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import get_overlap_profiler
from ..parallel import topology as topo
from ..runtime.resilience import run_with_timeout
from ..utils.logging import logger
from .config import DeepSpeedInferenceConfig


class InferenceEngine:
    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params: Any = None, mesh: Optional[Mesh] = None):
        self.config = config or DeepSpeedInferenceConfig()
        self.dtype = self.config.compute_dtype()
        # an explicit observability block arms the process-global
        # telemetry singletons before the serving stack is built (the
        # serving engine captures tracer/registry/profiler handles at
        # construction); None never touches them — an engine may be
        # joining a process another engine already configured
        if self.config.observability is not None:
            from ..observability import configure as _obs_configure
            import jax as _jax
            _obs_configure(self.config.observability,
                           rank=_jax.process_index())
        # int8 x TP composes: TP serving switches the quantizer to
        # per-output-channel scales (see _quantize_weights) whose scale
        # vector shards exactly like the kernel's last axis — no quant
        # group ever crosses a shard boundary.

        # kernel injection: on a TransformerLM this toggles the Pallas
        # flash/decode attention path (the reference swaps in fused CUDA
        # modules, replace_module.py:306; here kernels are a config bit).
        # Only the xla<->flash pair is rewritten: blocksparse/ring are
        # deliberate MODEL choices whose semantics (layouts, sequence
        # sharding) must survive serving.
        if hasattr(getattr(model, "config", None), "attn_impl") and \
                model.config.attn_impl in ("xla", "flash") and \
                not getattr(model.config, "attention_layers", ()) and \
                not getattr(model.config, "attn_softmax_scale", 0.0):
            # per-layer windows / non-standard softmax scale (GPT-Neo) pin
            # the model to the xla path — the Pallas kernels take neither
            import dataclasses as _dc
            want = "flash" if self.config.replace_with_kernel_inject else "xla"
            if model.config.attn_impl != want:
                model = type(model)(
                    _dc.replace(model.config, attn_impl=want),
                    getattr(model, "constrain", None))
        self.module = model

        tp = self.config.tensor_parallel.tp_size \
            if self.config.tensor_parallel.enabled else 1
        if mesh is None:
            n = len(jax.devices())
            if n % tp:
                raise ValueError(
                    f"tp_size {tp} does not divide {n} devices")
            from ..runtime.config import MeshConfig
            mesh = topo.build_mesh(MeshConfig(model=tp, data=n // tp))
        self.mesh = mesh

        ovl = get_overlap_profiler()
        # -- TP layout: model-provided specs or the auto-TP heuristic ------
        with ovl.setup_span("setup/param_specs"):
            shapes = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            if hasattr(model, "partition_specs"):
                self.param_specs = model.partition_specs(shapes)
            else:
                from ..module_inject.auto_tp import auto_tp_specs
                self.param_specs = auto_tp_specs(shapes, self.mesh)
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), self.param_specs,
                is_leaf=lambda x: isinstance(x, P))

        # -- weights: explicit > checkpoint > fresh init --------------------
        with ovl.setup_span("setup/place_params"):
            if params is not None:
                self.params = jax.device_put(
                    jax.tree_util.tree_map(self._cast, params), shardings)
            elif self.config.checkpoint:
                self.params = self._load_checkpoint(
                    self.config.checkpoint, self.config.checkpoint_tag,
                    shapes, shardings)
            else:
                logger.warning("init_inference without params or "
                               "checkpoint — using fresh random weights")
                # bound once, called once — never re-wrapped per call (the
                # TRACE003 discipline; __init__ runs once per engine)
                init_fn = jax.jit(
                    lambda r: jax.tree_util.tree_map(
                        self._cast, model.init(r)),
                    out_shardings=shardings)
                with self.mesh:
                    self.params = init_fn(jax.random.PRNGKey(0))

        # -- the layout the model's step reads its weights in, made ONCE,
        # here, whichever source they came from (a checkpoint and
        # ``model.init`` keep the published one).  The engine holds the
        # serving tree alone; a model that reads its weights as stored
        # hands back the tree it was given.  ``quant.enabled`` below
        # quantizes THIS tree: int8 leaves in the layout the step reads.
        if hasattr(model, "serving_params"):
            with ovl.setup_span("setup/serving_params"), self.mesh:
                laid = model.serving_params(self.params)
            if laid is not self.params:
                self.params = laid
                self.param_specs = model.partition_specs(laid)

        # -- int8 weight-only serving (reference GroupQuantizer at
        # module_inject/replace_module.py:150: qkv/mlp weights stored int8,
        # dequantized into the matmul) ---------------------------------
        self._quantized = False
        if self.config.quant.enabled:
            with ovl.setup_span("setup/quantize"):
                self._quantize_weights()

        self._fwd = None
        self._gen_fns: Dict[Tuple, Any] = {}
        self._latencies: list = []      # per-token DECODE-only seconds
        self._ttfts: list = []          # prefill -> first-token seconds
        self._serving = None
        # model-time profiling (reference inference/engine.py:159
        # profile_model_time / :503 model_times): disabled until enabled,
        # then every forward/generate call appends its synced wall time
        self.model_profile_enabled = False
        self._model_times: list = []
        self._profiled_keys: set = set()

    def _cast(self, x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(self.dtype)
        return x

    # ------------------------------------------------------------------
    # int8 weight-only
    # ------------------------------------------------------------------
    def _quantize_weights(self) -> None:
        """Matrix leaves → int8 + fp32 scales, kept as parallel trees.
        The ``blocks`` subtree (the bulk of the weights) quantizes
        PER-LAYER and dequantizes inside the model's scan body via the
        ``block_transform`` seam — the live full-precision set is ONE
        layer, not the tree (the role of the reference's per-gemm
        dequant, `csrc/transformer/inference/csrc/dequantize.cu`).
        Non-block leaves (with the default scope: nothing — embeddings/
        heads are excluded) dequantize on program entry."""
        from ..ops.quantizer.quantizer import quantize
        bits = self.config.quant.bits or 8
        tmpl = jax.device_get(jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), self.params))
        # Scope: attention/MLP matrices only by default (reference
        # GroupQuantizer scope) — embedding tables, the tied/untied lm_head
        # and the MLM head keep full precision unless
        # quant.quantize_embeddings widens it.
        skip_roots = (() if self.config.quant.quantize_embeddings
                      else ("embed", "pos_embed", "type_embed", "lm_head",
                            "mlm_head"))

        def flag(path, l):
            root = str(path[0].key) if path else ""
            return (len(l.shape) >= 2
                    and jnp.issubdtype(l.dtype, jnp.floating)
                    and root not in skip_roots)
        self._qflags = jax.tree_util.tree_map_with_path(flag, tmpl)
        # logical matrix shape per leaf: block leaves record the PER-LAYER
        # slice shape (the unit the scan body dequantizes)
        self._qshapes = jax.tree_util.tree_map_with_path(
            lambda p, l: (tuple(l.shape[1:])
                          if p and str(p[0].key) == "blocks"
                          else tuple(l.shape)), tmpl)

        tp_live = ((self.config.tensor_parallel.enabled
                    and self.config.tensor_parallel.tp_size > 1)
                   or (self.config.serving.enabled
                       and self.config.serving.mesh.model > 1))
        # grouped scales reshape the flat weight to [G, -1]: groups cross
        # TP shard boundaries, so TP serving uses per-output-CHANNEL
        # scales instead (reference GroupQuantizer slices groups per TP
        # rank, replace_module.py:150; per-channel is the partition-free
        # re-expression — the scale vector shards like the kernel's last
        # axis and dequant stays shard-local)
        self._qmode = "channel" if tp_live else "group"

        def g_of(leaf_shape):
            # largest divisor of n at or under n/2048: group count must
            # divide the element count (quantize reshapes to [G, -1])
            n = int(np.prod(leaf_shape))
            target = max(1, n // 2048)
            for g in range(target, 0, -1):
                if n % g == 0:
                    return g
            return 1

        levels = float(2 ** (bits - 1) - 1)

        def qz_one(l, f, shape):
            """Quantize one logical matrix of ``shape`` (the per-layer
            slice for stacked block leaves)."""
            if not f:
                return l, jnp.zeros((0, 1), jnp.float32)
            if self._qmode == "channel":
                a = jnp.max(jnp.abs(l.astype(jnp.float32)),
                            axis=tuple(range(l.ndim - 1)))
                s = jnp.where(a > 0, a / levels, 1.0)
                q = jnp.clip(jnp.round(l.astype(jnp.float32) / s),
                             -levels, levels)
                return q.astype(jnp.int8), s.astype(jnp.float32)
            q, s, _ = quantize(l, bits, g_of(shape), True)
            return q.astype(jnp.int8), s

        def qz(path, l, f):
            if path and str(path[0].key) == "blocks":
                # stacked [L, ...]: per-layer quantization so the scan
                # body can dequantize its own slice
                return jax.vmap(lambda w: qz_one(w, f, l.shape[1:]))(l)
            return qz_one(l, f, l.shape)

        # bound once, called once per quantization pass (TRACE003)
        qz_fn = jax.jit(lambda p: jax.tree_util.tree_map_with_path(
            qz, p, self._qflags,
            is_leaf=lambda x: isinstance(x, jax.Array)))
        with self.mesh:
            pairs = qz_fn(self.params)
        tup = lambda t: isinstance(t, tuple)  # noqa: E731
        self.params = jax.tree_util.tree_map(lambda t: t[0], pairs,
                                             is_leaf=tup)
        self._scales = jax.tree_util.tree_map(lambda t: t[1], pairs,
                                              is_leaf=tup)
        self._quantized = True
        # per-layer dequant rides the model's scan-body seam
        self.module.block_transform = self._block_dequant
        q_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(
            self.params))
        logger.info(f"int8 weight-only serving: params now "
                    f"{q_bytes / 2**20:.1f} MiB on device "
                    f"(bits={bits})")

    def _dequant_leaf(self, q, s, f, sh):
        if not f:
            return q
        if self._qmode == "channel":
            # per-output-channel: broadcast multiply on the last axis,
            # shard-local under TP
            return (q.astype(jnp.float32) * s).astype(self.dtype)
        from ..ops.quantizer.quantizer import dequantize
        return dequantize(q, s, None, sh, self.dtype)

    def _block_dequant(self, sl):
        """block_transform seam: one layer's {q, s} slice → standard
        block tree (full precision lives for one scan iteration)."""
        return jax.tree_util.tree_map(self._dequant_leaf, sl["q"],
                                      sl["s"], self._qflags["blocks"],
                                      self._qshapes["blocks"])

    def _model_params(self, params, scales=None):
        """What compiled programs call to get model-consumable params:
        non-block leaves dequantize here (default scope: none — they are
        excluded), block leaves stay int8 and ride into the scan as
        {q, s} for per-layer dequant via block_transform."""
        if not self._quantized:
            return params
        out = {k: jax.tree_util.tree_map(
            self._dequant_leaf, v, scales[k], self._qflags[k],
            self._qshapes[k]) for k, v in params.items() if k != "blocks"}
        out["blocks"] = {"q": params["blocks"], "s": scales["blocks"]}
        return out

    def _load_checkpoint(self, ckpt_dir: str, tag, shapes, shardings):
        """Restore the params subtree of a training checkpoint, resharded
        into the serving TP layout (reference _load_checkpoint,
        `inference/engine.py:387`, without per-architecture loaders)."""
        import os
        import orbax.checkpoint as ocp
        if tag is None:
            with open(os.path.join(ckpt_dir, "latest")) as f:
                tag = f.read().strip()
        path = os.path.join(os.path.abspath(ckpt_dir), str(tag), "state")
        target = {"params": jax.tree_util.tree_map(
            lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, self.dtype,
                                                 sharding=sh),
            shapes, shardings)}
        restore_args = ocp.checkpoint_utils.construct_restore_args(target)
        ckptr = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
        restored = ckptr.restore(
            path, args=ocp.args.PyTreeRestore(
                item=target, restore_args=restore_args,
                partial_restore=True))
        logger.info(f"inference weights loaded from {path} (tp="
                    f"{topo.mp_world_size(self.mesh)})")
        return restored["params"]

    # ------------------------------------------------------------------
    # forward: full-sequence logits
    # ------------------------------------------------------------------
    def forward(self, input_ids) -> jnp.ndarray:
        if self._fwd is None:
            with self.mesh:
                self._fwd = jax.jit(
                    lambda p, s, ids: self.module.apply(
                        self._model_params(p, s), ids))
        ids = jnp.asarray(input_ids)
        # a fresh shape triggers trace+compile (seconds) — exclude it from
        # the profile the way latency_stats drops its compile sample
        first = ("fwd", ids.shape) not in self._profiled_keys
        self._profiled_keys.add(("fwd", ids.shape))
        t0 = (time.perf_counter()
              if self.model_profile_enabled and not first else None)
        out = self._fwd(self.params, getattr(self, "_scales", None), ids)
        if t0 is not None:
            # async dispatch would undercount — sync, but under the
            # resilience timeout guard: a wedged device drops the sample
            # with a logged error instead of hanging the server
            if self._guarded_sync(out):
                self._model_times.append(time.perf_counter() - t0)
        return out

    def _guarded_sync(self, out) -> bool:
        """Deliberate device sync (any pytree) under the profile timeout
        guard. True iff the sync completed (sample is valid)."""
        from ..runtime.utils import host_transfer
        timeout = self.config.profile_sync_timeout_s
        if timeout <= 0:
            host_transfer(out, block=True)
            return True
        if run_with_timeout(lambda: host_transfer(out, block=True),
                            timeout):
            return True
        logger.error(
            f"device sync did not complete within {timeout:.0f}s — "
            f"dropping this profile sample (device wedged? raise "
            f"profile_sync_timeout_s if the model is just that large)")
        return False

    __call__ = forward

    # ------------------------------------------------------------------
    # model-time profiling (reference inference/engine.py:159,503)
    # ------------------------------------------------------------------
    def profile_model_time(self) -> None:
        """Start recording per-call model wall time; ``model_times``
        drains the record. Device-synced (block_until_ready) the way the
        reference syncs CUDA before/after the module call. Units: one
        entry per engine call — a ``forward`` entry is one forward, a
        ``generate`` entry is the WHOLE prefill+decode loop (the repo's
        decode is one fused jit program, so there is no per-step hook);
        calls that trigger a fresh trace+compile are excluded."""
        self.model_profile_enabled = True

    def model_times(self) -> list:
        """Recorded model times since the last call, then resets —
        reference semantics: raises if profiling was never enabled."""
        if not self.model_profile_enabled:
            raise RuntimeError(
                "model profiling is not enabled — call "
                "engine.profile_model_time() before timed calls")
        times, self._model_times = self._model_times, []
        return times

    # ------------------------------------------------------------------
    # generation
    # ------------------------------------------------------------------
    @staticmethod
    def _sample(logits, rng, temperature, top_k, top_p):
        """fp32 categorical sampling with optional top-k / nucleus filter;
        temperature 0 → greedy.  Delegates to the shared
        :mod:`~.sampling` module — generate() and the serving engine
        draw tokens through ONE implementation, which is what makes the
        seeded generate ↔ serving parity hold."""
        from .sampling import sample_tokens
        return sample_tokens(logits, rng, temperature, top_k, top_p)

    def _build_generate(self, batch: int, prompt_len: int, max_new: int,
                        temperature: float, top_k: int, top_p: float,
                        eos_token_id: Optional[int]):
        """Two programs, split at the first token: ``prefill`` (prompt
        forward + first sample) and ``decode`` (the scan over the
        remaining ``max_new - 1`` tokens).  The split is what lets
        ``latency_stats`` report TTFT and per-token decode latency as
        the separate quantities they are — one fused program could only
        report their blur (the pre-PR-4 per-token number divided prefill
        time across decode tokens)."""
        model = self.module
        cache_len = prompt_len + max_new
        if cache_len > self.config.max_out_tokens:
            raise ValueError(
                f"prompt+new = {cache_len} exceeds max_out_tokens "
                f"({self.config.max_out_tokens})")
        if batch > self.config.max_batch_size:
            raise ValueError(
                f"batch {batch} exceeds max_batch_size "
                f"({self.config.max_batch_size}) — raise it in the config "
                f"(it bounds the KV workspace, reference inference_context.h)")

        def prefill(params, scales, ids, true_len, rng):
            params = self._model_params(params, scales)
            cache = model.init_cache(batch, cache_len, dtype=self.dtype)
            logits, cache = model.apply(params, ids, cache=cache)
            # bucketing: ids are right-padded to the bucket; the padded
            # positions' cache slots are dropped by resetting the index to
            # the true length (decode overwrites them; the valid mask
            # hides anything beyond), and the next-token logits come from
            # the true last position
            cache = {**cache, "index": true_len}
            last = jax.lax.dynamic_slice_in_dim(
                logits, true_len - 1, 1, axis=1)[:, 0]
            # fold_in key schedule (inference/sampling.py): output token
            # j draws with fold_in(rng, j) — the same schedule the
            # serving engine uses per request, so a seeded generate()
            # and a seeded serving stream are token-identical
            tok = self._sample(last, jax.random.fold_in(rng, 0),
                               temperature, top_k, top_p)
            done = (jnp.zeros((batch,), jnp.bool_) if eos_token_id is None
                    else tok == eos_token_id)
            return cache, tok, rng, done

        def decode(params, scales, cache, tok, rng, done):
            params = self._model_params(params, scales)

            def step(carry, j):
                cache, tok, rng, done = carry
                logits, cache = model.apply(params, tok[:, None], cache=cache)
                # output index j's token: fold_in(rng, j), matching the
                # serving engine's per-request key schedule
                nxt = self._sample(logits[:, -1], jax.random.fold_in(rng, j),
                                   temperature, top_k, top_p)
                if eos_token_id is not None:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                return (cache, nxt, rng, done), tok

            (_, last, _, _), toks = jax.lax.scan(
                step, (cache, tok, rng, done), jnp.arange(1, max_new))
            return jnp.concatenate(
                [toks.swapaxes(0, 1), last[:, None]], axis=1)

        with self.mesh:
            # the decode program consumes the prefill state exactly once —
            # donating it keeps the KV cache in place between the two
            # programs (CPU backend implements no donation and would warn)
            donate = (2, 3) if jax.default_backend() == "tpu" else ()
            return jax.jit(prefill), jax.jit(decode, donate_argnums=donate)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 eos_token_id: Optional[int] = None,
                 rng: Optional[jax.Array] = None,
                 num_beams: int = 1) -> jnp.ndarray:
        """Prompt [B, T] → generated tokens [B, max_new_tokens]."""
        if num_beams > 1:
            # in-flight guard, reference inference/engine.py:544 _generate:
            # beam search multiplies the KV workspace by num_beams and the
            # decode kernels hold one cache line per sequence — reject
            # loudly instead of silently decoding beam 0 only
            raise NotImplementedError(
                "num_beams > 1 is not supported: the decode path holds one "
                "KV-cache line per batch row. Use sampling (temperature / "
                "top_k / top_p) or expand the batch with repeated prompts.")
        ids = jnp.asarray(input_ids)
        temperature = (self.config.temperature if temperature is None
                       else temperature)
        top_k = self.config.top_k if top_k is None else top_k
        top_p = self.config.top_p if top_p is None else top_p
        true_len = ids.shape[1]
        bucket = self.config.prompt_bucket
        if bucket and self.module.padded_prompt_refusal() is not None:
            # a model whose state the padding would run through compiles
            # for the prompt's own length
            bucket = 0
        if bucket:
            padded = max(bucket, -(-true_len // bucket) * bucket)
            # never let padding spill the KV workspace the exact shape
            # would have fit in
            padded = min(padded,
                         max(true_len,
                             self.config.max_out_tokens - max_new_tokens))
            if padded > true_len:
                ids = jnp.pad(ids, ((0, 0), (0, padded - true_len)))
        key = (ids.shape[0], ids.shape[1], max_new_tokens, temperature,
               top_k, top_p, eos_token_id)
        compiled_now = key not in self._gen_fns
        if compiled_now:
            self._gen_fns[key] = self._build_generate(*key)
        prefill_fn, decode_fn = self._gen_fns[key]
        scales = getattr(self, "_scales", None)
        # TTFT: prompt forward + first token, synced at the split point
        t0 = time.perf_counter()
        state = prefill_fn(self.params, scales, ids,
                           jnp.asarray(true_len, jnp.int32),
                           rng if rng is not None
                           else jax.random.PRNGKey(0))
        if self.model_profile_enabled:
            synced = self._guarded_sync(state)
        else:
            jax.block_until_ready(state)
            synced = True
        t1 = time.perf_counter()
        out = decode_fn(self.params, scales, *state)
        if self.model_profile_enabled:
            synced = self._guarded_sync(out) and synced
        else:
            out.block_until_ready()
        t2 = time.perf_counter()
        if synced:
            self._ttfts.append(t1 - t0)
            # decode-only per-token latency: the prefill cost lives in
            # TTFT, not amortized into the decode number
            self._latencies.append((t2 - t1) / max(max_new_tokens - 1, 1))
            if self.model_profile_enabled and not compiled_now:
                self._model_times.append(t2 - t0)
        return out

    def latency_stats(self) -> Dict[str, float]:
        """Decode and first-token latency over ``generate`` calls so far
        (reference `benchmarks/inference/gpt-bench.py` reporting).

        ``p50_ms``/``p90_ms``/``tokens_per_sec`` are DECODE-ONLY
        per-token numbers (prefill excluded); ``ttft_p50_ms``/
        ``ttft_p90_ms`` report prompt-to-first-token separately.  The
        pre-PR-4 number divided whole-call wall time (prefill included)
        by ``max_new_tokens``, which overstated decode latency exactly
        when prompts were long."""
        if not self._latencies:
            return {}
        lat = np.asarray(self._latencies[1:] or self._latencies)  # drop compile
        ttft = np.asarray(self._ttfts[1:] or self._ttfts)
        return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p90_ms": float(np.percentile(lat, 90) * 1e3),
                "tokens_per_sec": float(1.0 / np.mean(lat)),
                "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
                "ttft_p90_ms": float(np.percentile(ttft, 90) * 1e3)}

    # ------------------------------------------------------------------
    # continuous-batching serving (inference/serving/, docs/serving.md)
    # ------------------------------------------------------------------
    def serving_engine(self, rng: Optional[jax.Array] = None,
                       draft_model=None, draft_params=None):
        """The continuous-batching front end over this engine's weights:
        paged KV pool, iteration-level scheduler, single-trace batched
        decode with in-program per-request sampling.  Gated on the
        ``serving`` config block.

        ``rng`` seeds the engine's base sampling key (requests without
        their own ``seed`` derive from it).  ``draft_model`` (a smaller
        model sharing the target's vocab) arms speculative decoding:
        the draft proposes ``serving.spec_k`` tokens per slot per
        iteration and the target verifies them in the same single
        compiled step — token-exact vs plain decode under the same
        key (docs/serving.md "Speculative decoding")."""
        if not self.config.serving.enabled:
            raise ValueError(
                "continuous-batching serving is disabled — set "
                '{"serving": {"enabled": true}} in the inference config')
        if self._serving is None:
            from .serving import ServingEngine
            with get_overlap_profiler().setup_span("setup/serving_engine"):
                self._serving = ServingEngine(self, rng=rng,
                                              draft_model=draft_model,
                                              draft_params=draft_params)
        elif draft_model is not None \
                and self._serving._draft_model is not draft_model:
            raise ValueError(
                "serving engine already built without this draft model "
                "— pass draft_model on the FIRST serving_engine() call")
        return self._serving
