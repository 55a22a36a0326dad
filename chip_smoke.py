#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

    python3 chip_smoke.py [CHIPS]        # CHIPS = 1 (default) or 4

One process drives every chip it expects.  It takes the two normal entry
points at the full published width and depth of GPT-2 350M, with random
weights from a seed:

  train  ``ds.initialize`` -> ``engine.train_step`` for a few steps on
         one seeded batch (ZeRO-2 on one chip; ``mesh {"data": N}`` and
         ZeRO-3 on N): loss finite and lower at the end, one compile and
         none after the first step, the step program holds compiled
         Pallas kernels (``tpu_custom_call``), and on N chips every
         parameter / optimizer leaf has shards on N distinct devices.
  serve  ``ds.init_inference`` (the trained weights) ->
         ``serving_engine()`` -> staggered ``submit``/``step``/``run``
         through the paged pool, chunked prefill and the mixed decode
         program (``serving.mesh {"model": N}``): every greedy stream
         completes with finite logits, the step's two shapes are built
         by the first dispatch and never again (``decode_builds == 2``)
         and each runs at least one dispatch, no compile after warm-up,
         the pool drains, the step program holds ``tpu_custom_call``.
  kernel the paged decode and prefill kernel against the float32
         ``jax.numpy`` reference, on the chip, at the serving shapes; the
         flash kernels in the packed ``[B, T, H·D]`` form at 16 heads of
         64 (two heads a 128-lane pack, the single-block backward), of
         128 (one head a pack, the two-pass backward) and at 8 / 2 heads
         of 64 (a pack's two heads on one kv head), forward and
         gradients, against the same (the interpreter cannot see what
         Mosaic makes of the lane masks and the half swaps).
  latent (one chip) the latent paged kernel at 64 and at 128 heads, the
         experts' grouped product, and an expert layer with a sigmoid
         gate and a shared expert, at ``longcat-flash-omni``'s and
         ``openpangu-ultra-moe``'s widths against float32 ``jax.numpy``.

  hybrid (one chip) the paged kernel at 40 / 20 heads of 64 with a window
         of 512 (decode slots, and a chunk cut into walkers of 128 rows,
         the pages before the window poisoned), the selective scan
         ``ssm_chunk_scan`` at 5,120 channels x 16 against the loop, and
         the hybrid state-space block at ``phi-4-mini-flash-reasoning``'s
         widths and a shorter pattern served through ``serving_engine()``
         (three kinds of state a slot) against its float32 reference.
  ssd    (one chip) both lanes of the Mamba-2 recurrence
         (``ops/transformer/ssd_scan.py``) at ``granite-4.0-h-micro``'s
         widths (64 heads x 64 x 128) in bfloat16 against the loop over
         rows (the decode kernel in place, at the middle layer's rows of
         three layers' states), and the Mamba-2 / attention hybrid block
         at its widths and a shorter pattern served through
         ``serving_engine()`` against its float32 reference.

  kda    (one chip) both lanes of the gated delta rule
         (``ops/transformer/kda_scan.py``) at ``kimi-linear-48b-a3b``'s
         widths (32 heads x 128 x 128) against the loop over rows: the
         blocked form over a ragged chunk under a gate strong enough that
         ``1 / exp(G)`` over a block would overflow, the decode kernel in
         place at the middle layer's rows of three layers' states.
         ``python chip_smoke.py 1 kda`` runs this phase alone.

  block_lane (one chip) the builder's probe of generation by diffusion
         over blocks, no cell runs it: ``paged_block_attention`` at
         ``sdar-30b-a3b-chat``'s widths (48 slots x 4 rows, 32 / 4 heads
         of 128) against its float32 reference under the block mask, and
         its time at the mix's mean and longest contexts beside four
         calls of the decode kernel over the same pages.
         ``python chip_smoke.py 1 block`` runs this phase alone.

It fails — non-zero exit, no result line — when JAX shows anything but
CHIPS TPU devices; it never adapts downward, and nothing on the path is
caught.  Compile seconds are reported apart from run seconds, so a
second run on the same compile-cache directory shows the hit.  The last
line of stdout is one JSON object, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

SEED = 0
TRAIN_STEPS = 6
MICRO_BATCH = 16                       # sequences per chip per step
SEQ_LEN = 1024
#: (prompt tokens, new tokens) per request — greedy, staggered arrivals
REQUESTS = ((64, 32), (200, 48), (512, 64), (128, 40), (333, 56),
            (96, 32), (480, 64), (256, 36))
SERVING = {"enabled": True, "kv_block_size": 16, "num_kv_blocks": 512,
           "max_batch_slots": 8, "prefill_chunk_tokens": 256}
#: bf16 pool and queries against a float32 reference
KERNEL_ATOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(chips: int) -> dict:
    """The devices JAX reports, or exit: nothing below runs — and no
    result is printed — on another platform or chip count."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] != chips:
        sys.stderr.write(
            f"chip_smoke: needs exactly {chips} TPU device(s); JAX "
            f"reports {info['count']} x {info['platform']} "
            f"({info['kind']}). No result.\n")
        raise SystemExit(2)
    return info


class CompileLog:
    """XLA compilations from JAX's own monitoring events: how many, the
    seconds in the backend compiler (or in fetching the program from the
    persistent cache — what a warm cache shrinks), and the seconds spent
    tracing and lowering, which no cache saves."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        self.trace_s = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif name.startswith("/jax/core/compile/"):
            self.trace_s += secs

    def _event(self, name, **_):
        self.cache_hits += name == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return (self.compiles, self.cache_hits, self.compile_s,
                self.trace_s)

    def since(self, mark):
        return {"compiles": self.compiles - mark[0],
                "cache_hits": self.cache_hits - mark[1],
                "compile_s": round(self.compile_s - mark[2], 2),
                "trace_lower_s": round(self.trace_s - mark[3], 2)}


def distinct_shard_devices(tree) -> dict:
    """Over every array leaf: the fewest distinct devices any leaf has
    shards on, and the share of bytes held in leaves that are really
    partitioned (a shard smaller than the array)."""
    import jax
    fewest, split, total = None, 0, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = leaf.addressable_shards
        n = len({s.device for s in shards})
        fewest = n if fewest is None else min(fewest, n)
        total += leaf.nbytes
        if shards[0].data.shape != leaf.shape:
            split += leaf.nbytes
    return {"min_devices": fewest,
            "partitioned_byte_share": round(split / max(total, 1), 3)}


def train_phase(chips: int, model_config, log: CompileLog, device: dict,
                micro_batch: int = MICRO_BATCH, steps: int = TRAIN_STEPS):
    """A few optimizer steps through ``ds.initialize``; returns the
    phase record and the trained parameters."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import TransformerLM

    config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 0,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 2 if chips == 1 else 3},
        "mesh": {"data": chips},
    }
    engine, *_ = ds.initialize(model=TransformerLM(model_config),
                               config=config, rng=jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    batch = {"input_ids": rng.integers(
        0, model_config.vocab_size,
        (micro_batch * chips, model_config.max_seq_len), dtype=np.int32)}

    mark = log.mark()
    losses, step_s = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        metrics = engine.train_step(batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        step_s.append(time.perf_counter() - t0)
        if i == 0:
            first = log.since(mark)
            steady = log.mark()
    after = log.since(steady)

    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a repeated batch: {losses}")
    check(after["compiles"] == 0,
          f"train: {after['compiles']} compile(s) after the first step")
    text = engine._train_step_fn.lower(
        engine.state, engine.shard_batch(batch)).as_text()
    kernels = text.count("tpu_custom_call")
    check(kernels > 0, "train: no tpu_custom_call in the step program")
    placement = distinct_shard_devices(
        {k: engine.state[k] for k in ("params", "opt")})
    check(placement["min_devices"] == chips,
          f"train: a state leaf lives on {placement['min_devices']} "
          f"device(s), expected {chips}")
    if chips > 1:
        check(placement["partitioned_byte_share"] > 0.9,
              f"train: ZeRO-3 left the state replicated: {placement}")

    record = {"phase": "train", **device,
              "zero_stage": config["zero_optimization"]["stage"],
              "steps": steps, "tokens_per_step":
                  micro_batch * chips * model_config.max_seq_len,
              "loss_first": round(losses[0], 4),
              "loss_last": round(losses[-1], 4),
              "first_step": first, "compiles_after_first_step":
                  after["compiles"],
              "first_step_wall_s": round(step_s[0], 2),
              "steady_step_s": round(float(np.median(step_s[1:])), 4),
              "tpu_custom_calls_in_program": kernels, **placement}
    return record, engine.state["params"]


def serve_phase(chips: int, model_config, params, log: CompileLog,
                device: dict, requests=REQUESTS, serving=None,
                max_out_tokens: int = SEQ_LEN):
    """A handful of staggered greedy requests through
    ``init_inference(...).serving_engine()``."""
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import RequestStatus
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.observability import get_overlap_profiler

    serving = dict(serving or SERVING, mesh={"data": 1, "model": chips})
    eng = ds.init_inference(
        TransformerLM(model_config),
        {"dtype": "bfloat16", "max_out_tokens": max_out_tokens,
         "temperature": 0.0, "serving": serving}, params=params)
    srv = eng.serving_engine()

    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(0, model_config.vocab_size, n).tolist()
               for n, _ in requests]
    # the engine's own record of every dispatch: which shape of the step
    # each iteration ran (rows_computed), read after the drain
    profiler = get_overlap_profiler()
    profiler.reset()
    profiler.configure(enabled=True)
    mark = log.mark()
    t0 = time.perf_counter()
    submitted = []
    # staggered arrivals: three requests, a few iterations, three more,
    # a few more iterations, then the rest and a drain
    for i, (prompt, (_, new)) in enumerate(zip(prompts, requests)):
        submitted.append(srv.submit(prompt, max_new_tokens=new))
        if i == 0:
            srv.step()                      # warm-up: both shapes compile
            check(srv.decode_builds == 2,
                  f"serve: the first dispatch built {srv.decode_builds} "
                  f"of the step's 2 shapes")
            warm_s = time.perf_counter() - t0
            first = log.since(mark)
            steady = log.mark()
        elif i % 3 == 2:
            for _ in range(4):
                srv.step()
    srv.run()
    run_s = time.perf_counter() - t0 - warm_s
    after = log.since(steady)
    its, complete = profiler.iterations(t0, time.perf_counter())
    profiler.configure(enabled=False)
    profiler.reset()
    check(complete, "serve: the overlap profiler's ring wrapped")
    one = its[its["dispatches"] == 1]
    rows = {"decode_only": srv.num_slots,
            "mixed": srv.num_slots + srv.chunk_tokens}
    ran = {shape: int(np.sum(one["rows_computed"] == n))
           for shape, n in rows.items()}
    check(all(ran.values()) and sum(ran.values()) == len(one),
          f"serve: dispatches a shape of the step {ran}, rows {rows}")

    # the loop kept an iteration in flight: every dispatch but the idle
    # engine's first iteration was enqueued before its predecessor's
    # result was read, and greedy traffic with no eos voids no row
    n_dispatches = int(its["dispatches"].sum())
    ahead, void = (int(its[c].sum())
                   for c in ("ahead_dispatches", "void_rows"))
    check(n_dispatches - ahead == int(its["dispatches"][0]) and void == 0,
          f"serve: {ahead} of {n_dispatches} dispatches ran ahead, "
          f"{void} void row(s)")

    for req, (_, new) in zip(submitted, requests):
        check(req.status is RequestStatus.OK,
              f"serve: {req.req_id} ended {req.status} ({req.error})")
        check(len(req.output) == new,
              f"serve: {req.req_id} produced {len(req.output)} of {new} "
              f"tokens")
    check(srv.decode_builds == 2,
          f"serve: the step's two shapes were built {srv.decode_builds} "
          f"times")
    check(after["compiles"] == 0,
          f"serve: {after['compiles']} compile(s) after warm-up")
    check(srv.allocator.num_used == 0,
          f"serve: {srv.allocator.num_used} KV blocks held after drain")
    builds, held = srv.decode_builds, srv.allocator.num_used
    pool = distinct_shard_devices([srv._pool_k, srv._pool_v])
    check(pool["min_devices"] == chips,
          f"serve: KV pool on {pool['min_devices']} device(s), expected "
          f"{chips}")
    # the decode-only shape holds the decode kernel; the mixed one the
    # chunk kernel besides
    kernels = min(
        srv._step_fn.lower(*srv._idle_operands(chunk_lane)).as_text()
        .count("tpu_custom_call") for chunk_lane in (False, True))
    check(kernels > 0, "serve: a shape of the step without tpu_custom_call")

    tokens = sum(new for _, new in requests)
    record = {"phase": "serve", **device, "requests": len(requests),
              "prompt_tokens": sum(n for n, _ in requests),
              "new_tokens": tokens, "warmup": first,
              "warmup_wall_s": round(warm_s, 2),
              "compiles_after_warmup": after["compiles"],
              "run_s": round(run_s, 2),
              "decode_builds": builds, "dispatches_by_shape": ran,
              "dispatches": n_dispatches, "ahead_dispatches": ahead,
              "void_rows": void,
              "kv_blocks_held_after_drain": held,
              "tpu_custom_calls_in_program": kernels,
              "kv_pool_min_devices": pool["min_devices"],
              "kv_pool_partitioned_byte_share":
                  pool["partitioned_byte_share"]}
    return record


def kernel_phase(heads: int, head_dim: int, device: dict,
                 block: int = SERVING["kv_block_size"],
                 chunk: int = SERVING["prefill_chunk_tokens"]):
    """Paged decode and prefill kernel vs the float32 jnp reference, on
    the chip, at the shapes one serving shard runs (bf16 pool)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops import resolve_interpret
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        paged_attention_reference, paged_decode_attention,
        paged_prefill_attention, paged_prefill_reference)

    check(resolve_interpret(None) is False,
          "kernel: the package would interpret its Pallas kernels")
    rng = np.random.default_rng(SEED + 2)
    pages = SEQ_LEN // block
    lens = np.array([1, 64, 97, 200, 333, 512, 575, 0], np.int32)
    nb = 1 + len(lens) * pages
    tables = np.arange(1, nb, dtype=np.int32).reshape(len(lens), pages)
    pool_k, pool_v = (jnp.asarray(
        rng.standard_normal((nb, block, heads * head_dim)), jnp.bfloat16)
        for _ in range(2))
    f32 = lambda a: a.astype(jnp.float32)               # noqa: E731

    q = jnp.asarray(rng.standard_normal((len(lens), heads, head_dim)),
                    jnp.bfloat16)
    out = jax.jit(paged_decode_attention)(q, pool_k, pool_v, lens, tables)
    ref = paged_attention_reference(f32(q), f32(pool_k), f32(pool_v), lens,
                                    tables)
    decode_err = float(jnp.max(jnp.abs(f32(out) - ref)))
    check(bool(jnp.all(jnp.isfinite(f32(out)))), "kernel: decode not finite")
    check(decode_err < KERNEL_ATOL,
          f"kernel: paged decode off the f32 reference by {decode_err}")

    # a full chunk after `chunk` rows of context, then a ragged tail
    prefill_err = 0.0
    qc = jnp.asarray(rng.standard_normal((chunk, heads, head_dim)),
                     jnp.bfloat16)
    for base, n in ((chunk, chunk), (2 * chunk, chunk // 2 + 3)):
        out = jax.jit(paged_prefill_attention)(
            qc, pool_k, pool_v, base, n, tables[5])
        ref = paged_prefill_reference(f32(qc), f32(pool_k), f32(pool_v),
                                      base, n, tables[5])
        check(bool(jnp.all(jnp.isfinite(f32(out)[:n]))),
              "kernel: prefill not finite")
        prefill_err = max(prefill_err, float(jnp.max(jnp.abs(
            f32(out)[:n] - ref[:n]))))
    check(prefill_err < KERNEL_ATOL,
          f"kernel: paged prefill off the f32 reference by {prefill_err}")
    return {"phase": "kernel", **device, "heads": heads,
            "head_dim": head_dim, "kv_block_size": block,
            "decode_max_abs_err": round(decode_err, 5),
            "prefill_max_abs_err": round(prefill_err, 5),
            "flash": [flash_errors(16, 16, 64, SEQ_LEN),
                      flash_errors(16, 16, 128, 2 * SEQ_LEN),
                      flash_errors(8, 2, 64, 2 * SEQ_LEN)],
            "atol": KERNEL_ATOL}


def flash_errors(heads: int, kv_heads: int, head_dim: int, seq: int,
                 batch: int = 2):
    """The flash kernels (packed form: the shapes say so) against float32
    ``jax.numpy`` causal attention on the same bf16 inputs: the output's
    largest error, and the three gradients' of ``sum(out * w)``, each as
    a share of the reference's largest entry."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.transformer.flash_attention import (
        _heads_a_pack, flash_attention_bthd)

    what = f"kernel: flash at {heads} / {kv_heads} heads of {head_dim}"
    check(_heads_a_pack(heads, kv_heads, head_dim) is not None,
          f"{what}: the shape does not pack")
    rng = np.random.default_rng(SEED + 3)
    q, k, v, w = (jnp.asarray(rng.standard_normal(
        (batch, seq, n, head_dim)), jnp.bfloat16)
        for n in (heads, kv_heads, kv_heads, heads))
    f32 = lambda a: a.astype(jnp.float32)               # noqa: E731

    def plain(q, k, v):
        return L.gqa_attention(q, k, v, causal=True)

    def run(attend, *operands):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(f32(attend(q, k, v)) * f32(w)),
            argnums=(0, 1, 2)))(*operands)
    out = jax.jit(flash_attention_bthd)(q, k, v)
    ref = plain(f32(q), f32(k), f32(v))
    out_err = float(jnp.max(jnp.abs(f32(out) - ref)))
    _, grads = run(flash_attention_bthd, q, k, v)
    _, want = run(plain, f32(q), f32(k), f32(v))
    grad_err = max(float(jnp.max(jnp.abs(f32(g) - r)) / jnp.max(jnp.abs(r)))
                   for g, r in zip(grads, want))
    check(bool(jnp.all(jnp.isfinite(f32(out)))), f"{what} not finite")
    check(out_err < KERNEL_ATOL,
          f"{what} off the f32 reference by {out_err}")
    check(grad_err < KERNEL_ATOL,
          f"{what}: a gradient off the f32 reference by {grad_err} of "
          f"its largest entry")
    return {"heads": heads, "kv_heads": kv_heads, "head_dim": head_dim,
            "seq": seq,
            "out_max_abs_err": round(out_err, 5),
            "grad_max_rel_err": round(grad_err, 5)}


def latent_phase(device: dict, block: int = SERVING["kv_block_size"]):
    """The latent (MLA) paged kernel at 64 heads
    (``longcat-flash-omni.serve-longdoc-sat``) and at 128
    (``openpangu-ultra-moe.serve-reason-sat``: a decode walker of 128 rows,
    a chunk tile of 8 positions) over one [512 | 64 | 0] row of 640 lanes a
    token, 512-row chunks; the experts' grouped product (16 held experts
    6144 x 2048 at about 9 rows each); and an expert layer with a sigmoid
    gate and a shared expert (7680 x 2048, 640 rows), against float32
    ``jax.numpy``, on the chip.  Parity, and one pair of times: the
    decode walk at each cell's pool over a table of whole runs and over
    a scattered one (``latent_walk_times``)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        mla_paged_decode_attention, mla_paged_prefill_attention,
        mla_paged_reference)

    rng = np.random.default_rng(SEED + 3)
    f32 = lambda a: a.astype(jnp.float32)               # noqa: E731
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)         # noqa: E731
    lat, rope, lanes, chunk = 512, 64, 640, 512
    scale = 1.0 / np.sqrt(192.0)
    lens = np.array([1, 640, 1097, 2048, 3333, 4096, 6655, 0], np.int32)
    pages = 8192 // block
    nb = 1 + len(lens) * pages
    tables = np.arange(1, nb, dtype=np.int32).reshape(len(lens), pages)
    pool = bf(np.pad(rng.standard_normal((nb, block, lat + rope)),
                     ((0, 0), (0, 0), (0, lanes - lat - rope))))
    decode = jax.jit(lambda *a: mla_paged_decode_attention(*a, scale))
    prefill = jax.jit(lambda *a: mla_paged_prefill_attention(*a, scale))
    decode_err, prefill_err = {}, {}
    # the float32 reference holds [chunk, heads, context] scores: at 128
    # heads the chunks sit half as deep, in a table of half the pages
    for heads, view, chunks in (
            (64, pages, ((2560, chunk), (3072, chunk // 2 + 3))),
            (128, pages // 2, ((1536, chunk), (2048, chunk // 2 + 3)))):
        ql = bf(rng.standard_normal((len(lens), heads, lat)) * 0.3)
        qr = bf(rng.standard_normal((len(lens), heads, rope)) * 0.3)
        out = decode(ql, qr, pool, lens, tables)
        ref = mla_paged_reference(f32(ql)[:, None], f32(qr)[:, None], pool,
                                  lens - 1, lens, tables, scale)[:, 0]
        decode_err[heads] = float(jnp.max(jnp.abs(f32(out) - ref)))
        check(bool(jnp.all(jnp.isfinite(f32(out)))),
              f"latent: decode at {heads} heads not finite")
        check(decode_err[heads] < KERNEL_ATOL,
              f"latent: decode at {heads} heads off the f32 reference by "
              f"{decode_err[heads]}")

        # a full chunk deep in a context, then a ragged one that ends
        # mid-page
        qlc = bf(rng.standard_normal((chunk, heads, lat)) * 0.3)
        qrc = bf(rng.standard_normal((chunk, heads, rope)) * 0.3)
        table = tables[6][:view]
        prefill_err[heads] = 0.0
        for base, n in chunks:
            out = prefill(qlc, qrc, pool, base, n, table)
            ref = mla_paged_reference(f32(qlc)[None], f32(qrc)[None], pool,
                                      np.array([base]), np.array([base + n]),
                                      table[None], scale)[0]
            check(bool(jnp.all(jnp.isfinite(f32(out)[:n]))),
                  f"latent: prefill at {heads} heads not finite")
            prefill_err[heads] = max(prefill_err[heads], float(jnp.max(
                jnp.abs(f32(out)[:n] - ref[:n]))))
        check(prefill_err[heads] < KERNEL_ATOL,
              f"latent: prefill at {heads} heads off the f32 reference by "
              f"{prefill_err[heads]}")

    # the grouped product: 16 held experts, ~9 rows each, one pass
    held, h, f, rows_each = 16, 6144, 2048, 9
    w = bf(rng.standard_normal((held, h, f)) * 0.02)
    tiles = 1024 // dropless.TILE_ROWS
    x = np.zeros((1024, h), np.float32)
    for e in range(held):
        x[e * dropless.TILE_ROWS:e * dropless.TILE_ROWS + rows_each] = \
            rng.standard_normal((rows_each, h))
    x = bf(x)
    te = jnp.asarray(np.minimum(np.arange(tiles), held - 1), jnp.int32)
    live = jnp.asarray(held, jnp.int32)
    pallas = jax.jit(dropless.grouped_matmul)
    got = f32(pallas(x, w, te, live))[:held * dropless.TILE_ROWS]
    want = jnp.einsum(
        "erk,ekn->ern", f32(x)[:held * dropless.TILE_ROWS].reshape(
            held, dropless.TILE_ROWS, h), f32(w),
        precision="highest").reshape(-1, f)
    gmm_err = float(jnp.max(jnp.abs(got - want)))
    check(gmm_err < 5e-2, f"latent: grouped product off by {gmm_err}")
    gate_err = _shared_expert_layer_error()
    # the same measure and limit as the cell's `expert_rel_err`
    check(gate_err < 5e-2,
          f"latent: sigmoid gate + shared expert off by {gate_err}")
    return {"phase": "latent", **device,
            "decode_walk": latent_walk_times(block),
            "decode_max_abs_err": {str(h): round(e, 5)
                                   for h, e in decode_err.items()},
            "prefill_max_abs_err": {str(h): round(e, 5)
                                    for h, e in prefill_err.items()},
            "grouped_max_abs_err": round(gmm_err, 5),
            "shared_expert_layer_rel_err": round(gate_err, 5),
            "atol": KERNEL_ATOL}


#: (heads, slots, pages a slot's table, pool blocks, shortest and longest
#: context) of the two latent cells' decode walks
LATENT_WALKS = ((128, 128, 256, 17408, 256, 3000),
                (64, 48, 512, 12288, 1024, 5800))


def walk_tables(rng, need, pages: int, nb: int) -> dict:
    """Tables ``[slots, pages]`` for slots that hold ``need`` pages each, of
    a pool of ``nb`` blocks: ``runs`` — every aligned run of ``PAGE_RUN``
    entries consecutive pool blocks, as the cache manager hands them out —
    and ``scattered`` — the same blocks permuted, as a pool that kept no
    runs would hold."""
    from deepspeed_tpu.ops.transformer.paged_decode_attention import PAGE_RUN
    groups = rng.permutation((nb - 1) // PAGE_RUN)
    spans = np.concatenate([[0], np.cumsum(-(-need // PAGE_RUN))])
    out = {kind: np.zeros((len(need), pages), np.int32)
           for kind in ("runs", "scattered")}
    for s, n in enumerate(need):
        ids = (1 + groups[spans[s]:spans[s + 1], None] * PAGE_RUN
               + np.arange(PAGE_RUN)).reshape(-1)
        out["runs"][s, :n] = ids[:n]
        out["scattered"][s, :n] = rng.permutation(ids)[:n]
    return out


def _call_us(fn, q, *rest, reps: int = 20) -> float:
    """Microseconds a call of ``fn(q, *rest)``, ``reps`` of them chained
    on the device (each call's query waits for the one before; the best of
    three): a dispatch from this host costs some 250 us, more than the
    calls timed here."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chained(q, *rest):
        def one(_, q):
            # (every row of the result: no call that made one may go)
            return q + (jnp.sum(fn(q, *rest)[..., 0]) * 0).astype(q.dtype)
        return jax.lax.fori_loop(0, reps, one, q)
    chained(q, *rest).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        chained(q, *rest).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / reps)
    return round(best * 1e6, 1)


def latent_walk_times(block: int) -> dict:
    """The latent kernel's decode call at the two cells' pools and batches
    (17,408 blocks, 128 slots x 128 heads, contexts 256-3,000;
    12,288 blocks, 48 slots x 64 heads, contexts 1,024-5,800: a kernel's
    time depends on the pool it walks) over a table whose aligned runs of
    ``PAGE_RUN`` pages are consecutive pool blocks, as the cache manager
    hands them out, and over a scattered one, as a pool that kept no runs
    would hold: microseconds a call (``_call_us``), nanoseconds a page,
    and the share of the roofline (2 x heads x 1,088 operations a context
    token against 197 TFLOP/s, 1,152 bytes against 819 GB/s)."""
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        mla_paged_decode_attention)
    rng = np.random.default_rng(SEED + 4)
    out = {}
    for heads, slots, pages, nb, lo, hi in LATENT_WALKS:
        lens = rng.integers(lo, hi, slots).astype(np.int32)
        need = -(-lens // block)
        pool = jnp.asarray(rng.standard_normal((nb, block, 640),
                                               dtype=np.float32),
                           jnp.bfloat16)
        ql = jnp.asarray(rng.standard_normal((slots, heads, 512)) * 0.3,
                         jnp.bfloat16)
        qr = jnp.asarray(rng.standard_normal((slots, heads, 64)) * 0.3,
                         jnp.bfloat16)
        floor = max(2.0 * lens.sum() * heads * 1088 / 197e12,
                    lens.sum() * 1152 / 819e9)
        cell = {"pages": int(need.sum())}
        for kind, tables in walk_tables(rng, need, pages, nb).items():
            best = 1e-6 * _call_us(
                lambda *a: mla_paged_decode_attention(*a, 192 ** -0.5), ql,
                qr, pool, jnp.asarray(lens), jnp.asarray(tables))
            cell[kind] = {"us": round(best * 1e6, 1),
                          "ns_a_page": round(best * 1e9 / need.sum(), 1),
                          "roofline_pct": round(100 * floor / best, 1)}
        out[str(heads)] = cell
    return out


def hybrid_phase(device: dict, block: int = SERVING["kv_block_size"]):
    """The hybrid state-space block's kernels at its cell's widths and the
    block itself, served: see the module docstring."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from benchmark.lib import reference_phi4_flash as reference
    from deepspeed_tpu.models import build_model, phi4_flash_config
    from deepspeed_tpu.ops.transformer import ssm_scan
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        paged_attention_reference, paged_decode_attention,
        paged_prefill_attention, paged_prefill_reference)

    rng = np.random.default_rng(SEED + 5)
    f32 = lambda a: a.astype(jnp.float32)               # noqa: E731
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)         # noqa: E731
    heads, kv_heads, hd, window, chunk = 40, 20, 64, 512, 512
    lens = np.array([1, 511, 513, 1097, 2048, 3333, 0, 4096], np.int32)
    pages = 4096 // block
    nb = 2 + len(lens) * pages
    tables = np.arange(1, nb - 1, dtype=np.int32).reshape(len(lens), pages)
    pool_k = bf(rng.standard_normal((nb, block, kv_heads * hd)))
    pool_v = bf(rng.standard_normal((nb, block, kv_heads * hd)))
    # the pages a window has left are another slot's by now: poisoned
    dead = nb - 1
    poison_k, poison_v = (p.at[dead].set(jnp.nan) for p in (pool_k, pool_v))
    left = tables.copy()
    for b, n in enumerate(lens):
        left[b, :max(0, n - window) // block] = dead
    q = bf(rng.standard_normal((len(lens), heads, hd)) * 0.3)
    out = jax.jit(lambda *a: paged_decode_attention(*a, window=window))(
        q, poison_k, poison_v, lens, left)
    ref = paged_attention_reference(f32(q), pool_k, pool_v, lens, tables,
                                    window=window)
    decode_err = float(jnp.max(jnp.abs(f32(out) - ref)))
    check(bool(jnp.all(jnp.isfinite(f32(out)))), "hybrid: decode not finite")
    check(decode_err < KERNEL_ATOL,
          f"hybrid: window decode off the f32 reference by {decode_err}")
    qc = bf(rng.standard_normal((chunk, heads, hd)) * 0.3)
    prefill = jax.jit(lambda *a: paged_prefill_attention(
        *a, window=window, tile_rows=128))
    prefill_err = 0.0
    for base, n in ((0, chunk), (1536, chunk), (2048, chunk // 2 + 3)):
        table = tables[7].copy()
        table[:max(0, base - window + 1) // block] = dead
        out = prefill(qc, poison_k, poison_v, base, n, table)
        ref = paged_prefill_reference(f32(qc), pool_k, pool_v, base, n,
                                      tables[7], window=window)
        check(bool(jnp.all(jnp.isfinite(f32(out)[:n]))),
              "hybrid: window prefill not finite")
        prefill_err = max(prefill_err, float(jnp.max(
            jnp.abs(f32(out)[:n] - f32(ref)[:n]))))
    check(prefill_err < KERNEL_ATOL,
          f"hybrid: window prefill off the f32 reference by {prefill_err}")

    # the scan: 512 rows (a ragged 300 valid), from a given state
    di, n = 5120, 16
    x = jnp.asarray(rng.standard_normal((chunk, di)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((chunk, di)),
                                     jnp.float32) - 3.0)
    bm, cm = (jnp.asarray(rng.standard_normal((chunk, n)), jnp.float32)
              for _ in range(2))
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (di, n))
    d_skip = jnp.ones((di,), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((di, n)), jnp.float32)
    y, s1 = jax.jit(ssm_scan.ssm_chunk_scan)(
        x, dt, bm, cm, a, d_skip, ssm_scan.state_to_tiles(s0), 300)
    want_y, want_s = jax.jit(ssm_scan.ssm_scan_reference)(
        x, dt, bm, cm, a, d_skip, s0, 300)
    scan_err = max(float(jnp.max(jnp.abs(y[:300] - want_y[:300]))),
                   float(jnp.max(jnp.abs(
                       ssm_scan.state_from_tiles(s1) - want_s))))
    check(scan_err < 1e-3, f"hybrid: scan off the loop by {scan_err}")

    # the block, served: published widths, 1 + 1 + 1 pairs of the pattern
    model = build_model(phi4_flash_config(
        "mini", num_layers=6, pairs_self=1, pairs_cross=1, vocab_size=2048,
        max_seq_len=2048))
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), model.init(k)))(
            jax.random.PRNGKey(SEED + 6))
    srv = ds.init_inference(
        model, {"dtype": "bfloat16", "max_out_tokens": 2048,
                "temperature": 0.0, "serving": dict(SERVING,
                                                    num_kv_blocks=1024)},
        params=params).serving_engine()
    reqs = [srv.submit(rng.integers(0, 2048, p), max_new_tokens=m)
            for p, m in ((700, 12), (333, 12), (1100, 8))]
    srv.run()
    cfg = {"heads": heads, "kv_heads": kv_heads, "window": window,
           "eps": 1e-5, "state": n, "dt_rank": 160, "without": ()}
    gap = 0.0
    for r in reqs:
        check(len(r.output) == r.max_new_tokens, "hybrid: a stream ended "
              "short")
        full = jnp.asarray(list(r.prompt) + list(r.output))[None]
        lg = np.asarray(jax.jit(lambda p, i: reference.logits(
            p, i, cfg, last=r.max_new_tokens + 1))(params, full))[0]
        gap = max(gap, max(float(lg[j].max() - lg[j][tok])
                           for j, tok in enumerate(r.output)))
    held = srv.allocator.num_used_by_kind()
    check(gap < 0.25, f"hybrid: a served token {gap} under the reference's "
          f"best logit")
    check(not any(held.values()), f"hybrid: state held after drain {held}")
    check(srv.decode_builds == 2, "hybrid: the step's two shapes, no more")
    return {"phase": "hybrid", **device,
            "window_decode_max_abs_err": round(decode_err, 5),
            "window_prefill_max_abs_err": round(prefill_err, 5),
            "scan_max_abs_err": float(scan_err),
            "served_logit_gap_worst": round(gap, 4),
            "window_blocks_held": dict(srv.allocator.window_held_max),
            "atol": KERNEL_ATOL}


def ssd_hybrid_phase(device: dict):
    """The Mamba-2 recurrence's two lanes at their cell's widths and the
    hybrid block itself, served: see the module docstring."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from benchmark.lib import reference_granite_hybrid as reference
    from deepspeed_tpu.models import build_model, granite_hybrid_config
    from deepspeed_tpu.ops.transformer import ssd_scan

    rng = np.random.default_rng(SEED + 7)
    heads, hp, n, chunk, slots = 64, 64, 128, 512, 64

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)
    d_skip = jnp.ones((heads,), jnp.float32)
    # the chunk lane: 512 rows (a ragged 300 valid), from a given state,
    # its products' inputs in bfloat16
    x, bm, cm = normal(chunk, heads, hp), normal(chunk, n), normal(chunk, n)
    dt = jax.nn.softplus(normal(chunk, heads) - 3.0)
    s0 = normal(heads, hp, n)
    bf = lambda t: t.astype(jnp.bfloat16)               # noqa: E731
    y, s1 = jax.jit(ssd_scan.ssd_chunk_scan)(
        bf(x), dt, bf(bm), bf(cm), a, d_skip, s0, 300)
    want_y, want_s = jax.jit(ssd_scan.ssd_scan_reference)(
        bf(x), dt, bf(bm), bf(cm), a, d_skip, s0, 300)
    chunk_err = max(
        float(jnp.linalg.norm(y[:300] - want_y[:300])
              / jnp.linalg.norm(want_y[:300])),
        float(jnp.linalg.norm(s1 - want_s) / jnp.linalg.norm(want_s)))
    check(chunk_err < 1e-2, f"ssd: blocked scan off the loop by {chunk_err}")
    # the decode lane: the kernel compiled, over a buffer of three layers'
    # states at the middle layer's first row (a traced scalar), every
    # other slot idle; the other layers' rows come back as they were
    states = normal(3 * slots, heads, hp, n)
    active = jnp.arange(slots) % 2 == 0
    mine = slice(slots, 2 * slots)
    want = jax.jit(jax.vmap(
        lambda *r: ssd_scan.ssd_scan_reference(*(t[None] for t in r[:4]), a,
                                               d_skip, r[4])))(
        bf(x[:slots]), dt[:slots], bf(bm[:slots]), bf(cm[:slots]),
        states[mine])
    before = np.asarray(states)
    y, new = jax.jit(ssd_scan.ssd_decode_update, donate_argnums=6)(
        bf(x[:slots]), dt[:slots], bf(bm[:slots]), bf(cm[:slots]), a, d_skip,
        states, active, jnp.int32(slots))
    decode_err = max(
        float(jnp.max(jnp.abs(new[mine][::2] - want[1][::2]))),
        float(jnp.max(jnp.abs(y[::2] - want[0][::2, 0]))),
        float(np.max(np.abs(np.asarray(new[mine][1::2])
                            - before[mine][1::2]))),
        float(np.max(np.abs(np.asarray(new[:slots]) - before[:slots]))),
        float(np.max(np.abs(np.asarray(new[2 * slots:])
                            - before[2 * slots:]))))
    check(decode_err < 1e-3, f"ssd: decode update off the loop by "
          f"{decode_err}")
    del states, new, before

    # the block, served: published widths, a pattern of six layers
    pattern = ("mamba", "mamba", "attention") * 2
    model = build_model(granite_hybrid_config(
        "h-micro", num_layers=6, layer_types=pattern, vocab_size=2048,
        max_seq_len=2048))
    params = jax.jit(lambda k: jax.tree_util.tree_map(
        lambda t: t.astype(jnp.bfloat16), model.init(k)))(
            jax.random.PRNGKey(SEED + 8))
    srv = ds.init_inference(
        model, {"dtype": "bfloat16", "max_out_tokens": 2048,
                "temperature": 0.0, "serving": dict(SERVING,
                                                    num_kv_blocks=1024)},
        params=params).serving_engine()
    reqs = [srv.submit(rng.integers(0, 2048, p), max_new_tokens=m)
            for p, m in ((700, 12), (333, 12), (1100, 8))]
    srv.run()
    c = model.config
    cfg = {"layer_types": pattern, "heads": c.num_heads,
           "kv_heads": c.kv_heads, "eps": 1e-5, "ssm_heads": heads,
           "ssm_head_dim": hp, "state": n,
           "attention_multiplier": c.attn_softmax_scale,
           "embedding_multiplier": c.embedding_multiplier,
           "residual_multiplier": c.residual_multiplier,
           "logits_scaling": c.logits_scaling, "rope_theta": 10000,
           "without": ()}
    gap = 0.0
    for r in reqs:
        check(len(r.output) == r.max_new_tokens, "ssd: a stream ended short")
        full = jnp.asarray(list(r.prompt) + list(r.output))[None]
        lg = np.asarray(jax.jit(lambda p, i: reference.logits(
            p, i, cfg, last=r.max_new_tokens + 1))(params, full))[0]
        gap = max(gap, max(float(lg[j].max() - lg[j][tok])
                           for j, tok in enumerate(r.output)))
    held = srv.allocator.num_used_by_kind()
    check(gap < 0.25, f"ssd: a served token {gap} under the reference's "
          f"best logit")
    check(not any(held.values()), f"ssd: state held after drain {held}")
    check(srv.decode_builds == 2, "ssd: the step's two shapes, no more")
    return {"phase": "ssd", **device,
            "chunk_scan_rel_err": float(chunk_err),
            "decode_update_max_abs_err": float(decode_err),
            "served_logit_gap_worst": round(gap, 4)}


def kda_phase(device: dict):
    """The gated delta rule's two lanes at their cell's widths: see the
    module docstring."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer import kda_scan

    rng = np.random.default_rng(SEED + 9)
    heads, hd, chunk, slots = 32, 128, 512, 64

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(normal(chunk, heads, hd)) / hd ** 0.5, unit(
        normal(chunk, heads, hd))
    v = normal(chunk, heads, hd)
    beta = jax.nn.sigmoid(normal(chunk, heads))
    s0 = normal(heads, hd, hd)

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    # the chunk lane: 512 rows (a ragged 300 valid), from a given state,
    # its output's products on bfloat16 inputs; a mild gate and one whose
    # running sum passes -190 inside a block
    chunk_err = 0.0
    for strong in (0.05, 6.0):
        g = -strong * jnp.asarray(rng.uniform(size=(chunk, heads, hd)),
                                  jnp.float32)
        o, s1 = jax.jit(lambda *a: kda_scan.kda_chunk_scan(
            *a, product_dtype=jnp.bfloat16))(q, k, v, g, beta, s0, 300)
        want_o, want_s = jax.jit(kda_scan.kda_scan_reference)(
            q, k, v, g, beta, s0, 300)
        check(bool(jnp.all(jnp.isfinite(o[:300]))), "kda: blocked form not "
              f"finite at gate {strong}")
        chunk_err = max(chunk_err, rel(o[:300], want_o[:300]),
                        rel(s1, want_s))
    check(chunk_err < 1e-2, f"kda: blocked form off the loop by {chunk_err}")
    # the decode lane: the kernel compiled, over a buffer of three layers'
    # states at the middle layer's first row (a traced scalar), every
    # other slot idle; the other layers' rows come back as they were
    g = -0.05 * jnp.asarray(rng.uniform(size=(slots, heads, hd)), jnp.float32)
    states = normal(3 * slots, heads, hd, hd)
    active = jnp.arange(slots) % 2 == 0
    mine = slice(slots, 2 * slots)
    want = jax.jit(jax.vmap(
        lambda *r: kda_scan.kda_scan_reference(*(t[None] for t in r[:5]),
                                               r[5])))(
        q[:slots], k[:slots], v[:slots], g, beta[:slots], states[mine])
    before = np.asarray(states)
    o, new = jax.jit(kda_scan.kda_decode_update, donate_argnums=5)(
        *(a[:slots].reshape(slots, -1) for a in (q, k, v, g)), beta[:slots],
        states, active, jnp.int32(slots))
    decode_err = max(
        float(jnp.max(jnp.abs(new[mine][::2] - want[1][::2]))),
        float(jnp.max(jnp.abs(o.reshape(slots, heads, hd)[::2]
                              - want[0][::2, 0]))),
        float(np.max(np.abs(np.asarray(new[mine][1::2])
                            - before[mine][1::2]))),
        float(np.max(np.abs(np.asarray(new[:slots]) - before[:slots]))),
        float(np.max(np.abs(np.asarray(new[2 * slots:])
                            - before[2 * slots:]))))
    check(decode_err < 1e-4, f"kda: decode update off the loop by "
          f"{decode_err}")
    return {"phase": "kda", **device,
            "chunk_scan_rel_err": float(chunk_err),
            "decode_update_max_abs_err": float(decode_err)}


#: the decode calls of the two guard cells that run the plain paged kernel
#: beside SDAR's: (slots, query heads, kv heads, head dim, table pages,
#: pool blocks, shortest and longest context)
PAGED_WALKS = {"granite": (64, 32, 8, 64, 256, 8192, 200, 1800),
               "pythia": (24, 16, 16, 128, 128, 3072, 200, 2048)}


def block_lane_phase(device: dict, block: int = SERVING["kv_block_size"]):
    """Generation by diffusion over blocks, the builder's probe (no cell
    runs it): ``paged_block_attention`` at its cell's widths — 48 slots, 4
    rows a slot, 32 query heads over 4 kv heads of 128 — against the
    float32 reference under the block mask, and its time at the mix's
    mean context and at its longest, on tables of runs (what the cache
    manager hands out: one DMA an operand a run) and on scattered ones
    (a DMA a page), beside four calls of the decode kernel over the same
    pages (what a lane that walked a slot's pages once a row would
    cost).  ``decode_walk``: the decode call of Granite's and of Pythia's
    cell on the same two kinds of table, the plain kernel alone."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer import paged_decode_attention as pda

    rng = np.random.default_rng(SEED + 10)
    slots, rows, heads, kvh, hd, pages, nb = 48, 4, 32, 4, 128, 128, 4864
    pool_k, pool_v = (jnp.asarray(rng.standard_normal((nb, block, kvh * hd)),
                                  jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((slots, rows, heads, hd)),
                    jnp.bfloat16)
    lane = jax.jit(pda.paged_block_attention)

    def four(q, pk, pv, base, tables):
        return jnp.stack(
            [pda.paged_decode_attention(q[:, i], pk, pv, base + rows, tables)
             for i in range(rows)], axis=1)
    out = {"phase": "block_lane", **device}
    active = jnp.asarray(np.arange(slots) % 7 != 3, jnp.int32)
    live = np.asarray(active) > 0
    every = jnp.ones_like(active)
    for name, context in (("mean", 644), ("longest", 1532)):
        base = jnp.full((slots,), context // rows * rows - rows, jnp.int32)
        need = np.full((slots,), -(-context // block))
        for kind, tables in walk_tables(rng, need, pages, nb).items():
            tables = jnp.asarray(tables)
            got = lane(q, pool_k, pool_v, base, active, tables)
            want = pda._reference(
                q.astype(jnp.float32), pool_k, pool_v, base,
                jnp.where(active > 0, base + rows, 0), tables, None, None,
                0, block_rows=rows)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)[live]
                                        - want[live])))
            check(err < 2e-2, f"block lane off its reference by {err} at "
                  f"{context} on {kind} tables")
            check(not bool(jnp.any(got[~live] != 0)), "an idle slot's rows "
                  "are not zero")
            key = name if kind == "runs" else f"{name}_{kind}"
            out[f"{key}_lane_us"] = _call_us(
                pda.paged_block_attention, q, pool_k, pool_v, base, every,
                tables)
            out[f"{key}_max_abs_err"] = err
        out[f"{name}_four_decode_calls_us"] = _call_us(
            four, q, pool_k, pool_v, base, tables)
        # the context's K and V once, at the chip's memory rate
        out[f"{name}_bytes_floor_us"] = round(
            slots * 2 * context * kvh * hd * 2 / 819e9 * 1e6, 1)
    out["decode_walk"] = {}
    for cell, (slots, heads, kvh, hd, pages, nb, lo, hi) in \
            PAGED_WALKS.items():
        lens = rng.integers(lo, hi, slots).astype(np.int32)
        pool_k, pool_v = (jnp.asarray(
            rng.standard_normal((nb, block, kvh * hd), dtype=np.float32),
            jnp.bfloat16) for _ in range(2))
        q = jnp.asarray(rng.standard_normal((slots, heads, hd)),
                        jnp.bfloat16)
        walk = {"bytes_floor_us": round(
            2 * int(lens.sum()) * kvh * hd * 2 / 819e9 * 1e6, 1)}
        for kind, tables in walk_tables(rng, -(-lens // block), pages,
                                        nb).items():
            walk[f"{kind}_us"] = _call_us(
                pda.paged_decode_attention, q, pool_k, pool_v,
                jnp.asarray(lens), jnp.asarray(tables))
        out["decode_walk"][cell] = walk
    return out


def _shared_expert_layer_error(rows: int = 640) -> float:
    """One expert layer of the sandwich block at its published widths in
    bfloat16 — sigmoid top-8 gate over 256 outputs, renormalised and scaled
    by 2.5, 16 held experts through the grouped product, the shared expert
    over every row — against the benchmark's float32 reference of the
    same layer: the norm of the difference over the norm of the held
    experts' own part."""
    import jax
    import jax.numpy as jnp
    from benchmark.lib import reference_openpangu_ultra_moe as reference
    from deepspeed_tpu.models import build_model, openpangu_ultra_moe_config
    held = (0, 16)
    model = build_model(openpangu_ultra_moe_config(
        "718b", num_layers=2, first_k_dense=1, vocab_size=256,
        max_seq_len=256, experts_held=held))
    c = model.config
    cfg = {"n_routed_experts": c.n_routed_experts, "moe_topk": c.moe_topk,
           "scale": c.routed_scaling_factor}
    key = jax.random.PRNGKey(SEED + 4)
    p = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), model.init_superblock(key)))(key)
    u = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, rows, c.d_model)).astype(jnp.bfloat16)
    got = jax.jit(lambda p, u: model.expert_layer(p, u)[0])(p, u)

    def plain(p, u):
        with jax.default_matmul_precision("highest"):
            u = u.astype(jnp.float32)
            return (reference.moe(p, u, cfg, held),
                    reference.routed(p["moe"], u, cfg, held))
    want, own = jax.jit(plain)(p, u)
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(own))


def main(argv) -> int:
    chips = int(argv[1]) if len(argv) > 1 else 1
    device = require_tpu(chips)
    log = CompileLog()
    alone = {"kda": kda_phase, "block": block_lane_phase}
    if len(argv) == 3 and argv[2] in alone:    # that one-chip phase alone
        print(json.dumps(alone[argv[2]](device)), flush=True)
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0

    from deepspeed_tpu.models import gpt2_config
    model_config = gpt2_config("350m", max_seq_len=SEQ_LEN, remat="full",
                               attn_impl="flash", loss_chunk=256)
    t0 = time.perf_counter()
    record, params = train_phase(chips, model_config, log, device)
    print(json.dumps(record), flush=True)
    print(json.dumps(serve_phase(chips, model_config, params, log, device)),
          flush=True)
    print(json.dumps(kernel_phase(model_config.num_heads // chips,
                                  model_config.hdim, device)), flush=True)
    if chips == 1:
        print(json.dumps(latent_phase(device)), flush=True)
        print(json.dumps(hybrid_phase(device)), flush=True)
        print(json.dumps(ssd_hybrid_phase(device)), flush=True)
        print(json.dumps(kda_phase(device)), flush=True)
        print(json.dumps(block_lane_phase(device)), flush=True)
    print(json.dumps({"phase": "total", **device,
                      "wall_s": round(time.perf_counter() - t0, 1),
                      **log.since((0, 0, 0.0, 0.0))}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
