"""Pipeline-parallel engine.

Reference: ``PipelineEngine`` (`/root/reference/deepspeed/runtime/pipe/
engine.py:37`, 1376 LoC) — an instruction interpreter that exchanges
activations over NCCL p2p (`pipe/p2p.py:49,70`) with a meta-shape handshake
(`engine.py:827`), executes 1F1B instruction lists, reduces tied grads
(`engine.py:233`) and DP grads per boundary.

TPU-native redesign: the whole schedule is a single compiled program.

  - stages = slices of a stage-stacked param pytree, sharded over the
    ``pipe`` mesh axis (see `pipe/module.py`);
  - activation exchange = `lax.ppermute` shift-by-one inside a `lax.scan`
    over schedule ticks (the scan carry IS the reference's pipe buffer);
  - microbatch loop memory = a ring of S+1 stored stage inputs, whatever
    the microbatch count (the point of 1F1B);
  - tied-weight grad all-reduce = one psum over ``pipe`` of the embed and
    head gradients at the region's exit (reference's
    _exec_reduce_tied_grads);
  - 3D composition: the region is manual over the FULL
    ``(pipe, model, data)`` product. Each stage's forward/backward is a
    tensor-parallel program over ``model`` (per-shard head counts via
    ``tp_train_view``, exact gradients via the ``copy_to``/``reduce_from``
    pair in `parallel/collectives.py`, vocab-parallel embed + CE), the
    microbatch dim is sharded over the ``data`` product, and gradients
    leave the region through ONE collective per axis family: stage
    boundaries ride ``ppermute`` on ``pipe``, per-layer TP psums stay on
    ``model``, and the DP gradient reduction is a psum — or a ZeRO-2
    ``psum_scatter`` straight into the policy's grad layout
    (`zero/sharding.grad_reduce_plan`) — on ``data``. Three collective
    families, three axes, zero contention.

Bubble math matches TrainSchedule: M microbatches over S stages run
2 (M + S - 1) combined forward/backward ticks.
``measure_bubble_fraction`` turns that from arithmetic into a measured
gauge (``dstpu_train_bubble_frac``) via a two-point slope fit.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...models import layers as L
from ...observability import trace_span
from ...parallel import collectives as C
from ...parallel import topology as topo
from ...parallel.shard_map_compat import shard_map
from ..engine import DeepSpeedEngine, _count_jit_build, global_norm
from ..zero.sharding import constrain, grad_reduce_plan


def chunked_ce(proj, norm_fn, ln_params, y, tok, chunk, onehot,
               tp_axis=None):
    """Head loss of the pipeline schedule: final norm + chunked
    cross-entropy over `chunk`-token slices (the [mb, chunk, V] logits
    block is the only live vocab tensor). Returns (sum_nll, token_count).

    ``proj``: x → logits; ``onehot``: extract the target logit via a
    one-hot product instead of take_along_axis (gathers along a
    vocab-sharded dim crash the SPMD partitioner under manual axes).

    ``tp_axis``: vocab-parallel mode for the 3D engine — ``proj`` maps
    shard-local ``x`` to LOCAL ``[.., V/mp]`` logits and the softmax
    statistics reduce over the model axis (Megatron's vocab-parallel CE:
    shard-max via pmax on a stop_gradient'd copy, log-sum-exp and the
    target logit via ``reduce_from`` so backward stays exact; the
    one-hot of ``label - lo`` is all-zero off-shard).  The full [.., V]
    logits tensor never materializes."""
    mb, t = tok.shape
    x = norm_fn(ln_params, y)
    labels = jnp.concatenate([tok[:, 1:], jnp.zeros_like(tok[:, :1])],
                             axis=1)
    mask = jnp.ones((mb, t), jnp.float32).at[:, -1].set(0.0)
    n_chunks = t // chunk
    if tp_axis is not None:
        fin = C.copy_to(tp_axis)
        red = C.reduce_from(tp_axis)

    def to_chunks(a):
        return a.reshape(mb, n_chunks, chunk, *a.shape[2:]).swapaxes(0, 1)

    def body(carry, xs):
        xc, yc, mc = xs
        if tp_axis is not None:
            logits = proj(fin(xc))             # local [mb, chunk, V/mp]
            vloc = logits.shape[-1]
            lo = jax.lax.axis_index(tp_axis) * vloc
            # stop_gradient INSIDE the pmax: pmax has no JVP rule, so a
            # tangent-carrying operand fails to trace
            m = jax.lax.pmax(
                jnp.max(jax.lax.stop_gradient(logits), axis=-1), tp_axis)
            se = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
            lse = m + jnp.log(red(se))
            tgt = red(jnp.sum(logits * jax.nn.one_hot(
                yc - lo, vloc, dtype=logits.dtype), -1))
        else:
            logits = proj(xc)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            if onehot:
                tgt = jnp.sum(logits * jax.nn.one_hot(
                    yc, logits.shape[-1], dtype=logits.dtype), -1)
            else:
                tgt = jnp.take_along_axis(logits, yc[..., None],
                                          axis=-1)[..., 0]
        tot, cnt = carry
        return (tot + jnp.sum((lse - tgt) * mc), cnt + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (to_chunks(x), to_chunks(labels), to_chunks(mask)))
    return tot, cnt


class PipelinedLM:
    """Adapter: stage-stack a TransformerLM's params for pipeline execution.

    blocks leaves [L, ...] → [S, L/S, ...] (dim 0 sharded over ``pipe``);
    embeddings / final norm replicated over ``pipe`` (tied first/last-stage
    usage, reference PipelineModule TiedLayerSpec)."""

    def __init__(self, model, num_stages: int):
        cfg = model.config
        n_scan = cfg.scan_length
        if n_scan % num_stages != 0:
            raise ValueError(
                f"scanned blocks ({n_scan}) must divide evenly into "
                f"{num_stages} pipeline stages")
        self.model = model
        self.config = cfg
        self.num_stages = num_stages
        self.layers_per_stage = n_scan // num_stages

    def init(self, rng):
        params = self.model.init(rng)
        return self._stack(params)

    def _stack(self, params):
        s, lps = self.num_stages, self.layers_per_stage
        params = dict(params)
        params["blocks"] = jax.tree_util.tree_map(
            lambda x: x.reshape((s, lps) + x.shape[1:]), params["blocks"])
        return params

    def unstack(self, params):
        params = dict(params)
        params["blocks"] = jax.tree_util.tree_map(
            lambda x: x.reshape((-1,) + x.shape[2:]), params["blocks"])
        return params

    # set by PipelineEngine: vocab-sharded embeddings via one-hot matmuls
    # (gather on a sharded table crashes the SPMD partitioner inside the
    # partial-manual shard_map; the matmul form partitions cleanly)
    use_onehot_embed = False

    def partition_specs(self):
        specs = dict(self.model.partition_specs())
        specs["blocks"] = jax.tree_util.tree_map(
            lambda sp: P("pipe", *sp), specs["blocks"],
            is_leaf=lambda x: isinstance(x, P))
        if not self.use_onehot_embed:
            # no TP: replicate embed/head over `model` (nothing to shard)
            specs["embed"] = jax.tree_util.tree_map(
                lambda sp: P(*([None] * len(sp))), specs["embed"],
                is_leaf=lambda x: isinstance(x, P))
            if "lm_head" in specs:
                specs["lm_head"] = jax.tree_util.tree_map(
                    lambda sp: P(*([None] * len(sp))), specs["lm_head"],
                    is_leaf=lambda x: isinstance(x, P))
        return specs

    # engine-protocol loss (single-stage fallback / eval)
    def loss(self, params, batch):
        return self.model.loss(self.unstack(params), batch)


class PipelineEngine(DeepSpeedEngine):
    """Engine whose train step runs the compiled pipeline schedule.

    ``gradient_accumulation_steps`` is the microbatch count M (same meaning
    as the reference's engine: train_batch = micro * M * dp).

    One compiled schedule, 1F1B: the reference TrainSchedule
    (`schedule.py:182`) as ONE scan over 2(M+S-1) combined ticks —
    forward at tick 2m+s, backward at tick 2m+2S-1-s (closed forms of
    the even/odd instruction math, pinned by a validation test).
    Backward is hand-orchestrated jax.vjp per stage from a ring buffer
    of ≤ S+1 stored stage inputs, so activation memory is bounded by
    the in-flight microbatch count instead of the full schedule length.
    """

    def __init__(self, model, config=None, mesh=None, **kw):
        from ..config import DeepSpeedConfig
        config = (config if isinstance(config, DeepSpeedConfig)
                  else DeepSpeedConfig(config or {}))
        if mesh is None:
            mesh = topo.build_mesh(config.mesh)
        if topo.pp_world_size(mesh) < 2:
            raise ValueError("PipelineEngine needs a mesh with pipe>=2")
        self.num_stages = topo.pp_world_size(mesh)
        adapter = model if isinstance(model, PipelinedLM) else PipelinedLM(
            model, self.num_stages)
        adapter.use_onehot_embed = topo.mp_world_size(mesh) > 1
        self.adapter = adapter
        mcfg = adapter.config
        if getattr(mcfg, "attn_impl", None) in ("ring", "ulysses"):
            raise NotImplementedError(
                "ring/ulysses attention (sequence parallel) inside the "
                "compiled pipeline loop would nest manual collectives over "
                "pipe+sequence — not supported yet; use sequence "
                "parallelism without PP")
        ps = config.pipeline.stages
        if ps != "auto" and int(ps) != self.num_stages:
            raise ValueError(
                f"pipeline.stages ({ps}) != mesh pipe axis "
                f"({self.num_stages}): this config was exported for a "
                f"different topology")
        pmb = config.pipeline.micro_batches
        if pmb:
            tb, mb, gas = getattr(
                config, "_user_batch_triple",
                (config.train_batch_size,
                 config.train_micro_batch_size_per_gpu,
                 config.gradient_accumulation_steps))
            if gas is not None and gas != pmb:
                raise ValueError(
                    f"pipeline.micro_batches ({pmb}) conflicts with "
                    f"gradient_accumulation_steps ({gas})")
            # micro_batches IS the accumulation count M; rebalance the
            # batch triple around it (the per-device micro batch
            # re-derives from train_batch_size when that is pinned)
            config._user_batch_triple = (
                tb, None if tb is not None else mb, pmb)
        # -- 3D region setup -------------------------------------------
        self._mp = topo.mp_world_size(mesh)
        if dict(mesh.shape).get(topo.EXPERT_AXIS, 1) > 1:
            raise NotImplementedError(topo.EXPERT_AXIS_REFUSAL)
        if self._mp > 1:
            if mcfg.vocab_size % self._mp:
                raise ValueError(
                    f"model mesh axis ({self._mp}) must divide vocab_size "
                    f"({mcfg.vocab_size}) for vocab-parallel embed/CE")
            # per-shard head-count view with the exact-backward collective
            # pair armed; raises on indivisible heads
            self._tview = adapter.model.tp_train_view(
                self._mp, topo.MODEL_AXIS)
        else:
            self._tview = adapter.model
        self._plan = None            # grad-reduce plan, set at region build
        super().__init__(model=adapter, config=config, mesh=mesh, **kw)

    @property
    def micro_batches(self) -> int:
        return self.gradient_accumulation_steps

    def _stage_windows(self, model, sid):
        """This stage's slice of the per-layer attention-window vector
        (TransformerConfig.attention_layers — the GPT-Neo family), or None
        when the model has none. ``sid`` is the traced stage index, so the
        slice is dynamic while its length (layers per stage) is static."""
        wins = getattr(model, "_layer_windows", lambda: None)()
        if wins is None:
            return None
        lps = model.config.scan_length // self.num_stages
        return jax.lax.dynamic_slice(wins, (sid * lps,), (lps,))

    # -- 3D region plumbing ------------------------------------------------
    def _data_axes(self):
        """Size>1 data-parallel mesh axes, in mesh order (the ``data``
        leg of the 3D product; an expert axis is refused at build)."""
        ms = dict(self.mesh.shape)
        return tuple(a for a in (topo.DCN_DATA_AXIS, topo.DATA_AXIS)
                     if ms.get(a, 1) > 1)

    def _dp_prod(self) -> int:
        ms = dict(self.mesh.shape)
        return int(np.prod([ms[a] for a in self._data_axes()] or [1]))

    def _region_param_specs(self):
        """shard_map in_specs for the 3D region: the adapter's partition
        specs (``pipe`` on the blocks stack dim, ``model`` on the TP
        dims), with ``model`` stripped from the fused-qkv leaves — the
        global ``[q|k|v]`` packing cannot tile contiguously over the
        model axis, so qkv enters REPLICATED and each shard gathers its
        own permuted columns inside the differentiated region
        (`collectives.qkv_shard_columns`)."""
        specs = self.adapter.partition_specs()
        if self._mp <= 1:
            return specs

        def strip(path, sp):
            keys = tuple(getattr(p, "key", None) for p in path)
            if keys[-2:] in (("qkv", "kernel"), ("qkv", "bias")):
                return P(*[None if e == topo.MODEL_AXIS else e
                           for e in sp])
            return sp
        return jax.tree_util.tree_map_with_path(
            strip, specs, is_leaf=lambda x: isinstance(x, P))

    def _qkv_cols(self):
        """This model shard's fused-qkv column gather (traced row pick)."""
        c0 = self.adapter.model.config
        cols = jnp.asarray(C.qkv_shard_columns(
            c0.num_heads, c0.kv_heads, c0.hdim, self._mp))
        return cols[jax.lax.axis_index(topo.MODEL_AXIS)]

    def _tp_localize_fn(self, cols):
        """Block-param localizer applied INSIDE the differentiated
        functions: fused-qkv column gather (vjp scatters partial grads
        back into the global layout) and row-parallel bias pre-division
        (the reduce_from restores the bias exactly). Identity when the
        model axis is trivial."""
        if self._mp <= 1:
            return lambda bl: bl
        mp = self._mp

        def localize(bl):
            bl = dict(bl)
            attn = dict(bl["attn"])
            qkv = dict(attn["qkv"])
            qkv["kernel"] = jnp.take(qkv["kernel"], cols, axis=-1)
            if "bias" in qkv:
                qkv["bias"] = jnp.take(qkv["bias"], cols, axis=-1)
            attn["qkv"] = qkv
            out = dict(attn["out"])
            if "bias" in out:
                out["bias"] = out["bias"] / mp
            attn["out"] = out
            bl["attn"] = attn
            mlp = dict(bl["mlp"])
            fco = dict(mlp["fc_out"])
            if "bias" in fco:
                fco["bias"] = fco["bias"] / mp
            mlp["fc_out"] = fco
            bl["mlp"] = mlp
            return bl
        return localize

    def _tp_embed_fn(self, cfg, t):
        """Token+position embed for the region. mp>1: vocab-parallel
        masked take (off-shard rows zeroed, reduce_from over ``model``
        rejoins the replicated stream — identity backward, so the local
        table grad is exact); the positional embed adds AFTER the
        reduction, on the replicated stream (full grads every shard)."""
        tp = self._mp > 1
        red = C.reduce_from(topo.MODEL_AXIS) if tp else None
        onehot = getattr(self.adapter, "use_onehot_embed", False)

        def embed_fn(ep, tok):
            if tp:
                emb = ep["embed"]["embedding"].astype(cfg.dtype)
                vloc = emb.shape[0]
                lo = jax.lax.axis_index(topo.MODEL_AXIS) * vloc
                mine = (tok >= lo) & (tok < lo + vloc)
                x = jnp.take(emb, jnp.where(mine, tok - lo, 0), axis=0)
                x = red(jnp.where(mine[..., None], x, jnp.zeros_like(x)))
            else:
                embed = (L.embedding_apply_onehot if onehot
                         else L.embedding_apply)
                x = embed(ep["embed"], tok, cfg.dtype)
            if cfg.pos_embedding == "learned":
                pos = jnp.arange(t)[None, :]
                x = x + L.embedding_apply(ep["pos_embed"], pos, cfg.dtype)
            return x
        return embed_fn

    def _grad_exit_reduce(self, grads):
        """The per-axis exit collectives of the 3D region: one psum over
        ``model`` for the partial-gradient leaf set, then one psum — or
        ZeRO-2 ``psum_scatter`` per the precomputed plan — over the data
        product for every leaf. (``pipe`` reductions stay at the call
        sites: blocks are pipe-local, embed/head psum over pipe.)"""
        if self._mp > 1:
            grads = C.psum_tp_partials(grads, topo.MODEL_AXIS)
        daxes = self._data_axes()
        if daxes:
            plan_sub = {k: self._plan[k] for k in grads}
            grads = jax.tree_util.tree_map(
                lambda g, pl: C.reduce_over_data(g, pl, daxes),
                grads, plan_sub)
        return grads

    # ------------------------------------------------------------------
    # 1F1B: one compiled scan over combined fwd/bwd ticks
    # ------------------------------------------------------------------
    def _pipeline_value_and_grad(self, params, ids, scale):
        """Manual over the full ``(pipe, model, data)`` product. ids
        [M, mb_local, T] with
        the microbatch dim sharded over the data product; params in
        compute dtype, per the region specs (`_region_param_specs`).
        Returns (loss summed over microbatches AND data shards, grads
        summed the same way x ``scale``) — backward is hand-driven
        jax.vjp per stage, activations bounded by a ring of S+1 stored
        stage inputs; each stage body is the tensor-parallel program of
        ``tp_train_view`` (exact-gradient copy_to/reduce_from seams).

        Tick timing (validated against TrainSchedule, test_pipeline.py):
            forward  of microbatch m at stage s: tick 2m + s
            backward of microbatch m at stage s: tick 2m + 2S - 1 - s
        Activations ppermute forward each tick, cotangents backward; both
        are consumed exactly one tick after production.
        """
        cfg = self.adapter.config
        model = self._tview
        tp = self._mp > 1
        s = self.num_stages
        sid = jax.lax.axis_index(topo.PIPE_AXIS)
        m, mb, t = ids.shape
        cap = s + 1                      # ring capacity ≥ in-flight bound
        onehot = getattr(self.adapter, "use_onehot_embed", False)
        norm = (L.layernorm_apply if cfg.norm_type == "layernorm"
                else L.rmsnorm_apply)
        norm = partial(norm, eps=cfg.layernorm_eps)

        blocks_local = jax.tree_util.tree_map(lambda x: x[0],
                                              params["blocks"])
        eparams = {"embed": params["embed"]}
        if "pos_embed" in params:
            eparams["pos_embed"] = params["pos_embed"]
        tied = "lm_head" not in params
        hparams = {"ln_f": params["ln_f"],
                   ("embed" if tied else "lm_head"):
                       params["embed" if tied else "lm_head"]}

        embed_fn = self._tp_embed_fn(cfg, t)
        localize = self._tp_localize_fn(self._qkv_cols() if tp else None)
        win_local = self._stage_windows(model, sid)

        def stage_fn(bl, x):
            bl = localize(bl)   # inside the vjp: qkv grads scatter back
            def f(c, xs):
                bp, win = (xs, None) if win_local is None else xs
                y, _ = model._block(bp, c, None, None, win)
                return y, None
            y, _ = jax.lax.scan(
                f, x, bl if win_local is None else (bl, win_local))
            return y

        chunk = cfg.loss_chunk if (cfg.loss_chunk and
                                   t % max(cfg.loss_chunk, 1) == 0 and
                                   t > cfg.loss_chunk) else t

        def head_fn(hp, y, tok):
            """Per-microbatch MEAN CE via the chunked_ce head. Under
            TP the projection is shard-local ([.., V/mp] logits) and
            chunked_ce runs Megatron's vocab-parallel CE over ``model``."""
            def proj(xc):
                if tied:
                    return L.embedding_attend(hp["embed"], xc)
                return jnp.einsum("...d,dv->...v", xc,
                                  hp["lm_head"]["kernel"].astype(xc.dtype),
                                  preferred_element_type=jnp.float32)
            tot, cnt = chunked_ce(proj, norm, hp["ln_f"], y, tok, chunk,
                                  onehot,
                                  tp_axis=topo.MODEL_AXIS if tp else None)
            return tot / jnp.maximum(cnt, 1.0)

        perm_f = [(i, (i + 1) % s) for i in range(s)]
        perm_b = [(i, (i - 1) % s) for i in range(s)]
        f32 = jnp.float32

        def zeros_f32(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, f32), tree)

        def tick(carry, tt):
            act, cot, buf, g_bl, g_e, g_h, lsum = carry
            recv_act = jax.lax.ppermute(act, topo.PIPE_AXIS, perm_f)
            recv_cot = jax.lax.ppermute(cot, topo.PIPE_AXIS, perm_b)

            # ---- forward part: microbatch (tt - sid)/2 ------------------
            mf2 = tt - sid
            mf = jnp.clip(mf2 // 2, 0, m - 1)
            fvalid = (mf2 % 2 == 0) & (mf2 >= 0) & (mf2 // 2 < m)
            # embed only where it's real work: stage 0's valid fwd ticks
            # (under TP the one-hot embed is an mb·t·V·d matmul)
            x_in = jax.lax.cond(
                fvalid & (sid == 0),
                lambda: embed_fn(eparams, ids[mf]), lambda: recv_act)
            slot = mf % cap
            old = jax.lax.dynamic_index_in_dim(buf, slot, 0,
                                               keepdims=False)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(fvalid, x_in, old), slot, 0)
            # last stage never forwards its output anywhere; skip compute
            new_act = jax.lax.cond(
                fvalid & (sid < s - 1),
                lambda: stage_fn(blocks_local, x_in), lambda: act)

            # ---- backward part: microbatch (tt - (2S-1-sid))/2 ----------
            mb2 = tt - (2 * s - 1 - sid)
            mbk = jnp.clip(mb2 // 2, 0, m - 1)
            bvalid = (mb2 % 2 == 0) & (mb2 >= 0) & (mb2 // 2 < m)
            x_st = jax.lax.dynamic_index_in_dim(buf, mbk % cap, 0,
                                                keepdims=False)
            tok_b = ids[mbk]

            def bwd_last():
                lossv, vjp = jax.vjp(
                    lambda x, bl, hp: head_fn(hp, stage_fn(bl, x), tok_b),
                    x_st, blocks_local, hparams)
                dx, dbl, dhp = vjp(jnp.asarray(scale, f32))
                return dx, dbl, dhp, lossv

            def bwd_mid():
                _, vjp = jax.vjp(lambda x, bl: stage_fn(bl, x),
                                 x_st, blocks_local)
                dx, dbl = vjp(recv_cot)
                return (dx, dbl,
                        jax.tree_util.tree_map(jnp.zeros_like, hparams),
                        jnp.zeros((), f32))

            def bwd_skip():
                return (jnp.zeros_like(act),
                        jax.tree_util.tree_map(jnp.zeros_like,
                                               blocks_local),
                        jax.tree_util.tree_map(jnp.zeros_like, hparams),
                        jnp.zeros((), f32))

            dx, dbl, dhp, lossv = jax.lax.cond(
                bvalid,
                lambda: jax.lax.cond(sid == s - 1, bwd_last, bwd_mid),
                bwd_skip)

            dep = jax.lax.cond(
                bvalid & (sid == 0),
                lambda: jax.vjp(lambda ep: embed_fn(ep, tok_b),
                                eparams)[1](dx)[0],
                lambda: jax.tree_util.tree_map(jnp.zeros_like, eparams))

            g_bl = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(f32)[None], g_bl, dbl)
            g_e = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(f32), g_e, dep)
            g_h = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(f32), g_h, dhp)
            return (new_act, dx, buf, g_bl, g_e, g_h, lsum + lossv), None

        act0 = jnp.zeros((mb, t, cfg.d_model), cfg.dtype)
        buf0 = jnp.zeros((cap, mb, t, cfg.d_model), cfg.dtype)
        carry0 = (act0, act0, buf0,
                  zeros_f32(params["blocks"]), zeros_f32(eparams),
                  zeros_f32(hparams), jnp.zeros((), f32))
        (_, _, _, g_bl, g_e, g_h, lsum), _ = jax.lax.scan(
            tick, carry0, jnp.arange(2 * (m + s - 1)))

        psum = partial(jax.lax.psum, axis_name=topo.PIPE_AXIS)
        loss = jax.lax.psum(                   # last stage only; summed
            lsum, (topo.PIPE_AXIS,) + self._data_axes())
        grads = {"blocks": g_bl}               # stays pipe-local
        g_e = jax.tree_util.tree_map(psum, g_e)     # stage 0 only
        g_h = jax.tree_util.tree_map(psum, g_h)     # last stage only
        grads["ln_f"] = g_h["ln_f"]
        if tied:
            grads["embed"] = jax.tree_util.tree_map(
                jnp.add, g_e["embed"], g_h["embed"])
        else:
            grads["embed"] = g_e["embed"]
            grads["lm_head"] = g_h["lm_head"]
        if "pos_embed" in g_e:
            grads["pos_embed"] = g_e["pos_embed"]
        return loss, self._grad_exit_reduce(grads)

    def _build_train_step(self):
        # the schedule itself runs inside ONE jitted program (per-tick
        # stage work is the device profiler's domain); the host-side span
        # marks for how many stages/micros it was compiled
        with self._ovl.setup_span("setup/build_train_step"), \
                trace_span("pipe/build_schedule", stages=self.num_stages,
                           micro_batches=self.micro_batches):
            return self._build_train_step_traced()

    def _build_loss_grad_region(self):
        """The shard_map'd ``(params, ids, scale) -> (loss, grads)``
        program — shared by the train-step builder and the bubble
        probe: the 3D region (manual over pipe, model and the data
        product, ZeRO grad plan precomputed)."""
        daxes = self._data_axes()
        region_specs = self._region_param_specs()
        self._plan, gout = grad_reduce_plan(region_specs, self.grad_specs,
                                            daxes)
        ids_spec = (P(None, daxes if len(daxes) > 1 else daxes[0])
                    if daxes else P())
        # the region's param specs name `model` whatever its size, and a
        # spec may only name manual axes
        names = {topo.PIPE_AXIS, topo.MODEL_AXIS} | set(daxes)
        # 1F1B assembles exactly the head/embed/blocks grads; subset
        # the out-spec tree to match (tied embeds have no lm_head key)
        gout = {k: gout[k] for k in
                ("blocks", "ln_f", "embed", "lm_head", "pos_embed")
                if k in gout}
        return shard_map(
            self._pipeline_value_and_grad, mesh=self.mesh,
            in_specs=(region_specs, ids_spec, P()),
            out_specs=(P(), gout),
            axis_names=names)

    def _build_train_step_traced(self):
        sharded = self._build_loss_grad_region()
        # grads and per-micro mean losses are SUMS over microbatches
        # and data shards — normalize by both
        n_eff = float(self.micro_batches * self._dp_prod())

        def pipe_train_step(state, batch):
            ids = batch["input_ids"]        # [M, micro*dp, T]
            scale = self._current_scale(state)
            loss_sum, grads = sharded(
                self._cast_for_compute(state["params"]), ids, scale)
            new_state, metrics = self._apply_grads(state, grads, n_eff)
            metrics["loss"] = loss_sum / n_eff
            return new_state, metrics

        with self.mesh:
            self._train_step_fn = jax.jit(pipe_train_step,
                                          donate_argnums=(0,))
        _count_jit_build(pipe_train_step)
        return self._train_step_fn

    # ------------------------------------------------------------------
    # measured bubble fraction
    # ------------------------------------------------------------------
    def measure_bubble_fraction(self, micro_counts=None, repeats: int = 2,
                                seq_len: Optional[int] = None) -> Dict:
        """Measure the schedule's pipeline-bubble fraction on this
        engine's compiled loss+grad program (two-point slope fit).

        Timing the full program at two microbatch counts M1 < M gives
        the per-microbatch steady-state cost as the slope; the intercept
        is the fill/drain bubble:

            bubble = (t(M) - M * slope) / t(M)

        1F1B's ticks cond-skip the bubble slots' compute, so the
        intercept is small, well under the analytic (S-1)/(M+S-1) of a
        loop that runs every stage on every tick. Records the
        ``dstpu_train_bubble_frac`` gauge and
        returns the fit. Device-syncing — a profiling call, not a train
        step."""
        m_full = self.micro_batches
        if micro_counts is not None:
            m_small, m_full = micro_counts
        else:
            m_small = max(1, m_full // 2)
        if not m_small < m_full:
            raise ValueError(
                f"bubble fit needs two distinct microbatch counts, got "
                f"({m_small}, {m_full}) — run with "
                f"gradient_accumulation_steps >= 2")
        import time as _time
        cfg = self.adapter.config
        t_len = int(seq_len or cfg.max_seq_len)
        mb_global = self.train_batch_size // self.micro_batches
        with trace_span("pipe/bubble_probe", stages=self.num_stages,
                        m_small=m_small,
                        m_full=m_full):
            region = self._build_loss_grad_region()
            with self.mesh:
                probe = jax.jit(region)   # no donation: params are live
            _count_jit_build(region)
            params = self._cast_for_compute(self.state["params"])
            scale = jnp.asarray(1.0, jnp.float32)
            times = {}
            for m in (m_small, m_full):
                ids = jnp.zeros((m, mb_global, t_len), jnp.int32)
                jax.block_until_ready(probe(params, ids, scale))  # compile
                best = float("inf")
                for _ in range(max(1, repeats)):
                    t0 = _time.perf_counter()
                    jax.block_until_ready(probe(params, ids, scale))
                    best = min(best, _time.perf_counter() - t0)
                times[m] = best
            slope = (times[m_full] - times[m_small]) / (m_full - m_small)
            frac = 0.0
            if times[m_full] > 0:
                frac = (times[m_full] - m_full * slope) / times[m_full]
            frac = min(1.0, max(0.0, frac))
        self._ovl.record_bubble(frac)
        return {"bubble_frac": frac, "stages": self.num_stages,
                "micro_counts": (m_small, m_full),
                "step_time_s": times[m_full], "per_micro_s": max(slope, 0.0)}
