"""A training cell of a block that routes experts (``zaya1-8b``):
``ds.initialize`` -> ``engine.train_step`` on one seeded batch, repeated,
as ``runners/train.py`` runs the dense models.  What this kind adds to
``train``:

- the build: ``models.build_model`` of a config subclass (``train.py``
  builds ``TransformerLM`` only), every published width checked against
  what the program built (``PUBLISHED``), the optimizer's schedule from
  the traffic file (``scheduler``);
- the loop keeps ONE STEP QUEUED behind the one it waits for (a step's
  end is still ``block_until_ready`` on its loss): the host's dispatch, a
  few ms that some processes take longer over, runs under the device's
  step, as in a training loop that does not read every loss;
- the step's counters: ``train_step`` hands back what the model's loss
  counted (``moe_picks``, ``moe_picks_held``, ``moe_rows_max_expert``,
  ``moe_experts_touched``, ``router_bias_abs_max``) as device scalars; they
  are read after the window, so no step waits on them.  From them the
  ``moe_*`` values, ``flops_per_token`` with the experts at the held
  picks' share (``lib/costs_moe_train.py``) and the work of the two
  grouped-product kernels;
- ``correct`` holds ONE STEP OF THE TIMED PROGRAM to the reference
  (``StepCheck``): the run's second ``engine.train_step`` on the timed
  batch (inside the warm-up; the first runs at a learning rate of 0).  A
  few leaves — two experts' three matrices, ``Wr``, ``gamma``, the
  depthwise taps, ``tau``, ``Wv2``, ``b``, a norm's scale, a slice of the
  embedding — and their two moments are copied to the host before and
  after it (one at a time: ``memory_peak_bytes`` stays the training's
  own); all the weights before it are the seed's and are made again.
  (c) the gradient the step applied, read back from its first moment
  (``(m' - beta1 m) / (1 - beta1)``), against
  ``lib/reference_zaya.py``'s gradient on BOTH sequences at those
  weights times the reference's own clip factor, and
  the norm the step reported against the reference's; (e) the leaves'
  change against a plain AdamW step (``plain_adamw``: warm-up, bias
  correction, decay) from that gradient and the copied moments.  The
  reference's side is computed after the window, with the engine's state
  dropped for its room;
- and, after the window on the weights it left: (a) ``engine.eval_loss``
  on two seeded sequences against the reference's loss; (b) the first
  layer's expert sublayer against the reference's loop over experts; (d)
  every loss finite, the median of the window's last five under the
  first, no compile in the window.  ``memory_peak_bytes`` is read before
  anything that runs after the window.

``--set control=...`` (beyond the contract; the driver passes none)
reads the comparison against a reference that lacks one thing, for the
controls in ``PERF.md`` section 4: ``float8``, ``half_batch``, ``conv``,
``qk_mean``, ``value_shift``, ``key_temperature``, ``router_carry``,
``expert_dw``; a list of them, or ``all`` (each in turn, into
``diag.controls``; ``correct`` is then the sound comparison's).  The
controls of (e) need no reference and are read in every run
(``diag.update_controls``), as is the clip left out of (c).
"""
from __future__ import annotations

import functools
import math
import time

import numpy as np

from ..lib import (costs, costs_latent, costs_moe_train, device,
                   model as model_lib, reference_zaya as reference)

#: every number ``correct`` holds, with its limit.  Each limit lies
#: between a sound reading and a control's (my chip runs, PR 44: 13 sound
#: runs over 12 seeds of the rebuilt check; ``PERF.md`` section 4 quotes
#: the same runs)
LIMITS = {
    # (a) |engine.eval_loss - reference loss| on two fresh sequences, on
    # the weights the window left.  Sound 2.5e-5 to 7.5e-4 (3.6e-3 the
    # worst of 24 earlier runs); the reference without the value shift
    # 1.8e-2 / 2.6e-2, without the q-k mean 4.7e-2; float8 weights 1.1e-4:
    # the precision hardly moves a loss, it is (b)'s
    "loss_abs_err": 1e-2,
    # (b) the first layer's expert sublayer over 8,192 seeded rows against
    # the reference's loop over experts: the norm of the difference over
    # the rows where both sides made the same pick.  Sound 0.0054, float8
    # weights 0.234 / 0.246 ...
    "expert_rel_err": 0.02,
    # ... and the share of rows where they did not (near-tied scores that
    # a bfloat16 product decides the other way).  Sound 0.0001-0.0038; a
    # router that adds its bias in another unit 0.987-0.997
    "pick_flip_share": 0.02,
    # (c) the worst compared leaf's |applied gradient - clip factor x
    # reference gradient| over the norm of the latter; the reference takes
    # the program's picks, so flipped rows are not in it.  Sound
    # 0.036-0.069 (gamma, Wr, the norm's scale; the experts' matrices
    # 0.013-0.016 — they read 0.06-0.25 while each side routed for
    # itself); half the batch 0.725; the clip left out 0.906-0.908 (the
    # factor is 0.093: ``grad_rel_err_no_clip``, read in every run); the
    # reference without the value shift 1.43; a compared expert's dw left
    # out: infinite; float8 weights 0.049: (b)'s to refuse ...
    "grad_rel_err": 0.2,
    # ... the flipped rows are here: the share of the batch's (layer, row)
    # pairs that the program's forward pass and the reference route apart
    # at the step's weights.  Sound 0.0073-0.0084; the reference without
    # the value shift 0.77 (near the start every score is near 1/16) ...
    "step_pick_flip_share": 0.05,
    # ... and the global norm the step reported (10.6-10.8) against the
    # reference's.  Sound 2.3e-5 to 3.8e-4; float8 weights 1.1e-3; half
    # the batch 0.104; without the value shift 0.129
    "grad_norm_rel_err": 0.01,
    # (e) the worst leaf's |change - plain AdamW's| over the norm of the
    # latter, and the same of the second moment.  Sound 3.7e-5 to 2.5e-4;
    # the state left as it was 1.0, the learning rate without its warm-up
    # 0.950, no decay 0.106 (a norm's scale: 0.1 x 1 beside Adam's 1)
    "update_rel_err": 5e-3,
}
SPANS = ("train_step",)
CONTROLS = ("float8", "half_batch", "conv", "qk_mean", "value_shift",
            "key_temperature", "router_carry", "expert_dw")
#: the engine's step that is held to the reference, counted from 0: the
#: first after the one that runs at a learning rate of 0
CHECKED_STEP = 1
#: rows of the embedding that are compared
EMBEDDING_ROWS = 4096

#: configuration key -> what the program built
PUBLISHED = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
             "num_attention_heads": "num_heads",
             "num_key_value_heads": "kv_heads", "head_dim": "hdim",
             "moe_intermediate_size": "expert_d_ff",
             "router_hidden_size": "router_hidden",
             "cca_time0": "cca_time0", "cca_time1": "cca_time1",
             "partial_rotary_factor": "rotary_pct",
             "rms_norm_eps": "layernorm_eps", "hidden_act": "activation",
             "attention_bias": "use_bias", "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len",
             "tie_word_embeddings": "tie_embeddings"}


def build(config: dict, tiny: dict | None = None):
    """``(model config, reference settings)``; every published size is
    checked against what the program built."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import cca_moe, transformer as T
    prog = config["program"]
    kwargs = dict(prog["kwargs"])
    if tiny:
        kwargs.update(tiny["model"])
    if "dtype" in kwargs:
        kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    kwargs["experts_held"] = tuple(kwargs["experts_held"])
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    lo, hi = mc.held
    if not tiny:
        built = {k: getattr(mc, v) for k, v in PUBLISHED.items()}
        built.update(num_experts=hi - lo, num_experts_per_tok=1,
                     router_bias_unit=cca_moe.ROUTER_BIAS_UNIT)
        want = {k: config[k] for k in built}
        if built != want or mc.n_routed_experts != \
                config["published"]["num_experts"] or mc.rotary_base != \
                config["rope_parameters"]["hybrid"]["rope_theta"]:
            raise ValueError(f"the program built {built}, the "
                             f"configuration file says {want}")
    ref = {"heads": mc.num_heads, "kv_heads": mc.kv_heads,
           "head_dim": mc.hdim, "rotary_dim": mc.rotary_dim,
           "rope_theta": mc.rotary_base, "eps": mc.layernorm_eps,
           "held": (lo, hi), "bias_unit": config["router_bias_unit"]}
    return mc, ref


def _rel(got, want) -> float:
    got = np.asarray(got, np.float32).ravel()
    want = np.asarray(want, np.float32).ravel()
    return float(np.linalg.norm(got - want)
                 / (np.linalg.norm(want) + 1e-30))


def _worst(got: dict, want: dict) -> dict:
    """``{leaf: norm of the difference over norm}``; a stack of experts
    reads its worst expert, each over its own norm."""
    return {n: max(_rel(g, w) for g, w in zip(got[n], want[n]))
            if n.startswith("experts.") else _rel(got[n], want[n])
            for n in want}


def _float8(tree):
    """Every weight rounded to float8 (e4m3), the nearest precision below
    the configuration's bfloat16."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype), tree)


def _lacking(ref_cfg: dict, control: str | None):
    """``(the reference's settings, what it does to the weights first)``
    for the sound comparison (None) or a control that changes the
    reference."""
    cfg = dict(ref_cfg)
    if control in ("conv", "qk_mean", "value_shift", "key_temperature",
                   "router_carry"):
        cfg["without"] = (control,)
    return cfg, _float8 if control == "float8" else (lambda tree: tree)


def _compared(tree: dict, to_host: bool = False) -> dict:
    """The leaves the step check follows, of a tree shaped like the
    parameters (weights, gradients, moments): the first layer's first and
    last held expert's three matrices, ``Wr``, ``gamma``, the depthwise
    taps, ``tau``, ``Wv2``, ``b`` and the second norm's scale of every
    layer, and the embedding's first rows.  ``to_host`` fetches each as it
    is cut, so that no two cuts are on the device at once: the copies must
    not show in ``memory_peak_bytes``."""
    take = np.asarray if to_host else (lambda a: a)
    blocks = tree["blocks"]
    out = {f"experts.{n}": take(w[0, np.array([0, -1])])
           for n, w in blocks["moe"]["experts"].items()}
    router = blocks["moe"]["router"]
    for name, leaf in (
            ("router_in", router["in"]["kernel"]), ("gamma", router["gamma"]),
            ("conv0_taps", blocks["attn"]["conv0"]["taps"]),
            ("tau", blocks["attn"]["tau"]),
            ("v2", blocks["attn"]["v2"]["kernel"]),
            ("bias", blocks["moe"]["bias"]), ("ln2", blocks["ln2"]["scale"]),
            ("embedding", tree["embed"]["embedding"][:EMBEDDING_ROWS])):
        out[name] = take(leaf)
    return out


def plain_adamw(p, g, m, v, lr, t, betas, eps, weight_decay):
    """One AdamW step on one leaf in float32 numpy, ``t`` counted from 1:
    ``(the new weights, the new second moment)``."""
    f = np.float32
    b1, b2 = betas
    m = f(b1) * m + f(1 - b1) * g
    v = f(b2) * v + f(1 - b2) * g * g
    u = (m / f(1 - b1 ** t)) / (np.sqrt(v / f(1 - b2 ** t)) + f(eps))
    return p - f(lr) * (u + f(weight_decay) * p), v


def plain_warmup(step: int, sched: dict) -> float:
    """``WarmupLR``, linear: the learning rate of the step counted from
    0."""
    at = min(step, sched["warmup_num_steps"]) / sched["warmup_num_steps"]
    return sched["warmup_min_lr"] + at * (
        sched["warmup_max_lr"] - sched["warmup_min_lr"])


class StepCheck:
    """One step of the timed program against the reference: (c) and (e)
    of the module's docstring.  ``before`` and ``after`` bracket the
    checked ``engine.train_step``; ``against`` is called after the window,
    when the engine's state has been dropped."""

    def __init__(self, engine, model, ref_cfg, mix, ids, rng):
        self.engine, self.model, self.ref_cfg = engine, model, ref_cfg
        self.mix, self.ids, self.rng = mix, np.asarray(ids), rng

    @staticmethod
    def _copied(state) -> tuple:
        return tuple(_compared(tree, to_host=True) for tree in (
            state["params"], state["opt"]["m"], state["opt"]["v"]))

    def before(self):
        self.p0, self.m0, self.v0 = self._copied(self.engine.state)

    def after(self, out):
        self.p1, self.m1, self.v1 = self._copied(self.engine.state)
        self.reported_norm = float(out["grad_norm"])
        b1 = self.mix["optimizer"]["params"]["betas"][0]
        #: the gradient the step applied (averaged, clipped)
        self.applied = {n: (self.m1[n] - np.float32(b1) * self.m0[n])
                        / np.float32(1 - b1) for n in self.m1}

    def update_errs(self, **fault) -> float:
        """(e): the worst leaf's change, and second moment, against plain
        AdamW from the applied gradient.  ``fault`` leaves one thing out
        of the comparison's own side, for the controls: ``unchanged`` (the
        state as it was before the step), ``no_warmup``, ``no_decay``."""
        opt, sched = self.mix["optimizer"]["params"], self.mix["scheduler"]
        lr = opt["lr"] if fault.get("no_warmup") else plain_warmup(
            CHECKED_STEP, sched["params"])
        decay = 0.0 if fault.get("no_decay") else opt["weight_decay"]
        worst = 0.0
        for n, g in self.applied.items():
            p0 = self.p0[n]
            want_p, want_v = plain_adamw(
                p0, g, self.m0[n], self.v0[n], lr, CHECKED_STEP + 1,
                opt["betas"], opt["eps"], decay)
            got_p, got_v = (p0, self.v0[n]) if fault.get("unchanged") \
                else (self.p1[n], self.v1[n])
            worst = max(worst, _rel(got_p - p0, want_p - p0),
                        _rel(got_v, want_v))
        return worst

    @functools.cached_property
    def device_params(self):
        """Every weight as it was before the step.  The first step runs at
        a learning rate of 0, so they are the seed's: made again by the
        engine's own ``init_state`` (a copy of them all would show in
        ``memory_peak_bytes``) and held to the copied leaves bit for
        bit."""
        params = self.engine.init_state(self.rng)["params"]
        again = _compared(params, to_host=True)
        if not all(np.array_equal(again[n], self.p0[n]) for n in again):
            raise RuntimeError("the weights before the checked step are "
                               "not the seed's")
        return params

    @functools.cached_property
    def program_picks(self):
        """``[L, B, T]``: every layer's pick by the model's own forward
        pass on the compute-type weights (the fused step hands out none;
        this is the same code in a program of its own)."""
        import jax
        import jax.numpy as jnp
        model, engine = self.model, self.engine
        norm = model._norm_fn()

        def forward(params, ids):
            params = engine._cast_for_compute(params)
            x = model._embed_tokens(params, ids)

            def body(carry, bp):
                x, r_prev = carry
                bp = model.block_transform(bp)
                x = x + model._cca(bp["attn"], norm(bp["ln1"], x))
                u = norm(bp["ln2"], x)
                pick = model._route(bp["moe"], u, r_prev)[1].index[:, 0]
                y, r = model._moe(bp["moe"], u, r_prev)[:2]
                return (x + y, r), pick.reshape(u.shape[:2])
            width = model.config.router_hidden
            return jax.lax.scan(
                body, (x, jnp.zeros(x.shape[:2] + (width,), jnp.float32)),
                params["blocks"])[1]
        return np.asarray(jax.jit(forward)(self.device_params,
                                           jnp.asarray(self.ids)))

    def against(self, control: str | None = None) -> dict:
        """(c) against the reference, sound (``control`` None) or lacking
        one thing; (e) beside it."""
        import jax
        import jax.numpy as jnp
        cfg, prepare = _lacking(self.ref_cfg, control)
        picks, params = self.program_picks, self.device_params

        def ref_loss(params, ids, picks):
            return reference.loss(prepare(params), ids, cfg, jax.checkpoint,
                                  picks)
        grads_of = jax.jit(jax.grad(ref_loss))
        picks_of = jax.jit(lambda params, ids: reference.own_picks(
            prepare(params), ids, cfg))
        # a sequence at a time: the batch's gradient is their mean
        rows = range(1 if control == "half_batch" else len(self.ids))
        total, flipped = None, 0.0
        for at in rows:
            ids = jnp.asarray(self.ids[at:at + 1])
            one = jax.device_get(grads_of(
                params, ids, jnp.asarray(picks[:, at:at + 1])))
            total = one if total is None else jax.tree_util.tree_map(
                np.add, total, one)
            flipped += float(np.mean(np.asarray(picks_of(params, ids))
                                     != picks[:, at:at + 1]))
        scale = np.float32(1.0 / len(rows))
        norm = math.sqrt(sum(
            float(np.vdot(a, a)) for a in jax.tree_util.tree_leaves(total))
        ) * float(scale)
        factor = min(1.0, float(self.mix["gradient_clipping"]) / norm)
        want = {n: np.float32(factor) * scale * a
                for n, a in _compared(total).items()}
        if control == "expert_dw":      # one compared expert's, left out
            want["experts.w_up"][0] = 0.0
        by_name = _worst(self.applied, want)
        unclipped = {n: a / np.float32(factor) for n, a in want.items()}
        return {"grad_rel_err": max(by_name.values()),
                "grad_rel_err_by_name": by_name,
                # a control in every run: the reference's side not clipped
                "grad_rel_err_no_clip": max(
                    _worst(self.applied, unclipped).values()),
                "step_pick_flip_share": flipped / len(rows),
                "grad_norm_rel_err": abs(self.reported_norm - norm) / norm,
                "grad_norm": self.reported_norm, "clip_factor": factor,
                "update_rel_err": self.update_errs()}


class ForwardCheck:
    """(a) and (b) on the weights the window left: the program's side
    once, the reference's side for the sound comparison and for each
    control."""

    def __init__(self, engine, model, ref_cfg, seq, seed):
        import jax
        self.ref_cfg = ref_cfg
        mc = model.config
        rng = np.random.default_rng([int(seed), 0xC4EC])
        self.two = rng.integers(0, mc.vocab_size, (2, seq), dtype=np.int32)
        self.params = engine.state["params"]
        rows = engine.train_micro_batch_size_per_gpu
        self.loss = float(engine.eval_loss(
            {"input_ids": np.tile(self.two, (max(rows // 2, 1), 1))[:rows]}))
        # (b): layer 0's expert sublayer over one sequence's worth of rows
        self.moe0 = jax.tree_util.tree_map(lambda a: a[0],
                                           self.params["blocks"]["moe"])
        key = model_lib.seed_key(int(seed) + 0xE4)
        k1, k2 = jax.random.split(key)
        self.u = jax.random.normal(k1, (1, seq, mc.d_model))
        self.r_prev = jax.random.normal(k2, (1, seq, mc.router_hidden))

        def program_experts(moe, u, r_prev):
            moe = engine._cast_for_compute(moe)
            u = u.astype(mc.dtype)
            pick = model._route(moe, u, r_prev)[1].index
            return model._moe(moe, u, r_prev)[0], pick.reshape(u.shape[:2])
        self.expert_out, self.pick = jax.jit(program_experts)(
            self.moe0, self.u, self.r_prev)

    def against(self, control: str | None = None) -> dict:
        import jax
        import jax.numpy as jnp
        cfg, prepare = _lacking(self.ref_cfg, control)
        want_loss = float(jax.jit(lambda params, ids: reference.loss(
            prepare(params), ids, cfg, jax.checkpoint))(
                self.params, jnp.asarray(self.two)))

        def ref_experts(moe, u, r_prev):
            with jax.default_matmul_precision("highest"):
                moe = prepare(moe)
                prob = reference.router(moe["router"], u, r_prev, cfg)[1]
                return (reference.experts(moe, u, r_prev, cfg)[0],
                        jnp.argmax(prob + cfg["bias_unit"] * moe["bias"],
                                   axis=-1))
        whole, pick = jax.jit(ref_experts)(self.moe0, self.u, self.r_prev)
        same = (pick == self.pick)[..., None]
        return {"loss_abs_err": abs(self.loss - want_loss),
                "loss_reference": want_loss,
                "expert_rel_err": _rel(jnp.where(same, self.expert_out, 0),
                                       jnp.where(same, whole, 0)),
                "pick_flip_share": 1.0 - float(jnp.mean(same))}


def within(errs: dict) -> bool:
    return all(errs[name] <= limit for name, limit in LIMITS.items())


def run(ctx) -> dict:
    import jax
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model

    mix, chips = ctx.mix, ctx.cell["chips"]
    mc, ref_cfg = build(ctx.config, ctx.tiny)
    model = build_model(mc)
    micro = int(mix["micro_batch_per_chip"])
    seq = int(ctx.tiny["model"]["max_seq_len"]) if ctx.tiny \
        else int(mix["seq_len"])
    engine, *_ = ds.initialize(
        model=model, rng=model_lib.seed_key(ctx.seed),
        config={"train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": 1, "steps_per_print": 0,
                "bf16": {"enabled": mix["bf16"]},
                "optimizer": mix["optimizer"],
                "scheduler": mix["scheduler"],
                "gradient_clipping": mix["gradient_clipping"],
                "zero_optimization": {"stage": mix["zero_stage"]},
                "mesh": {"data": chips}})
    rng = np.random.default_rng([int(ctx.seed), 0xBA7C])
    rows = micro * chips
    ids = rng.integers(0, mc.vocab_size, (rows, seq), dtype=np.int32)
    batch = engine.shard_batch({"input_ids": ids})

    def counters(out):
        return {k: v for k, v in out.items()
                if k.startswith(("moe_", "router_"))}
    # the first step compiles and runs at a learning rate of 0; the second
    # is the one held to the reference
    checked = StepCheck(engine, model, ref_cfg, mix, ids,
                        model_lib.seed_key(ctx.seed))
    outs = [engine.train_step(batch)]
    checked.before()
    outs.append(engine.train_step(batch))
    checked.after(outs[-1])
    loss_first = float(outs[0]["loss"])
    counted = [counters(out) for out in outs]     # device scalars, a step
    # the step enqueued and not yet waited on
    ahead = [engine.train_step(batch)]

    def step():
        """Enqueue the next step, then wait for the one before it: the
        host's part of a step (a few ms of dispatch, which some processes
        take longer over) runs under the device's, as a training loop
        that does not read every loss lets it.  A step's end is still
        ``block_until_ready`` on the loss it returned."""
        with jax.profiler.TraceAnnotation("train_step"):
            ahead.append(engine.train_step(batch))
            out = ahead.pop(0)
            counted.append(counters(out))
            return float(jax.block_until_ready(out["loss"]))

    step()
    compiles_before = ctx.compile_log.compiles
    ends = np.zeros(1 << 16)
    losses = []
    tracing = False
    trace_at = ctx.seconds - float(mix["trace_seconds"])
    w0 = time.perf_counter()
    setup_s = device.process_age_s()
    ends[0] = w0
    n = 1
    first_in_window = len(counted)
    traced_from = None
    while True:
        if ctx.trace and not tracing and ends[n - 1] - w0 >= trace_at:
            ctx.start_trace()
            tracing, traced_from = True, len(counted)
        losses.append(step())
        ends[n] = time.perf_counter()
        n += 1
        if ends[n - 1] - w0 >= ctx.seconds:
            break
    w1 = w0 + ctx.seconds
    red = ctx.stop_trace(SPANS) if tracing else {}
    compiles_in_window = ctx.compile_log.compiles - compiles_before
    jax.block_until_ready(ahead.pop()["loss"])    # the step still queued
    memory = device.memory_peak()             # the training's own
    ends = ends[:n]
    step_ms = np.diff(ends[ends <= w1]) * 1e3

    # -- what the program counted ------------------------------------------
    counted = jax.device_get(counted)

    def total(name, since):
        return float(sum(float(c[name]) for c in counted[since:]))
    lo, hi = mc.held
    held = hi - lo
    picks = total("moe_picks", first_in_window)
    picks_held = total("moe_picks_held", first_in_window)
    layer_calls = float(len(counted) - first_in_window) * mc.num_layers
    values = {
        "setup_s": setup_s,
        "moe_held_share": 100.0 * picks_held / picks,
        # the fullest held expert's rows over the mean rows a held expert
        "moe_imbalance": total("moe_rows_max_expert", first_in_window)
        / max(picks_held / held, 1e-9),
        "moe_rows_per_expert": picks_held / (layer_calls * held),
        "moe_touched_share": 100.0 * total(
            "moe_experts_touched", first_in_window) / (layer_calls * held),
        "flops_per_token": costs_moe_train.flops_per_token(
            mc.layer_params(), mc.num_layers, mc.vocab_size, mc.d_model,
            mc.num_heads, mc.hdim, seq, picks_held / picks),
    }
    work = {}
    if red:
        # the traced steps' kernels: with remat="full" every forward
        # kernel runs twice (once recomputed) and every backward once
        steps_traced = red["window_s"] / (np.median(step_ms) * 1e-3)
        per_chip = (micro, seq, mc.num_heads, mc.kv_heads, mc.hdim)
        fwd = costs.flash_attention_cost(*per_chip, backward=False)
        bwd = costs.flash_attention_cost(*per_chip, backward=True)
        n_fwd = 2 if mc.remat == "full" else 1
        kernels = {"flash": (
            (n_fwd * fwd[0] + bwd[0]) * mc.num_layers * steps_traced,
            (n_fwd * fwd[1] + bwd[1]) * mc.num_layers * steps_traced)}
        # a step's held picks and touched experts, from the traced steps'
        # own counters, scaled to the traced window
        n_traced = max(len(counted) - traced_from, 1)
        per_step = steps_traced / n_traced
        t_held = total("moe_picks_held", traced_from) * per_step
        t_touched = total("moe_experts_touched", traced_from) * per_step
        once = costs_latent.grouped_experts_cost(
            t_held, t_touched, mc.d_model, mc.expert_d_ff)
        # forward (and its recomputation) and dx
        kernels["moe_grouped_matmul"] = tuple(
            (n_fwd + 1) * v for v in once)
        kernels["moe_grouped_matmul_dw"] = \
            costs_moe_train.grouped_product_dw_cost(
                t_held, mc.num_layers * steps_traced, held, mc.d_model,
                mc.expert_d_ff)
        for name, (flops, nbytes) in kernels.items():
            least, bound = costs.roofline_seconds(flops, nbytes, ctx.peaks)
            work[name] = {"least_s": least, "bound": bound}

    # -- correct, after the window -----------------------------------------
    # the optimizer's moments go first, then every weight: the float32
    # reference at 8,192 positions needs the room
    engine.state = {k: v for k, v in engine.state.items() if k != "opt"}
    forward = ForwardCheck(engine, model, ref_cfg, seq, ctx.seed)
    control = mix.get("control")
    only = control if isinstance(control, str) and control in CONTROLS \
        else None
    in_turn = CONTROLS if control == "all" else tuple(control) \
        if isinstance(control, list) else ()
    errs = forward.against(None if only == "half_batch" else only)
    controls = {c: forward.against(c) if c != "half_batch" else dict(errs)
                for c in in_turn}
    engine.state = forward.params = forward.moe0 = None
    errs.update(checked.against(only))
    for c in in_turn:
        controls[c].update(checked.against(c))
        controls[c]["refused"] = not within(controls[c])
    update_controls = {
        fault: checked.update_errs(**{fault: True})
        for fault in ("unchanged", "no_warmup", "no_decay")}
    last = float(np.median(losses[-5:]))
    ok = (within(errs) and all(map(math.isfinite, losses))
          and last < loss_first and compiles_in_window == 0)
    return {
        "correct": bool(ok), "attempted": len(losses), "failed": 0,
        "window": (w0, w1), "memory": memory,
        "values": values,
        "series": {"step_ms": step_ms},
        "steps": {"ends": ends, "work": rows * seq / chips},
        "trace": red, "work": work,
        "diag": {"loss_engine": forward.loss, **errs,
                 "loss_first": loss_first, "loss_last_median": last,
                 "steps": len(losses),
                 # the step's time and the held share by third of the
                 # window: how far the routing's drift moves the rate
                 "step_ms_by_third": [float(np.median(t)) for t in
                                      np.array_split(step_ms, 3)],
                 "held_share_by_third": [
                     100.0 * sum(float(c["moe_picks_held"]) for c in part)
                     / sum(float(c["moe_picks"]) for c in part)
                     for part in np.array_split(
                         np.array(counted[first_in_window:]), 3)],
                 "compiles_in_window": compiles_in_window,
                 "router_bias_abs_max": float(
                     counted[-1]["router_bias_abs_max"]),
                 # how the routing drifts: the held share at a few steps
                 "held_share_at_step": {
                     str(at): 100.0 * float(counted[at]["moe_picks_held"])
                     / float(counted[at]["moe_picks"])
                     for at in sorted({0, 2, 5, 10, 20, 40, 80,
                                       len(counted) - 1})
                     if at < len(counted)},
                 "bounds": {k: v["bound"] for k, v in work.items()},
                 "limits": LIMITS, "update_controls": update_controls,
                 "controls": controls},
    }
