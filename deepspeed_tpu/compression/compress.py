"""Compression: config-driven QAT, pruning, activation quant, layer cut.

Role-equivalent of the reference compression subsystem
(`/root/reference/deepspeed/compression/compress.py:97` init_compression,
`basic_layer.py:134` LinearLayer_Compress with its sparse/row/head pruning
enables at :159,179, `utils.py` TopKBinarizer, `config.py` nested
shared_parameters/different_groups schema, `compress.py:127`
redundancy_clean) and the MoQ scheduler (`runtime/quantize.py:9`).

Functional redesign: the reference wraps nn.Linear modules in
compress-aware replicas whose forward applies masks/fake-quant; here every
technique is a PURE PARAMS TRANSFORM composed into ``compress_params(
params, step)`` and applied inside the loss before the forward — masks are
recomputed from the live weights each step (the reference's l1 mode) with
straight-through gradients, schedules are traceable functions of the step
counter, and ``redundancy_clean`` burns the masks in by applying the same
transform once. Activation quantization needs a seam inside the model and
rides ``TransformerConfig.act_quant_bits`` (models/layers.py dense paths).

Config: accepts the reference's nested schema (shared_parameters +
different_groups with modules scopes) and a flat convenience form.
Unsupported methods (topk/movement pruning needs auxiliary trainable
scores; channel pruning is a conv concept) reject loudly.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..ops.quantizer.quantizer import fake_quantize
from ..utils.logging import logger


@dataclasses.dataclass(frozen=True)
class WeightQuantizeConfig:
    """Mirrors the reference's weight_quantization block
    (`compression/config.py` surface, trimmed to the implemented parts)."""
    enabled: bool = False
    start_bits: int = 16         # no-op precision until quantize_period ends
    target_bits: int = 8
    quantize_period: int = 1000  # steps per halving of precision (MoQ ramp)
    quantize_groups: int = 1
    symmetric: bool = True
    # regex over param path ("blocks/mlp/fc_in/kernel"); None = all kernels
    modules: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class PruningGroup:
    """One different_groups entry: a keep-ratio over a module scope."""
    dense_ratio: float = 0.5
    modules: Optional[str] = None     # regex; None = technique default


@dataclasses.dataclass(frozen=True)
class SparsePruningConfig:
    enabled: bool = False
    method: str = "l1"                # l1 | topk (topk rejects)
    schedule_offset: int = 0
    groups: Sequence[PruningGroup] = ()


@dataclasses.dataclass(frozen=True)
class RowPruningConfig:
    enabled: bool = False
    method: str = "l1"
    schedule_offset: int = 0
    groups: Sequence[PruningGroup] = ()


@dataclasses.dataclass(frozen=True)
class HeadPruningConfig:
    enabled: bool = False
    method: str = "l1"
    schedule_offset: int = 0
    num_heads: int = 0                # required when enabled
    groups: Sequence[PruningGroup] = ()


@dataclasses.dataclass(frozen=True)
class ChannelPruningConfig:
    """Prune conv OUTPUT channels (reference `enable_channel_pruning`,
    compression/basic_layer.py:503) — targets the 4-D [kh, kw, cin, cout]
    kernels of the conv family (models/diffusion.py UNet/VAE)."""
    enabled: bool = False
    method: str = "l1"
    schedule_offset: int = 0
    groups: Sequence[PruningGroup] = ()


@dataclasses.dataclass(frozen=True)
class ActivationQuantConfig:
    enabled: bool = False
    bits: int = 8
    symmetric: bool = False           # reference default asymmetric
    range_calibration: str = "dynamic"
    schedule_offset: int = 0
    # static calibrated absmax per model seam site (attn_in, mlp_in) —
    # produced by calibrate_activation_ranges; required when
    # range_calibration == "static"
    ranges: Sequence[float] = ()


@dataclasses.dataclass(frozen=True)
class LayerReductionConfig:
    enabled: bool = False
    keep_number_layer: int = 0
    teacher_layer: Sequence[int] = ()


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    weight_quantization: WeightQuantizeConfig = WeightQuantizeConfig()
    sparse_pruning: SparsePruningConfig = SparsePruningConfig()
    row_pruning: RowPruningConfig = RowPruningConfig()
    head_pruning: HeadPruningConfig = HeadPruningConfig()
    channel_pruning: ChannelPruningConfig = ChannelPruningConfig()
    activation_quantization: ActivationQuantConfig = ActivationQuantConfig()
    layer_reduction: LayerReductionConfig = LayerReductionConfig()

    @property
    def any_param_transform(self) -> bool:
        return (self.weight_quantization.enabled
                or self.sparse_pruning.enabled or self.row_pruning.enabled
                or self.head_pruning.enabled
                or self.channel_pruning.enabled)


# ---------------------------------------------------------------------------
# config parsing (reference nested schema + flat convenience form)
# ---------------------------------------------------------------------------
def _modules_regex(scope) -> Optional[str]:
    """different_groups "modules" may be a list of fnmatch-ish names or a
    regex string; '*' scopes mean all. Reference configs use torch-dotted
    module names while this framework's param paths are slash-separated —
    literal dots in list scopes therefore match either separator."""
    if scope in (None, "*", ["*"]):
        return None
    if isinstance(scope, str):
        return scope
    parts = [re.escape(m).replace(r"\*", ".*").replace(r"\.", r"[./]")
             for m in scope]
    return "|".join(parts)


def _parse_groups(block: Dict, ratio_key: str) -> List[PruningGroup]:
    out = []
    for name, g in (block.get("different_groups") or {}).items():
        params = g.get("params", g)
        ratio = params.get(ratio_key)
        if ratio is None:
            raise ValueError(f"group {name}: {ratio_key} must be set")
        out.append(PruningGroup(
            dense_ratio=float(ratio),
            modules=_modules_regex(g.get("modules", "*"))))
    return out


def _parse_pruning(block: Dict, cls, ratio_key: str, **extra):
    if not block:
        return cls()
    shared = block.get("shared_parameters", block)
    enabled = bool(shared.get("enabled", False))
    method = shared.get("method", "l1")
    if enabled and method not in ("l1", "topk"):
        raise ValueError(f"{cls.__name__}: unknown method '{method}' "
                         f"(l1 | topk)")
    groups = _parse_groups(block, ratio_key)
    if not groups and "dense_ratio" in shared:
        groups = [PruningGroup(dense_ratio=float(shared["dense_ratio"]),
                               modules=_modules_regex(
                                   shared.get("modules", "*")))]
    if enabled and not groups:
        raise ValueError(f"{cls.__name__} enabled but no groups give a "
                         f"dense_ratio (different_groups or flat "
                         f"dense_ratio)")
    return cls(enabled=enabled, method=method,
               schedule_offset=int(shared.get("schedule_offset", 0)),
               groups=tuple(groups), **extra)


def parse_compression_config(d: Dict[str, Any]) -> CompressionConfig:
    d = d or {}
    wq_block = d.get("weight_quantization", {})
    if "shared_parameters" in wq_block:
        sp = wq_block["shared_parameters"]
        groups = wq_block.get("different_groups") or {}
        if len(groups) > 1:
            raise NotImplementedError(
                "weight_quantization with multiple different_groups "
                "(per-scope bit-widths) is not built — dropping groups "
                "silently would mis-quantize; use one group")
        g0 = next(iter(groups.values()), {})
        gp = g0.get("params", {})
        # an explicit enabled=false wins over the presence of groups
        enabled = bool(sp.get(
            "enabled", sp.get("quantize_weight_in_forward", bool(groups))))
        wq = WeightQuantizeConfig(
            enabled=enabled,
            start_bits=int(gp.get("start_bits", 16)),
            target_bits=int(gp.get("target_bits", 8)),
            quantize_period=int(gp.get("quantization_period", 1000)),
            quantize_groups=int(sp.get("quantize_groups", 1)),
            symmetric=(sp.get("quantization_type", "symmetric")
                       == "symmetric"),
            modules=_modules_regex(g0.get("modules", "*")))
    else:
        wq = WeightQuantizeConfig(**wq_block)

    aq_block = d.get("activation_quantization", {})
    if "shared_parameters" in aq_block:
        sp = aq_block["shared_parameters"]
        groups = aq_block.get("different_groups") or {}
        if len(groups) > 1:
            raise NotImplementedError(
                "activation_quantization with multiple different_groups is "
                "not built — use one group")
        g0 = next(iter(groups.values()), {})
        gp = g0.get("params", {})
        aq = ActivationQuantConfig(
            enabled=bool(sp.get("enabled", False)),
            bits=int(gp.get("bits", 8)),
            symmetric=(sp.get("quantization_type", "asymmetric")
                       == "symmetric"),
            range_calibration=sp.get("range_calibration", "dynamic"),
            schedule_offset=int(sp.get("schedule_offset", 0)),
            ranges=tuple(sp.get("ranges", ())))
    else:
        aq = ActivationQuantConfig(**aq_block)
    if aq.enabled and aq.range_calibration == "static" and not aq.symmetric:
        raise NotImplementedError(
            "static activation ranges are symmetric-absmax "
            "(fake_quantize_static); set quantization_type='symmetric' "
            "or use dynamic calibration for the asymmetric path")
    lr_block = d.get("layer_reduction", {})
    lr = LayerReductionConfig(
        enabled=bool(lr_block.get("enabled", False)),
        keep_number_layer=int(lr_block.get("keep_number_layer", 0)),
        teacher_layer=tuple(lr_block.get("teacher_layer", ())))
    if lr.enabled:
        if lr.teacher_layer and lr.keep_number_layer and \
                len(lr.teacher_layer) != lr.keep_number_layer:
            raise ValueError("layer_reduction: len(teacher_layer) != "
                             "keep_number_layer")

    return CompressionConfig(
        weight_quantization=wq,
        sparse_pruning=_parse_pruning(d.get("sparse_pruning", {}),
                                      SparsePruningConfig,
                                      "dense_ratio"),
        row_pruning=_parse_pruning(d.get("row_pruning", {}),
                                   RowPruningConfig, "dense_ratio"),
        head_pruning=_parse_pruning(
            d.get("head_pruning", {}), HeadPruningConfig, "dense_ratio",
            num_heads=int(
                d.get("head_pruning", {}).get("shared_parameters",
                                              d.get("head_pruning", {}))
                .get("num_heads", 0))),
        channel_pruning=_parse_pruning(d.get("channel_pruning", {}),
                                       ChannelPruningConfig, "dense_ratio"),
        activation_quantization=aq,
        layer_reduction=lr)


# ---------------------------------------------------------------------------
# schedules + masks
# ---------------------------------------------------------------------------
def bits_at_step(cfg: WeightQuantizeConfig, step) -> jnp.ndarray:
    """MoQ precision schedule (reference runtime/quantize.py): halve the
    bit-width every ``quantize_period`` steps until target_bits."""
    halvings = jnp.floor_divide(step, max(cfg.quantize_period, 1))
    bits = cfg.start_bits / (2.0 ** halvings)
    return jnp.maximum(bits, float(cfg.target_bits))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def topk_mask(scores: jnp.ndarray, keep_ratio: float) -> jnp.ndarray:
    """Keep the top ``keep_ratio`` fraction by score (the reference's
    TopKBinarizer threshold, compression/utils.py) — mask is
    stop-gradiented so gradients flow straight through to the weights."""
    flat = scores.reshape(-1)
    k = max(1, int(round(keep_ratio * flat.size)))
    thresh = jax.lax.top_k(flat, k)[0][-1]
    return jax.lax.stop_gradient(
        (scores >= thresh).astype(scores.dtype))


def _sparse_mask(w, ratio):
    return topk_mask(jnp.abs(w.astype(jnp.float32)), ratio).astype(w.dtype)


def movement_mask(scores, keep_ratio):
    """Straight-through top-k over TRAINABLE scores (reference
    TopKBinarizer, `compression/utils.py:6`): forward value is the hard
    top-k mask of the scores, backward passes the gradient straight to
    the scores — so ∂L/∂score = ∂L/∂(w·mask) · w, the movement-pruning
    update (scores grow where keeping the weight helps)."""
    hard = topk_mask(scores, keep_ratio)          # stop-gradiented
    return hard + scores - jax.lax.stop_gradient(scores)


MASK_SCORES_KEY = "_mask_scores"


def _row_scores_init(w):
    """Per-output-feature L1 norms (also the channel-pruning init: conv
    kernels reduce [kh, kw, cin] the same way row kernels reduce [in])."""
    return jnp.sum(jnp.abs(w.astype(jnp.float32)),
                   axis=tuple(range(w.ndim - 1)))


def _head_scores_init(w, nh):
    return jnp.sum(jnp.abs(w.astype(jnp.float32)).reshape(nh, -1), axis=1)


def add_movement_scores(params, cfg) -> Dict:
    """Attach trainable mask-score leaves for every kernel a topk pruning
    group targets — sparse (per-element, the reference TopKBinarizer's
    unstructured scope), row/channel (per output feature/channel) and
    head (per attention head), mirroring the reference applying
    TopKBinarizer at every one of those scopes (basic_layer.py:159,179,
    503). Scores initialize to the corresponding L1 statistic so step 0
    reproduces magnitude pruning; training then moves them. Returns a
    NEW params dict with a ``_mask_scores`` subtree; row/head/channel
    score keys are suffixed ``#row``/``#head``/``#channel`` so multiple
    techniques may target the same kernel."""
    if isinstance(cfg, dict):
        cfg = parse_compression_config(cfg)
    wants = []        # (suffix, groups, default_scope, init_fn)
    if cfg.sparse_pruning.enabled and cfg.sparse_pruning.method == "topk":
        wants.append(("", cfg.sparse_pruning.groups, "sparse",
                      lambda w: jnp.abs(w).astype(jnp.float32)))
    if cfg.row_pruning.enabled and cfg.row_pruning.method == "topk":
        wants.append(("#row", cfg.row_pruning.groups, "row",
                      _row_scores_init))
    if cfg.head_pruning.enabled and cfg.head_pruning.method == "topk":
        nh = cfg.head_pruning.num_heads
        if nh <= 0:
            raise ValueError("head_pruning topk needs num_heads")
        wants.append(("#head", cfg.head_pruning.groups, "head",
                      lambda w: _head_scores_init(w, nh)))
    if cfg.channel_pruning.enabled and \
            cfg.channel_pruning.method == "topk":
        wants.append(("#channel", cfg.channel_pruning.groups, "channel",
                      _row_scores_init))
    if not wants:
        raise ValueError("add_movement_scores: no pruning technique with "
                         "method='topk' is enabled in this config")
    scores: Dict[str, jnp.ndarray] = {}

    def visit(path, leaf):
        name = _path_str(path)
        if leaf.ndim < 2 or not name.endswith("kernel"):
            return leaf
        for suffix, groups, scope, init in wants:
            rxs = [re.compile(g.modules or _DEFAULT_SCOPES[scope])
                   for g in groups]
            if any(rx.search(name) for rx in rxs):
                stacked = name.startswith("blocks") and suffix
                scores[name + suffix] = (jax.vmap(init)(leaf) if stacked
                                         else init(leaf))
        return leaf
    jax.tree_util.tree_map_with_path(visit, params)
    if not scores:
        raise ValueError("add_movement_scores: no kernel matched any topk "
                         "pruning scope")
    return {**params, MASK_SCORES_KEY: scores}


def _row_mask(w, ratio):
    """Structured: prune OUTPUT features (last axis) by their L1 norm —
    the reference's row pruning on [out, in] torch layouts maps to the
    output axis of this framework's [in, out] kernels."""
    norms = jnp.sum(jnp.abs(w.astype(jnp.float32)),
                    axis=tuple(range(w.ndim - 1)))
    keep = topk_mask(norms, ratio)
    # [1, out]: broadcastable per-layer AND stable under the stacked-leaf
    # vmap (which prepends the scan axis)
    return keep.astype(w.dtype)[None, :]


def _head_mask(w, ratio, num_heads):
    """Prune attention heads by the L1 norm of their slice of the output
    projection ([nh*hd, d] leading axis grouped per head — reference
    head_pruning_enable on attn output matrices, basic_layer.py:179)."""
    nh = num_heads
    if w.shape[0] % nh:
        raise ValueError(f"head pruning: leading dim {w.shape[0]} not "
                         f"divisible by num_heads {nh}")
    per_head = jnp.sum(jnp.abs(w.astype(jnp.float32)).reshape(
        nh, -1), axis=1)
    keep = topk_mask(per_head, ratio)                       # [nh]
    return jnp.repeat(keep, w.shape[0] // nh).astype(w.dtype)  # [nh*hd]


# ---------------------------------------------------------------------------
# the composite transform
# ---------------------------------------------------------------------------
_DEFAULT_SCOPES = {
    "sparse": r"kernel$",
    "row": r"mlp/fc_in/kernel$",
    "head": r"attn/out/kernel$",
    # the conv family's kernels (models/diffusion.py: conv1/conv2/
    # conv_shortcut and the spatial transformer's 1x1 proj_in/proj_out,
    # all HWIO). The lookbehind excludes ff/proj_in|proj_out — those are
    # the DENSE GEGLU feedforward kernels, not convs.
    "channel": r"(conv[^/]*|(?<!ff/)proj_in|(?<!ff/)proj_out)/kernel$",
}


def _gate(step, offset):
    return (step >= offset) if offset else True


def compress_params(params, cfg, step):
    """Apply every enabled param-side technique at ``step`` (traceable).
    ``cfg`` — CompressionConfig or legacy WeightQuantizeConfig. A
    ``_mask_scores`` subtree (movement pruning, `add_movement_scores`)
    is consumed here and stripped from the returned tree."""
    if isinstance(cfg, WeightQuantizeConfig):
        cfg = CompressionConfig(weight_quantization=cfg)
    scores = None
    if isinstance(params, dict) and MASK_SCORES_KEY in params:
        scores = params[MASK_SCORES_KEY]
        params = {k: v for k, v in params.items() if k != MASK_SCORES_KEY}
    wq = cfg.weight_quantization
    pattern = re.compile(wq.modules) if wq.modules else None
    levels: List[int] = []
    if wq.enabled:
        b = wq.start_bits
        while b > wq.target_bits:
            levels.append(b)
            b //= 2
        levels.append(wq.target_bits)

    prunes = []   # (mask_fn, regex, offset, score_suffix|None)
    sp = cfg.sparse_pruning
    for g in (sp.groups if sp.enabled else ()):
        rx = re.compile(g.modules or _DEFAULT_SCOPES["sparse"])
        if sp.method == "topk":
            prunes.append(
                (lambda w, s, r=g.dense_ratio:
                 movement_mask(s, r).astype(w.dtype),
                 rx, sp.schedule_offset, ""))
        else:
            prunes.append((lambda w, r=g.dense_ratio: _sparse_mask(w, r),
                           rx, sp.schedule_offset, None))
    rp = cfg.row_pruning
    for g in (rp.groups if rp.enabled else ()):
        rx = re.compile(g.modules or _DEFAULT_SCOPES["row"])
        if rp.method == "topk":
            prunes.append(
                (lambda w, s, r=g.dense_ratio:
                 movement_mask(s, r).astype(w.dtype)[None, :],
                 rx, rp.schedule_offset, "#row"))
        else:
            prunes.append((lambda w, r=g.dense_ratio: _row_mask(w, r),
                           rx, rp.schedule_offset, None))
    if cfg.head_pruning.enabled:
        hp = cfg.head_pruning
        nh = hp.num_heads
        if nh <= 0:
            raise ValueError("head_pruning needs num_heads")
        for g in hp.groups:
            rx = re.compile(g.modules or _DEFAULT_SCOPES["head"])
            if hp.method == "topk":
                prunes.append(
                    (lambda w, s, r=g.dense_ratio:
                     jnp.repeat(movement_mask(s, r),
                                w.shape[0] // nh).astype(w.dtype)[:, None],
                     rx, hp.schedule_offset, "#head"))
            else:
                prunes.append(
                    (lambda w, r=g.dense_ratio:
                     _head_mask(w, r, nh)[:, None],
                     rx, hp.schedule_offset, None))
    cp = cfg.channel_pruning
    for g in (cp.groups if cp.enabled else ()):
        rx = re.compile(g.modules or _DEFAULT_SCOPES["channel"])
        if cp.method == "topk":
            prunes.append(
                (lambda w, s, r=g.dense_ratio:
                 movement_mask(s, r).astype(w.dtype)[None, :],
                 rx, cp.schedule_offset, "#channel"))
        else:
            # output-channel L1 over [kh, kw, cin]: _row_mask reduces
            # every axis but the last, so it IS the channel decision on
            # 4-D conv kernels (its [1, out] mask broadcasts to HWIO)
            prunes.append((lambda w, r=g.dense_ratio: _row_mask(w, r),
                           rx, cp.schedule_offset, None))

    def transform(path, leaf):
        name = _path_str(path)
        if leaf.ndim < 2 or not name.endswith("kernel"):
            return leaf
        out = leaf
        # stacked-scan leaves carry a leading layer axis: masks are
        # per-LAYER decisions (the reference masks each weight matrix),
        # so vmap the mask over it
        stacked = name.startswith("blocks") and leaf.ndim >= 2
        for mask_fn, rx, offset, suffix in prunes:
            if rx.search(name):
                uses_scores = suffix is not None
                if uses_scores:
                    s = (scores or {}).get(name + suffix)
                    if s is None:
                        raise ValueError(
                            f"movement pruning: no trainable scores for "
                            f"'{name + suffix}' — call "
                            f"add_movement_scores(params, cfg) before "
                            f"training")
                    mask = (jax.vmap(mask_fn)(out, s) if stacked
                            else mask_fn(out, s))
                else:
                    mask = (jax.vmap(mask_fn)(out) if stacked
                            else mask_fn(out))
                gate = _gate(step, offset)
                mask = jnp.where(gate, mask, jnp.ones_like(mask))
                out = out * mask
        if wq.enabled and (pattern is None or pattern.search(name)):
            branches = [
                (lambda l, bb=bb: l if bb >= 16 else fake_quantize(
                    l, int(bb), wq.quantize_groups, wq.symmetric))
                for bb in levels]
            idx = jnp.clip(
                jnp.floor_divide(step, max(wq.quantize_period, 1)),
                0, len(levels) - 1)
            out = jax.lax.switch(idx, branches, out)
        return out

    return jax.tree_util.tree_map_with_path(transform, params)


def redundancy_clean(params, cfg, step=None):
    """Burn the masks/quantization in (reference compress.py:127): one
    application of the full transform at the END of the schedule, producing
    params to export/serve."""
    if isinstance(cfg, dict):
        cfg = parse_compression_config(cfg)
    if isinstance(cfg, WeightQuantizeConfig):
        cfg = CompressionConfig(weight_quantization=cfg)
    if step is None:
        step = jnp.asarray(10 ** 9)
    return compress_params(params, cfg, step)


# ---------------------------------------------------------------------------
# layer reduction
# ---------------------------------------------------------------------------
def apply_layer_reduction(model, params, lr_cfg: LayerReductionConfig):
    """Teacher → student: keep the stacked-scan rows ``teacher_layer``
    (reference layer_reduction init via module-name remapping; with the
    stacked layer axis it is one gather). Returns
    (student_model, student_params)."""
    import dataclasses as dc

    from ..models.transformer import TransformerLM
    c = model.config
    total = c.num_layers
    layers = list(lr_cfg.teacher_layer)
    if not layers:
        n = lr_cfg.keep_number_layer
        if not n:
            raise ValueError("layer_reduction needs teacher_layer or "
                             "keep_number_layer")
        # evenly spaced, always including the last layer
        layers = [round(i * (total - 1) / max(n - 1, 1)) for i in range(n)]
    if any(i < 0 or i >= total for i in layers):
        raise ValueError(
            f"teacher_layer {layers} out of range 0..{total - 1}")
    idx = jnp.asarray(layers, jnp.int32)
    new_params = dict(params)
    new_params["blocks"] = jax.tree_util.tree_map(
        lambda l: jnp.take(l, idx, axis=0), params["blocks"])
    student_cfg = dc.replace(model.config, num_layers=len(layers))
    student = TransformerLM(student_cfg, constrain=model.constrain)
    return student, new_params


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def init_compression(model, compression_config: Dict[str, Any]):
    """Reference `compress.py:97` surface: returns a wrapped loss with
    signature (params, batch, step=0) training through the enabled
    techniques. Activation quantization rebuilds the model with its seam
    set (`init_compression_model`); layer_reduction is a PARAMS+MODEL
    rewrite that init_compression cannot do (it never sees params) — call
    `apply_layer_reduction(model, params, cfg.layer_reduction)` first."""
    cfg = (compression_config
           if isinstance(compression_config, CompressionConfig)
           else parse_compression_config(compression_config))
    if cfg.layer_reduction.enabled:
        raise ValueError(
            "layer_reduction cannot be applied by init_compression (it "
            "rewrites params AND model depth) — call "
            "apply_layer_reduction(model, params, ...) first, then pass "
            "the student here with layer_reduction removed")
    aq = cfg.activation_quantization
    model_q = init_compression_model(model, cfg)
    if aq.enabled and aq.schedule_offset:
        # schedule_offset (reference act-quant config): full-precision
        # activations until the offset step, quantized after — both
        # branches trace once, the step gate selects at runtime
        base = model

        def model_loss(params, batch, step):
            return jax.lax.cond(
                step >= aq.schedule_offset,
                lambda p: model_q.loss(p, batch),
                lambda p: base.loss(p, batch), params)
    else:
        def model_loss(params, batch, step):
            del step
            return model_q.loss(params, batch)

    if not cfg.any_param_transform:
        if not aq.enabled:
            logger.warning("init_compression: nothing enabled — loss "
                           "returned unchanged")

        def plain_loss(params, batch, step=0):
            return model_loss(params, batch, step)
        return plain_loss

    def compressed_loss(params, batch, step=0):
        return model_loss(compress_params(params, cfg, step), batch, step)

    return compressed_loss


def init_compression_model(model, cfg: CompressionConfig):
    """Model-side techniques: activation quantization flips the model's
    act-quant seam (TransformerConfig.act_quant_bits)."""
    aq = cfg.activation_quantization
    if not aq.enabled:
        return model
    import dataclasses as dc

    from ..models.transformer import TransformerLM
    if not isinstance(model, TransformerLM):
        raise NotImplementedError(
            "activation_quantization needs the model's dense-input seam; "
            "only TransformerLM carries it (act_quant_bits)")
    ranges = ()
    if aq.range_calibration == "static":
        if not aq.ranges:
            raise ValueError(
                "range_calibration='static' needs calibrated ranges — "
                "run calibrate_activation_ranges(model, params, batches) "
                "and put the result in activation_quantization.ranges")
        if len(aq.ranges) != len(TransformerLM._ACT_SITES):
            raise ValueError(
                f"activation_quantization.ranges must carry one absmax "
                f"per seam site {TransformerLM._ACT_SITES}")
        ranges = tuple(float(r) for r in aq.ranges)
    new_cfg = dc.replace(model.config, act_quant_bits=aq.bits,
                         act_quant_symmetric=aq.symmetric,
                         act_quant_ranges=ranges)
    return TransformerLM(new_cfg, constrain=model.constrain)


def calibrate_activation_ranges(model, params, batches) -> tuple:
    """Static-range calibration pass (the machinery the reference's
    range_calibration='static' mode assumes): run the model's blocks
    EAGERLY over calibration batches with the act-quant seam in record
    mode, returning per-site absmax ordered as ``_ACT_SITES``
    (attn_in, mlp_in). Eager per-layer walk — lax.scan/remat would trace
    the seam and hide the values."""
    import dataclasses as dc

    import numpy as np

    from ..models.transformer import TransformerLM
    if not isinstance(model, TransformerLM):
        raise NotImplementedError(
            "calibration needs TransformerLM's seam sites")
    calib_model = TransformerLM(dc.replace(model.config, act_quant_bits=0,
                                           act_quant_ranges=()),
                                constrain=model.constrain)
    calib_model._act_calib = {}
    c = calib_model.config
    for batch in batches:
        ids = jnp.asarray(np.asarray(batch["input_ids"]))
        x = calib_model._embed_tokens(params, ids)
        wins = calib_model._layer_windows()
        for i in range(c.num_layers):
            lp = jax.tree_util.tree_map(lambda l, i=i: l[i],
                                        params["blocks"])
            x, _ = calib_model._block(
                lp, x, window=wins[i] if wins is not None else None)
    calib = calib_model._act_calib
    del calib_model._act_calib
    return tuple(calib.get(site, 0.0)
                 for site in TransformerLM._ACT_SITES)


class MovementPruningModel:
    """Engine-facing wrapper for movement (topk) pruning: ``init`` carries
    the trainable mask scores (`add_movement_scores`), ``loss`` trains
    through the straight-through masks, and ``partition_specs`` gives each
    score leaf ITS kernel's spec so TP shardings survive. Pass to
    ds.initialize like any model — the scores are ordinary trainable
    leaves the optimizer updates (the reference trains TopKBinarizer
    mask_scores the same way)."""

    def __init__(self, model, compression_config):
        cfg = (compression_config
               if isinstance(compression_config, CompressionConfig)
               else parse_compression_config(compression_config))
        self.cfg = cfg
        self._inner = init_compression_model(model, cfg)

    def init(self, rng):
        return add_movement_scores(self._inner.init(rng), self.cfg)

    def loss(self, params, batch, step=0):
        return self._inner.loss(compress_params(params, self.cfg, step),
                                batch)

    def partition_specs(self, params=None):
        inner = self._inner.partition_specs()

        def lookup(name):
            node = inner
            for part in name.split("/"):
                node = (node[int(part)] if isinstance(node, (list, tuple))
                        else node[part])
            return node
        from jax.sharding import PartitionSpec
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        score_specs = {}
        for name, shp in shapes[MASK_SCORES_KEY].items():
            if "#" in name:
                # row/head/channel scores are REDUCED shapes ([out]/[nh])
                # — tiny vectors, replicated (the kernel's spec no longer
                # matches their rank)
                score_specs[name] = PartitionSpec(*([None] * len(shp.shape)))
            else:
                score_specs[name] = lookup(name)
        return {**inner, MASK_SCORES_KEY: score_specs}

    def __getattr__(self, name):
        return getattr(self._inner, name)


def post_training_quantize(params, cfg):
    """One-shot PTQ of the weight leaves (serving-time compression)."""
    if isinstance(cfg, dict):
        cfg = WeightQuantizeConfig(**cfg.get("weight_quantization", cfg))
    frozen = dataclasses.replace(cfg, enabled=True,
                                 start_bits=cfg.target_bits,
                                 quantize_period=1)
    return compress_params(params, frozen, jnp.asarray(10 ** 9))
