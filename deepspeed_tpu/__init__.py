"""deepspeed_tpu — a TPU-native training & inference framework with the
capability surface of DeepSpeed (reference: /root/reference, v0.8.2),
built on JAX/XLA/Pallas over named-axis device meshes.

Top-level API mirrors the reference `deepspeed/__init__.py`:
    initialize()        (`__init__.py:52`)  → engine for training
    init_inference()    (`__init__.py:233`) → engine for serving
    init_distributed()  → multi-host bootstrap
    add_config_arguments() (`__init__.py:210`)
"""
from __future__ import annotations

import os
from typing import Any, Optional, Tuple

__version__ = "0.1.0"

from . import comm  # noqa: F401
from .accelerator.tpu_accelerator import get_accelerator  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401
from .observability.overlap import get_overlap_profiler
from .runtime.config import DeepSpeedConfig  # noqa: F401
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader  # noqa: F401
from .parallel.topology import build_mesh  # noqa: F401


def enable_compile_cache() -> Optional[str]:
    """Keep compiled programs across processes in JAX's persistent
    compilation cache; returns its directory.  Called by
    :func:`initialize` and :func:`init_inference` before their first
    compile, so every entry point shares one cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    no path is set here.  Otherwise the cache lives at the fixed,
    git-ignored ``<checkout>/.jax_cache`` — the path is part of the
    cache key, so it is never derived from a temp name, pid or time.
    CPU runs (the test suite) compile nothing worth keeping and are
    left alone.

    Being the first thing both entry points call, it also registers —
    once a process, whatever the backend — the overlap profiler's pair
    of ``jax.monitoring`` listeners, so that every program traced,
    lowered, compiled or fetched from here on leaves a build record
    (``get_overlap_profiler().builds()``, docs/observability.md)."""
    import jax
    get_overlap_profiler().listen_for_builds()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def initialize(args: Any = None,
               model: Any = None,
               optimizer: Any = None,
               model_parameters: Any = None,
               training_data: Any = None,
               lr_scheduler: Any = None,
               mesh: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Any = None,
               config: Any = None,
               config_params: Any = None,
               loss_fn: Any = None,
               param_specs: Any = None,
               rng: Any = None) -> Tuple:
    """Build a training engine. Reference: `deepspeed/__init__.py:52`.

    `model` is a functional model (init/apply/loss, optional partition_specs)
    rather than an nn.Module; `optimizer` may be a deepspeed_tpu Optimizer,
    an optax GradientTransformation, or None (config-driven). Returns
    ``(engine, optimizer, dataloader, lr_scheduler)`` exactly like the
    reference (`__init__.py:150`).
    """
    del model_parameters  # params are part of engine state in JAX
    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if model is None:
        raise ValueError("deepspeed_tpu.initialize requires a model")
    reason = getattr(model, "training_refusal", lambda: None)()
    if reason is not None:
        raise NotImplementedError(
            f"deepspeed_tpu.initialize cannot train this model: {reason}")
    with get_overlap_profiler().setup_span("setup/initialize"):
        if dist_init_required:
            init_distributed()
        enable_compile_cache()

        # Engine dispatch rides the topology: a mesh whose ``pipe`` axis
        # is >= 2 — passed in or declared by the config's mesh block
        # (e.g. an autotuner-exported 3D winner) — trains under the
        # compiled pipeline schedule; no separate entry point.
        ds_config = (config if isinstance(config, DeepSpeedConfig)
                     else DeepSpeedConfig(config or {}))
        if mesh is None:
            mesh = build_mesh(ds_config.mesh)
        from .parallel.topology import pp_world_size
        engine_cls = DeepSpeedEngine
        if pp_world_size(mesh) >= 2:
            from .runtime.pipe.engine import PipelineEngine
            engine_cls = PipelineEngine
        engine = engine_cls(model=model, config=ds_config, mesh=mesh,
                            optimizer=optimizer, lr_scheduler=lr_scheduler,
                            loss_fn=loss_fn, param_specs=param_specs,
                            rng=rng)
        dataloader = None
        if training_data is not None:
            dataloader = DeepSpeedDataLoader(
                training_data, batch_size=engine.train_batch_size,
                collate_fn=collate_fn)
    return engine, engine.optimizer, dataloader, engine.lr_schedule


def init_inference(model: Any = None, config: Any = None,
                   params: Any = None, mesh: Any = None, **kwargs):
    """Build an inference engine. Reference: `deepspeed/__init__.py:233`
    (merges config dict + kwargs the same way).

    ``params`` — explicit weights pytree (e.g. from
    `module_inject.convert_hf_model`); otherwise ``config.checkpoint`` is
    restored TP-sliced, else fresh weights."""
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig
    with get_overlap_profiler().setup_span("setup/init_inference"):
        enable_compile_cache()
        if isinstance(config, DeepSpeedInferenceConfig):
            cfg = (config.model_copy(update=kwargs) if kwargs else config)
        else:
            cfg_dict = dict(config) if isinstance(config, dict) else {}
            cfg_dict.update(kwargs)
            cfg = DeepSpeedInferenceConfig(**cfg_dict)
        return InferenceEngine(model, cfg, params=params, mesh=mesh)


def init_diffusion(unet_config=None, vae_config=None, text_config=None,
                   state_dicts=None, params=None, scheduler=None):
    """Build a Stable-Diffusion-class serving pipeline — the TPU-native
    equivalent of the reference's ``generic_injection`` over a diffusers
    pipeline (`module_inject/replace_module.py:211`,
    `model_implementations/diffusers/unet.py` DSUNet): jit-compiled UNet
    step + VAE decode replace CUDA-graph capture; XLA fuses the bias-add/
    GroupNorm chains the reference hand-wrote in ``csrc/spatial``.

    ``state_dicts`` — optional dict with any of "unet" / "vae" /
    "text_encoder" mapping to HF-named checkpoints (diffusers /
    transformers conventions); missing entries fall back to ``params`` or
    fresh initialization.
    """
    import jax as _jax
    from .models.diffusion import (AutoencoderKL, CLIPTextConfig,
                                   CLIPTextEncoder, StableDiffusionPipeline,
                                   UNet2DCondition, UNetConfig, VAEConfig)
    from .module_inject import diffusion_policies as pol
    unet = UNet2DCondition(unet_config or UNetConfig())
    vae = AutoencoderKL(vae_config or VAEConfig())
    text = CLIPTextEncoder(text_config or CLIPTextConfig())
    sds = state_dicts or {}
    unknown = set(sds) - {"unet", "vae", "text_encoder"}
    if unknown:
        raise ValueError(
            f"init_diffusion: unknown state_dicts entries {sorted(unknown)}"
            f" — expected a subset of ['unet', 'vae', 'text_encoder'] "
            f"(refusing a silent partial load)")
    params = dict(params or {})
    if "unet" in sds:
        params["unet"] = pol.load_unet(unet.config, sds["unet"])
    if "vae" in sds:
        params["vae"] = pol.load_vae(vae.config, sds["vae"])
    if "text_encoder" in sds:
        params["text_encoder"] = pol.load_clip_text(text.config,
                                                    sds["text_encoder"])
    for name, mod in (("unet", unet), ("vae", vae), ("text_encoder", text)):
        if name not in params:
            params[name] = mod.init(_jax.random.PRNGKey(0))
    pipe = StableDiffusionPipeline(unet, vae, text, scheduler=scheduler)
    return pipe, params


def add_config_arguments(parser):
    """Reference `deepspeed/__init__.py:210` — argparse plumbing."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the JSON config file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser
