"""Flops profiler: XLA cost analysis instead of module hooks.

Role-equivalent of the reference FlopsProfiler
(`/root/reference/deepspeed/profiling/flops_profiler/profiler.py:18`), which
monkey-patches torch functionals and walks module hooks to count MACs.
Under XLA the compiler already knows the op-level cost of the whole
program: ``compiled.cost_analysis()`` returns exact flops/bytes for the
step function, so profiling is a query, not an instrumentation pass.

Also provides the analytic 6ND transformer estimate (the number the
community's MFU tables use) so throughput → MFU works even for programs
XLA declines to cost (e.g. with custom Pallas calls, whose flops the
compiler cannot see — the analytic path is then the honest denominator).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax

from ...utils.logging import logger

# bf16 dense peak FLOPS per chip by TPU generation (Google Cloud TPU
# documentation, per-generation system architecture pages), keyed by a
# substring of ``device_kind``.
PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12,
    "v5": 459e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
}


def chip_peak_flops(device=None) -> float:
    """Published bf16 peak of ``device`` (default: the first device).  A
    device that is not in the table is an error, not a default — a
    utilization against a made-up peak describes nothing."""
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    for key, val in sorted(PEAK_FLOPS.items(), key=lambda kv: -len(kv[0])):
        if key in kind:
            return val
    raise ValueError(
        f"no published peak FLOP/s for device_kind "
        f"{device.device_kind!r} (platform {device.platform!r}); known: "
        f"{sorted(PEAK_FLOPS)}")


def compiled_cost(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """Lower+compile ``fn`` for the given args and return XLA's cost
    analysis ({'flops': ..., 'bytes accessed': ...}). Costs are for the
    WHOLE program across all devices it spans."""
    lowered = jax.jit(fn).lower(*args, **kwargs) if not hasattr(
        fn, "lower") else fn.lower(*args, **kwargs)
    compiled = lowered.compile()
    return dict(compiled.cost_analysis() or {})


def transformer_flops_per_token(num_params: int, num_layers: int,
                                d_model: int, seq_len: int) -> float:
    """Fwd+bwd train flops per token: 6N + attention term 12·L·d·T
    (the PaLM-paper accounting used by every MFU table)."""
    return 6.0 * num_params + 12.0 * num_layers * d_model * seq_len


class FlopsProfiler:
    """Engine-attached profiler (reference profiler.py FlopsProfiler):
    profiles the engine's compiled train step at ``profile_step`` and
    reports flops, flops/step, and achieved MFU from measured step time."""

    def __init__(self, engine, config=None):
        self.engine = engine
        self.config = config or engine._config.flops_profiler
        self.profiled: Optional[Dict[str, Any]] = None

    def profile(self, batch) -> Dict[str, Any]:
        eng = self.engine
        if eng._train_step_fn is None:
            eng._build_train_step()
        if any(not isinstance(v, jax.Array) for v in
               jax.tree_util.tree_leaves(batch)):
            batch = eng.shard_batch(batch)
        cost = compiled_cost(eng._train_step_fn, eng.state, batch)
        flops = float(cost.get("flops", 0.0))
        n_params = eng.num_parameters()
        out = {
            "xla_flops_per_step": flops,
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "params": n_params,
        }
        # analytic cross-check (and fallback when XLA won't cost the program)
        mcfg = getattr(eng.model, "config", None)
        if mcfg is not None and hasattr(mcfg, "d_model"):
            tokens = eng.train_batch_size * mcfg.max_seq_len
            out["analytic_flops_per_step"] = tokens * \
                transformer_flops_per_token(n_params, mcfg.num_layers,
                                            mcfg.d_model, mcfg.max_seq_len)
        self.profiled = out
        return out

    def mfu(self, step_time_s: float, seq_len: Optional[int] = None) -> float:
        """Achieved model-flops utilization for a measured step time."""
        if self.profiled is None:
            raise RuntimeError("call profile(batch) first")
        flops = (self.profiled.get("analytic_flops_per_step")
                 or self.profiled["xla_flops_per_step"])
        n_dev = max(jax.device_count(), 1)
        return flops / step_time_s / (chip_peak_flops() * n_dev)

    def print_profile(self, step_time_s: Optional[float] = None) -> None:
        if self.profiled is None:
            return
        p = self.profiled
        lines = [f"params: {p['params']/1e6:.1f}M",
                 f"XLA flops/step: {p['xla_flops_per_step']:.3e}",
                 f"bytes accessed/step: {p['bytes_accessed']:.3e}"]
        if "analytic_flops_per_step" in p:
            lines.append(
                f"analytic flops/step: {p['analytic_flops_per_step']:.3e}")
        if step_time_s:
            lines.append(f"MFU: {100*self.mfu(step_time_s):.1f}%")
        logger.info("flops profile | " + " | ".join(lines))
