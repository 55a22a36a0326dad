"""Single autotuning experiment, run in its own process.

The subprocess half of the experiment scheduler (reference: the launcher
job each `autotuning/scheduler.py` slot sshes out — here a plain child
process). Reads a JSON spec, builds the model + engine, times a few
steps, writes a JSON result; every failure mode is converted into a
result file (oom/error) or a nonzero exit the ResourceManager maps to
"crash"."""
from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM", "Attempting to allocate")


def lm_factory(config_dict: Dict[str, Any]):
    """Default factory: TransformerLM from a JSON-safe config dict
    (dtype fields as strings)."""
    import jax.numpy as jnp
    from ..models.transformer import TransformerConfig, TransformerLM
    d = dict(config_dict)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
              "float16": jnp.float16}
    for k in ("dtype", "param_dtype"):
        if isinstance(d.get(k), str):
            d[k] = dtypes[d[k]]
    return TransformerLM(TransformerConfig(**d))


def _resolve(path: str):
    mod, _, name = path.partition(":")
    import importlib
    return getattr(importlib.import_module(mod), name)


def run(spec: Dict[str, Any]) -> Dict[str, Any]:
    fault = spec.get("inject_fault")
    if fault == "crash":
        sys.exit(41)
    if fault == "hang":
        time.sleep(3600)
    import numpy as np
    import deepspeed_tpu as ds
    factory = _resolve(spec.get(
        "model_factory", "deepspeed_tpu.autotuning.exp_runner:lm_factory"))
    model = factory(spec["model_config"])
    try:
        engine, _, _, _ = ds.initialize(model=model, config=spec["cfg"])
        seq = int(spec.get("seq")
                  or getattr(model.config, "max_seq_len", 128))
        vocab = int(getattr(model.config, "vocab_size", 1024))
        rs = np.random.RandomState(0)
        batch = {"input_ids": rs.randint(
            0, vocab, (engine.train_batch_size, seq), dtype=np.int32)}
        m = engine.train_step(batch)
        float(m["loss"])
        steps = int(spec.get("steps", 3))
        t0 = time.perf_counter()
        for _ in range(steps):
            m = engine.train_step(batch)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        return {"status": "ok",
                "samples_per_sec": engine.train_batch_size / dt,
                "step_seconds": dt, "detail": ""}
    except Exception as e:  # classified, not propagated
        status = ("oom" if any(s in str(e) for s in _OOM_MARKERS)
                  else "error")
        return {"status": status, "samples_per_sec": None,
                "detail": f"{type(e).__name__}: {str(e)[:300]}"}


def main() -> None:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    result = run(spec)
    with open(spec["result_path"], "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
