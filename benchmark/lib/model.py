"""From a configuration file to the program's model: the file holds the
published keys; ``program`` in it says how the repo builds that model.  The
published sizes are checked against what the program built."""
from __future__ import annotations

import json
import os

from . import REPO_ROOT


def load_config(path_from_root: str) -> dict:
    with open(os.path.join(REPO_ROOT, path_from_root)) as f:
        return json.load(f)


def build(config: dict, tiny: dict | None = None):
    """``(TransformerConfig, reference settings)``.  ``tiny`` replaces sizes
    for the CPU rehearsal only; a cell never passes it."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer as T
    prog = config["program"]
    kwargs = dict(prog["kwargs"])
    if tiny:
        kwargs.update(tiny["model"])
        kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    mc = getattr(T, prog["builder"])(prog["size"], **kwargs)
    if not tiny:
        built = {"layers": mc.num_layers, "hidden": mc.d_model,
                 "heads": mc.num_heads, "ffn": mc.ff_dim,
                 "vocab": mc.vocab_size, "positions": mc.max_seq_len,
                 "tied": mc.tie_embeddings}
        want = {k: config[v] for k, v in config["published_keys"].items()}
        if built != want:
            raise ValueError(f"the program built {built}, the "
                             f"configuration file says {want}")
    ref = {"family": config["family"], "heads": mc.num_heads,
           "layernorm_eps": mc.layernorm_eps, "rotary_pct": mc.rotary_pct,
           "rotary_base": mc.rotary_base, "tied": mc.tie_embeddings}
    return mc, ref


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    import jax
    import numpy as np
    return jax.random.PRNGKey(
        int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1))


def serving_weights(model, seed: int, dtype):
    """Weights made on the device in one jitted call from the seed, in the
    type they are served in."""
    import jax
    init = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda a: a.astype(dtype), model.init(key)))
    return init(seed_key(seed))
