"""The one general traffic generator.  A traffic mix is a data file of
parameters; every seed offers the same multiset of work in another order."""
from __future__ import annotations

import json
import os

import numpy as np

from . import BENCH_DIR


def load(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def stratified_pairs(prompt_lens, output_lens, block: int, blocks: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``blocks * block`` (prompt, output) length pairs.  Each block holds
    every combination of a prompt length and an output length equally often
    (a full factorial), in an order drawn from the seed: every seed offers
    the same multiset of requests, and any stretch of a run sees the same
    mix."""
    combos = np.array([(p, o) for p in prompt_lens for o in output_lens],
                      np.int64)
    if block % len(combos):
        raise ValueError(
            f"block {block} must be a multiple of the {len(combos)} "
            f"combinations of prompt and output length")
    one = np.tile(combos, (block // len(combos), 1))
    return np.concatenate([rng.permutation(one) for _ in range(blocks)])


def stratified_exponential_gaps(rate: float, block: int, blocks: int,
                                rng: np.random.Generator) -> np.ndarray:
    """Gaps between arrivals with mean ``1 / rate``: a fixed quantile grid of
    the exponential (``block`` points), shuffled by the seed in each block —
    every seed offers the same multiset of gaps."""
    grid = -np.log1p(-(np.arange(block) + 0.5) / block)
    grid *= 1.0 / (rate * grid.mean())
    return np.concatenate([rng.permutation(grid) for _ in range(blocks)])


def requests(mix: dict, seed: int, vocab_size: int) -> dict:
    """The requests a serving mix offers: lengths, token ids and (open loop)
    due times in seconds from the start of the arrivals."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    blocks = int(mix["blocks"])
    block = int(mix["block"])
    pairs = stratified_pairs(mix["prompt_lens"], mix["output_lens"], block,
                             blocks, rng)
    flat = rng.integers(0, vocab_size, int(pairs[:, 0].sum()), dtype=np.int32)
    ends = np.cumsum(pairs[:, 0])
    prompts = [flat[e - n:e] for e, n in zip(ends, pairs[:, 0])]
    out = {"prompt_len": pairs[:, 0], "max_new": pairs[:, 1],
           "prompts": prompts, "due": None}
    if mix["loop"] == "open":
        gaps = stratified_exponential_gaps(float(mix["rate_rps"]), block,
                                           blocks, rng)
        out["due"] = np.cumsum(gaps) - gaps[0]
    return out
