"""Shared token sampling: ONE semantics for generate() and serving.

Both the sequential :meth:`InferenceEngine.generate` loop and the
serving engine's single compiled mixed step draw tokens through this
module, so a request streamed through the continuous-batching front end
is token-identical to the same prompt pushed through ``generate()``
under the same PRNG key (the seeded-parity test pins it).

Two call shapes over the same math:

  * :func:`sample_tokens` — static Python scalars for temperature /
    top-k / top-p (the generate() path).  Filters compile away when
    neutral, and ``temperature == 0`` is a plain argmax.
  * :func:`sample_tokens_per_row` — PER-ROW traced arrays (the serving
    path): every decode slot carries its own temperature/top-k/top-p/
    key as step *inputs*, so the serving step (its two shapes, with and
    without the chunk lane) serves any mix of sampling configs without
    retracing (``decode_builds == 2``).

The two paths are bit-identical for the same logits + key: the dynamic
path's neutral filters (``top_k == 0`` → keep all, ``top_p >= 1`` →
keep all) mask nothing and leave the logits bytes untouched, and both
paths feed the identical filtered array to the identical categorical
draw.

What runs when (the per-row path).  Everything is data, but not
everything is always computed: each stage runs only when some row of
THE CALL needs it, decided inside the program by a ``lax.cond`` on a
scalar of the rows' own state — no second program, no host choice.

  ==========================================  =========================
  the call's rows                             what runs
  ==========================================  =========================
  none samples (every ``temperature`` 0)      ``argmax`` — no division,
                                              no sort, no softmax, no
                                              random bits
  some sample, none of those sets a filter    + the division and one
                                              categorical draw a row
  some sampling row sets ``top_k > 0``        + the top-k stage: one
                                              full sort
  some sampling row sets ``top_p < 1``        + the top-p stage: one
                                              full sort, softmax, cumsum
  ==========================================  =========================

A stage that runs is the unconditional arithmetic over the whole array
and a stage that is skipped would have masked nothing in any row whose
draw is kept, so the tokens are the same bytes whichever side a call
takes (``test_serving.py`` holds them to the every-stage-always body).
The serving engine counts the same predicates on the host, from the
operands it fills: ``sampled_rows`` / ``filtered_rows`` of the overlap
record (docs/observability.md).

Key schedule (`fold_in`, not a split chain): the token at OUTPUT index
``j`` of a request is always sampled with ``fold_in(request_key, j)``.
The key depends only on (request key, position) — never on batch
composition, scheduling order, preemption count, or whether the token
was proposed speculatively — which is what makes serving streams
reproducible across mesh shapes and makes the speculative verify lane
token-exact against the non-speculative sampler.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import math

from ..observability.overlap import scoped

#: which of a block's masked rows a denoise forward fills (generation by
#: diffusion over blocks, docs/serving.md)
UNMASK_RULES = ("low_confidence_static", "low_confidence_dynamic",
                "sequential")


@scoped("sample")
def fold_in_keys(keys: jax.Array, indices: jax.Array) -> jax.Array:
    """Per-row ``fold_in``: ``keys`` [..., 2] uint32 raw key data,
    ``indices`` [...] int32 → folded raw key data, same shape."""
    flat_k = keys.reshape(-1, 2)
    flat_i = indices.reshape(-1)
    out = jax.vmap(jax.random.fold_in)(flat_k, flat_i)
    return out.reshape(keys.shape)


@scoped("sample")
def sample_tokens(logits, key, temperature, top_k, top_p):
    """fp32 categorical sampling over ``logits [..., V]`` with ONE key
    and static (Python-scalar) sampling params; temperature 0 = greedy
    argmax.  Neutral filters (top_k 0, top_p >= 1) are skipped at trace
    time."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k:
        # O(V·k) top_k, not a full O(V log V) sort — this runs once per
        # decoded token over the whole vocab
        kth = jax.lax.top_k(logits, top_k)[0][..., -1][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p (keep the first
        # token crossing the threshold)
        cutoff_idx = jnp.sum((cum < top_p).astype(jnp.int32), axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[..., None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


@scoped("sample")
def sample_tokens_per_row(logits, keys, temperature, top_k, top_p):
    """Per-row sampling for the serving step: ``logits [B, V]`` with
    PER-ROW traced params — ``keys [B, 2]`` uint32, ``temperature [B]``
    f32, ``top_k [B]`` int32 (0 = off), ``top_p [B]`` f32 (>= 1 = off).
    Rows with ``temperature == 0`` take the greedy argmax of the raw
    logits (bit-exact vs the static path).

    Everything is data, nothing is shape: one trace a shape of the
    serving step covers every per-slot sampling mix (the
    ``decode_builds == 2`` contract).  What
    no row asks for is not computed: the draw and each filter sit
    behind a ``lax.cond`` on a scalar of THIS call's rows (module
    docstring, "What runs when").  Call it plainly: under ``vmap`` a
    ``cond`` becomes a ``select`` and every call sorts again
    (``test_tpu_compile.py`` holds the compiled step to it).

    The top-k threshold comes from a sort + rank compare instead of
    ``lax.top_k`` (whose k must be static); the selected threshold
    VALUE is identical, so the masked array matches the static path
    byte-for-byte."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    t = jnp.asarray(temperature, jnp.float32)
    k = jnp.asarray(top_k, jnp.int32)
    p = jnp.asarray(top_p, jnp.float32)
    samples = t > 0.0

    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def top_k_filter(scaled):
        # k-th largest value as the keep threshold (k = V keeps
        # everything and leaves the bytes untouched)
        k_eff = jnp.where(k > 0, jnp.clip(k, 1, v), v)
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[..., None],
                                  axis=-1)
        return jnp.where(scaled < kth, -jnp.inf, scaled)

    def top_p_filter(filt):
        # nucleus over the top-k-filtered logits, matching the static
        # path's filter order; p >= 1 pins the cutoff to the minimum so
        # nothing masks (cumsum rounding must not shave the tail)
        s2 = jnp.sort(filt, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(s2, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum((cum < p[..., None]).astype(jnp.int32),
                             axis=-1)
        cutoff_idx = jnp.where(p >= 1.0, v - 1, cutoff_idx)
        cutoff = jnp.take_along_axis(s2, cutoff_idx[..., None], axis=-1)
        return jnp.where(filt < cutoff, -jnp.inf, filt)

    def sampled():
        filt = logits / jnp.maximum(t, 1e-8)[..., None]
        # a filter that no SAMPLING row set masks nothing in the rows
        # whose draw is kept: skipping its sort hands those draws the
        # identical bytes
        for asked, stage in ((k > 0, top_k_filter), (p < 1.0, top_p_filter)):
            filt = jax.lax.cond(jnp.any(samples & asked), stage,
                                lambda x: x, filt)

        def draw(kk, row):
            return jax.random.categorical(kk, row)
        drawn = jax.vmap(draw)(keys.reshape(-1, 2),
                               filt.reshape(-1, v)).reshape(t.shape)
        return jnp.where(t <= 0.0, greedy(), drawn).astype(jnp.int32)

    return jax.lax.cond(jnp.any(samples), sampled, greedy)


@scoped("block_unmask")
def block_unmask(logits, block, n_fill, *, mask_id: int, rule: str,
                 threshold: float = 0.9):
    """One denoise step of generation by diffusion over blocks, for every
    slot at once, on the device: ``logits [S, B, V]`` float32 (row ``i``'s
    predict the token AT row ``i``), ``block [S, B]`` int32 as it stands
    (``mask_id`` where nothing is filled yet), ``n_fill [S]`` int32 — rows
    this step may fill (0: none, the slot's forward is a commit or it
    rides nothing).  Every row draws ``x0`` (greedy; the mask token itself
    is never drawn) with confidence ``c = softmax(logits)[x0]`` (over the
    outputs that can be drawn, float32), and among the rows that still
    hold the mask:

      * ``low_confidence_static`` — the ``n_fill`` of highest ``c`` (ties
        to the leftmost) take their ``x0``;
      * ``low_confidence_dynamic`` — every row with ``c > threshold`` if
        there are at least ``n_fill`` of them, else as static;
      * ``sequential`` — the ``n_fill`` leftmost.

    A filled row never changes again.  Returns the block after the step,
    ``[S, B]`` int32."""
    if rule not in UNMASK_RULES:
        raise ValueError(f"block_unmask: rule {rule!r} is none of "
                         f"{UNMASK_RULES}")
    s, b, v = logits.shape
    lg = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, 1, v), 2)
                   == mask_id, -jnp.inf, logits.astype(jnp.float32))
    x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    top = jnp.max(lg, axis=-1)
    # log c: the drawn logit under the log-sum-exp (the best, drawn greedily)
    logc = -jnp.log(jnp.sum(jnp.exp(lg - top[..., None]), axis=-1))
    masked = block == mask_id
    n = n_fill[:, None]
    i, j = jnp.arange(b)[:, None], jnp.arange(b)[None, :]
    if rule == "sequential":
        before = (j < i)[None]
    else:
        before = ((logc[:, None, :] > logc[:, :, None])
                  | ((logc[:, None, :] == logc[:, :, None]) & (j < i)[None]))
    # a masked row's rank among the masked rows of its block
    rank = jnp.sum(before & masked[:, None, :], axis=-1)
    fill = masked & (rank < n)
    if rule == "low_confidence_dynamic":
        high = masked & (logc > math.log(threshold))
        enough = jnp.sum(high, axis=-1, keepdims=True) >= n
        fill = jnp.where(enough & (n > 0), high, fill)
    return jnp.where(fill, x0, block).astype(jnp.int32)
