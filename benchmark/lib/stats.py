"""Arithmetic from stamp series to numbers.  Pure numpy, no JAX: tested on
hand-made series in ``benchmark/tests``."""
from __future__ import annotations

import math

import numpy as np

#: what an unfinished or failed request's latency is printed as: JSON has no
#: infinity, and any limit a reader sets is below it
INF_MS = 1e12


def order_stat(values, q: float) -> float:
    """The exact ``q`` order statistic (nearest rank, no interpolation): the
    smallest value with at least ``q`` of the sample at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return math.nan
    return float(v[max(0, math.ceil(q * v.size) - 1)])


def finite_ms(x: float) -> float:
    return INF_MS if math.isinf(x) else x


def whole_step_rate(step_ends, w0: float, w1: float, work):
    """Work per second over all the whole steps of the window: the work of
    the steps that ended after the first step end inside ``[w0, w1]``, over
    the time from that first step end to the last one inside.  No window
    edge is in it, and a stalled step counts in full.  ``work`` is one
    number (every step does the same) or one number per step end."""
    e = np.asarray(step_ends, dtype=np.float64)
    inside = np.flatnonzero((e >= w0) & (e <= w1))
    if inside.size < 2:
        return math.nan
    done = np.broadcast_to(np.asarray(work, dtype=np.float64), e.shape)
    return float(done[inside[1:]].sum() / (e[inside[-1]] - e[inside[0]]))


def slice_rates(it_ends, it_tokens, w0: float, w1: float, n: int = 10):
    """Rate of each of ``n`` equal slices of ``[w0, w1]``: the tokens made
    visible by the iterations that ended in the slice, over the time from the
    last iteration end before the slice to the last one inside it.  Whole
    iterations only; a slice in which no iteration ended has rate 0."""
    e = np.asarray(it_ends, dtype=np.float64)
    tok = np.asarray(it_tokens, dtype=np.float64)
    edges = w0 + (w1 - w0) * np.arange(n + 1) / n
    rates = []
    for k in range(n):
        inside = np.flatnonzero((e > edges[k]) & (e <= edges[k + 1]))
        before = np.flatnonzero(e <= edges[k])
        if inside.size == 0 or before.size == 0:
            rates.append(0.0)
            continue
        span = e[inside[-1]] - e[before[-1]]
        rates.append(float(tok[inside].sum() / span))
    return rates


def slice_median_rate(it_ends, it_tokens, w0, w1, n: int = 10) -> float:
    """Median of the slice rates: what the rate is when nothing stalls.  A
    stall of up to four slices in ten does not move it, so it is a per-layer
    reading beside the whole-window rate, never the judged one."""
    return float(np.median(slice_rates(it_ends, it_tokens, w0, w1, n)))


def stall_share(it_starts, it_ends, w0, w1, factor: float = 3.0) -> float:
    """Share (%) of the window spent in iterations longer than ``factor``
    times the median iteration."""
    s = np.asarray(it_starts, dtype=np.float64)
    e = np.asarray(it_ends, dtype=np.float64)
    keep = (e > w0) & (e <= w1)
    d = (e - s)[keep]
    if d.size == 0:
        return math.nan
    return float(100.0 * d[d > factor * np.median(d)].sum() / (w1 - w0))
