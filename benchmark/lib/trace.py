"""Reduction from a profiler trace (``.xplane.pb``) to numbers: device busy
union, an operation's own time, exposed collective time and the attribution
of idle gaps to what the host was doing.  Read with nothing but JAX's
``ProfileData``; checked on a recorded trace in ``benchmark/tests``."""
from __future__ import annotations

import glob
import math
import os
import re
from collections import defaultdict

import numpy as np

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_key(name: str) -> str:
    """A short key for an operation.  On the TPU an event's name is the whole
    HLO instruction (``%fusion.12 = bf16[..] fusion(..)``): keep the
    instruction's name without XLA's instance number, so that ``fusion.12``
    and ``fusion.13`` add up, and mark compiled Pallas kernels, whose names
    (``closed_call``, ``checkpoint``) say little."""
    short = re.sub(r"[.\d]+$", "", name.split(" = ", 1)[0].lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in name:
        short += " (pallas kernel)"
    return short


def read(path: str, span_names) -> dict:
    """Device operation events per device and the client's host spans.  Off
    the TPU (the CPU rehearsal) the host plane's HLO events stand in as one
    device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    planes = list(data.planes)
    on_tpu = any(DEVICE_PLANE.match(p.name) for p in planes)
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
                    elif not on_tpu and any(k == "hlo_op"
                                            for k, _ in e.stats):
                        devices.setdefault("/host:CPU", []).append(
                            (e.name, float(e.start_ns),
                             float(e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def self_times(events):
    """Events sorted by start (longer first on ties) with each one's own
    time: its duration less what the events nested wholly inside it cover
    (a ``while`` and its body).  Returns ``(events, own_ns, is_leaf)``."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in events]
    leaf = [True] * len(events)
    stack = []
    for i, (_, start, dur) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            if start + dur <= events[parent][1] + events[parent][2]:
                own[parent] -= dur                 # nested: a child
                leaf[parent] = False
        stack.append(i)
    return events, own, leaf


def union(intervals):
    """Merged, sorted list of ``(start, end)``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def innermost(spans) -> list:
    """``spans`` cut into disjoint, sorted ``(start, end, name)``
    pieces, each instant under the span that covers it and began last: a
    phase inside the engine's iteration inside the client's ``serve_step``
    is the phase's, and what the iteration leaves of ``serve_step`` (the
    client inside its span, outside the engine) is ``serve_step``'s."""
    out, stack, cur = [], [], 0.0
    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])) + [
            ("", math.inf, math.inf)]:
        while stack and stack[-1][2] <= start:      # the top ends first
            top = stack.pop()
            if top[2] > cur:
                out.append((cur, top[2], top[0]))
                cur = top[2]
        if stack and start > cur:
            out.append((cur, start, stack[-1][0]))
        cur = start
        stack.append((name, start, end))
    return out


def reduce(path: str, span_names, window=None) -> dict:
    """All the numbers a traced run reports.  The traced window runs from
    the start of the first span named in ``window`` (all of ``span_names``
    unless given: a serving runner names the client's own, since a trace
    that opens inside a ``step()`` holds that step's later phases and not
    its ``serve_step``) to the end of the last; device events are clipped
    to it.  Seconds are averaged over the devices."""
    return reduce_events(read(path, set(span_names)), window)


def reduce_events(raw: dict, window=None) -> dict:
    """``reduce`` on events already read: ``{"devices": {name: [(name,
    start_ns, duration_ns)]}, "spans": [(name, start_ns, end_ns)]}``.  Each
    part of an idle gap is booked once, to the innermost span that covers
    it (``none``: to no span); a whole gap counts, for ``idle_gap_longest``
    (``(seconds, seconds into the window at which it opens)``) and
    ``idle_gaps_over_1ms``, under the name that holds most of it."""
    bounds = [s for s in raw["spans"] if window is None or s[0] in window]
    if not bounds or not raw["devices"]:
        return {}
    lo = min(s[1] for s in bounds)
    hi = max(s[2] for s in bounds)
    pieces = [(max(s, lo), min(e, hi), name)
              for s, e, name in innermost(raw["spans"])
              if min(e, hi) > max(s, lo)]
    ndev = len(raw["devices"])
    own_by_name = defaultdict(float)
    calls_by_name = defaultdict(int)
    busy_s = exposed_s = 0.0
    gaps_by_span = defaultdict(float)
    longest = defaultdict(tuple)
    over_1ms = defaultdict(int)
    for events in raw["devices"].values():
        events, own, leaf = self_times(
            [e for e in events if e[1] + e[2] > lo and e[1] < hi])
        for (name, start, dur), o in zip(events, own):
            inside = min(start + dur, hi) - max(start, lo)
            own_by_name[name] += o * inside / dur if dur else 0.0
            calls_by_name[name] += 1
        busy = union(clip([(s, s + d) for _, s, d in events], lo, hi))
        busy_s += total(busy)
        coll = union(clip([(s, s + d) for (n, s, d), lf in zip(events, leaf)
                           if lf and COLLECTIVE.search(op_key(n))], lo, hi))
        comp = union(clip([(s, s + d) for (n, s, d), lf in zip(events, leaf)
                           if lf and not COLLECTIVE.search(op_key(n))],
                          lo, hi))
        exposed_s += total(subtract(coll, comp))
        j = 0
        for gs, ge in subtract([(lo, hi)], busy):
            parts = defaultdict(float)
            while j < len(pieces) and pieces[j][1] <= gs:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < ge:
                ps, pe, name = pieces[k]
                parts[name] += min(ge, pe) - max(gs, ps)
                k += 1
            parts["none"] = max(ge - gs - sum(parts.values()), 0.0)
            for name, part in parts.items():
                gaps_by_span[name] += part
            name = max(parts, key=parts.get)
            longest[name] = max(longest[name], (ge - gs, gs - lo))
            over_1ms[name] += ge - gs > 1e6
    ns = 1e-9 / ndev
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s * ns,
        "exposed_collective_s": exposed_s * ns,
        "op_s": {k: v * ns for k, v in own_by_name.items()},
        "op_calls": {k: v / ndev for k, v in calls_by_name.items()},
        "idle_gap_s": {k: v * ns for k, v in gaps_by_span.items()},
        "idle_gap_longest": {k: (v[0] * 1e-9, v[1] * 1e-9)
                             for k, v in longest.items()},
        "idle_gaps_over_1ms": {k: v / ndev for k, v in over_1ms.items()
                               if v},
        "devices": ndev,
    }


def matching(red: dict, pattern: str, what: str = "op_s") -> float:
    """Seconds (or calls) of the operations whose event name — on the TPU
    the whole HLO instruction, shapes included — matches ``pattern``."""
    rx = re.compile(pattern)
    return float(sum(v for k, v in red.get(what, {}).items()
                     if rx.search(k)))


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time and the idle time by what the host was doing."""
    def head(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]
    by_key = defaultdict(float)
    for name, seconds in red.get("op_s", {}).items():
        by_key[op_key(name)] += seconds
    return {"device_ops": head(by_key),
            "idle_gaps": head(red.get("idle_gap_s", {}))}
