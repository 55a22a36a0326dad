"""Reduction from a profiler trace (``.xplane.pb``) to numbers: device busy
union, an operation's own time, exposed collective time and the attribution
of idle gaps to what the host was doing.  Read with nothing but JAX's
``ProfileData``; checked on a recorded trace in ``benchmark/tests``."""
from __future__ import annotations

import glob
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


def reduce(path: str, span_names) -> dict:
    """All the numbers a traced run reports.  The traced window runs from
    the start of the client's first span in the trace to the end of its
    last; device events are clipped to it.  Seconds are averaged over the
    devices."""
    return reduce_events(read(path, set(span_names)))


def reduce_events(raw: dict) -> dict:
    """``reduce`` on events already read: ``{"devices": {name: [(name,
    start_ns, duration_ns)]}, "spans": [(name, start_ns, end_ns)]}``."""
    if not raw["spans"] or not raw["devices"]:
        return {}
    lo = raw["spans"][0][1]
    hi = max(s[2] for s in raw["spans"])
    ndev = len(raw["devices"])
    own_by_name = defaultdict(float)
    calls_by_name = defaultdict(int)
    busy_s = exposed_s = 0.0
    gaps_by_span = defaultdict(float)
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
        for gs, ge in subtract([(lo, hi)], busy):
            left = ge - gs
            for name, ss, se in raw["spans"]:
                part = min(ge, se) - max(gs, ss)
                if part > 0:
                    gaps_by_span[name] += part
                    left -= part
            gaps_by_span["none"] += max(left, 0.0)
    ns = 1e-9 / ndev
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s * ns,
        "exposed_collective_s": exposed_s * ns,
        "op_s": {k: v * ns for k, v in own_by_name.items()},
        "op_calls": {k: v / ndev for k, v in calls_by_name.items()},
        "idle_gap_s": {k: v * ns for k, v in gaps_by_span.items()},
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
