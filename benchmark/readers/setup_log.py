"""From the program's set-up log (``get_overlap_profiler().setup_spans()``
and ``.builds()``: the spans the entry points and both engines put around
their set-up steps, and one record a program traced, lowered, compiled or
fetched, with its name, its three durations, whether the persistent cache
held it and whether an engine declared it its own): what of ``setup_s``
is the program's.  "Before the window" is ``end_s <= w0`` on
``time.perf_counter()``'s clock, the clock of ``obs["window"]``.

``program_share`` (%): the union of the top-level set-up spans and of the
``own`` builds that ended outside every span (a step's own shape, built by
its first call), over ``setup_s``: the part of the judged metric that a
change to the program can move; the rest is the interpreter's start, the
imports and the benchmark's own work (weights from the seed, the reference,
the fill).  ``step_build_s``: trace + lower + compile-or-fetch of the
``own`` builds; ``step_trace_lower_s``: their trace + lower, which no cache
saves (``trace_lower_s`` is the same over every program of the process, so
this never exceeds it); ``cache_miss_programs``: builds, own or not, that
the persistent cache did not hold (0 in a warm checkout); ``place_s``: the
spans that put bytes on the device (``PLACE``), less what the programs
built under them took (a jitted ``init``, the per-slot state's maker), so
that no compiler is in it and it does not move with the cache.

The line's ``diag.setup`` holds the table the numbers come from — every
span with its seconds and self seconds, the own builds by name — and
``builds_in_window``, the names of what was built INSIDE the window (none,
or ``correct`` is false by ``compiles_in_window``).  A program without the
two accessors (a commit before they existed) gives None, and the metric is
left out of the line."""
import math

import numpy as np

from deepspeed_tpu.observability.overlap import get_overlap_profiler

#: the spans that move bytes onto the device and run no compiler
PLACE = ("setup/place_params", "setup/serving_params", "setup/pools",
         "setup/state_init")


def _union_s(intervals) -> float:
    """Seconds covered by ``(begin, end)`` intervals that may overlap."""
    total, edge = 0.0, -math.inf
    for begin, end in sorted(intervals):
        if end > edge:
            total += end - max(begin, edge)
            edge = end
    return total


def log(obs):
    """``{"spans", "builds"}`` before the window, read once a run and kept
    in ``obs``; None where the program keeps no set-up log."""
    if "setup_log" in obs:
        return obs["setup_log"]
    prof = get_overlap_profiler()
    read_spans = getattr(prof, "setup_spans", None)
    read_builds = getattr(prof, "builds", None)
    if read_spans is None or read_builds is None:
        obs["setup_log"] = None
        return None
    w0, w1 = obs["window"]
    spans = read_spans()
    spans = spans[spans["end_s"] <= w0]
    builds = read_builds(-math.inf, w0)
    obs["setup_log"] = {"spans": spans, "builds": builds}
    name_of = {int(s["id"]): str(s["name"]) for s in spans}
    seconds = spans["end_s"] - spans["begin_s"]
    inner = {}                  # a span's id -> the seconds of its children
    for parent, took in zip(spans["parent"], seconds):
        inner[int(parent)] = inner.get(int(parent), 0.0) + float(took)
    obs.setdefault("diag", {})["setup"] = {
        "spans": [{"name": str(s["name"]),
                   "parent": name_of.get(int(s["parent"]), ""),
                   "s": float(took),
                   "self_s": float(took) - inner.get(int(s["id"]), 0.0)}
                  for s, took in zip(spans, seconds)],
        "own_builds": [{"name": str(b["fun_name"]),
                        "trace_s": float(b["trace_s"]),
                        "lower_s": float(b["lower_s"]),
                        "compile_s": float(b["compile_s"]),
                        "cache": str(b["cache"]),
                        "span": name_of.get(int(b["span"]), "")}
                       for b in builds[builds["own"]]],
        "builds": len(builds),
        # of every program the log saw: beside the line's process-wide
        # ``trace_lower_s``, which also counts the traces of the functions
        # a program calls, once more each
        "builds_trace_lower_s": float((builds["trace_s"]
                                       + builds["lower_s"]).sum()),
        "builds_compile_s": float(builds["compile_s"].sum()),
        "cache": {state: int((builds["cache"] == state).sum())
                  for state in ("hit", "miss", "off")},
        "builds_in_window": [str(n) for n in
                             read_builds(w0, w1)["fun_name"]],
        "dropped": int(getattr(prof, "setup_log_dropped", 0))}
    return obs["setup_log"]


def read(obs, of):
    held = log(obs)
    if held is None:
        return None
    spans, builds = held["spans"], held["builds"]
    own = builds[builds["own"]]
    if of == "program_share":
        top = spans[spans["parent"] == -1]
        outside = own[own["span"] == -1]
        covered = _union_s(
            list(zip(top["begin_s"], top["end_s"]))
            + list(zip(outside["begin_s"], outside["end_s"])))
        return 100.0 * covered / obs["values"]["setup_s"]
    if of == "step_build_s":
        return float((own["trace_s"] + own["lower_s"]
                      + own["compile_s"]).sum())
    if of == "step_trace_lower_s":
        return float((own["trace_s"] + own["lower_s"]).sum())
    if of == "cache_miss_programs":
        return float((builds["cache"] == "miss").sum())
    if of == "place_s":
        placed = spans[np.isin(spans["name"], PLACE)]
        under = builds[np.isin(builds["span"], placed["id"])]
        return float((placed["end_s"] - placed["begin_s"]).sum()
                     - (under["trace_s"] + under["lower_s"]
                        + under["compile_s"]).sum())
    raise ValueError(f"setup_log: nothing called {of!r}")
