#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs one cell (an entry of ``workloads`` in ``BENCHMARK.json``) in this one
process, on the chips of the machine it is started on, and prints as its
last line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``) and ``device``.  Without the TPUs the cell asks
for it exits 2 and prints no result.  Everything a cell is made of — its
configuration, its traffic mix, its metrics — is a file found by name; see
``benchmark/README.md``.
"""
from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib import device, model as model_lib, trace, traffic  # noqa: E402,E501


class Context:
    """What a runner is handed: the cell, its files and the profiler."""

    def __init__(self, cell, config, mix, seed, seconds, trace_on, peaks,
                 compile_log, tiny=None, keep_trace=False):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds, self.trace = seed, seconds, trace_on
        self.peaks, self.compile_log, self.tiny = peaks, compile_log, tiny
        self.trace_dir = os.path.join(HERE, ".trace", cell["name"])
        self.trace_started_at = math.inf
        self.keep_trace = keep_trace

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the client's own spans only
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.trace_started_at = time.perf_counter()

    def stop_trace(self, span_names, window=None) -> dict:
        """Stop, reduce the trace to numbers, and keep nothing on disk
        (``keep_trace`` is for recording the tests' small trace).  The
        spans named in ``window`` (all of them unless given) set the traced
        window."""
        import jax
        jax.profiler.stop_trace()
        red = trace.reduce(trace.newest_xplane(self.trace_dir), span_names,
                           window)
        if not self.keep_trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        return red


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` this cell reports: those with no
    ``workloads`` key and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def declaration_path(name: str) -> str:
    """A metric's declaration: ``benchmark/metrics/<name>.json``, or, for a
    name that carries a cell prefix (``sat.``, ``paced.``, ``train.``: cells
    that report different end-to-end metrics need different names for one
    quantity), the file of the name without it."""
    for stem in (name, name.split(".", 1)[-1]):
        path = os.path.join(HERE, "metrics", stem + ".json")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no declaration for metric {name!r}")


def declaration(name: str) -> dict:
    with open(declaration_path(name)) as f:
        return json.load(f)


def evaluate(metric: dict, obs: dict):
    """A declaration names a reader ``benchmark/readers/<reader>.py`` and its
    arguments.  A reader that finds nothing to read returns None and the
    metric is left out of the line."""
    decl = declaration(metric["name"])
    reader = importlib.import_module("benchmark.readers." + decl["reader"])
    value = reader.read(obs, **decl.get("args", {}))
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return None
    return {"value": float(value), "unit": metric["unit"]}


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace_on: bool, peaks: dict, compile_log, tiny=None,
             mix_overrides=None, keep_trace=False) -> tuple:
    """Run one cell and evaluate its metrics: ``(result line, obs)``.  The
    tests call this in-process with ``tiny`` sizes on the CPU."""
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = model_lib.load_config(cfg_entry["file"])
    mix = dict(traffic.load(cell["traffic"]), **(mix_overrides or {}))
    ctx = Context(cell, config, mix, seed, seconds, trace_on, peaks,
                  compile_log, tiny, keep_trace)
    runner = importlib.import_module("benchmark.runners." + mix["kind"])
    obs = runner.run(ctx)
    memory = obs["memory"]      # taken by the runner while its programs live
    obs["values"].update(compile_s=compile_log.compile_s,
                         trace_lower_s=compile_log.trace_lower_s,
                         memory_peak_bytes=memory["peak"],
                         peak_hbm_gib=memory["peak"] / 2**30)
    obs["peaks"], obs["chips"] = peaks, cell["chips"]
    group = "per_layer" if trace_on else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, group, workload):
        got = evaluate(m, obs)
        if got is not None:
            metrics[m["name"]] = got
    line = {"correct": obs["correct"], "attempted": obs["attempted"],
            "failed": obs["failed"], "metrics": metrics}
    line["diag"] = dict(obs["diag"], setup_s=obs["values"]["setup_s"],
                        memory=memory, cache_hits=compile_log.cache_hits,
                        compiles=compile_log.compiles)
    if trace_on and obs["trace"]:
        line["breakdown"] = trace.breakdown(obs["trace"])
        # the sum cannot say whether it is one stall or a hundred, nor
        # whether it is the traced window's first iteration
        line["diag"]["idle_gaps"] = {
            "longest_s_at_s": obs["trace"]["idle_gap_longest"],
            "over_1ms": obs["trace"]["idle_gaps_over_1ms"]}
    return line, obs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the three flags below are beyond the contract's four; the driver passes
    # none of them.  Their only use is the README's two procedures.
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="replace one key of the traffic file: only for the "
                    "README's knee sweep and pool sizing")
    ap.add_argument("--stamps", help="write the client's stamps to this "
                    ".json: only for the README's stall hunt")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under benchmark/.trace/: only "
                    "for recording the tests' small trace")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    if not os.path.isdir(os.path.join(ROOT, "deepspeed_tpu")):
        sys.stderr.write("benchmark: no system under test beside the "
                         "benchmark (deepspeed_tpu/). No result.\n")
        return 2
    # JAX's persistent compilation cache at a fixed place inside the
    # checkout (the program's own ds.enable_compile_cache() uses the same)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    info = device.require_tpu(cell["chips"])
    compile_log = device.CompileLog()
    overrides = {k: json.loads(v) for k, v in
                 (s.split("=", 1) for s in args.set)}
    line, obs = run_cell(bench, args.workload, args.seed, args.seconds,
                         bool(args.trace), device.peaks(info["kind"]),
                         compile_log, mix_overrides=overrides,
                         keep_trace=args.keep_trace)
    info["memory_peak_bytes"] = obs["values"]["memory_peak_bytes"]
    if args.trace and obs["trace"]:
        info["busy_s"] = obs["trace"]["busy_s"]
        info["window_s"] = obs["trace"]["window_s"]
    line["device"] = info
    if args.stamps and "stamps" in obs:
        os.makedirs(os.path.dirname(os.path.abspath(args.stamps)),
                    exist_ok=True)
        with open(args.stamps, "w") as f:
            json.dump({k: (v.tolist() if hasattr(v, "tolist") else v)
                       for k, v in obs["stamps"].items()}, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
