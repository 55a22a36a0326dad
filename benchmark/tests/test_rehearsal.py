"""Each cell that ``rehearse.py`` has sizes for (the others rehearse in
files of their own, named there) on the CPU at a tiny size, through the
harness's own ``run_cell``, and the shape of the line it would print.  The
four-chip cell runs on 4 virtual CPU devices."""
import json
import os
import subprocess
import sys

import pytest

import rehearse
from benchmark import run as harness
from benchmark.lib import traffic
from benchmark.runners import serve

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = harness.load_benchmark()


def _rehearse(workload, trace_on):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), workload,
         str(trace_on)], capture_output=True, text=True, timeout=600,
        env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace_on", (0, 1))
@pytest.mark.parametrize("workload", [
    w["name"] for w in BENCH["workloads"]
    if traffic.load(w["traffic"])["kind"] in rehearse.TINY])
def test_cell_rehearsal_prints_the_contract_line(workload, trace_on):
    line = _rehearse(workload, trace_on)
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert line["device"]["count"] == cell["chips"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = "per_layer" if trace_on else "end_to_end"
    declared = {m["name"]: m for m in
                harness.metrics_of(BENCH, group, workload)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        assert isinstance(got["value"], float)
    if trace_on:
        assert len(line["metrics"]) >= 5
        assert len(line["breakdown"]["device_ops"]) <= 10
        gaps = dict(line["breakdown"]["idle_gaps"])
        assert set(gaps) <= set(serve.SPANS) | {"train_step", "none"}
        assert min(gaps.values()) >= 0.0
        assert set(line["diag"]["idle_gaps"]["longest_s_at_s"]) <= set(gaps)
        if "serve_step" in gaps:
            # an idle gap is named by the engine's phase
            assert set(gaps) & set(serve.SPANS[len(serve.CLIENT_SPANS):])
    else:
        # every end-to-end metric of the cell is there, none of them 0
        assert set(line["metrics"]) == set(declared)
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_command_prints_no_result_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "No result" in out.stderr
