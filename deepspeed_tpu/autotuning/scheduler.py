"""Experiment scheduler: autotuning candidates as isolated subprocess jobs.

Role-equivalent of the reference ``ResourceManager``
(`/root/reference/deepspeed/autotuning/scheduler.py:28`): there,
experiments are launched as ssh/pdsh launcher jobs across nodes with a
slot pool and early termination; here each experiment is a local
subprocess running `autotuning/exp_runner.py` — crash/timeout isolation
means a candidate that OOMs the whole process, deadlocks, or segfaults
costs one job, not the tune (the round-3 verdict's gap #3: an in-process
candidate crash killed the whole tune).

A job spec is a JSON dict:
  {"cfg": <engine config>, "model_factory": "pkg.mod:callable",
   "model_config": {...}, "steps": 3, "seq": 64,
   "result_path": "...", "inject_fault": None|"crash"|"hang",
   "timeout_s": <optional per-spec override of the pool timeout>}

``inject_fault`` is a chaos hook honoured by the runner (used by the
fault-isolation tests; the reference has no in-band fault injection —
SURVEY §5.3 — this framework treats it as part of the contract).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils.logging import logger


def initialized_accelerator() -> Optional[str]:
    """The accelerator platform this process's JAX has ALREADY
    initialized, else None — asked without initializing anything (a
    process that merely imported jax holds no chip)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform


class ResourceManager:
    """Run job specs over a bounded pool of subprocess slots.

    A chip belongs to one process at a time, and every experiment needs
    the whole accelerator: the scheduling process must not have touched
    JAX on it (``run`` raises rather than let the children hang), and
    more than one slot is for CPU runs (``JAX_PLATFORMS=cpu``) only."""

    def __init__(self, slots: int = 1, timeout_s: float = 600.0,
                 env: Optional[Dict[str, str]] = None,
                 poll_s: float = 0.2):
        self.slots = max(1, int(slots))
        self.timeout_s = float(timeout_s)
        self.env = dict(env or {})
        self.poll_s = poll_s
        platforms = self.env.get("JAX_PLATFORMS",
                                 os.environ.get("JAX_PLATFORMS", ""))
        if self.slots > 1 and platforms != "cpu":
            raise ValueError(
                f"autotune scheduler: {self.slots} slots would run that "
                f"many experiments at once, each needing the whole "
                f"accelerator — use slots=1, or JAX_PLATFORMS=cpu")

    def _launch(self, spec_path: str,
                log_path: str) -> subprocess.Popen:
        """Child output goes to a per-job LOG FILE, not a pipe: a verbose
        experiment would fill the ~64KiB pipe buffer, block mid-run, and
        get misclassified as a timeout (advisor r4, low)."""
        env = dict(os.environ)
        env.update(self.env)
        logf = open(log_path, "wb")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m",
                 "deepspeed_tpu.autotuning.exp_runner", spec_path],
                stdout=logf, stderr=subprocess.STDOUT, env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))))
        finally:
            logf.close()      # the child holds its own fd from here
        return proc

    def run(self, specs: List[Dict[str, Any]],
            workdir: str) -> List[Dict[str, Any]]:
        """Execute all specs; returns one result dict per spec (same
        order): {"status": ok|oom|error|crash|timeout, "samples_per_sec",
        "detail"}."""
        held = initialized_accelerator()
        if held is not None and self.env.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError(
                f"autotune scheduler: this process has already "
                f"initialized JAX on {held!r} and so holds the chip its "
                f"experiment subprocesses need — they would fail or "
                f"hang.  Schedule from a process that has not touched "
                f"JAX (build the specs there, or pass them in), or tune "
                f"in-process with Autotuner.tune()")
        os.makedirs(workdir, exist_ok=True)
        results: List[Optional[Dict[str, Any]]] = [None] * len(specs)
        pending = deque()
        for i, spec in enumerate(specs):
            spec = dict(spec)
            spec.setdefault("result_path",
                            os.path.join(workdir, f"result_{i}.json"))
            sp = os.path.join(workdir, f"spec_{i}.json")
            with open(sp, "w") as f:
                json.dump(spec, f)
            lp = os.path.join(workdir, f"job_{i}.log")
            budget = float(spec.get("timeout_s", self.timeout_s))
            pending.append((i, sp, spec["result_path"], lp, budget))
        running: Dict[int, Any] = {}

        def tail(log_path: str, n: int = 300) -> str:
            try:
                with open(log_path, "rb") as f:
                    f.seek(0, os.SEEK_END)
                    f.seek(max(0, f.tell() - n))
                    return f.read().decode(errors="replace")
            except OSError:
                return ""

        def harvest(i, proc, result_path, log_path, timed_out=False,
                    budget=None):
            if timed_out:
                proc.kill()
                proc.wait()
                results[i] = {"status": "timeout", "samples_per_sec": None,
                              "detail": (f"killed after {budget}s; "
                                         f"{tail(log_path)}")}
                return
            proc.wait()
            if os.path.exists(result_path):
                with open(result_path) as f:
                    results[i] = json.load(f)
            else:
                results[i] = {
                    "status": "crash", "samples_per_sec": None,
                    "detail": (f"exit={proc.returncode}; "
                               f"{tail(log_path)}")}

        while pending or running:
            while pending and len(running) < self.slots:
                i, sp, rp, lp, budget = pending.popleft()
                proc = self._launch(sp, lp)
                running[i] = (proc, rp, lp, time.monotonic(), budget)
                logger.info(f"autotune scheduler: job {i} launched "
                            f"(pid {proc.pid}, "
                            f"{len(running)}/{self.slots} slots)")
            done = []
            for i, (proc, rp, lp, t0, budget) in running.items():
                if proc.poll() is not None:
                    harvest(i, proc, rp, lp)
                    done.append(i)
                elif time.monotonic() - t0 > budget:
                    harvest(i, proc, rp, lp, timed_out=True, budget=budget)
                    done.append(i)
            for i in done:
                running.pop(i)
                logger.info(f"autotune scheduler: job {i} -> "
                            f"{results[i]['status']}")
            if running and not done:
                time.sleep(self.poll_s)
        return [r for r in results]
