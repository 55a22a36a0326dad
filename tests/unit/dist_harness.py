"""Multi-process distributed test harness.

Role-equivalent of the reference ``DistributedTest``
(`/root/reference/tests/unit/common.py:69`): fork one REAL process per
rank, initialize the distributed runtime in each, run the test body, and
fail the test if any rank fails. The single-process 8-virtual-device mesh
(conftest.py) covers collective MATH; this harness covers what it cannot —
`jax.distributed` bring-up, the launcher env contract, and every
``jax.process_count() > 1`` branch.

Usage:
    result = run_distributed(WORKER_SRC, world=2)
    # WORKER_SRC is python source run in each process with
    # `process_id`, `num_processes`, `tmp` (shared scratch dir) bound and
    # jax.distributed initialized on the CPU backend
    # (2 local devices per process).
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional

_PRELUDE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "")
    + " --xla_force_host_platform_device_count={local_devices}").strip()
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:{port}",
                           num_processes={world}, process_id={rank})
process_id, num_processes = {rank}, {world}
tmp = {tmp!r}
import sys
sys.path.insert(0, {repo!r})
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_distributed(worker_src: str, world: int = 2,
                    local_devices: int = 2, timeout: float = 420,
                    env: Optional[Dict[str, str]] = None,
                    tmp: Optional[str] = None) -> str:
    """Fork ``world`` processes running ``worker_src``; raises on any
    nonzero exit with the failing rank's output. Returns the shared tmp
    dir (rank outputs land there)."""
    port = _free_port()
    tmp = tmp or tempfile.mkdtemp(prefix="dist_test_")
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    procs: List[subprocess.Popen] = []
    logs = []
    for rank in range(world):
        code = _PRELUDE.format(port=port, world=world, rank=rank,
                               local_devices=local_devices, tmp=tmp,
                               repo=repo) + worker_src
        penv = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        penv.update(env or {})
        log = open(os.path.join(tmp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code], env=penv,
            stdout=log, stderr=subprocess.STDOUT))
    codes = []
    try:
        for p in procs:
            codes.append(p.wait(timeout=timeout))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for log in logs:
            log.close()
    if any(c != 0 for c in codes):
        details = []
        for rank, c in enumerate(codes):
            if c != 0:
                with open(os.path.join(tmp, f"rank{rank}.log")) as f:
                    details.append(f"--- rank {rank} (exit {c}) ---\n"
                                   + f.read()[-4000:])
        raise AssertionError(
            f"distributed workers failed (codes {codes}):\n"
            + "\n".join(details))
    return tmp
