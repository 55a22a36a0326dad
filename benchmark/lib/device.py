"""The device the numbers are taken on: the table of published peaks, the
refuse-without-a-TPU rule, compile accounting and the memory peak.

``require_tpu`` and ``CompileLog`` are copies of ``chip_smoke.py``'s (see
PERF.md, Open questions: the originals are for a later PR to fold away).
"""
from __future__ import annotations

import os
import sys

#: Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" system architecture page:
#: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def process_age_s() -> float:
    """Seconds since this process was created (``/proc``, 10 ms grain), so
    that ``setup_s`` counts interpreter start-up and imports too."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def require_tpu(chips: int) -> dict:
    """The devices JAX reports, or exit 2 with no result: nothing runs on
    another platform or another number of chips than the cell asks for."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] != chips:
        sys.stderr.write(
            f"benchmark: the cell needs exactly {chips} TPU device(s); JAX "
            f"reports {info['count']} x {info['platform']} "
            f"({info['kind']}). No result.\n")
        raise SystemExit(2)
    peaks(info["kind"])
    return info


def memory_peak() -> dict:
    """Peak bytes on the fullest chip, in every cell the same two parts:
    ``allocator``, the allocator's measured peak of live buffers (on this
    runtime it leaves out the temporaries of a running program), and
    ``program_temp``, the temporaries of the largest program this process
    has loaded, as the runtime's loaded executable states them (a
    compile-time figure; nothing is lowered or compiled for it)."""
    import jax
    import jax.extend
    allocator = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in jax.devices())
    temp = 0
    for exe in jax.extend.backend.get_backend().live_executables():
        try:
            temp = max(temp, int(
                exe.get_compiled_memory_stats().temp_size_in_bytes))
        except Exception:       # an executable without memory statistics
            continue
    return {"allocator": allocator, "program_temp": temp,
            "peak": allocator + temp}


class CompileLog:
    """XLA compilations from JAX's monitoring events: how many, the seconds
    in the backend compiler (or fetching from the persistent cache), and the
    seconds tracing and lowering, which no cache saves."""

    def __init__(self):
        from jax import monitoring
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        self.trace_lower_s = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif name.startswith("/jax/core/compile/"):
            self.trace_lower_s += secs

    def _event(self, name, **_):
        self.cache_hits += name == "/jax/compilation_cache/cache_hits"
