"""Accelerator abstraction.

Role-equivalent of the reference's `accelerator/abstract_accelerator.py:5`
``DeepSpeedAccelerator`` (~60-method ABC over torch.cuda). Under XLA most of
that surface (streams, events, pinned memory, tensor factories) is
compiler-managed, so this is a *capability probe + memory/RNG facade*:
what remains meaningful on TPU is device identity, HBM stats, host memory,
RNG seeding, and the communication substrate name.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np


class TPUAccelerator:
    _name = "tpu"

    def __init__(self):
        self._device_cache = None

    # -- identity ---------------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        devs = self.devices()
        if device_index is None:
            return devs[0].platform if devs else "cpu"
        return str(devs[device_index])

    def devices(self):
        if self._device_cache is None:
            self._device_cache = jax.devices()
        return self._device_cache

    def device_count(self) -> int:
        return len(self.devices())

    def current_device(self) -> int:
        return 0

    def current_device_name(self) -> str:
        return self.device_name(0)

    def is_available(self) -> bool:
        return any(d.platform != "cpu" for d in self.devices())

    def communication_backend_name(self) -> str:
        return "xla"  # ICI/DCN collectives compiled by XLA

    def device_kind(self) -> str:
        devs = self.devices()
        return devs[0].device_kind if devs else "cpu"

    # -- memory -----------------------------------------------------------
    def memory_stats(self, device_index: int = 0) -> dict:
        d = self.devices()[device_index]
        try:
            s = d.memory_stats() or {}
        except Exception:
            s = {}
        return s

    def memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("peak_bytes_in_use", 0))

    def reset_peak_memory_stats(self, device_index: int = 0) -> None:
        pass  # XLA exposes no reset; peak is monotone per process

    def total_memory(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: int = 0) -> int:
        s = self.memory_stats(device_index)
        return int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0))

    def host_memory_info(self) -> dict:
        try:
            pages = os.sysconf("SC_PHYS_PAGES")
            avail = os.sysconf("SC_AVPHYS_PAGES")
            psz = os.sysconf("SC_PAGE_SIZE")
            return {"total": pages * psz, "available": avail * psz}
        except (ValueError, OSError):
            return {"total": 0, "available": 0}

    # -- RNG (functional: return keys, don't mutate hidden state) ---------
    def manual_seed(self, seed: int):
        return jax.random.PRNGKey(seed)

    def default_generator(self, seed: int = 0):
        return jax.random.PRNGKey(seed)

    # -- capability probe -------------------------------------------------
    def supported_dtypes(self):
        import jax.numpy as jnp
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    def preferred_dtype(self):
        import jax.numpy as jnp
        return jnp.bfloat16

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def lazy_call(self, callback):
        callback()

    def synchronize(self, device_index: Optional[int] = None) -> None:
        jax.effects_barrier()


_accel: Optional[TPUAccelerator] = None


def get_accelerator() -> TPUAccelerator:
    global _accel
    if _accel is None:
        _accel = TPUAccelerator()
    return _accel


def set_accelerator(accel) -> None:
    global _accel
    _accel = accel
