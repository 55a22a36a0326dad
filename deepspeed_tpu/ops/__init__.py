"""Pallas TPU kernels and native host ops.

Every kernel in this package compiles for the TPU.  The Pallas
interpreter is an explicit choice for CPU test runs and nothing else:
``tests/conftest.py`` turns it on once with :func:`interpret_kernels`,
a test may pass ``interpret=True`` to one call, and nothing inside the
package ever selects it from the backend.  Without the switch a kernel
on a CPU backend fails to lower — it does not give way to a reference.
"""
from __future__ import annotations

from typing import Optional

import jax

_interpret = False


def interpret_kernels(on: bool = True) -> None:
    """Process-wide default for kernels called without ``interpret=``
    (the models' call sites).  Test harnesses only."""
    global _interpret
    _interpret = bool(on)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The ``interpret`` flag a kernel wrapper hands to ``pallas_call``:
    the explicit argument, else the :func:`interpret_kernels` default.
    Interpreting on an accelerator would report the interpreter's
    behaviour under the chip's name, so it raises there."""
    if interpret is None:
        interpret = _interpret
    if interpret and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"Pallas interpret mode requested on the "
            f"{jax.default_backend()!r} backend — kernels only interpret "
            f"on CPU test runs")
    return interpret
