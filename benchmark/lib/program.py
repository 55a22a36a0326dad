"""What the program recorded about itself: the serving engine's one
instrument, ``deepspeed_tpu/observability/overlap.py``, which the serving
runner switches on with ``--trace 1``.  Its iteration and request records
are stamped with ``time.perf_counter()``, the clock of ``obs["window"]``.
A program that keeps no such records (a commit before they existed) gives
None, and the metric is left out of the line."""
from __future__ import annotations

from deepspeed_tpu.observability.overlap import get_overlap_profiler


def records(obs: dict, what: str):
    """The program's ``what`` (``iterations`` | ``requests``) records that
    belong to the window ``(w0, w1]`` — an iteration by its end, a request
    by its submit — or None if the program keeps none, kept none there, or
    its ring wrapped past the window's opening."""
    read = getattr(get_overlap_profiler(), what, None)
    if read is None:
        return None
    recs, complete = read(*obs["window"])
    if what == "iterations":
        recs = recs[recs["kind"] == "serving"]
    return recs if complete and len(recs) else None
