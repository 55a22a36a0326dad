"""A kernel's share (%) of its roofline over the traced window: the least
time the chip could take for the work the runner counted from shapes
(``benchmark/lib/costs.py``) over the kernel's own time in the trace."""
from ..lib import trace


def read(obs, pattern, work):
    red, need = obs.get("trace"), obs.get("work", {}).get(work)
    if not red or not need:
        return None
    spent = trace.matching(red, pattern)
    return None if spent <= 0 else 100.0 * need["least_s"] / spent
