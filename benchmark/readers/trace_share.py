"""From the trace: the own time of the device operations whose name matches
``pattern``, as a share (%) of the device's busy time; or one of the trace's
own shares of the traced window (``of`` = idle | exposed_collective).  A
``<key>`` in the pattern stands for a size of this cell that the runner put
under ``shapes`` (the pool's block size and row width); a cell without that
size does not report the metric."""
import re

from ..lib import trace


def fill(pattern: str, shapes: dict):
    """``pattern`` with every ``<key>`` replaced, or None if one is not a
    size of this cell."""
    keys = set(re.findall(r"<(\w+)>", pattern))
    if not keys <= set(shapes):
        return None
    for k in keys:
        pattern = pattern.replace(f"<{k}>", str(shapes[k]))
    return pattern


def read(obs, pattern=None, of="busy"):
    red = obs.get("trace")
    if not red:
        return None
    if of == "idle":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if of == "exposed_collective":
        return 100.0 * red["exposed_collective_s"] / red["window_s"]
    pattern = fill(pattern, obs.get("shapes", {}))
    if pattern is None:
        return None
    return 100.0 * trace.matching(red, pattern) / red["busy_s"]
