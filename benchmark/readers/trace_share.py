"""From the trace: the own time of the device operations whose name matches
``pattern``, as a share (%) of the device's busy time; or one of the trace's
own shares of the traced window (``of`` = idle | exposed_collective)."""
from ..lib import trace


def read(obs, pattern=None, of="busy"):
    red = obs.get("trace")
    if not red:
        return None
    if of == "idle":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if of == "exposed_collective":
        return 100.0 * red["exposed_collective_s"] / red["window_s"]
    return 100.0 * trace.matching(red, pattern) / red["busy_s"]
