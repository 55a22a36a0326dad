"""Work per second from the client's stamps of step ends (training steps
or serving iterations).  ``whole_steps``: all the whole steps between the
first and the last one that ended inside the window.  ``slice_median``: the
median of ten slice rates, where the runner counted the work of every step
(the serving iterations)."""
import numpy as np

from ..lib import stats


def read(obs, kind):
    st = obs.get("steps")
    if st is None:
        return None
    w0, w1 = obs["window"]
    if kind == "whole_steps":
        return stats.whole_step_rate(st["ends"], w0, w1, st["work"])
    if np.ndim(st["work"]) == 0:
        return None
    return stats.slice_median_rate(st["ends"], st["work"], w0, w1)
