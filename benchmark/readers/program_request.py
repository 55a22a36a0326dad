"""An exact order statistic (ms) of the time between two of the engine's
own stamps of a request (``submit_time``, ``admit_time``,
``first_token_time``, ``finish_time``), over the TERMINAL requests
submitted in the window: the engine records a request when it ends, so one
still live when the run's drain is cut is not in the statistic at all.  A
terminal request that never reached the later stamp (cancelled in the
queue) counts as infinitely late (``stats.INF_MS``)."""
import numpy as np

from ..lib import program, stats


def read(obs, start, end, q):
    reqs = program.records(obs, "requests")
    if reqs is None:
        return None
    ms = (reqs[end] - reqs[start]) * 1e3
    return stats.order_stat(np.where(np.isnan(ms), stats.INF_MS, ms), q)
