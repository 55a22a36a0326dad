"""Share of the window spent in iterations over ``factor`` x the median."""
from ..lib import stats


def read(obs, factor=3.0):
    st = obs.get("steps")
    if st is None or "starts" not in st:
        return None
    return stats.stall_share(st["starts"], st["ends"], *obs["window"], factor)
