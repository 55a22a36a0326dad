"""An exact order statistic of one of the client's stamp series."""
from ..lib import stats


def read(obs, series, q):
    s = obs["series"].get(series)
    return None if s is None or len(s) == 0 else stats.order_stat(s, q)
