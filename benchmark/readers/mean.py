"""The mean of one of the client's stamp series: every sample counts."""


def read(obs, series):
    s = obs["series"].get(series)
    return None if s is None or len(s) == 0 else float(s.mean())
