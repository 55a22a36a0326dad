"""A scalar the runner counted or timed (a counter, a set-up time)."""


def read(obs, key, scale=1.0):
    v = obs["values"].get(key)
    return None if v is None else v * scale
