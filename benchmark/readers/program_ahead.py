"""Share (%) of the in-window dispatches that the serving loop enqueued
before it had read their predecessor's result (the engine's counter
``ahead_dispatches`` over ``dispatches``): 100 less one dispatch after
every drain.  A program whose loop keeps nothing in flight has no such
counter; the metric is then left out of the line."""
from ..lib import program


def read(obs):
    its = program.records(obs, "iterations")
    if its is None or "ahead_dispatches" not in its.dtype.names:
        return None
    dispatched = int(its["dispatches"].sum())
    if dispatched == 0:
        return None
    return 100.0 * float(its["ahead_dispatches"].sum()) / dispatched
