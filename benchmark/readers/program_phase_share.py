"""Share (%) of the serving loop's time that one phase of the engine's
iteration took, by the engine's own marks: the phase's summed seconds over
the span from the first in-window iteration's begin to the last one's end.
``phase`` is one of the engine's five (plan, operands, enqueue,
device_wait, apply) or ``outside``: the same span less all iterations,
which is the caller between ``step()``s.  The six sum to 100."""
from ..lib import program


def read(obs, phase):
    its = program.records(obs, "iterations")
    if its is None:
        return None
    span = its["end_s"][-1] - its["begin_s"][0]
    if span <= 0:
        return None
    if phase == "outside":
        spent = span - (its["end_s"] - its["begin_s"]).sum()
    else:
        spent = its[phase + "_s"].sum()
    return 100.0 * float(spent) / float(span)
