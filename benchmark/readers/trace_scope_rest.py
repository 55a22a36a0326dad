"""From the trace and the program's own table: the time the program books
to ``scope`` OUTSIDE the kernels whose name matches ``less`` — a lane of
the scope that runs as plain XLA operations and so has no kernel name of
its own to match (``trace_scope_share`` makes the join) — as a share (%)
of the device's busy time; or, with ``work``, the least time the chip
could take for the work the runner counted for that lane
(``trace_roofline``'s ``work``) over it.  A program without
``program_scopes``, a run that kept no trace, or a scope with nothing
outside those kernels gives None, and the metric is left out of the
line."""
from ..lib import trace
from . import trace_scope_share


def read(obs, scope, less, work=None):
    by = trace_scope_share.booked(obs)
    if not by:
        return None
    rest = by[scope] - trace.matching(obs["trace"], less)
    if rest <= 0:
        return None
    if work is None:
        return 100.0 * rest / obs["trace"]["busy_s"]
    need = obs.get("work", {}).get(work)
    return 100.0 * need["least_s"] / rest if need else None
