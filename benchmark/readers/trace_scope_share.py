"""From the trace and the program's own table: the own time of the device
operations that the program books to ``scope``, as a share (%) of the
device's busy time.  A TPU event is named after its HLO instruction and
carries no layer name; the program declares its layers with
``jax.named_scope`` and ``get_overlap_profiler().program_scopes()`` reads
them back from the compiled text of the step programs it has loaded,
keyed by ``scope_key(instruction)``: the join is made here, after the
window.  ``scope`` is one of the program's ``SCOPES``, ``unnamed`` (no
declared scope, and feeding no single one), ``ambiguous`` (two loaded
programs disagree about the key) or ``recompute`` (booked to its scope
AND here: a backward pass's second run of its forward).  The scopes,
``unnamed`` and ``ambiguous`` sum to 100.  A program without
``program_scopes`` (a commit before it existed) or a run that kept no
trace gives None, and the metric is left out of the line."""
import time
from collections import defaultdict

from deepspeed_tpu.observability import overlap

RECOMPUTE = "recompute"


def booked(obs) -> dict:
    """``{scope: seconds}`` over the traced window, joined once a run and
    kept in ``obs``; the seconds the table took and every scope's share go
    into the line's ``diag``.  Empty where there is nothing to join."""
    if "scope_s" in obs:
        return obs["scope_s"]
    by = obs["scope_s"] = defaultdict(float)
    red = obs.get("trace")
    table_of = getattr(overlap.get_overlap_profiler(), "program_scopes",
                       None)
    if not red or table_of is None:
        return by
    began = time.perf_counter()
    table = table_of()
    build_s = time.perf_counter() - began
    if not table:
        return by
    nowhere = (overlap.UNNAMED, False)
    for name, seconds in red["op_s"].items():
        scope, recomputed = table.get(overlap.scope_key(name), nowhere)
        by[scope] += seconds
        if recomputed:
            by[RECOMPUTE] += seconds
    obs["diag"].update(
        scope_table_s=build_s, scope_table_keys=len(table),
        scope_share={k: 100.0 * v / red["busy_s"] for k, v in by.items()})
    return by


def read(obs, scope):
    by = booked(obs)
    if not by:
        return None
    return 100.0 * by[scope] / obs["trace"]["busy_s"]
