"""A ratio (%) of the engine's own per-iteration counters over the
in-window iterations.  ``second_dispatch``: iterations that dispatched the
mixed program more than once, over all.  ``useful_rows``: rows that
carried a token (decoding slots + prompt-chunk tokens) over the rows the
program computed whatever rode."""
from ..lib import program


def read(obs, of):
    its = program.records(obs, "iterations")
    if its is None:
        return None
    if of == "second_dispatch":
        return 100.0 * float((its["dispatches"] > 1).mean())
    if of == "useful_rows":
        computed = int(its["rows_computed"].sum())
        if computed == 0:
            return None
        return 100.0 * float((its["decode_rows"]
                              + its["chunk_rows"]).sum()) / computed
    raise ValueError(f"program_count: no ratio {of!r}")
