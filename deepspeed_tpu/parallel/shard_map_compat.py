"""The in-tree ``shard_map`` call.

Every shard_map call site goes through :func:`shard_map` below
(``dstpu-lint`` MESH004 enforces it), so the two choices the package
makes everywhere live in one place: regions name the mesh axes they are
MANUAL over (``axis_names``; the rest stay GSPMD-auto), and the
replication/VMA check is off unless a call site asks for it.
"""
from __future__ import annotations

from typing import Iterable, Optional

import jax


def shard_map(f, mesh, in_specs, out_specs,
              axis_names: Optional[Iterable[str]] = None,
              check: bool = False):
    """``jax.shard_map`` with the package defaults.

    ``mesh``: the device mesh, or ``None`` inside an enclosing
    ``shard_map`` — there the context mesh is the only legal one.
    ``axis_names``: the mesh axes the function is MANUAL over; ``None``
    — the ``jax.shard_map`` default — means every mesh axis.  A spec may
    only name manual axes.  ``check``: the replication/VMA consistency
    check (``check_vma``), off by default.
    """
    kwargs = {} if mesh is None else {"mesh": mesh}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check, **kwargs)
