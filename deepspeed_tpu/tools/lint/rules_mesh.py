"""MESH — mesh/sharding discipline ahead of the multi-chip refactor.

ROADMAP item 1 spreads ``shard_map``/``NamedSharding`` across the whole
runtime; these rules make the conventions that refactor depends on
machine-checked BEFORE it lands:

  MESH001  ``shard_map``/``pjit`` without explicit ``in_specs`` AND
           ``out_specs`` (``in_shardings``/``out_shardings`` for pjit)
           — implicit specs silently replicate, and the first OOM at
           scale is days away from the cause
  MESH002  collective (``psum``/``pmean``/``ppermute``/...) with a
           string-literal axis name not declared in
           ``parallel/topology.py`` — a typo'd axis raises at trace
           time only on the code path that runs it
  MESH003  ``Mesh(...)`` constructed outside ``parallel/topology.py``
           — device order IS the topology contract (model innermost
           rides ICI); route through ``build_mesh``.  Hard-coded
           device-list literals upgrade the finding to error.
  MESH004  ``jax.shard_map`` attribute use or a ``shard_map`` import
           from jax outside ``parallel/shard_map_compat.py`` — the
           wrapper there holds the package's defaults (named manual
           axes, ``check_vma`` off), and ``jax.experimental.shard_map``
           is gone from the installed jax

MESH002's declared-axis set is parsed from the project's
``parallel/topology.py`` (``AXIS_ORDER`` elements + ``*_AXIS`` string
constants); when the project has no topology module the rule stays
silent rather than guessing.
"""
from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import (Finding, Project, Severity, SourceModule,
                   callee_name as _callee_name, enclosing_scope,
                   get_symtab, src_of as _src)

COMPAT_REL = "parallel/shard_map_compat.py"
TOPOLOGY_REL = "parallel/topology.py"

#: collective -> positional index of its axis-name argument
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "ppermute": 1,
    "all_gather": 1, "all_to_all": 1, "psum_scatter": 1,
    "pbroadcast": 1, "axis_index": 0, "axis_size": 0,
}


def declared_axes(project: Project) -> Optional[Set[str]]:
    """Axis names ``parallel/topology.py`` declares: the ``AXIS_ORDER``
    tuple elements plus every ``*_AXIS`` string constant.  ``None``
    when the project carries no topology module."""
    topo = project.by_rel(TOPOLOGY_REL)
    if topo is None:
        return None
    axes: Set[str] = set()
    for node in topo.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        name = node.targets[0].id
        value = node.value
        if name == "AXIS_ORDER" and isinstance(value, (ast.Tuple,
                                                       ast.List)):
            for e in value.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value,
                                                              str):
                    axes.add(e.value)
        elif name.endswith("_AXIS") and isinstance(value, ast.Constant) \
                and isinstance(value.value, str):
            axes.add(value.value)
    return axes


# ---------------------------------------------------------------------------
# MESH001 — shard_map/pjit without explicit specs
# ---------------------------------------------------------------------------
def _check_explicit_specs(mod: SourceModule, call: ast.Call,
                          findings: List[Finding]) -> None:
    name = _callee_name(call)
    kw = {k.arg for k in call.keywords}
    if name == "shard_map":
        have = ({"in_specs", "out_specs"} <= kw
                or len(call.args) >= 4)
    else:  # pjit
        have = ({"in_shardings", "out_shardings"} <= kw
                or {"in_specs", "out_specs"} <= kw
                or len(call.args) >= 3)
    if not have:
        findings.append(Finding(
            rule="MESH001", severity=Severity.ERROR, path=mod.rel,
            line=call.lineno, col=call.col_offset,
            message=f"`{name}` without explicit in/out specs — implicit "
                    f"specs silently replicate every operand; state the "
                    f"layout (in_specs=/out_specs=) so the mesh "
                    f"refactor can trust call sites",
            scope=enclosing_scope(call), detail=name))


# ---------------------------------------------------------------------------
# MESH002 — undeclared literal axis names in collectives
# ---------------------------------------------------------------------------
def _axis_literal(call: ast.Call, pos: int) -> Optional[ast.Constant]:
    for k in call.keywords:
        if k.arg == "axis_name":
            v = k.value
            return v if isinstance(v, ast.Constant) and \
                isinstance(v.value, str) else None
        # ``axis=`` is the INTEGER array axis on all_gather/all_to_all/
        # psum_scatter — only a string constant there is an axis NAME;
        # anything else must not mask the positional name check
        if k.arg == "axis" and isinstance(k.value, ast.Constant) and \
                isinstance(k.value.value, str):
            return k.value
    if pos < len(call.args):
        a = call.args[pos]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a
    return None


def _check_collective_axes(mod: SourceModule, call: ast.Call,
                           axes: Set[str],
                           findings: List[Finding]) -> None:
    name = _callee_name(call)
    lit = _axis_literal(call, _COLLECTIVES[name])
    if lit is None or lit.value in axes:
        return
    findings.append(Finding(
        rule="MESH002", severity=Severity.ERROR, path=mod.rel,
        line=lit.lineno, col=lit.col_offset,
        message=f"`{name}` over axis {lit.value!r}, which "
                f"parallel/topology.py does not declare "
                f"({', '.join(sorted(axes))}) — a typo'd axis raises "
                f"only on the code path that runs it",
        scope=enclosing_scope(call), detail=f"{name}:{lit.value}"))


# ---------------------------------------------------------------------------
# MESH003 — Mesh() outside the topology module
# ---------------------------------------------------------------------------
def _check_mesh_ctor(mod: SourceModule, call: ast.Call,
                     findings: List[Finding]) -> None:
    hardcoded = bool(call.args) and isinstance(
        call.args[0], (ast.List, ast.Tuple))
    findings.append(Finding(
        rule="MESH003",
        severity=Severity.ERROR if hardcoded else Severity.WARNING,
        path=mod.rel, line=call.lineno, col=call.col_offset,
        message=("Mesh(...) built from a hard-coded device list — "
                 if hardcoded else "direct Mesh(...) construction — ")
                + "device order IS the topology contract (model "
                  "innermost rides ICI neighbors); route through "
                  "parallel/topology.build_mesh",
        scope=enclosing_scope(call),
        detail="hardcoded" if hardcoded else "direct"))


# ---------------------------------------------------------------------------
# MESH004 — shard_map call sites bypassing the in-tree wrapper
# ---------------------------------------------------------------------------
def _check_shard_map_compat(mod: SourceModule, symtab,
                            findings: List[Finding]) -> None:
    for node in symtab.attributes[mod.rel]:
        if node.attr == "shard_map" and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "jax":
            findings.append(Finding(
                rule="MESH004", severity=Severity.ERROR, path=mod.rel,
                line=node.lineno, col=node.col_offset,
                message="direct `jax.shard_map` — route through "
                        "parallel/shard_map_compat.shard_map, which "
                        "holds the package's defaults",
                scope=enclosing_scope(node), detail="jax.shard_map"))
    idx = symtab.index(mod)
    seen: Set[str] = set()
    for _alias, (src, attr) in idx.from_imports.items():
        bypass = (src == "jax.experimental.shard_map"
                  or (attr == "shard_map"
                      and src in ("jax", "jax.experimental")))
        if not bypass or src in seen:
            continue
        seen.add(src)
        findings.append(Finding(
            rule="MESH004", severity=Severity.ERROR, path=mod.rel,
            line=1, col=0,
            message=f"importing shard_map from `{src}` — route "
                    f"through parallel/shard_map_compat.shard_map",
            detail=f"import:{src}"))


#: sentinel: ``run(project)`` computes the axes itself; the incremental
#: engine passes the context's set (possibly None) explicitly, because a
#: single-module project cannot see ``parallel/topology.py``
_AXES_UNSET = object()


def run(project: Project, axes=_AXES_UNSET) -> List[Finding]:
    symtab = get_symtab(project)
    if axes is _AXES_UNSET:
        axes = declared_axes(project)
    findings: List[Finding] = []
    for mod in project.modules:
        in_compat = mod.rel.endswith(COMPAT_REL)
        in_topo = mod.rel.endswith(TOPOLOGY_REL)
        for call in symtab.calls[mod.rel]:
            name = _callee_name(call)
            if name in ("shard_map", "pjit") and not in_compat:
                _check_explicit_specs(mod, call, findings)
            if name in _COLLECTIVES and axes is not None:
                _check_collective_axes(mod, call, axes, findings)
            if name == "Mesh" and not in_topo:
                _check_mesh_ctor(mod, call, findings)
        if not in_compat:
            _check_shard_map_compat(mod, symtab, findings)
    return findings
