"""PALLAS — TPU kernel hazards in ``pallas_call`` kernels and wrappers.

The serving stack's worst bugs were kernel-shaped and mechanically
detectable: a Pallas name the installed jax had renamed broke 20 tests
(PR 5), and a masked ``0 × NaN`` v-row re-poisoned recycled KV blocks
until the zeroing convention (PR 6).  These rules pin both, plus the
accumulator/DMA disciplines the in-tree kernels follow:

  PALLAS001  a Pallas TPU name the installed jax has removed or
             deprecated: ``pltpu.TPUCompilerParams`` (now
             ``pltpu.CompilerParams``), ``pltpu.ANY`` (now ``pl.ANY``)
  PALLAS002  select-by-multiply on a boolean mask inside a kernel
             (``mask * v``) — masked rows give probability ~0 but
             ``0 * NaN = NaN``, so recycled-pool garbage poisons the
             accumulator; use ``jnp.where(mask, v, 0)``
  PALLAS003  non-f32 scratch accumulator (``pltpu.VMEM(..., bf16)``) —
             online-softmax state must accumulate in float32
  PALLAS004  ``jnp.pad`` inside a pallas_call wrapper — the pad copies
             the operand through HBM; ragged tails belong in the
             BlockSpec index_map (re-map past-the-end pages)
  PALLAS005  BlockSpec ``index_map`` reading mutable instance state
             (``self.*``) or calling impure host functions — the map is
             evaluated per grid step inside the compiled program; host
             state is baked at trace or crashes

Kernel detection: a function passed (directly or via
``functools.partial``) as ``pallas_call``'s first argument, or any
function with ≥ 2 ``*_ref`` parameters (the Pallas ref convention).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from .core import (Finding, Project, Severity, SourceModule,
                   callee_name as _callee_attr, enclosing_function,
                   get_symtab, src_of as _src)

#: retired ``pltpu`` attribute -> its replacement
_RETIRED = {"TPUCompilerParams": "pltpu.CompilerParams", "ANY": "pl.ANY"}
_ACC_BAD_DTYPES = {"bfloat16", "float16", "float8_e4m3fn", "float8_e5m2"}
#: call roots an index_map may use (pure, trace-safe index math)
_INDEX_OK_ROOTS = {"jnp", "jax", "lax", "pl", "pltpu"}
_INDEX_OK_BARE = {"min", "max", "abs", "divmod", "sum", "len"}


def _is_pallas_call(call: ast.Call) -> bool:
    return _callee_attr(call) == "pallas_call"


def _kernel_names_for(mod_calls: List[ast.Call]) -> Set[str]:
    """Function NAMES passed as pallas_call's first arg (bare or via
    functools.partial)."""
    out: Set[str] = set()
    for call in mod_calls:
        if not _is_pallas_call(call) or not call.args:
            continue
        a0 = call.args[0]
        if isinstance(a0, ast.Call) and \
                _callee_attr(a0) == "partial" and a0.args:
            a0 = a0.args[0]
        if isinstance(a0, ast.Name):
            out.add(a0.id)
        elif isinstance(a0, ast.Attribute):
            out.add(a0.attr)
    return out


def _is_kernel_fn(fn: ast.AST, kernel_names: Set[str]) -> bool:
    name = getattr(fn, "name", "")
    if name in kernel_names:
        return True
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args +
              fn.args.kwonlyargs]
    return sum(1 for p in params if p.endswith("_ref")) >= 2


# ---------------------------------------------------------------------------
# PALLAS001 — retired Pallas TPU names
# ---------------------------------------------------------------------------
def _check_retired_names(mod: SourceModule, symtab,
                         findings: List[Finding]) -> None:
    for node in symtab.attributes[mod.rel]:
        new = _RETIRED.get(node.attr)
        if new is None or not (isinstance(node.value, ast.Name)
                               and node.value.id == "pltpu"):
            continue
        findings.append(Finding(
            rule="PALLAS001", severity=Severity.ERROR, path=mod.rel,
            line=node.lineno, col=node.col_offset,
            message=f"`{_src(node)}` is retired in the installed jax "
                    f"— use `{new}`",
            scope=_scope_of(node), detail=node.attr))
    tgt = symtab.index(mod).from_imports.get("TPUCompilerParams")
    if tgt is not None:
        findings.append(Finding(
            rule="PALLAS001", severity=Severity.ERROR, path=mod.rel,
            line=1, col=0,
            message=f"importing `TPUCompilerParams` from {tgt[0]} — the "
                    f"installed jax exports `CompilerParams`",
            detail="import:TPUCompilerParams"))


def _scope_of(node: ast.AST) -> str:
    from .core import enclosing_scope
    return enclosing_scope(node)


# ---------------------------------------------------------------------------
# PALLAS002 — select-by-multiply on a mask inside a kernel
# ---------------------------------------------------------------------------
def _mask_names(fn: ast.AST) -> Set[str]:
    """Names bound (anywhere in the kernel, incl. the nested ``pl.when``
    bodies) to a boolean mask: a comparison, a boolean combination of
    comparisons, or ``.astype(...)`` of one."""
    def is_masky(e: ast.AST) -> bool:
        if isinstance(e, ast.Compare):
            return True
        if isinstance(e, ast.BoolOp):
            return all(is_masky(v) for v in e.values)
        if isinstance(e, ast.BinOp) and isinstance(
                e.op, (ast.BitAnd, ast.BitOr)):
            return is_masky(e.left) and is_masky(e.right)
        if isinstance(e, ast.Call) and _callee_attr(e) == "astype" and \
                isinstance(e.func, ast.Attribute):
            return is_masky(e.func.value)
        if isinstance(e, (ast.Subscript,)):
            return is_masky(e.value)
        return False

    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and is_masky(node.value):
            for t in node.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
    return out


def _check_select_by_multiply(mod: SourceModule, fn: ast.AST,
                              findings: List[Finding]) -> None:
    masks = _mask_names(fn)

    def is_mask_operand(e: ast.AST) -> bool:
        if isinstance(e, ast.Compare):
            return True
        if isinstance(e, ast.Name):
            return e.id in masks
        if isinstance(e, ast.Subscript):
            return is_mask_operand(e.value)
        if isinstance(e, ast.Call) and _callee_attr(e) == "astype" and \
                isinstance(e.func, ast.Attribute):
            return is_mask_operand(e.func.value)
        return False

    for node in ast.walk(fn):
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Mult)):
            continue
        for side in (node.left, node.right):
            if is_mask_operand(side):
                findings.append(Finding(
                    rule="PALLAS002", severity=Severity.ERROR,
                    path=mod.rel, line=node.lineno, col=node.col_offset,
                    message=f"select-by-multiply `{_src(node)}` in a "
                            f"Pallas kernel — masked rows make the "
                            f"factor 0 but 0*NaN=NaN, so recycled-pool "
                            f"garbage poisons the accumulator; use "
                            f"jnp.where(mask, v, 0)",
                    scope=f"{getattr(fn, 'name', '<kernel>')}",
                    detail=f"mult:{_src(side, 24)}"))
                break


# ---------------------------------------------------------------------------
# PALLAS003 — non-f32 scratch accumulators
# ---------------------------------------------------------------------------
def _check_scratch_dtypes(mod: SourceModule, call: ast.Call,
                          findings: List[Finding]) -> None:
    for node in ast.walk(call):
        if not isinstance(node, ast.keyword) or \
                node.arg != "scratch_shapes":
            continue
        for vm in ast.walk(node.value):
            if not (isinstance(vm, ast.Call)
                    and _callee_attr(vm) == "VMEM"
                    and len(vm.args) >= 2):
                continue
            dt = vm.args[1]
            dt_name = dt.attr if isinstance(dt, ast.Attribute) else \
                dt.id if isinstance(dt, ast.Name) else ""
            if dt_name in _ACC_BAD_DTYPES:
                findings.append(Finding(
                    rule="PALLAS003", severity=Severity.ERROR,
                    path=mod.rel, line=vm.lineno, col=vm.col_offset,
                    message=f"`{_src(vm)}` — scratch accumulators must "
                            f"be float32; accumulating online-softmax "
                            f"state in {dt_name} loses the low bits "
                            f"the recurrence depends on",
                    scope=_scope_of(vm), detail=dt_name))


# ---------------------------------------------------------------------------
# PALLAS004 — jnp.pad inside a pallas_call wrapper
# ---------------------------------------------------------------------------
def _check_wrapper_pads(mod: SourceModule, symtab,
                        findings: List[Finding]) -> None:
    wrappers = set()
    for call in symtab.calls[mod.rel]:
        if _is_pallas_call(call):
            fn = enclosing_function(call)
            if fn is not None:
                wrappers.add(fn)
    for fn in sorted(wrappers, key=lambda f: (f.lineno, f.col_offset)):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    symtab.dotted(node.func) in ("jnp.pad", "np.pad",
                                                 "jax.numpy.pad"):
                findings.append(Finding(
                    rule="PALLAS004", severity=Severity.WARNING,
                    path=mod.rel, line=node.lineno, col=node.col_offset,
                    message=f"`{_src(node)}` inside a pallas_call "
                            f"wrapper — the pad round-trips the operand "
                            f"through HBM; handle ragged tails in the "
                            f"BlockSpec index_map (re-map past-the-end "
                            f"pages to the last valid block)",
                    scope=fn.name, detail="pad"))


# ---------------------------------------------------------------------------
# PALLAS005 — index_map closures over mutable / host state
# ---------------------------------------------------------------------------
def _index_map_fns(mod: SourceModule, symtab) -> List[ast.AST]:
    """Functions passed as args to ``pl.BlockSpec(...)`` — lambdas
    inline, or local defs resolved by name within the module."""
    local_defs: Dict[str, ast.AST] = {
        f.name: f for f in symtab.functions[mod.rel]}
    out: List[ast.AST] = []
    for call in symtab.calls[mod.rel]:
        if _callee_attr(call) != "BlockSpec":
            continue
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(a, ast.Lambda):
                out.append(a)
            elif isinstance(a, ast.Name) and a.id in local_defs:
                out.append(local_defs[a.id])
    return out


def _check_index_maps(mod: SourceModule, symtab,
                      findings: List[Finding]) -> None:
    for fn in _index_map_fns(mod, symtab):
        name = getattr(fn, "name", "<lambda>")
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id in ("self", "cls"):
                findings.append(Finding(
                    rule="PALLAS005", severity=Severity.ERROR,
                    path=mod.rel, line=node.lineno, col=node.col_offset,
                    message=f"BlockSpec index_map `{name}` reads "
                            f"`{_src(node)}` — mutable instance state "
                            f"is baked in at trace time; pass it as a "
                            f"scalar-prefetch operand instead",
                    scope=name, detail=f"state:{_src(node, 24)}"))
            elif isinstance(node, ast.Call):
                dotted = symtab.dotted(node.func)
                root = dotted.split(".")[0] if dotted else ""
                if not dotted:
                    continue
                if root in _INDEX_OK_ROOTS or \
                        ("." not in dotted and dotted in _INDEX_OK_BARE):
                    continue
                findings.append(Finding(
                    rule="PALLAS005", severity=Severity.ERROR,
                    path=mod.rel, line=node.lineno, col=node.col_offset,
                    message=f"BlockSpec index_map `{name}` calls "
                            f"`{_src(node)}` — index maps run inside "
                            f"the compiled grid walk; only pure "
                            f"jnp/jax/pl index math is allowed",
                    scope=name, detail=f"call:{dotted}"))


def run(project: Project) -> List[Finding]:
    symtab = get_symtab(project)
    findings: List[Finding] = []
    for mod in project.modules:
        _check_retired_names(mod, symtab, findings)
        kernel_names = _kernel_names_for(symtab.calls[mod.rel])
        for fn in symtab.functions[mod.rel]:
            if _is_kernel_fn(fn, kernel_names):
                _check_select_by_multiply(mod, fn, findings)
        for call in symtab.calls[mod.rel]:
            if _is_pallas_call(call):
                _check_scratch_dtypes(mod, call, findings)
        _check_wrapper_pads(mod, symtab, findings)
        _check_index_maps(mod, symtab, findings)
    return findings
