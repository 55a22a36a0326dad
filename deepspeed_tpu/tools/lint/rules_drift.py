"""DRIFT — cross-artifact drift between code, docs and CI scripts.

The repo's contract surfaces live in three kinds of artifact that
nothing ties together: metric names registered in code vs the docs
tables operators grep, fault-injection sites vs the chaos matrices that
sweep them, and config keys vs the constants and reference tables that
declare them.  Each pair drifts silently — a gauge can stay registered
for two PRs before any docs table mentions it.  These
rules generalize LIFE003's doc-catalog check into a reconciler driven
by the PR 7 symbol table:

  DRIFT001  metric registered in code (literal, f-string template, or
            ``tenant_metric_name`` call shape — dynamic segments match
            any token) with no row in any docs table
  DRIFT002  ``dstpu_*`` name in a docs table that no code path
            registers — a dashboard built from that row reads zeros
  DRIFT003  ``FaultInjector.check`` site missing from the documented
            catalog (docs/resilience.md) or from every ``run_tests.sh``
            chaos matrix — an unswept failure path (subsumes LIFE003)
  DRIFT004  ``serving.*`` / ``observability.*`` config key drift: a key
            consumed by the config dataclasses without a docs
            config-table row or without a ``*_DEFAULT`` constant, and a
            documented key no dataclass consumes

Templated names use ``*`` for dynamic segments on both sides: code
``f"dstpu_comm_volume_bytes_{op}"`` becomes ``dstpu_comm_volume_bytes_*``
and the docs placeholder ``dstpu_comm_volume_bytes_<op>`` becomes the
same; either side's wildcard matches one-or-more characters of the
other.

The family is assembly-shaped: per-module extraction (cached by the
incremental engine) plus a cheap global pass over docs/ and
run_tests.sh each run.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import (Finding, Project, Severity, SourceModule,
                   callee_name as _callee_name, enclosing_function,
                   enclosing_scope, get_symtab)
from .rules_life import SITE_DOC, _injector_site

DOCS_DIR = "docs"
CHAOS_SCRIPT = "run_tests.sh"

#: registry kinds whose first argument is a metric name
_METRIC_KINDS = ("counter", "gauge", "histogram")

#: marker comment --fix appends DRIFT001 row stubs under (docs side)
METRICS_TABLE_MARK = "<!-- dstpu-lint: metrics-table -->"

_METRIC_TOKEN_RE = re.compile(r"^dstpu_[a-z0-9_*]+$")
_CONFIG_KEY_RE = re.compile(r"^[a-z_][a-z0-9_]*(\.[a-z0-9_]+)+$")
_BACKTICK_RE = re.compile(r"`([^`\s]+)`")
_PLACEHOLDER_RE = re.compile(r"<[^<>]*>")
_CHAOS_SITE_RE = re.compile(
    r"([a-z_][a-z0-9_.]*)=(?:fail|fatal|truncate|delay|kill)\b")

#: config-tree anchors: dataclass name -> dotted docs prefix
CONFIG_ANCHORS = {"ServingConfig": "serving",
                  "ObservabilityConfig": "observability"}


# ---------------------------------------------------------------------------
# per-module extraction — all outputs JSON-serializable for the engine
# ---------------------------------------------------------------------------
class _MetricResolver:
    """Resolve a registry call's first argument to a name template.

    Handles literals, f-strings (dynamic segments become ``*``),
    ``tenant_metric_name(...)`` call shapes, local-name indirection
    (``base = tenant_metric_name(...); reg.gauge(f"{base}_x")``) and
    one level of same-class method return chains
    (``self._series(...)`` returning a template).
    """

    def __init__(self, mod: SourceModule):
        self.mod = mod
        self.methods: Dict[str, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.methods.setdefault(node.name, node)

    def resolve(self, node: ast.AST, fn: Optional[ast.AST],
                depth: int = 0) -> Optional[str]:
        if depth > 4:
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        if isinstance(node, ast.JoinedStr):
            parts: List[str] = []
            for v in node.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                elif isinstance(v, ast.FormattedValue):
                    inner = self.resolve(v.value, fn, depth + 1)
                    parts.append(inner if inner is not None else "*")
            return "".join(parts)
        if isinstance(node, ast.Call):
            if _callee_name(node) == "tenant_metric_name":
                segs: List[str] = []
                for a in node.args:
                    s = self.resolve(a, fn, depth + 1)
                    segs.append(s if s is not None and "*" not in s
                                else "*")
                return "_".join(segs) if segs else None
            if isinstance(node.func, ast.Attribute) and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id == "self":
                meth = self.methods.get(node.func.attr)
                if meth is not None:
                    return self._method_return(meth, depth + 1)
            return None
        if isinstance(node, ast.Name) and fn is not None:
            for stmt in ast.walk(fn):
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name) and \
                        stmt.targets[0].id == node.id:
                    return self.resolve(stmt.value, fn, depth + 1)
        return None

    def _method_return(self, meth: ast.AST, depth: int) -> Optional[str]:
        for node in ast.walk(meth):
            if isinstance(node, ast.Return) and node.value is not None:
                # method params are dynamic by definition: resolve with
                # fn=None so bare names fall back to wildcards
                got = self.resolve(node.value, None, depth)
                if got is not None:
                    return got
        return None


def _registryish(recv: ast.AST) -> bool:
    if isinstance(recv, ast.Call):
        name = _callee_name(recv)
    elif isinstance(recv, ast.Attribute):
        name = recv.attr
    elif isinstance(recv, ast.Name):
        name = recv.id
    else:
        return False
    low = name.lower()
    return "registry" in low or low in ("reg", "obs", "metrics")


def extract_metrics(mod: SourceModule, symtab) -> List[List[object]]:
    """[[name-template, line, col, scope], ...] for one module."""
    out: List[List[object]] = []
    resolver = _MetricResolver(mod)
    for call in symtab.calls[mod.rel]:
        f = call.func
        if not (isinstance(f, ast.Attribute) and f.attr in _METRIC_KINDS
                and call.args and _registryish(f.value)):
            continue
        name = resolver.resolve(call.args[0], enclosing_function(call))
        if name is None or not name.startswith("dstpu_"):
            continue
        out.append([name, call.lineno, call.col_offset,
                    enclosing_scope(call)])
    # pre-registered core metrics: module-level literal tuples of
    # (kind, name, help) — the observability package's warm-up list
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "_CORE_METRICS"
                and isinstance(node.value, (ast.Tuple, ast.List))):
            continue
        for entry in node.value.elts:
            if isinstance(entry, (ast.Tuple, ast.List)) and \
                    len(entry.elts) >= 2 and \
                    isinstance(entry.elts[1], ast.Constant) and \
                    isinstance(entry.elts[1].value, str):
                out.append([entry.elts[1].value, entry.elts[1].lineno,
                            entry.elts[1].col_offset, "_CORE_METRICS"])
    return out


def extract_sites(mod: SourceModule, symtab) -> List[List[object]]:
    """[[site, line, col, scope], ...] — FaultInjector.check sites."""
    out: List[List[object]] = []
    for call in symtab.calls[mod.rel]:
        lit = _injector_site(call)
        if lit is None:
            continue
        out.append([lit.value, lit.lineno, lit.col_offset,
                    enclosing_scope(call)])
    return out


def _default_const(value: Optional[ast.AST]) -> Optional[str]:
    if isinstance(value, ast.Attribute) and \
            value.attr.endswith("_DEFAULT"):
        return value.attr
    if isinstance(value, ast.Name) and value.id.endswith("_DEFAULT"):
        return value.id
    return None


def _factory_class(value: Optional[ast.AST]) -> Optional[str]:
    if isinstance(value, ast.Call) and _callee_name(value) == "field":
        for kw in value.keywords:
            if kw.arg == "default_factory" and \
                    isinstance(kw.value, ast.Name):
                return kw.value.id
    return None


def extract_config_classes(mod: SourceModule
                           ) -> Dict[str, List[Dict[str, object]]]:
    """class name -> ordered field facts, for modules named config.py.
    Field fact: {name, line, ann, factory, const} where ``ann``/
    ``factory`` name a possibly-nested config class and ``const`` is the
    ``*_DEFAULT`` default when the field is a leaf key."""
    if not mod.rel.endswith("config.py"):
        return {}
    out: Dict[str, List[Dict[str, object]]] = {}
    for node in mod.tree.body:
        if not isinstance(node, ast.ClassDef):
            continue
        fields: List[Dict[str, object]] = []
        for stmt in node.body:
            if not (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                continue
            ann = stmt.annotation
            ann_name = ann.id if isinstance(ann, ast.Name) else None
            fields.append({
                "name": stmt.target.id, "line": stmt.lineno,
                "ann": ann_name,
                "factory": _factory_class(stmt.value),
                "const": _default_const(stmt.value),
            })
        if fields:
            out[node.name] = fields
    return out


# ---------------------------------------------------------------------------
# docs / script parsing (assembly-time; cheap enough to redo every run)
# ---------------------------------------------------------------------------
def _doc_files(root: str) -> List[str]:
    d = os.path.join(root, DOCS_DIR)
    if not os.path.isdir(d):
        return []
    return [os.path.join(d, fn) for fn in sorted(os.listdir(d))
            if fn.endswith(".md")]


def _table_rows(path: str):
    """(lineno, line) for markdown table rows (skips separator rows)."""
    with open(path, "r", encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            s = line.strip()
            if s.startswith("|") and not set(s) <= set("|-: "):
                yield i, s


def docs_metric_rows(root: str) -> List[Tuple[str, str, int]]:
    """(template, docs rel path, line) per backticked ``dstpu_*`` table
    token; ``<placeholder>`` segments become ``*`` wildcards."""
    out: List[Tuple[str, str, int]] = []
    for path in _doc_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                s = line.strip()
                if not (s.startswith("|") and not set(s) <= set("|-: ")):
                    continue
                for raw in re.findall(r"`([^`]+)`", s):
                    tok = _PLACEHOLDER_RE.sub("*", raw)
                    if _METRIC_TOKEN_RE.match(tok):
                        out.append((tok, rel, i))
    return out


def docs_config_rows(root: str) -> List[Tuple[str, str, int]]:
    """(dotted key, docs rel, line) for config-table rows; keys in
    observability.md are written relative to the ``observability``
    block and get the prefix applied; only ``serving.*`` /
    ``observability.*`` keys participate in DRIFT004."""
    out: List[Tuple[str, str, int]] = []
    for path in _doc_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        is_obs_doc = os.path.basename(path) == "observability.md"
        for i, s in _table_rows(path):
            cells = s.strip("|").split("|")
            if not cells:
                continue
            # keys live in the first column; backticked keys in
            # description cells are cross-references, not declarations
            for raw in _BACKTICK_RE.findall(cells[0]):
                if not _CONFIG_KEY_RE.match(raw):
                    continue
                key = raw
                if not key.startswith(("serving.", "observability.")):
                    if not is_obs_doc:
                        continue
                    key = f"observability.{key}"
                out.append((key, rel, i))
    return out


def chaos_plan_sites(root: str) -> Optional[Set[str]]:
    """Sites named by any ``site=kind`` fault plan in run_tests.sh;
    None when the script is absent (fixture projects)."""
    path = os.path.join(root, CHAOS_SCRIPT)
    if not os.path.isfile(path):
        return None
    with open(path, "r", encoding="utf-8") as f:
        return {m.group(1) for m in _CHAOS_SITE_RE.finditer(f.read())}


def documented_site_catalog(root: str) -> Optional[Set[str]]:
    from .rules_life import documented_sites
    return documented_sites(root)


# ---------------------------------------------------------------------------
# wildcard matching
# ---------------------------------------------------------------------------
def _wild_regex(template: str) -> "re.Pattern[str]":
    return re.compile(
        ".+".join(re.escape(part) for part in template.split("*")))


def _wild_match(a: str, b: str) -> bool:
    """Template match in either direction: each side's ``*`` consumes
    one-or-more characters of the other."""
    if "*" not in a and "*" not in b:
        return a == b
    probe_a = a.replace("*", "\x00w\x00")
    probe_b = b.replace("*", "\x00w\x00")
    return bool(_wild_regex(b).fullmatch(probe_a)
                or _wild_regex(a).fullmatch(probe_b))


def _matched(name: str, pool: List[str]) -> bool:
    return any(_wild_match(name, other) for other in pool)


# ---------------------------------------------------------------------------
# assembly
# ---------------------------------------------------------------------------
def _resolve_config_keys(
        config_facts: Dict[str, Dict[str, List[Dict[str, object]]]]
) -> List[Tuple[str, Optional[str], str, int]]:
    """Flatten the anchored config trees: (dotted key, const, rel,
    line) per leaf field reachable from a CONFIG_ANCHORS class."""
    classes: Dict[str, List[Dict[str, object]]] = {}
    owner: Dict[str, str] = {}
    for rel in sorted(config_facts):
        for cls, fields in config_facts[rel].items():
            if cls not in classes:
                classes[cls] = fields
                owner[cls] = rel
    out: List[Tuple[str, Optional[str], str, int]] = []

    def walk(cls: str, prefix: str, seen: Tuple[str, ...]) -> None:
        if cls in seen:
            return
        for fld in classes.get(cls, []):
            nested = None
            for cand in (fld.get("ann"), fld.get("factory")):
                if isinstance(cand, str) and cand in classes:
                    nested = cand
                    break
            key = f"{prefix}.{fld['name']}"
            if nested is not None:
                walk(nested, key, seen + (cls,))
            else:
                out.append((key, fld.get("const"), owner[cls],
                            int(fld["line"])))

    for cls, prefix in sorted(CONFIG_ANCHORS.items()):
        if cls in classes:
            walk(cls, prefix, ())
    return out


def assemble(root: str,
             metric_facts: Dict[str, List[List[object]]],
             site_facts: Dict[str, List[List[object]]],
             config_facts: Dict[str, Dict[str, List[Dict[str, object]]]]
             ) -> List[Finding]:
    findings: List[Finding] = []

    # -- DRIFT001/002: metrics <-> docs tables -------------------------
    doc_rows = docs_metric_rows(root)
    doc_names = [n for n, _rel, _ln in doc_rows]
    code_entries: List[Tuple[str, str, int, int, str]] = []
    for rel in sorted(metric_facts):
        for name, line, col, scope in metric_facts[rel]:
            code_entries.append((str(name), rel, int(line), int(col),
                                 str(scope)))
    code_names = [e[0] for e in code_entries]
    if doc_rows or not os.path.isdir(os.path.join(root, DOCS_DIR)):
        reported: Set[str] = set()
        if os.path.isdir(os.path.join(root, DOCS_DIR)):
            for name, rel, line, col, scope in code_entries:
                if name in reported or _matched(name, doc_names):
                    continue
                reported.add(name)
                findings.append(Finding(
                    rule="DRIFT001", severity=Severity.WARNING, path=rel,
                    line=line, col=col,
                    message=f"metric `{name}` is registered here but "
                            f"appears in no docs table — operators "
                            f"cannot discover it and dashboards drift "
                            f"from code (add a row, or run --fix for a "
                            f"stub)",
                    scope=scope, detail=name))
        # docs->code direction only when the linted project registers
        # metrics at all: a partial run (self-lint, --rules subsets over
        # one directory) cannot prove a docs row has no registrar
        reported_docs: Set[str] = set()
        for name, rel, line in (doc_rows if code_entries else []):
            if name in reported_docs or _matched(name, code_names):
                continue
            reported_docs.add(name)
            findings.append(Finding(
                rule="DRIFT002", severity=Severity.WARNING, path=rel,
                line=line, col=0,
                message=f"docs table names metric `{name}` but no code "
                        f"path registers it — a dashboard built from "
                        f"this row reads zeros forever",
                detail=name))

    # -- DRIFT003: fault sites <-> resilience.md + chaos matrices ------
    catalog = documented_site_catalog(root)
    chaos = chaos_plan_sites(root)
    seen_sites: Set[str] = set()
    for rel in sorted(site_facts):
        for site, line, col, scope in site_facts[rel]:
            site = str(site)
            if site in seen_sites:
                continue
            seen_sites.add(site)
            missing: List[str] = []
            if catalog is not None and site not in catalog:
                missing.append(f"the documented catalog ({SITE_DOC})")
            if chaos is not None and site not in chaos:
                missing.append(f"every {CHAOS_SCRIPT} chaos matrix")
            if not missing:
                continue
            findings.append(Finding(
                rule="DRIFT003", severity=Severity.WARNING, path=rel,
                line=int(line), col=int(col),
                message=f"fault-injection site {site!r} is missing from "
                        f"{' and from '.join(missing)} — an unlisted "
                        f"site is a failure path CI never sweeps",
                scope=str(scope), detail=site))

    # -- DRIFT004: config keys <-> constants <-> docs tables -----------
    code_keys = _resolve_config_keys(config_facts)
    doc_keys = docs_config_rows(root)
    doc_key_set = {k for k, _rel, _ln in doc_keys}
    code_key_set = {k for k, _c, _rel, _ln in code_keys}
    if code_keys:
        for key, const, rel, line in code_keys:
            if const is None:
                findings.append(Finding(
                    rule="DRIFT004", severity=Severity.WARNING, path=rel,
                    line=line, col=0,
                    message=f"config key `{key}` has no *_DEFAULT "
                            f"constant — the schema default lives only "
                            f"in this dataclass field, invisible to "
                            f"constants.py and to CFG002's dead-default "
                            f"check",
                    detail=f"no-constant:{key}"))
            if doc_keys and key not in doc_key_set:
                findings.append(Finding(
                    rule="DRIFT004", severity=Severity.WARNING, path=rel,
                    line=line, col=0,
                    message=f"config key `{key}` has no docs "
                            f"config-table row — a knob users cannot "
                            f"discover is schema drift",
                    detail=f"undocumented:{key}"))
        reported_keys: Set[str] = set()
        for key, rel, line in doc_keys:
            if key in reported_keys or key in code_key_set:
                continue
            reported_keys.add(key)
            findings.append(Finding(
                rule="DRIFT004", severity=Severity.WARNING, path=rel,
                line=line, col=0,
                message=f"docs config table names `{key}` but no "
                        f"config dataclass consumes it — users who set "
                        f"this key get a silent no-op",
                detail=f"stale-doc:{key}"))
    return findings


def run(project: Project) -> List[Finding]:
    symtab = get_symtab(project)
    metric_facts: Dict[str, List[List[object]]] = {}
    site_facts: Dict[str, List[List[object]]] = {}
    config_facts: Dict[str, Dict[str, List[Dict[str, object]]]] = {}
    for mod in project.modules:
        metrics = extract_metrics(mod, symtab)
        if metrics:
            metric_facts[mod.rel] = metrics
        sites = extract_sites(mod, symtab)
        if sites:
            site_facts[mod.rel] = sites
        cfg = extract_config_classes(mod)
        if cfg:
            config_facts[mod.rel] = cfg
    return assemble(project.root, metric_facts, site_facts, config_facts)
