"""dstpu-lint CLI.

Exit codes: 0 = clean (no findings beyond the baseline), 1 = new
findings, 2 = usage / internal error. See ``docs/lint.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from .baseline import Baseline
from .core import Finding

FAMILIES = ("SYNC", "TRACE", "LOCK", "CFG", "TEST", "PALLAS", "MESH",
            "LIFE", "DET", "FLEET", "DRIFT")

RULE_CATALOG = {
    "SYNC001": "`.item()` device→host sync in a hot path",
    "SYNC002": "float()/int() of a computed value in a hot path",
    "SYNC003": "np.asarray/device_get/block_until_ready not routed "
               "through host_transfer()",
    "TRACE001": "Python if/while on a traced value in a jitted function",
    "TRACE002": "impure host call (time/np.random/...) baked in at trace",
    "TRACE003": "jax.jit constructed per call (immediate call / in-loop)",
    "TRACE004": "unhashable literal in a static_argnums position",
    "LOCK001": "attribute mutated without the lock that guards it "
               "elsewhere",
    "LOCK002": "lock-acquisition-order inversion",
    "LOCK003": "thread neither daemon=True nor joined",
    "CFG001": "config key constant consumed nowhere",
    "CFG002": "*_DEFAULT constant consumed nowhere",
    "CFG003": "raw string config key not declared in constants.py",
    "TEST001": "pytest marker not registered in pytest.ini",
    "PALLAS001": "Pallas TPU name the installed jax retired "
                 "(TPUCompilerParams, pltpu.ANY)",
    "PALLAS002": "select-by-multiply on a mask in a kernel (0*NaN "
                 "poison) — use jnp.where(mask, v, 0)",
    "PALLAS003": "non-f32 scratch accumulator in a pallas_call kernel",
    "PALLAS004": "jnp.pad inside a pallas_call wrapper",
    "PALLAS005": "BlockSpec index_map reads mutable state / calls host "
                 "functions",
    "MESH001": "shard_map/pjit without explicit in_specs/out_specs",
    "MESH002": "collective over an axis name topology.py does not "
               "declare",
    "MESH003": "Mesh(...) constructed outside parallel/topology.py",
    "MESH004": "shard_map call site bypassing "
               "parallel/shard_map_compat",
    "LIFE001": "allocator allocate/fork with no reachable free",
    "LIFE002": "terminal RequestStatus stamped outside _terminalize()",
    "DET001": "ad-hoc randomness (random.*/np.random/unpinned PRNGKey) "
              "in serving code",
    "DET002": "set iterated into an order-sensitive sink "
              "(digest/score/ordering) — wrap in sorted()",
    "DET003": "wall-clock read in a function with an injectable clock "
              "parameter",
    "DET004": "dict .values()/.items() iteration that mutates the dict "
              "mid-loop",
    "DRIFT001": "metric registered in code but absent from every docs "
                "table",
    "DRIFT002": "metric named in a docs table that no code registers",
    "DRIFT003": "FaultInjector site missing from docs/resilience.md or "
                "the run_tests.sh chaos matrices (subsumes LIFE003)",
    "DRIFT004": "serving.*/observability.* config key drift between "
                "dataclasses, constants and docs tables",
    "FLEET001": "ReplicaState transition not guarded per _TRANSITIONS",
    "FLEET002": "terminal ReplicaState stamped outside the lifecycle "
                "owner",
}


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dstpu-lint",
        description="AST-based TPU-hazard & concurrency static analyzer "
                    "for deepspeed_tpu (stdlib-only; see docs/lint.md)")
    p.add_argument("paths", nargs="*",
                   help="files/directories to lint (default: the "
                        "deepspeed_tpu package under --root)")
    p.add_argument("--root", default=None,
                   help="repo root findings are reported relative to "
                        "(default: cwd)")
    p.add_argument("--baseline", default=None,
                   help="baseline JSON; only findings beyond it fail "
                        "(default: <root>/lint_baseline.json when "
                        "present)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline; report and fail on every "
                        "finding")
    p.add_argument("--write-baseline", action="store_true",
                   help="write the current findings to the baseline "
                        "file and exit 0")
    p.add_argument("--check-markers", action="store_true",
                   help="also verify pytest markers used under "
                        "<root>/tests are registered in pytest.ini")
    p.add_argument("--tests-dir", default=None,
                   help="tests directory for --check-markers")
    p.add_argument("--pytest-ini", default=None,
                   help="pytest.ini path for --check-markers")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule/family prefixes to keep "
                        "(e.g. SYNC,LOCK001)")
    p.add_argument("--min-severity", default=None,
                   choices=("info", "warning", "error"),
                   help="drop findings below this severity tier")
    p.add_argument("--sarif", default=None, metavar="PATH",
                   help="also write the findings as SARIF 2.1.0 "
                        "(baselined findings marked suppressed) — the "
                        "CI artifact forges annotate diffs from")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--quiet", "-q", action="store_true",
                   help="suppress the grandfathered-finding lines "
                        "(printed by default so the report always "
                        "carries rule IDs and file:line)")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--no-cache", action="store_true",
                   help="bypass the incremental cache: full re-analysis, "
                        "nothing read or written")
    p.add_argument("--cache-file", default=None, metavar="PATH",
                   help="incremental cache location (default: "
                        "<root>/.dstpu_lint_cache.json)")
    p.add_argument("--changed", action="store_true",
                   help="report only findings in files changed vs HEAD "
                        "(git diff + untracked); analysis still covers "
                        "everything so cross-file rules stay sound")
    p.add_argument("--fix", action="store_true",
                   help="apply mechanical autofixes (DET002 sorted() "
                        "wrap, DRIFT001 docs-row stubs), then re-lint")
    return p


def _summary_line(findings: List[Finding], new: List[Finding],
                  dt: float, cache_note: str = "") -> str:
    per_family = {fam: [0, 0] for fam in FAMILIES}
    for f in findings:
        per_family.setdefault(f.family, [0, 0])
        per_family[f.family][0] += 1
    for f in new:
        per_family.setdefault(f.family, [0, 0])
        per_family[f.family][1] += 1
    fams = "  ".join(
        f"{fam}: {tot} ({nw} new)"
        for fam, (tot, nw) in per_family.items())
    return (f"dstpu-lint: {len(findings)} finding(s), "
            f"{len(new)} new, {len(findings) - len(new)} baselined "
            f"[{dt:.1f}s{cache_note}]\n  {fams}")


def main(argv: Optional[List[str]] = None) -> int:
    args = _build_parser().parse_args(argv)
    if args.list_rules:
        for rule, desc in sorted(RULE_CATALOG.items()):
            print(f"{rule}  {desc}")
        return 0

    root = os.path.abspath(args.root or os.getcwd())
    paths = args.paths
    if not paths:
        default = os.path.join(root, "deepspeed_tpu")
        if not os.path.isdir(default):
            print("dstpu-lint: no paths given and no deepspeed_tpu/ "
                  f"under {root}", file=sys.stderr)
            return 2
        paths = [default]
    for p in paths:
        if not os.path.exists(p):
            print(f"dstpu-lint: no such path: {p}", file=sys.stderr)
            return 2

    baseline_path = args.baseline
    if baseline_path is not None and not args.write_baseline and \
            not os.path.isfile(baseline_path):
        # an explicit path that doesn't exist is a usage error (likely a
        # typo in a CI config) — treating it as an empty baseline would
        # report every grandfathered finding as NEW and misdirect the
        # developer away from the real cause
        print(f"dstpu-lint: baseline not found: {baseline_path}",
              file=sys.stderr)
        return 2
    if baseline_path is None and not args.no_baseline:
        cand = os.path.join(root, "lint_baseline.json")
        if os.path.isfile(cand):
            baseline_path = cand
    if args.write_baseline and not baseline_path:
        baseline_path = os.path.join(root, "lint_baseline.json")

    rules = None
    if args.rules:
        if args.write_baseline:
            # a rule-filtered run sees only a slice of the findings —
            # writing it would silently drop every other family's
            # grandfathered entries and break the ratchet
            print("dstpu-lint: --write-baseline cannot be combined with "
                  "--rules (the baseline must cover every family)",
                  file=sys.stderr)
            return 2
        rules = tuple(r.strip() for r in args.rules.split(",")
                      if r.strip())

    from .engine import EngineStats, changed_paths, lint_paths_cached

    def _run() -> Optional[List[Finding]]:
        errors: List[str] = []
        try:
            got = lint_paths_cached(
                paths, root=root, rules=rules,
                check_markers=args.check_markers,
                tests_dir=args.tests_dir, pytest_ini=args.pytest_ini,
                errors=errors, min_severity=args.min_severity,
                cache_file=args.cache_file, no_cache=args.no_cache,
                stats=stats)
        except RecursionError as e:  # pragma: no cover - pathological
            print(f"dstpu-lint: internal error: {e}", file=sys.stderr)
            return None
        if errors:
            # an unparsable file is unanalyzed coverage: its hazards AND
            # its baselined findings silently vanish — that must fail
            # the gate, not shrink it
            for err in errors:
                print(f"dstpu-lint: cannot parse: {err}", file=sys.stderr)
            return None
        return got

    t0 = time.perf_counter()
    stats = EngineStats()
    findings = _run()
    if findings is None:
        return 2

    if args.fix and findings:
        from .fixes import apply_fixes
        fixed = apply_fixes(root, findings)
        for rel in sorted(fixed):
            print(f"dstpu-lint: fixed {fixed[rel]} finding(s) in {rel}")
        if fixed:
            findings = _run()  # re-lint: fixes changed content hashes
            if findings is None:
                return 2

    if args.changed:
        changed = changed_paths(root)
        if changed is None:
            print("dstpu-lint: --changed needs git; reporting all "
                  "findings", file=sys.stderr)
        else:
            findings = [f for f in findings if f.path in changed]
    dt = time.perf_counter() - t0

    if args.write_baseline:
        Baseline.from_findings(findings).save(baseline_path)
        print(f"dstpu-lint: wrote {len(findings)} finding(s) to "
              f"{baseline_path}")
        return 0

    if args.no_baseline or not baseline_path:
        new, old = findings, []
    else:
        try:
            bl = Baseline.load(baseline_path)
        except (ValueError, OSError) as e:
            print(f"dstpu-lint: {e}", file=sys.stderr)
            return 2
        new, old = bl.split(findings)

    if args.sarif:
        from .sarif import write_sarif
        write_sarif(args.sarif, new, old, RULE_CATALOG)

    if args.format == "json":
        print(json.dumps({
            "new": [vars(f) for f in new],
            "baselined": [vars(f) for f in old],
            "elapsed_s": round(dt, 3),
        }, indent=2))
        return 1 if new else 0

    for f in new:
        print(f"NEW  {f.render()}")
    if not args.quiet:
        for f in old:
            print(f"base {f.render()}")
    cache_note = ""
    if stats.total_modules:
        cache_note = (f", {stats.reanalyzed}/{stats.total_modules} "
                      f"analyzed")
    print(_summary_line(findings, new, dt, cache_note))
    if new:
        print("dstpu-lint: FAIL — fix the new findings above, suppress "
              "a deliberate one with `# dstpu: ignore[RULE]`, or "
              "regenerate the baseline (--write-baseline) with a "
              "reviewer's sign-off.")
    return 1 if new else 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
