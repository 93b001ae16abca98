"""graftcheck CLI: `python -m midgpt_tpu.analysis [paths...] [options]`.

Exit status: 0 when no active findings (and, with --audit, every audit
passes); 1 otherwise. Default output is one `path:line:col: GCnnn message`
line per finding; --json emits ONE JSON line (schema in
analysis/bench_contract.py) so automated drivers can consume findings
without scraping.

Pass 1 (the lint), pass 3 (the lifecycle/dataflow pass) and pass 4 (the
concurrency/boundary pass) perform no JAX backend initialization; --audit
opts into pass 2, which forces the CPU backend before first JAX use (the
audit's budgets are pinned on the CPU lowering — analysis/budgets.py) and
compiles a few tiny abstract programs.

--fail-on-new compares active findings against the committed baseline
(analysis/graftcheck_baseline.json, keyed by (rule, relative path,
message) — line-number-free so unrelated edits don't churn it) and exits
nonzero only on NEW findings; it also diffs the static jit-wrapper census
against analysis/jit_surface_baseline.json (keyed (path, name)) so a new
jit wrapper or a widened static-arg set fails until deliberately re-pinned.
--update-baseline rewrites both baselines from the current tree.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import typing as tp

from midgpt_tpu.analysis.concurrency import CONCURRENCY_RULES, concurrency_paths
from midgpt_tpu.analysis.jit_surface import (
    diff_surface,
    jit_surface,
    load_baseline,
    save_baseline,
)
from midgpt_tpu.analysis.lifecycle import LIFECYCLE_RULES, lifecycle_paths
from midgpt_tpu.analysis.lint import DEFAULT_LINT_ROOTS, RULES, lint_paths

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "graftcheck_baseline.json")


def _repo_root() -> str:
    import midgpt_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(midgpt_tpu.__file__)))


def _default_paths() -> tp.List[str]:
    """Resolve DEFAULT_LINT_ROOTS against the repo root (the parent of the
    midgpt_tpu package), so the CLI works from any cwd."""
    repo = _repo_root()
    return [p for p in (os.path.join(repo, r) for r in DEFAULT_LINT_ROOTS) if os.path.exists(p)]


def _baseline_key(f, repo: str) -> tp.Tuple[str, str, str]:
    path = os.path.abspath(f.path) if isinstance(f.path, str) else f.path
    try:
        rel = os.path.relpath(path, repo)
    except ValueError:
        rel = f.path
    return (f.rule, rel.replace(os.sep, "/"), f.message)


def main(argv: tp.Optional[tp.Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftcheck", description="JAX/TPU-aware static analysis for midgpt_tpu"
    )
    ap.add_argument(
        "paths",
        nargs="*",
        help="files/directories to lint (default: the package, tools/ and "
        "the top-level entry points; tests/ is excluded — fixtures there "
        "are deliberate violations)",
    )
    ap.add_argument("--json", action="store_true", help="one JSON line (driver contract)")
    ap.add_argument(
        "--rules",
        type=str,
        default=None,
        help="comma-separated rule subset, e.g. GC001,GC009",
    )
    ap.add_argument(
        "--audit",
        action="store_true",
        help="also run pass 2 (compiled-artifact audit; imports jax, CPU-only)",
    )
    ap.add_argument(
        "--fail-on-new",
        action="store_true",
        help="exit nonzero only on findings absent from the committed "
        "baseline (analysis/graftcheck_baseline.json)",
    )
    ap.add_argument(
        "--update-baseline",
        action="store_true",
        help="rewrite the committed baseline from the current findings",
    )
    args = ap.parse_args(argv)

    known = {**RULES, **LIFECYCLE_RULES, **CONCURRENCY_RULES}
    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",")]
        unknown = [r for r in rules if r not in known]
        if unknown:
            ap.error(f"unknown rule(s) {unknown}; known: {sorted(known)}")

    paths = args.paths or _default_paths()
    lint_rules = None if rules is None else [r for r in rules if r in RULES]
    life_rules = None if rules is None else [r for r in rules if r in LIFECYCLE_RULES]
    conc_rules = None if rules is None else [r for r in rules if r in CONCURRENCY_RULES]
    active: tp.List = []
    suppressed: tp.List = []
    n_files = 0
    if rules is None or lint_rules:
        active, suppressed, n_files = lint_paths(paths, lint_rules)
    p3_active: tp.List = []
    p3_suppressed: tp.List = []
    t0 = time.perf_counter()
    if rules is None or life_rules:
        p3_active, p3_suppressed, p3_files = lifecycle_paths(paths, life_rules)
        n_files = max(n_files, p3_files)
    pass3_wall_ms = (time.perf_counter() - t0) * 1000.0
    p4_active: tp.List = []
    p4_suppressed: tp.List = []
    t0 = time.perf_counter()
    if rules is None or conc_rules:
        p4_active, p4_suppressed, p4_files = concurrency_paths(paths, conc_rules)
        n_files = max(n_files, p4_files)
    pass4_wall_ms = (time.perf_counter() - t0) * 1000.0
    active = sorted(
        active + p3_active + p4_active,
        key=lambda f: (f.path, f.line, f.col, f.rule),
    )
    suppressed = suppressed + p3_suppressed + p4_suppressed

    # jit-surface census (always computed: `jit_surface_count` is part of
    # the --json contract); the baseline diff only gates under
    # --fail-on-new, like the findings baseline.
    surface = jit_surface(paths, rel_to=_repo_root())

    audit_report: tp.Optional[tp.Dict[str, tp.Any]] = None
    audit_error: tp.Optional[str] = None
    if args.audit:
        # Force CPU before any backend touch: the census budgets are
        # pinned on the CPU lowering, whatever device is attached.
        import jax

        jax.config.update("jax_platforms", "cpu")
        from midgpt_tpu.analysis.hlo_audit import run_audit

        try:
            audit_report = run_audit()
        except AssertionError as e:
            audit_error = str(e)

    repo = _repo_root()
    new_findings = active
    surface_problems: tp.List[str] = []
    if args.update_baseline:
        with open(BASELINE_PATH, "w", encoding="utf-8") as fh:
            json.dump(
                [
                    {"rule": r, "path": p, "message": m}
                    for r, p, m in sorted(_baseline_key(f, repo) for f in active)
                ],
                fh,
                indent=1,
            )
            fh.write("\n")
        save_baseline(surface)
    if args.fail_on_new:
        baseline: tp.Set[tp.Tuple[str, str, str]] = set()
        if os.path.exists(BASELINE_PATH):
            with open(BASELINE_PATH, "r", encoding="utf-8") as fh:
                baseline = {
                    (e["rule"], e["path"], e["message"]) for e in json.load(fh)
                }
        new_findings = [f for f in active if _baseline_key(f, repo) not in baseline]
        surface_problems = diff_surface(surface, load_baseline())

    failed = (
        bool(new_findings)
        or bool(surface_problems)
        or audit_error is not None
    )
    if args.json:
        out: tp.Dict[str, tp.Any] = {
            "tool": "graftcheck",
            "count": len(active),
            "suppressed": len(suppressed),
            "files_scanned": n_files,
            "findings": [f.to_dict() for f in active],
            "pass3_count": len(p3_active),
            "pass3_suppressed": len(p3_suppressed),
            "pass3_wall_ms": pass3_wall_ms,
            "pass4_count": len(p4_active),
            "pass4_suppressed": len(p4_suppressed),
            "pass4_wall_ms": pass4_wall_ms,
            "jit_surface_count": len(surface),
        }
        if args.fail_on_new:
            out["new_count"] = len(new_findings)
            out["jit_surface_new"] = len(surface_problems)
        if args.audit:
            out["audit"] = audit_report if audit_error is None else {"error": audit_error}
        print(json.dumps(out))
    else:
        report = new_findings if args.fail_on_new else active
        for f in report:
            print(f.format())
        for p in surface_problems:
            print(f"jit-surface: {p}")
        if audit_error is not None:
            print(f"audit: FAILED — {audit_error}")
        elif audit_report is not None:
            print(f"audit: ok — {json.dumps(audit_report)}")
        tail = (
            f"graftcheck: {len(active)} finding(s), {len(suppressed)} "
            f"suppressed, {n_files} file(s) scanned "
            f"(pass 3: {len(p3_active)} finding(s) in {pass3_wall_ms:.0f} ms; "
            f"pass 4: {len(p4_active)} finding(s) in {pass4_wall_ms:.0f} ms; "
            f"jit surface: {len(surface)} wrapper(s))"
        )
        if args.fail_on_new:
            tail += (
                f"; {len(new_findings)} new vs baseline, "
                f"{len(surface_problems)} jit-surface change(s)"
            )
        print(tail)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
