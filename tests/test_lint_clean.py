"""The permanent tier-1 gate: the shipped tree is graftcheck-clean.

Every future PR that introduces a lax.cond-in-kernel, a host sync in a jit
scope, an untiled BlockSpec literal, a use-after-donate, trace-time RNG/
clock, or an uncited parity claim fails HERE with a rule ID and file:line
— and any suppression added to get past it must carry a justification.
"""

import json
import os

from midgpt_tpu.analysis.__main__ import BASELINE_PATH, _default_paths, _repo_root
from midgpt_tpu.analysis.concurrency import concurrency_paths
from midgpt_tpu.analysis.jit_surface import (
    JIT_SURFACE_BASELINE_PATH,
    jit_surface,
    load_baseline,
)
from midgpt_tpu.analysis.lifecycle import lifecycle_paths
from midgpt_tpu.analysis.lint import iter_python_files, lint_paths, parse_suppressions


def test_tree_is_violation_free():
    active, _suppressed, n_files = lint_paths(_default_paths())
    assert n_files > 50, "lint roots resolved to almost nothing — path bug?"
    assert active == [], "\n" + "\n".join(f.format() for f in active)


def test_tree_is_lifecycle_clean():
    """Pass 3 (GC009/GC010/GC011) on the whole tree: zero unsuppressed
    findings. A page-lifecycle leak, an engine touch from the event loop,
    or an unbounded static-arg domain fails here with file:line."""
    active, _suppressed, n_files = lifecycle_paths(_default_paths())
    assert n_files > 50, "lifecycle roots resolved to almost nothing — path bug?"
    assert active == [], "\n" + "\n".join(f.format() for f in active)


def test_tree_is_concurrency_clean():
    """Pass 4 (GC013-GC016) on the whole tree: zero unsuppressed findings.
    A thread-escape engine mutation, an allocating signal handler, a
    non-plain-data handoff payload, or a field-dropping structured raise
    fails here with file:line."""
    active, _suppressed, n_files = concurrency_paths(_default_paths())
    assert n_files > 50, "concurrency roots resolved to almost nothing — path bug?"
    assert active == [], "\n" + "\n".join(f.format() for f in active)


def test_jit_surface_baseline_pins_clean_tree():
    """The committed jit-surface manifest must match the live census
    exactly (and be non-empty — the tree HAS jit wrappers): a new wrapper,
    a widened static-arg set, or a regressed GC011 verdict fails here
    until the baseline is deliberately re-pinned via --update-baseline."""
    current = jit_surface(_default_paths(), rel_to=_repo_root())
    baseline = load_baseline(JIT_SURFACE_BASELINE_PATH)
    assert len(baseline) > 0, "committed jit_surface_baseline.json is empty"
    cur = {(e["path"], e["name"]): e for e in current}
    base = {(e["path"], e["name"]): e for e in baseline}
    assert cur == base, (
        "jit surface drifted from the committed baseline; review the "
        "change, then run `python -m midgpt_tpu.analysis --update-baseline`"
    )


def test_baseline_matches_clean_tree():
    """The committed --fail-on-new baseline must be empty while the tree is
    clean; a stale non-empty baseline would mask reintroduced findings."""
    with open(BASELINE_PATH, encoding="utf-8") as fh:
        assert json.load(fh) == []


def test_every_suppression_is_justified():
    """`# graftcheck: disable=GCnnn` alone is not an explanation. Require a
    justification clause long enough to say *why* the rule does not apply
    (the satellite contract: zero unexplained findings at merge)."""
    bare = []
    for path in iter_python_files(_default_paths()):
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        for s in parse_suppressions(src):
            text = s.justification.strip(" -—:—")
            if len(text) < 10:
                bare.append(f"{path}:{s.line}: disable={','.join(s.rules)}")
    assert not bare, "unjustified suppressions:\n" + "\n".join(bare)


def test_default_roots_exclude_tests():
    """tests/ holds deliberate-violation fixtures; the default scan must
    never pull them in (it would make the clean gate unsatisfiable)."""
    for path in iter_python_files(_default_paths()):
        assert os.sep + "tests" + os.sep not in path, path


def test_the_serving_stack_imports_point_one_way():
    """sampling/pages.py sits under sampling/serve.py, and both under what is
    built on the engine (the topology layers, the static analyser): an import
    of one of those, at module level or inside a function, puts a cycle back.
    No file of models/ imports sampling/."""
    import ast

    def imported(rel):
        with open(os.path.join(_repo_root(), rel), encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        names = set()
        for node in ast.walk(tree):  # every depth: a function-level import is an import
            if isinstance(node, ast.Import):
                names |= {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom) and node.module:
                names |= {node.module} | {f"{node.module}.{a.name}" for a in node.names}
        return names

    above = ("midgpt_tpu.sampling.disagg", "midgpt_tpu.sampling.fleet", "midgpt_tpu.sampling.fleet_proc",
             "midgpt_tpu.analysis")
    for rel, banned in (
        ("midgpt_tpu/sampling/pages.py", above + ("midgpt_tpu.sampling.serve",)),
        ("midgpt_tpu/sampling/serve.py", above),
    ):
        names = imported(rel)
        assert len(names) > 5, rel
        bad = sorted(n for n in names if any(n == b or n.startswith(b + ".") for b in banned))
        assert not bad, f"{rel} imports {bad}"
    # models/ sits under sampling/: a family's step CONSTRUCTS the cache it returns (models/gpt.py `ServeCache`)
    families = sorted(f for f in os.listdir(os.path.join(_repo_root(), "midgpt_tpu", "models")) if f.endswith(".py"))
    assert len(families) >= 9, families
    for f in families:
        bad = sorted(n for n in imported(f"midgpt_tpu/models/{f}") if (n + ".").startswith("midgpt_tpu.sampling."))
        assert not bad, f"midgpt_tpu/models/{f} imports {bad}"
