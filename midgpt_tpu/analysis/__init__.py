"""graftcheck — JAX/TPU-aware static analysis for this repo.

Three passes (docs/ANALYSIS.md is the rule catalog):

  * **Pass 1 — AST lint** (`analysis.lint`, no JAX import): walks package
    source and flags the compilation-behavior footguns that CLAUDE.md and
    docs/ANALYSIS.md record as hard-won gotchas — control flow in Pallas kernel
    bodies, host syncs inside jitted scopes, untiled BlockSpec literals,
    use-after-donate, wall-clock/np.random reachable from traced code, and
    uncited parity claims. Rules GC001-GC006, suppressible inline with
    `# graftcheck: disable=GCnnn — justification`.
  * **Pass 2 — compiled-artifact audit** (`analysis.hlo_audit`, builds on
    utils/hlo.py): executable pins over post-optimization HLO and the jit
    compile cache — recompile counting, while-body collective census, fp32
    master-param presence — so the scheduling/parity claims in SERVING.md
    and SURVEY.md §7 are tested, not remembered. Its numeric budgets live
    in `analysis.budgets`, the single manifest both the audit and
    tests/test_recompile_pins.py consume.
  * **Pass 3 — lifecycle/dataflow** (`analysis.lifecycle`, no JAX import):
    interprocedural checks over the serving stack — page-ownership
    balance on every path including exception edges (GC009), ServeEngine
    mutation confinement to the driver-loop serialization boundary and
    no-await-mid-mutation (GC010), and bounded-domain proofs for values
    flowing into trailing static jit args (GC011). Same suppression
    machinery as pass 1.

`analysis.bench_contract` is the checker for the one-JSON-line convention
of the two summary CLIs (this package's --json mode, tools/chaos_run.py).

CLI: `python -m midgpt_tpu.analysis [paths...] [--json] [--audit]
[--fail-on-new] [--update-baseline]` (tools/graftcheck.py is a path-setup
wrapper). Passes 1 and 3 never initialize a JAX backend, so the lint gate
is safe to run on hosts where device init is slow or unavailable;
--fail-on-new gates CI on the committed graftcheck_baseline.json.
"""

from midgpt_tpu.analysis.lifecycle import (
    LIFECYCLE_RULES,
    lifecycle_paths,
    lifecycle_source,
)
from midgpt_tpu.analysis.lint import (
    DEFAULT_LINT_ROOTS,
    Finding,
    RULES,
    lint_paths,
    lint_source,
)

__all__ = [
    "DEFAULT_LINT_ROOTS",
    "Finding",
    "LIFECYCLE_RULES",
    "RULES",
    "lifecycle_paths",
    "lifecycle_source",
    "lint_paths",
    "lint_source",
]
