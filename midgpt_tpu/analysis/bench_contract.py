"""Checker for the one-JSON-line convention of the repo's two summary CLIs.

`python -m midgpt_tpu.analysis --json` (tools/graftcheck.py) and
tools/chaos_run.py each print exactly ONE line of JSON to stdout for a
caller that consumes it blind — a stray print, a NaN (json.dumps emits bare
`NaN`, which is not JSON), or a silently renamed field breaks that caller
with no test noticing. This module is the single place the convention is
written down; tests/test_graftcheck.py and tests/test_chaos_serve.py run
the real entry points and validate their stdout through it. Rates and
latencies are not its business: those come from benchmarks/run.py.

Checkers return a list of problem strings (empty = conformant) rather than
raising, so callers can aggregate.
"""

from __future__ import annotations

import json
import typing as tp

Number = (int, float)


def _reject_nonfinite(value: str) -> tp.NoReturn:
    raise ValueError(f"non-finite JSON constant {value!r} (NaN/Infinity is not JSON)")


def parse_single_json_line(stdout: str) -> tp.Tuple[tp.Optional[dict], tp.List[str]]:
    """Enforce 'stdout is exactly one JSON object line'. Returns (record,
    problems); record is None when parsing failed."""
    problems: tp.List[str] = []
    lines = [l for l in stdout.splitlines() if l.strip()]
    if len(lines) != 1:
        problems.append(f"expected exactly 1 non-empty stdout line, got {len(lines)}")
        if not lines:
            return None, problems
    try:
        rec = json.loads(lines[-1], parse_constant=_reject_nonfinite)
    except ValueError as e:
        problems.append(f"last line is not valid JSON: {e}")
        return None, problems
    if not isinstance(rec, dict):
        problems.append(f"JSON line is a {type(rec).__name__}, not an object")
        return None, problems
    return rec, problems


def _require(
    rec: dict, spec: tp.Dict[str, tp.Tuple[type, ...]], problems: tp.List[str]
) -> None:
    for key, types in spec.items():
        if key not in rec:
            problems.append(f"missing required field {key!r}")
        elif not isinstance(rec[key], types) or isinstance(rec[key], bool):
            problems.append(
                f"field {key!r} has type {type(rec[key]).__name__}, expected "
                + "/".join(t.__name__ for t in types)
            )


def check_train_chaos(rec: dict) -> tp.List[str]:
    """tools/chaos_run.py degraded-IO / elastic-topology summary
    (docs/ROBUSTNESS.md "Elastic resume & watchdog"): a supervised training
    run with hang_step / ckpt_enospc / resume_reshard armed. The record
    carries the recovery claim, so its gates are structural:

      * status == "ok" and at least one requested fault actually FIRED —
        an unfaulted pass claims nothing about recovery.
      * detected_at_ms is a number >= 0 (the registry observer timestamped
        the first firing; a null means the plan never triggered).
      * loss_parity is literal true — the post-recovery trajectory matches
        an unfaulted reference run of the same config (rtol covers only
        the f32 reassociation of a re-derived data-axis all-reduce after
        a mesh change; the batch order is positional and exact).
      * final_mesh names the geometry the run FINISHED on (axes + device
        count) so a resume_reshard record proves the topology actually
        changed hands."""
    problems: tp.List[str] = []
    _require(
        rec,
        {
            "tool": (str,),
            "bench": (str,),
            "status": (str,),
            "wall_s": Number,
            "faults_requested": (list,),
            "faults_fired": (dict,),
            "detected_at_ms": Number,
            "restarts": (int,),
            "final_mesh": (dict,),
            "n_devices_final": (int,),
            "loss_final": Number,
        },
        problems,
    )
    if rec.get("bench") != "train_chaos":
        problems.append(
            f"field 'bench' is {rec.get('bench')!r}, expected 'train_chaos'"
        )
    if rec.get("status") != "ok":
        problems.append(
            f"status {rec.get('status')!r} != 'ok' — recovery did not complete"
        )
    fired = rec.get("faults_fired")
    if isinstance(fired, dict) and sum(fired.values()) < 1:
        problems.append(
            "faults_fired is empty — no fault fired, the recovery claim is vacuous"
        )
    d = rec.get("detected_at_ms")
    if isinstance(d, Number) and d < 0:
        problems.append(f"detected_at_ms {d} < 0")
    if rec.get("loss_parity") is not True:
        problems.append(
            "field 'loss_parity' must be literal true — the recovered "
            "trajectory must match the unfaulted reference run"
        )
    fm = rec.get("final_mesh")
    if isinstance(fm, dict):
        if not isinstance(fm.get("n_devices"), int) or fm["n_devices"] < 1:
            problems.append(
                f"final_mesh.n_devices {fm.get('n_devices')!r} must be an int >= 1"
            )
        if not isinstance(fm.get("axes"), dict) or not fm.get("axes"):
            problems.append("final_mesh.axes must be a non-empty object")
    r = rec.get("restarts")
    if isinstance(r, int) and r < 0:
        problems.append(f"restarts {r} < 0")
    return problems


def check_graftcheck(rec: dict) -> tp.List[str]:
    """The graftcheck CLI's own --json line."""
    problems: tp.List[str] = []
    _require(
        rec,
        {
            "tool": (str,),
            "count": (int,),
            "suppressed": (int,),
            "files_scanned": (int,),
            "findings": (list,),
            "pass3_count": (int,),
            "pass3_suppressed": (int,),
            "pass3_wall_ms": (int, float),
            "pass4_count": (int,),
            "pass4_suppressed": (int,),
            "pass4_wall_ms": (int, float),
            "jit_surface_count": (int,),
        },
        problems,
    )
    for i, f in enumerate(rec.get("findings", [])):
        if not isinstance(f, dict):
            problems.append(f"findings[{i}] is not an object")
            continue
        _require(
            f,
            {"rule": (str,), "path": (str,), "line": (int,), "message": (str,)},
            problems,
        )
    return problems


PROFILES: tp.Dict[str, tp.Callable[[dict], tp.List[str]]] = {
    "train_chaos": check_train_chaos,
    "graftcheck": check_graftcheck,
}


def check_bench_stdout(
    stdout: str, profile: str
) -> tp.Tuple[tp.Optional[dict], tp.List[str]]:
    """Parse + schema-check a CLI's stdout against a profile."""
    rec, problems = parse_single_json_line(stdout)
    if rec is not None:
        problems.extend(PROFILES[profile](rec))
    return rec, problems
