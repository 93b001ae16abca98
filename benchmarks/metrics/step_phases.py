"""step program: where a step's device time goes, by the `jax.named_scope`s the
program opens (models/gpt.py: embed / block / attn / mlp / final_norm;
training/train.py and ops/loss.py: cast_params / lm_head_loss / grad_accum /
optimizer / health). Per-op exclusive time of the traced window, each op put
to the INNERMOST known scope on its scope path, forward and backward
together: a scope shows bare, as `jvp(scope)`, as `transpose(jvp(scope))`, and
under `checkpoint` / `rematted_computation` where remat is on. Per optimizer
step, mean over the chips. `step.unattributed_ms` is everything else (the other
scopes, and ops with no scope: collectives, copies, loop wrappers), so the five
sum to the exclusive time of the window, which is `step.device_ms` up to the
idle gaps inside control-flow ops.

Where the scope path comes from: the v5e trace names an op by its HLO
instruction (`fusion.2826`) and carries no `tf_op` / `hlo_module` stat (my chip
run, PR 24: an op event holds its offset, its duration and nothing else), so the
path is the instruction's `metadata={op_name="jit(step)/.../<scope>/<op>"}` in
the optimized HLO of the compiled step program, which the program hands out
(`training/train.py` `last_runtime().step_program_text()`: the same avals as the
loop's call, so the persistent cache's entry of the running program). The reader
checks that the text IS the traced program (nearly all traced time must be in
ops the text names) and reports nothing otherwise.

Also reports nothing when no op names the scopes PR 24 added: the persistent
compile cache's key leaves metadata out, so a step program loaded from a cache
the parent commit filled carries the parent's scopes (PERF.md: per-layer runs
after a scope change start from a cache that has not seen the parent). A
program without `last_runtime()` (the parent of PR 24) reports nothing."""

import re

REPORTED = ("attn", "mlp", "lm_head_loss", "optimizer")
OTHER = ("embed", "final_norm", "block", "cast_params", "grad_accum", "health")
NEW_IN_PR24 = ("lm_head_loss", "optimizer")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_WRAPPERS = ("jvp", "transpose", "vmap", "pmap", "remat", "checkpoint")
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"', re.M)
_NAMED = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", re.M)


def innermost_scope(op_name: str):
    """The last path component of `op_name` that is a known scope, wrappers
    (`jvp(..)`, `transpose(jvp(..))`) stripped; None if there is none."""
    for part in reversed(op_name.split("/")):
        words = _WORD.findall(part)
        if words and words[-1] in REPORTED + OTHER and all(w in _WRAPPERS for w in words[:-1]):
            return words[-1]
    return None


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or not run["counters"]["traced_steps"]:
        return None
    log = run["log"]
    import importlib

    # by module path: the package re-exports a FUNCTION called `train`
    train = importlib.import_module("midgpt_tpu.training.train")
    rt = getattr(train, "last_runtime", lambda: None)()
    if rt is None:
        log("step_phases: this program hands out no last_runtime(); step.*_ms left out")
        return None
    text = rt.step_program_text()
    op_name = dict(_INSTRUCTION.findall(text))
    in_text = set(_NAMED.findall(text))
    reduce = run["load"]("reduce.py")
    names = ts["trace"]["names"]
    excl = {}
    for dev in ts["devices"]:
        for i, ns in reduce.exclusive_ns(dev["ops"])[0].items():
            excl[names[i]] = excl.get(names[i], 0) + ns
    per_ms = 1.0 / 1e6 / max(1, ts["n_devices"]) / run["counters"]["traced_steps"]
    log("step_phases: the twenty largest ops (ms a step a chip, name, scope path):\n" + "\n".join(
        f"  {ns * per_ms:9.3f}  {n}  {op_name.get(n, '<no op_name>')[:150]}"
        for n, ns in sorted(excl.items(), key=lambda kv: -kv[1])[:20]))
    total = sum(excl.values())
    known = sum(ns for n, ns in excl.items() if n in in_text)
    if total <= 0 or known < 0.98 * total:
        log(f"step_phases: only {100.0 * known / max(1, total):.1f} % of the traced time is in ops the "
            f"compiled step program's text names: the text is not the traced program; step.*_ms left out")
        return None
    by_scope = {}
    for n, ns in excl.items():
        scope = innermost_scope(op_name.get(n, "")) or "<none>"
        by_scope[scope] = by_scope.get(scope, 0) + ns
    missing = [s for s in NEW_IN_PR24 if not by_scope.get(s)]
    if missing:
        log(f"step_phases: no op names scope {' or '.join(missing)}: stale cache? (a step program "
            f"loaded from a cache filled before the scopes existed carries the old metadata); "
            f"scopes seen: {sorted(by_scope)}; step.*_ms left out")
        return None
    out = {f"step.{s}_ms": by_scope.get(s, 0) * per_ms for s in REPORTED}
    out["step.unattributed_ms"] = sum(v for s, v in by_scope.items() if s not in REPORTED) * per_ms
    busy = ts["busy_ns_mean"] / 1e6 / run["counters"]["traced_steps"]
    log("step phases, ms a step a chip: " + " ".join(f"{k[5:-3]} {v:.2f}" for k, v in out.items())
        + f"; sum {sum(out.values()):.2f} beside step.device_ms {busy:.2f}; unattributed holds: "
        + " ".join(f"{s} {v * per_ms:.2f}" for s, v in sorted(by_scope.items(), key=lambda kv: -kv[1])
                   if s not in REPORTED))
    return out
