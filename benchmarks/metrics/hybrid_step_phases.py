"""step program, hybrid family: where the step's device time goes by the scopes
that models/kimi_linear.py and ops/moe.py open INSIDE `attn` and `mlp`: `kda`
(the whole KDA mixer), `kda_scan` (the chunked recurrence alone, inside `kda`),
`mla`, `moe_route` (router, top-k, the search for each buffer row's token,
gather, weighted scatter-add), `moe_experts` (the grouped matmuls and the
shared expert). Exclusive op time of the traced window, each op put to the
INNERMOST of these five on its scope path, forward and backward together, ms
an optimizer step. `step.kda_ms` holds `kda_scan`'s too; the others are
disjoint; all of them are inside step.attn_ms / step.mlp_ms, which
step_phases.py reports by the outer scopes.

How the path is found is step_phases.py's (the traced op's HLO instruction
name looked up in the compiled step program's text), and its helpers are used:
this file adds only the scope names. A program that opens none of these scopes
(every GPT cell; the parent of PR 26) reports nothing."""

SCOPES = ("kda_scan", "kda", "mla", "moe_route", "moe_experts")


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or not run["counters"]["traced_steps"]:
        return None
    import importlib

    log = run["log"]
    sp = run["load"]("metrics/step_phases.py")
    train = importlib.import_module("midgpt_tpu.training.train")
    rt = getattr(train, "last_runtime", lambda: None)()
    if rt is None:
        return None
    text = rt.step_program_text()
    op_name = dict(sp._INSTRUCTION.findall(text))
    in_text = set(sp._NAMED.findall(text))
    reduce = run["load"]("reduce.py")
    names = ts["trace"]["names"]
    excl = {}
    for dev in ts["devices"]:
        for i, ns in reduce.exclusive_ns(dev["ops"])[0].items():
            excl[names[i]] = excl.get(names[i], 0) + ns
    total = sum(excl.values())
    known = sum(ns for n, ns in excl.items() if n in in_text)
    if total <= 0 or known < 0.98 * total:
        log("hybrid_step_phases: the compiled step program's text is not the traced program; left out")
        return None

    def innermost(path: str):
        for part in reversed(path.split("/")):
            words = sp._WORD.findall(part)
            if words and words[-1] in SCOPES and all(w in sp._WRAPPERS for w in words[:-1]):
                return words[-1]
        return None

    by_scope = {}
    for n, ns in excl.items():
        s = innermost(op_name.get(n, ""))
        if s is not None:
            by_scope[s] = by_scope.get(s, 0) + ns
    if not by_scope:
        log("hybrid_step_phases: no traced op names kda / mla / moe_route / moe_experts; left out")
        return None
    per_ms = 1.0 / 1e6 / max(1, ts["n_devices"]) / run["counters"]["traced_steps"]
    ms = {s: by_scope.get(s, 0) * per_ms for s in SCOPES}
    out = {"step.kda_ms": ms["kda"] + ms["kda_scan"], "step.kda_scan_ms": ms["kda_scan"],
           "step.mla_ms": ms["mla"], "step.moe_route_ms": ms["moe_route"],
           "step.moe_experts_ms": ms["moe_experts"]}
    log("hybrid step phases, ms a step: " + " ".join(f"{k[5:-3]} {v:.2f}" for k, v in out.items()))
    return out
