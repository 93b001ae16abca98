"""model step (serve), a family served from a LATENT cache: where an engine
round's device time goes by the scopes that models/pangu_ultra.py and
ops/moe.py open INSIDE `attn` and `mlp` in the serving programs: `attn_latent`
(the low-rank query and key/value projections, norms, rotary, the absorbed
fold, the latent write, the attention, the output projections), `moe_route`
(router, top-k, pair weights, the weighted sum), `moe_experts` (the routed
experts' matmuls), `moe_shared` (the shared expert's). Exclusive op time of the
traced window, each op put to the INNERMOST of these on its scope path, prefill
and decode programs together, ms an engine round;
`serve.model_unattributed_ms` is everything else (embedding, norms outside
these scopes, the dense layer, the head, sampling, copies).

The method is serve_family_scopes.py's (the instruction's `op_name` in the
optimized text of the compiled serving programs, `ServeEngine.program_texts()`;
an op is put to its PROGRAM by the trace's `XLA Modules` line first; 98 % of
the traced time must be in ops those texts name), through the same helpers of
step_phases.py, reduce.py and serve_prefill.py. That reader's `_attribute`
reads its scope list and the attention kernel's scope (`attn_global`) from its
own module, and no PR but a benchmark PR may edit it, so the walk over the
trace is REPEATED here with this family's list (PERF.md section 7: one function
that takes both is a benchmark PR's to make). It gates on the `attn_latent`
scope: a program without it (every other cell; the parent of PR 39) reports
nothing. The family-neutral quantities keep the names the benchmark has
(`serve.moe_route_ms`, ...): no twins. On this family's cell
serve_family_scopes.py reports too (it finds `moe_route`), with every
`attn_latent` op in its `serve.model_unattributed_ms`; run.py merges the
readers in file-name order, so this file's values, read later, are the ones
the line carries.
"""

import bisect
import re

SCOPES = ("attn_latent", "moe_route", "moe_experts", "moe_shared")
GATE = "attn_latent"
PROGRAMS = {"prefill": "_serve_prefill_chunk", "decode": "_serve_decode_chunk"}
_CUSTOM = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', re.M)


def attribute(run):
    """{"scope": {scope: ns}, "kernel": {"attention" | "kv_write": ns}, "total": ns,
    "known": ns} over the traced window, summed over the chips; None where the
    program or the trace gives nothing to read."""
    if "_latent_attribution" not in run:  # two readers ask; lowering every program again costs seconds each
        run["_latent_attribution"] = _attribute(run)
    return run["_latent_attribution"]


def _attribute(run):
    ts = run.get("trace_summary")
    if run["kind"] != "serve" or not ts or not run["counters"].get("traced_rounds"):
        return None
    if not any(k.startswith("kv.latent_") for k in run["counters"]):  # no latent cache: no program text is asked for
        return None
    try:
        from midgpt_tpu.sampling.serve import ServeEngine

        texts = ServeEngine.program_texts()
    except (ImportError, AttributeError):
        return None
    if not texts or not any(GATE in t for t in texts.values()):
        return None
    sp, reduce = run["load"]("metrics/step_phases.py"), run["load"]("reduce.py")
    prefill = run["load"]("metrics/serve_prefill.py")

    def innermost(path):
        for part in reversed(path.split("/")):
            words = sp._WORD.findall(part)
            if words and words[-1] in SCOPES and all(w in sp._WRAPPERS for w in words[:-1]):
                return words[-1]
        return None

    scope_of, named, kernel_of = {}, {}, {}
    for label, text in texts.items():
        prog = next((p for p, fn in PROGRAMS.items() if label.startswith(fn.lstrip("_"))), None)
        if prog is None:
            continue
        named.setdefault(prog, set()).update(sp._NAMED.findall(text))
        for inst, path in sp._INSTRUCTION.findall(text):
            scope_of.setdefault(prog, {}).setdefault(inst, innermost(path))
        for inst, path in _CUSTOM.findall(text):
            which = "kv_write" if "kv_write" in path else "attention" if GATE in path else None
            kernel_of.setdefault(prog, {}).setdefault(inst, which)
    try:
        modules = prefill.module_events(reduce.find_xplane(prefill.TRACE_DIR))
    except FileNotFoundError:
        modules = []
    if not modules:
        run["log"]("serve_latent_scopes: the trace has no XLA Modules line; ops are not put to their program")
    names = ts["trace"]["names"]
    out = {"scope": {}, "kernel": {}, "total": 0, "known": 0}
    for dev in ts["devices"]:
        mods = sorted((s, s + d, n) for plane, n, s, d in modules if plane == dev["name"])
        starts = [m[0] for m in mods]
        by_prog = {}
        for op in dev["ops"]:
            j = bisect.bisect_right(starts, op[1]) - 1
            mod = mods[j][2] if j >= 0 and op[1] < mods[j][1] else ""
            prog = next((p for p, fn in PROGRAMS.items() if fn in mod), "other")
            if not modules:
                n = names[op[0]]
                prog = next((p for p in ("decode", "prefill") if n in named.get(p, ())), "other")
            by_prog.setdefault(prog, []).append(op)
        for prog, ops in by_prog.items():
            for i, ns in reduce.exclusive_ns(ops)[0].items():
                n = names[i]
                out["total"] += ns
                if n in named.get(prog, ()) or prog == "other":  # "other": the host-side sampling's small programs
                    out["known"] += ns
                s = scope_of.get(prog, {}).get(n)
                if s is not None:
                    out["scope"][s] = out["scope"].get(s, 0) + ns
                k = kernel_of.get(prog, {}).get(n)
                if k is not None:
                    out["kernel"][k] = out["kernel"].get(k, 0) + ns
    return out


def read(run):
    got = attribute(run)
    rounds = run["counters"].get("traced_rounds") if got else None
    if not got or not rounds:
        return None
    log = run["log"]
    if got["total"] <= 0 or got["known"] < 0.98 * got["total"]:
        log(f"serve_latent_scopes: only {100.0 * got['known'] / max(1, got['total']):.1f} % of the traced time is in "
            f"ops the serving programs' texts name; left out")
        return None
    if not got["scope"].get(GATE):
        log("serve_latent_scopes: no traced op names the attn_latent scope (stale compile cache?); left out")
        return None
    per_ms = 1.0 / 1e6 / max(1, run["trace_summary"]["n_devices"]) / rounds
    out = {f"serve.{s}_ms": got["scope"].get(s, 0) * per_ms for s in SCOPES}
    out["serve.model_unattributed_ms"] = (got["total"] - sum(got["scope"].values())) * per_ms
    log(f"serve scopes (latent family), device ms an engine round over {rounds} rounds, "
        f"{100.0 * got['known'] / got['total']:.2f} % of the traced time named: "
        + " ".join(f"{k[6:-3]} {v:.2f}" for k, v in out.items()))
    return out
