"""model step (serve), a family with kinds of layers: where an engine round's
device time goes by the scopes that models/mimo_v2.py and ops/moe.py open
INSIDE `attn` and `mlp` in the serving programs: `attn_global`, `attn_window`
(projections, rotary, the K/V write, the attention, the output projection of a
layer of that kind), `moe_route` (router, top-k, pair weights, the weighted
sum), `moe_experts` (the experts' matmuls). Exclusive op time of the traced
window, each op put to the INNERMOST of these on its scope path, prefill and
decode programs together, ms an engine round;
`serve.model_unattributed_ms` is everything else (embedding, norms, the dense
layer, the head, sampling, copies).

How the path is found: the v5e trace names an op by its HLO instruction and
carries no scope path (PERF.md §6 PR 24), so the path is the instruction's
`op_name` in the optimized text of the compiled serving programs, which the
engine hands out (`ServeEngine.program_texts()`). Two programs reuse
instruction names, so an op is first put to its PROGRAM by the trace's `XLA
Modules` line (one event per execution, named after the jitted function:
serve_prefill.py's reader), then looked up in the texts of that function's
programs (its page-bucket variants are one graph at different widths). A trace without that line (the CPU rehearsal) looks an op up in the decode
programs' texts, then the prefill's. Nothing is reported unless 98 % of the traced time is in ops those texts name (as
step_phases.py), nor when no op names a scope (a text loaded from a compile
cache filled before the scopes existed carries the old metadata: PERF.md §7),
nor for a program without `program_texts()` or without these scopes (every GPT
cell; the parent of PR 30).
"""

import bisect
import re

SCOPES = ("attn_global", "attn_window", "moe_route", "moe_experts")
PROGRAMS = {"prefill": "_serve_prefill_chunk", "decode": "_serve_decode_chunk"}
_CUSTOM = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', re.M)


def attribute(run):
    """{"scope": {scope: ns}, "kernel": {"attention" | "kv_write": ns}, "total": ns,
    "known": ns} over the traced window, summed over the chips; None where the
    program or the trace gives nothing to read."""
    if "_family_attribution" not in run:  # two readers ask; lowering every program again costs seconds each
        run["_family_attribution"] = _attribute(run)
    return run["_family_attribution"]


def _attribute(run):
    ts = run.get("trace_summary")
    # `traced_rounds`: only a cell that counts them can be read per round, and asking a GPT
    # cell's 45 programs for their text (a trace and a lowering each) to find no scope is a minute
    if run["kind"] != "serve" or not ts or not run["counters"].get("traced_rounds"):
        return None
    try:
        from midgpt_tpu.sampling.serve import ServeEngine

        texts = ServeEngine.program_texts()
    except (ImportError, AttributeError):
        return None
    if not texts or not any(s in t for t in texts.values() for s in SCOPES):
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
            which = "kv_write" if "kv_write" in path else "attention" if "attn_global" in path else None
            kernel_of.setdefault(prog, {}).setdefault(inst, which)
    try:
        modules = prefill.module_events(reduce.find_xplane(prefill.TRACE_DIR))
    except FileNotFoundError:
        modules = []
    if not modules:
        # no module line (the CPU rehearsal's pseudo-device): every op is looked up in the decode
        # programs' texts first, then the prefill's; the 98 % rule below still holds the result
        run["log"]("serve_family_scopes: the trace has no XLA Modules line; ops are not put to their program")
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
                if n in named.get(prog, ()):
                    out["known"] += ns
                elif prog == "other":
                    out["known"] += ns  # the host-side sampling's small programs: no text asked for, unattributed
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
        log(f"serve_family_scopes: only {100.0 * got['known'] / max(1, got['total']):.1f} % of the traced time is in "
            f"ops the serving programs' texts name; left out")
        return None
    if not got["scope"]:
        log("serve_family_scopes: no traced op names a scope (stale compile cache?); left out")
        return None
    per_ms = 1.0 / 1e6 / max(1, run["trace_summary"]["n_devices"]) / rounds
    out = {f"serve.{s}_ms": got["scope"].get(s, 0) * per_ms for s in SCOPES}
    out["serve.model_unattributed_ms"] = (got["total"] - sum(got["scope"].values())) * per_ms
    log(f"serve scopes, device ms an engine round over {rounds} rounds: "
        + " ".join(f"{k[6:-3]} {v:.2f}" for k, v in out.items()))
    return out
