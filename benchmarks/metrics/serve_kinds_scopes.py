"""model step (serve), a family with KINDS of attention layer whose
configuration file says what to read: where an engine round's device time goes
by the named scopes the serving programs open, and which Mosaic custom calls
are which kind's decode attention. From the configuration file's `metrics`
group:

    "metrics": {"scope_metrics": {"attn_global": "serve.attn_global_ms", "attn_gate": "serve.attn_gate_ms", ...},
                "attention_kernels": {"attn_global": {"kind": "global", "metric": "global_decode_attention"},
                                      "attn_window": {"kind": "window", "metric": "window_decode_attention"}},
                "arithmetic": "arithmetic_trinity.py", "expert_scopes": ["moe_route", "moe_experts"], ...}

A configuration without `scope_metrics` (every cell before PR 46) reports
nothing; this file gates on no cell's and no family's name. It is
serve_looped_scopes.py's walk (whose regular expression, program names and 98 %
rule are called, not copied) with two things that walk cannot give: SEVERAL
attention kernel scopes (that file's group names one), and the scopes' time BY
PROGRAM (serve_kinds_reads.py divides the decode programs' expert time by the
bytes their loops had to read). A `benchmark` PR that folds the readers into
one (PERF.md section 7) should keep this shape of `attribute`.

The method: exclusive op time of the traced window, each op put to its PROGRAM
by the trace's `XLA Modules` line and to the INNERMOST listed scope on its
`op_name` path in the optimized text of the compiled serving programs
(`ServeEngine.program_texts()`), prefill and decode programs together, ms an
engine round; `serve.model_unattributed_ms` is everything else (embedding,
norms outside the scopes, the dense layer, sampling, copies). A listed scope
INSIDE another (`attn_gate` inside `attn_window`) takes its ops out of the
outer one's time. 98 % of the traced time must be in ops those texts name.

On a cell of this kind the older readers run too (run.py calls every file, in
file-name order, later files winning): serve_family_scopes.py finds `moe_route`
and reports its four scopes (right where the program names its attention
scopes `attn_global` / `attn_window`, with the gate's time inside them and the
shared expert and the head in its `serve.model_unattributed_ms`);
serve_family_cache.py reads the two kinds' counters, which are its own names;
serve_family_kernels.py asks `arithmetic_mimo_v2.py` about a model that is not
MiMo's, raises KeyError and is dropped by run.py; serve_latent_* and
serve_looped_* find no latent counters and no `scopes` group and report
nothing. This file's values, read after serve_family_*'s, are the ones the line
carries.
"""

import bisect
import re

# an instruction of a compiled program's text: name, the first element type of what it gives, the rest of its line
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \(?([a-z]+[0-9]*)[^\n]*? [\w\-]+\(([^\n]*)$", re.M)
_OPERAND = re.compile(r"%([\w.\-]+)")


def combine_of(text, kernels, scope_of):
    """{instruction: kernel scope} of the ops that MERGE a kernel's partial
    results outside it (a split-K call gives float32 (m, l, acc) partials a
    partition; `ops/online_softmax.merge_partials` and `finalize` are XLA):
    from each custom call in `kernels` ({instruction: scope}) along its users,
    as long as what flows is float32 and the user lies in the kernel's scope or
    carries no path; the first user that gives another type (the cast back to
    the stream's) is the last. A kernel that finalizes in itself gives the
    stream's type and has no such ops."""
    made, users = {}, {}
    for name, dtype, rest in _RESULT.findall(text):
        made[name] = dtype
        for operand in set(_OPERAND.findall(rest)):
            users.setdefault(operand, []).append(name)
    out = {}
    for kernel, scope in kernels.items():
        frontier = [kernel] if made.get(kernel) == "f32" else []
        while frontier:
            for user in users.get(frontier.pop(), ()):
                if user in out or user in kernels or scope_of.get(user, scope) not in (scope, None):
                    continue
                out[user] = scope
                if made.get(user) == "f32":
                    frontier.append(user)
    return out


def settings(run):
    """The configuration file's `metrics` group, or None where it is not this reader's."""
    m = run["config"].get("metrics")
    return m if isinstance(m, dict) and m.get("scope_metrics") else None


def attribute(run):
    """{"scope": {program: {scope: ns}}, "kernel": {kernel scope | "kv_write": ns of
    the custom calls' durations}, "calls": {the same: events}, "combine": {kernel scope: exclusive ns of the ops
    that merge its partials, `combine_of`}, "program": {"prefill" | "decode" | "other": ns}, "total": ns,
    "known": ns} over the traced window, summed over the chips; None where the
    configuration, the program or the trace gives nothing to read."""
    if "_kinds_attribution" not in run:  # three readers ask; lowering every program again costs seconds each
        run["_kinds_attribution"] = _attribute(run)
    return run["_kinds_attribution"]


def _attribute(run):
    ts, cfg = run.get("trace_summary"), settings(run)
    if run["kind"] != "serve" or not cfg or not ts or not run["counters"].get("traced_rounds"):
        return None
    try:
        from midgpt_tpu.sampling.serve import ServeEngine

        texts = ServeEngine.program_texts()
    except (ImportError, AttributeError):
        return None
    scopes, kernels = tuple(cfg["scope_metrics"]), tuple(cfg.get("attention_kernels", ()))
    if not texts or not any(f"/{s}/" in t for t in texts.values() for s in scopes):
        return None
    looped = run["load"]("metrics/serve_looped_scopes.py")
    sp, reduce = run["load"]("metrics/step_phases.py"), run["load"]("reduce.py")
    prefill = run["load"]("metrics/serve_prefill.py")

    def innermost(path):
        for part in reversed(path.split("/")):
            words = sp._WORD.findall(part)
            if words and words[-1] in scopes and all(w in sp._WRAPPERS for w in words[:-1]):
                return words[-1]
        return None

    scope_of, named, kernel_of, combine = {}, {}, {}, {}
    for label, text in texts.items():
        prog = next((p for p, fn in looped.PROGRAMS.items() if label.startswith(fn.lstrip("_"))), None)
        if prog is None:
            continue
        named.setdefault(prog, set()).update(sp._NAMED.findall(text))
        for inst, path in sp._INSTRUCTION.findall(text):
            scope_of.setdefault(prog, {}).setdefault(inst, innermost(path))
        for inst, path in looped._CUSTOM.findall(text):
            which = "kv_write" if "kv_write" in path else innermost(path)
            kernel_of.setdefault(prog, {}).setdefault(inst, which if which == "kv_write" or which in kernels else None)
        attention = {i: k for i, k in kernel_of.get(prog, {}).items() if k in kernels and f"%{i} = " in text}
        for inst, k in combine_of(text, attention, scope_of[prog]).items():
            combine.setdefault(prog, {}).setdefault(inst, k)
    try:
        modules = prefill.module_events(reduce.find_xplane(prefill.TRACE_DIR))
    except FileNotFoundError:
        modules = []
    if not modules:
        run["log"]("serve_kinds_scopes: the trace has no XLA Modules line; ops are not put to their program")
    names = ts["trace"]["names"]
    out = {"scope": {}, "kernel": {}, "calls": {}, "combine": {}, "program": {}, "total": 0, "known": 0}
    for dev in ts["devices"]:
        mods = sorted((s, s + d, n) for plane, n, s, d in modules if plane == dev["name"])
        starts = [m[0] for m in mods]
        by_prog = {}
        for op in dev["ops"]:
            j = bisect.bisect_right(starts, op[1]) - 1
            mod = mods[j][2] if j >= 0 and op[1] < mods[j][1] else ""
            prog = next((p for p, fn in looped.PROGRAMS.items() if fn in mod), "other")
            if not modules:
                n = names[op[0]]
                prog = next((p for p in ("decode", "prefill") if n in named.get(p, ())), "other")
            by_prog.setdefault(prog, []).append(op)
        for prog, ops in by_prog.items():
            per_scope = out["scope"].setdefault(prog, {})
            for i, ns in reduce.exclusive_ns(ops)[0].items():
                n = names[i]
                out["total"] += ns
                out["program"][prog] = out["program"].get(prog, 0) + ns
                if n in named.get(prog, ()) or prog == "other":  # "other": the host-side sampling's small programs
                    out["known"] += ns
                s = scope_of.get(prog, {}).get(n)
                if s is not None:
                    per_scope[s] = per_scope.get(s, 0) + ns
                k = combine.get(prog, {}).get(n)
                if k is not None:
                    out["combine"][k] = out["combine"].get(k, 0) + ns
            # a kernel's time is its events' DURATION (reduce.kernel_ns: "kernels are leaf ops"; on the chip the
            # trace nests nothing inside these custom calls: durations and exclusive time read the same, PR 46)
            for i, _, d in ops:
                k = kernel_of.get(prog, {}).get(names[i])
                if k is not None:
                    out["kernel"][k] = out["kernel"].get(k, 0) + d
                    out["calls"][k] = out["calls"].get(k, 0) + 1
        _coverage(run, ts, dev, mods, by_prog, looped.PROGRAMS)
    return out


def _coverage(run, ts, dev, mods, by_prog, programs):
    """Says how much of the traced window the device's events cover: the
    programs' runs on the `XLA Modules` line inside the window, their summed
    time beside their ops', and the longest stretches with no op at all (a
    profiler that drops events leaves such holes, and every per-step figure
    divides what the trace kept by what the host counted)."""
    lo, hi = ts["lo"], ts["hi"]
    runs = {p: [(s, e) for s, e, n in mods if fn in n and e > lo and s < hi] for p, fn in programs.items()}
    ops = sorted((s, s + d) for prog_ops in by_prog.values() for _, s, d in prog_ops)
    holes, end = [], lo
    for s, e in ops + [(hi, hi)]:
        if s - end > 2_000_000:
            holes.append((s - end, end - lo))
        end = max(end, e)
    holes.sort(reverse=True)
    run["log"](f"trace coverage, {dev['name']}: window {(hi - lo) / 1e6:.0f} ms, {len(ops)} op events from "
               f"{(ops[0][0] - lo) / 1e6 if ops else 0:.1f} to {(ops[-1][1] - lo) / 1e6 if ops else 0:.1f} ms; program runs on the "
               f"modules line: " + ", ".join(f"{p} {len(r)} ({sum(e - s for s, e in r) / 1e6:.0f} ms; ops "
                                             f"{sum(d for _, _, d in by_prog.get(p, ())) / 1e6:.0f} ms incl. nested)" for p, r in runs.items())
               + f"; ops outside any run {sum(d for _, _, d in by_prog.get('other', ())) / 1e6:.1f} ms; {len(holes)} stretches over 2 ms "
               f"with no op, {sum(h for h, _ in holes) / 1e6:.0f} ms in all, the longest (ms, at ms): "
               + " ".join(f"{h / 1e6:.1f}@{at / 1e6:.0f}" for h, at in holes[:6]))


def named_enough(run, got, who):
    """serve_looped_scopes.py's 98 % rule, under this reader's name."""
    return run["load"]("metrics/serve_looped_scopes.py").named_enough(run, got, who)


def read(run):
    got = attribute(run)
    rounds = run["counters"].get("traced_rounds") if got else None
    if not got or not rounds or not named_enough(run, got, "serve_kinds_scopes"):
        return None
    if not any(got["scope"].values()):
        run["log"]("serve_kinds_scopes: no traced op names a listed scope (stale compile cache?); left out")
        return None
    per_ms = 1.0 / 1e6 / max(1, run["trace_summary"]["n_devices"]) / rounds
    metrics = settings(run)["scope_metrics"]
    by_scope = {s: sum(p.get(s, 0) for p in got["scope"].values()) for s in metrics}
    out = {metric: by_scope[s] * per_ms for s, metric in metrics.items()}
    out["serve.model_unattributed_ms"] = (got["total"] - sum(by_scope.values())) * per_ms
    run["log"](f"serve scopes by kind (from the configuration's list), device ms an engine round over {rounds} rounds, "
               f"{100.0 * got['known'] / got['total']:.2f} % of the traced time named: "
               + " ".join(f"{k} {v:.2f}" for k, v in out.items()))
    return out
