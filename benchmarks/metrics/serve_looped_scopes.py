"""model step (serve), a family whose configuration file says what to read:
where an engine round's device time goes by the named scopes the serving
programs open. The scope list, the metric each scope reports under, the scope
the paged decode attention kernel is called in and the arithmetic module come
from the configuration file's `metrics` group:

    "metrics": {"scopes": {"attn": "serve.loop_attn_ms", "mlp": "serve.loop_mlp_ms", ...},
                "attention_kernel_scope": "attn", "attention_metric": "looped_decode_attention",
                "cache_kind": "looped", "arithmetic": "arithmetic_ouro.py"}

so a further family brings a configuration file and no further copy of this
walk (PERF.md section 7 asks a benchmark PR to fold `serve_family_scopes.py`
and `serve_latent_scopes.py`, whose lists are constants, into one; this file
is written to be that one). A configuration without the group (every cell
before PR 41) reports nothing.

The method is serve_family_scopes.py's: exclusive op time of the traced
window, each op put to its PROGRAM by the trace's `XLA Modules` line and to the
INNERMOST listed scope on its `op_name` path in the optimized text of the
compiled serving programs (`ServeEngine.program_texts()`), prefill and decode
programs together, ms an engine round; `serve.model_unattributed_ms` (the name
the benchmark has) is everything else (embedding, sampling, the loops'
bookkeeping, copies). 98 % of the traced time must be in ops those texts name.
The Mosaic custom calls are found the same way: `kv_write` on the path is the
in-place write, the attention kernel's scope the paged decode attention
(serve_looped_kernels.py). Per-program totals ride along for
serve_looped_cache.py's weight-read share.
"""

import bisect
import re

PROGRAMS = {"prefill": "_serve_prefill_chunk", "decode": "_serve_decode_chunk"}
_CUSTOM = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', re.M)


def settings(run):
    """The configuration file's `metrics` group, or None where it has none."""
    m = run["config"].get("metrics")
    return m if isinstance(m, dict) and m.get("scopes") else None


def attribute(run):
    """{"scope": {scope: ns}, "kernel": {"attention" | "kv_write": ns}, "program":
    {"prefill" | "decode" | "other": ns}, "total": ns, "known": ns} over the
    traced window, summed over the chips; None where the configuration, the
    program or the trace gives nothing to read."""
    if "_looped_attribution" not in run:  # three readers ask; lowering every program again costs seconds each
        run["_looped_attribution"] = _attribute(run)
    return run["_looped_attribution"]


def _attribute(run):
    ts, cfg = run.get("trace_summary"), settings(run)
    if run["kind"] != "serve" or not cfg or not ts or not run["counters"].get("traced_rounds"):
        return None
    try:
        from midgpt_tpu.sampling.serve import ServeEngine

        texts = ServeEngine.program_texts()
    except (ImportError, AttributeError):
        return None
    scopes, kernel_scope = tuple(cfg["scopes"]), cfg.get("attention_kernel_scope")
    if not texts or not any(f"/{s}/" in t for t in texts.values() for s in scopes):
        return None
    sp, reduce = run["load"]("metrics/step_phases.py"), run["load"]("reduce.py")
    prefill = run["load"]("metrics/serve_prefill.py")

    def innermost(path):
        for part in reversed(path.split("/")):
            words = sp._WORD.findall(part)
            if words and words[-1] in scopes and all(w in sp._WRAPPERS for w in words[:-1]):
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
            which = "kv_write" if "kv_write" in path else "attention" if innermost(path) == kernel_scope else None
            kernel_of.setdefault(prog, {}).setdefault(inst, which)
    try:
        modules = prefill.module_events(reduce.find_xplane(prefill.TRACE_DIR))
    except FileNotFoundError:
        modules = []
    if not modules:
        run["log"]("serve_looped_scopes: the trace has no XLA Modules line; ops are not put to their program")
    names = ts["trace"]["names"]
    out = {"scope": {}, "kernel": {}, "program": {}, "total": 0, "known": 0}
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
                out["program"][prog] = out["program"].get(prog, 0) + ns
                if n in named.get(prog, ()) or prog == "other":  # "other": the host-side sampling's small programs
                    out["known"] += ns
                s = scope_of.get(prog, {}).get(n)
                if s is not None:
                    out["scope"][s] = out["scope"].get(s, 0) + ns
                k = kernel_of.get(prog, {}).get(n)
                if k is not None:
                    out["kernel"][k] = out["kernel"].get(k, 0) + ns
    return out


def named_enough(run, got, who):
    """Whether 98 % of the traced time is in ops the programs' texts name (says so where not)."""
    if got["total"] > 0 and got["known"] >= 0.98 * got["total"]:
        return True
    run["log"](f"{who}: only {100.0 * got['known'] / max(1, got['total']):.1f} % of the traced time is in "
               f"ops the serving programs' texts name; left out")
    return False


def read(run):
    got = attribute(run)
    rounds = run["counters"].get("traced_rounds") if got else None
    if not got or not rounds or not named_enough(run, got, "serve_looped_scopes"):
        return None
    scopes = settings(run)["scopes"]
    if not got["scope"]:
        run["log"]("serve_looped_scopes: no traced op names a listed scope (stale compile cache?); left out")
        return None
    per_ms = 1.0 / 1e6 / max(1, run["trace_summary"]["n_devices"]) / rounds
    out = {metric: got["scope"].get(s, 0) * per_ms for s, metric in scopes.items()}
    out["serve.model_unattributed_ms"] = (got["total"] - sum(got["scope"].values())) * per_ms
    run["log"](f"serve scopes (from the configuration's list), device ms an engine round over {rounds} rounds, "
               f"{100.0 * got['known'] / got['total']:.2f} % of the traced time named: "
               + " ".join(f"{k} {v:.2f}" for k, v in out.items()))
    return out
