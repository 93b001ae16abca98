"""entry and set-up: what the process's compiled programs cost it, from the
per-program table utils/compile_cache.py keeps (jax.monitoring duration events,
each carrying the jitted function's name): seconds in `backend_compile_duration`
over all programs (a compile on a cache miss, the load on a hit), seconds
tracing + lowering them, and how many there were. The table itself is logged,
slowest first. A program whose compile_cache has no `current()` (the parent of
PR 24) reports nothing."""


def read(run):
    from midgpt_tpu.utils import compile_cache

    stats = getattr(compile_cache, "current", lambda: None)()
    if stats is None:
        run["log"]("setup_programs: compile_cache.current() gives nothing; setup.* per-program metrics left out")
        return None
    run["log"]("set-up by program:\n" + stats.summary())
    t = stats.totals()
    return {"setup.compile_or_load_s": t["compile_or_load_s"],
            "setup.trace_lower_s": t["trace_lower_s"],
            "setup.programs": float(t["programs"])}
