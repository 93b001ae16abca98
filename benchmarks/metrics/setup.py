"""entry and set-up: compile-cache misses of the whole process, and compile
requests inside the measured window (must read 0). Source: program_counter
(jax.monitoring events; utils/compile_cache.py counters)."""


def read(run):
    c = run["counters"]
    return {"setup.compile_misses": float(c["setup.compile_misses"]),
            "window.compiles": float(c["window.compiles"])}
