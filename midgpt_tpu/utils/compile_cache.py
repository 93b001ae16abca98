"""Persistent XLA compilation cache for the entry points.

The 124M train step is a fully unrolled 12-layer program and the serving
engine compiles a handful of programs per dtype; a second process running
the same program should load it, not compile it again. `enable()` is
called once by launch.py, sample.py, the benchmark's cells and
chip_smoke.py's JAX children, before first backend use:

  * `JAX_COMPILATION_CACHE_DIR` set: no path is set in code — JAX reads the
    variable itself, so whoever runs the program places the cache.
  * unset: `<checkout>/.jax_cache`, a fixed path. The directory is part of
    the cache key's environment, so a temp name, pid or timestamp in it
    would never hit.

Every compile is kept, not only those over JAX's default one-second floor:
what consecutive processes share is mostly the dozens of sub-second programs
around the big ones (casts, PRNG, sampling), and on a chip each of them is a
compile a later process need not repeat.

Tests leave the cache off (tests/conftest.py never calls this).
"""

from __future__ import annotations

import dataclasses
import os
import typing as tp

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
# jax/_src/dispatch.py hands each of these duration events the jitted
# function's name (`fun_name=`). `backend_compile_duration` wraps the cache
# lookup too, so on a hit it holds the load.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_load_s",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass
class ProgramCost:
    """Seconds one jitted function's programs cost this process."""

    calls: int = 0  # programs compiled or loaded under this name
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_or_load_s: float = 0.0

    @property
    def total_s(self) -> float:
        return self.trace_s + self.lower_s + self.compile_or_load_s


@dataclasses.dataclass
class CompileCacheStats:
    """What this process asked of the persistent cache (jax.monitoring
    events): `requests` compiles consulted it, `hits` were loaded from it,
    `writes` were compiled and stored; and what each program cost, by the
    jitted function's name: seconds tracing, lowering, and compiling or
    loading (`programs`), with the cache's own retrieval seconds in total."""

    dir: str
    requests: int = 0
    hits: int = 0
    writes: int = 0
    retrieval_s: float = 0.0
    programs: tp.Dict[str, ProgramCost] = dataclasses.field(default_factory=dict)

    def _on_event(self, name: str, **kw) -> None:
        field = _EVENTS.get(name)
        if field is not None:
            setattr(self, field, getattr(self, field) + 1)

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if name == _RETRIEVAL:
            self.retrieval_s += secs
            return
        phase = _PHASES.get(name)
        if phase is None:
            return
        fun = str(kw.get("fun_name", "?"))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]  # lowering and compiling say jit(f), tracing says f
        cost = self.programs.setdefault(fun, ProgramCost())
        setattr(cost, phase, getattr(cost, phase) + secs)
        if phase == "compile_or_load_s":
            cost.calls += 1

    def totals(self) -> tp.Dict[str, float]:
        """Seconds over all programs: tracing + lowering, and compiling or
        loading; and how many programs (compile events) there were."""
        costs = self.programs.values()
        return {
            "trace_lower_s": sum(c.trace_s + c.lower_s for c in costs),
            "compile_or_load_s": sum(c.compile_or_load_s for c in costs),
            "programs": sum(c.calls for c in costs),
        }

    def summary(self, top: int = 12) -> str:
        """For the entry points to print at exit. The first line is parsed by
        chip_smoke.py (key=value pairs after the prefix); then one line per
        program, slowest first: where set-up's compile time went."""
        lines = [
            f"compile_cache: dir={self.dir} requests={self.requests} "
            f"hits={self.hits} writes={self.writes}"
        ]
        ranked = sorted(self.programs.items(), key=lambda kv: -kv[1].total_s)
        if ranked:
            t = self.totals()
            lines.append(
                f"  set-up by program: {t['programs']} compiled or loaded in "
                f"{t['compile_or_load_s']:.2f} s (cache retrieval "
                f"{self.retrieval_s:.2f} s), traced + lowered in "
                f"{t['trace_lower_s']:.2f} s"
            )
        for fun, c in ranked[:top]:
            lines.append(
                f"  {c.total_s:8.2f} s  {fun}  x{c.calls}  trace {c.trace_s:.2f} "
                f"lower {c.lower_s:.2f} compile_or_load {c.compile_or_load_s:.2f}"
            )
        if len(ranked) > top:
            rest = sum(c.total_s for _, c in ranked[top:])
            lines.append(f"  {rest:8.2f} s  {len(ranked) - top} other programs")
        return "\n".join(lines)


_CURRENT: tp.Optional[CompileCacheStats] = None


def current() -> tp.Optional[CompileCacheStats]:
    """The stats `enable()` last made in this process (None before it)."""
    return _CURRENT


def enable() -> CompileCacheStats:
    import jax
    import jax.monitoring

    global _CURRENT
    cache_dir = os.environ.get(_ENV)
    if not cache_dir:
        cache_dir = os.path.join(_REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    stats = _CURRENT = CompileCacheStats(dir=cache_dir)
    jax.monitoring.register_event_listener(stats._on_event)
    jax.monitoring.register_event_duration_secs_listener(stats._on_duration)
    return stats
