"""Persistent XLA compilation cache for the entry points.

The 124M train step is a fully unrolled 12-layer program and the serving
engine compiles a handful of programs per dtype; a second process running
the same program should load it, not compile it again. `enable()` is
called once by launch.py, sample.py, bench.py and chip_smoke.py's JAX
children, before first backend use:

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

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}


@dataclasses.dataclass
class CompileCacheStats:
    """What this process asked of the persistent cache (jax.monitoring
    events): `requests` compiles consulted it, `hits` were loaded from it,
    `writes` were compiled and stored."""

    dir: str
    requests: int = 0
    hits: int = 0
    writes: int = 0

    def _on_event(self, name: str, **kw) -> None:
        field = _EVENTS.get(name)
        if field is not None:
            setattr(self, field, getattr(self, field) + 1)

    def summary(self) -> str:
        """One line for the entry points to print at exit (chip_smoke.py
        parses it: key=value pairs after the prefix)."""
        return (
            f"compile_cache: dir={self.dir} requests={self.requests} "
            f"hits={self.hits} writes={self.writes}"
        )


def enable() -> CompileCacheStats:
    import jax
    import jax.monitoring

    cache_dir = os.environ.get(_ENV)
    if not cache_dir:
        cache_dir = os.path.join(_REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    stats = CompileCacheStats(dir=cache_dir)
    jax.monitoring.register_event_listener(stats._on_event)
    return stats
