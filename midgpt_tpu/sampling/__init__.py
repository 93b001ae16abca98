"""Sampling and serving. The package's names resolve on first use, so that
importing one module of it (`sampling.pages`, `sampling.engine`) imports
neither the serving engine nor the server built on it."""

import importlib

_HOME = {
    "generate": "engine",
    "ServeEngine": "serve",
    "BackpressureError": "serve",
    "AsyncServeServer": "server",
    "ServerDraining": "server",
    "Scheduler": "scheduler",
    "FCFSScheduler": "scheduler",
    "SLOScheduler": "scheduler",
    "PrefixCache": "prefix_cache",
    "MatchResult": "prefix_cache",
}

__all__ = list(_HOME)


def __getattr__(name: str):
    if name in _HOME:
        return getattr(importlib.import_module(f"{__name__}.{_HOME[name]}"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
