"""Unified observability: span tracing, flight recorder, metrics export.

One `Observability` object bundles the two primitives (obs/trace.py span
tracer with its bounded flight-recorder ring, obs/metrics.py registry) plus
the serving round-timing decomposition. It is JAX-free and clock-injected:
constructing one compiles nothing, touches no device, and — wired through
`ServeEngine(obs=...)` — adds zero XLA programs and zero jit statics (the
recompile pin in tests/test_recompile_pins.py holds that line).

Round decomposition semantics (docs/OBSERVABILITY.md has the full story):
the engine loop reads its injected clock at four boundaries per round —

    t0      batch assembly starts
    t1      jit call returned (dispatch enqueued; NOT compute done)
    t_land  np.asarray(...) force returned — the round's one device
            sync: the tokens are on the host
    t_post  token commit / trie bookkeeping done

— and derives `t_dispatch` = t1-t0 (host assembly + enqueue),
`t_device_wait` = t_land-t1 (device compute + the copy to the host),
`t_host_post` = t_post-t_land. These aggregate to p50/p95 in histograms
and surface on `stats()["obs"]["round_decomp"]`, loadgen's serve_slo
points, and the bench_serve profiles — the baseline artifact ROADMAP
item 3's round-overlap dispatch A/Bs against. Under overlap="double"
(sampling/serve.py `_step_overlapped`) round N settles one step late, so
its t1 -> t_land window CONTAINS host work for other rounds; the engine
reports that overlapped span via `hidden_s` and it surfaces as the
`overlap_hidden` decomposition entry (`overlap_hidden_ms` on the bench
lines) — the host time the overlap actually hid, the A/B headline of
docs/SERVING.md "Round-overlap dispatch".

The module-level `flight_recorder()` singleton is the always-on crash
recorder for the training path: train/checkpoint/supervisor record into
it without plumbing, and crash paths (`DivergenceError`, SIGTERM drain,
serving chaos) call `dump_flight_recorder(rundir)` for postmortems.
"""

from __future__ import annotations

import os
import time
import typing as tp

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import NULL_TRACER, Tracer

__all__ = [
    "Observability",
    "Tracer",
    "NULL_TRACER",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "flight_recorder",
    "dump_flight_recorder",
]


class Observability:
    """Tracer + metrics + round decomposition, one handle.

    `enabled=False` (or just not passing an Observability at all —
    engine code holds NULL_TRACER in that case) keeps every
    instrumentation site free: no clock reads, no ring appends, and the
    scheduling/token path bit-identical to obs-off.
    """

    def __init__(
        self,
        capacity: int = 16384,
        clock: tp.Callable[[], float] = time.perf_counter,
    ):
        self.clock = clock
        self.tracer = Tracer(capacity=capacity, clock=clock)
        self.metrics = MetricsRegistry()
        # round decomposition histograms, seconds; surfaced in ms
        self._h_dispatch = self.metrics.histogram(
            "round_dispatch_s", "batch assembly + jit enqueue per round"
        )
        self._h_device = self.metrics.histogram(
            "round_device_wait_s", "dispatch return to host landing (device "
            "compute + copy to host)"
        )
        self._h_post = self.metrics.histogram(
            "round_host_post_s", "token commit + trie bookkeeping per round"
        )
        self._h_hidden = self.metrics.histogram(
            "round_overlap_hidden_s", "host work overlapped under an "
            "in-flight dispatch (round-overlap dispatch; 0 when off)"
        )
        self._rounds = self.metrics.counter(
            "rounds_decomposed", "rounds with timing decomposition recorded"
        )

    # -- round timing ---------------------------------------------------

    def record_round(
        self, kind: str, tid: str,
        t0: float, t1: float, t_land: float, t_post: float,
        hidden_s: float = 0.0,
    ) -> None:
        """Record one engine round's boundary clock readings (see module
        docstring for the four-boundary semantics). Also emits the three
        phase spans into the flight recorder with explicit timestamps —
        no extra clock reads beyond the four the engine already took.
        `hidden_s` is the slice of t1 -> t_land spent doing OTHER rounds'
        host work under round-overlap dispatch (the engine reads the clock
        once more as the settle force starts); it defaults to 0.0 so
        classic rounds record an honest zero."""
        self._h_dispatch.observe(t1 - t0)
        self._h_device.observe(t_land - t1)
        self._h_post.observe(t_post - t_land)
        self._h_hidden.observe(hidden_s)
        self._rounds.inc()
        self.tracer.complete(f"{kind}.dispatch", "round", tid, t0, t1 - t0)
        self.tracer.complete(
            f"{kind}.device_wait", "round", tid, t1, t_land - t1
        )
        self.tracer.complete(
            f"{kind}.host_post", "round", tid, t_land, t_post - t_land
        )

    def round_decomp(self) -> tp.Dict[str, tp.Any]:
        """p50/p95/mean per phase, milliseconds (stats() schema)."""
        def _ms(h: Histogram) -> tp.Dict[str, float]:
            s = h.summary()
            return {
                "n": s["n"],
                "mean_ms": round(s["mean"] * 1e3, 3),
                "p50_ms": round(s["p50"] * 1e3, 3),
                "p95_ms": round(s["p95"] * 1e3, 3),
                "max_ms": round(s["max"] * 1e3, 3),
            }

        return {
            "rounds": int(self._rounds.value),
            "dispatch": _ms(self._h_dispatch),
            "device_wait": _ms(self._h_device),
            "host_post": _ms(self._h_post),
            "overlap_hidden": _ms(self._h_hidden),
        }

    # -- unified stats schema -------------------------------------------

    def snapshot(self) -> tp.Dict[str, tp.Any]:
        """The `stats()["obs"]` payload shared by engine/server/
        supervisor: enabled flag, round decomposition, full metrics
        snapshot, and flight-recorder health."""
        snap = self.metrics.snapshot()
        snap.update(
            enabled=True,
            round_decomp=self.round_decomp(),
            spans=len(self.tracer),
            spans_dropped=self.tracer.dropped,
        )
        return snap

    def dump(self, rundir: str, filename: str = "flight_recorder.json") -> str:
        """Write the Chrome trace + a .prom metrics dump into `rundir`."""
        os.makedirs(rundir, exist_ok=True)
        path = self.tracer.dump(os.path.join(rundir, filename))
        prom = os.path.join(rundir, filename.rsplit(".", 1)[0] + ".prom")
        with open(prom, "w", encoding="utf-8") as fh:
            fh.write(self.metrics.to_prometheus())
        return path


DISABLED_SNAPSHOT: tp.Dict[str, tp.Any] = {"enabled": False}

_FLIGHT: tp.Optional[Observability] = None


def flight_recorder() -> Observability:
    """Process-global always-on recorder for the training/supervisor path
    (serving constructs per-engine Observability explicitly). Lazy so
    importing midgpt_tpu never pays for it."""
    global _FLIGHT
    if _FLIGHT is None:
        _FLIGHT = Observability()
    return _FLIGHT


def dump_flight_recorder(
    rundir: str, filename: str = "flight_recorder.json"
) -> tp.Optional[str]:
    """Dump the global recorder if it was ever touched; None otherwise.
    Crash paths call this unconditionally — a run that never recorded
    anything leaves no file rather than an empty lie."""
    if _FLIGHT is None:
        return None
    return _FLIGHT.dump(rundir, filename)
