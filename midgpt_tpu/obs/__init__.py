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
              t_a  the numpy arguments, page bucket and split are chosen
              t_k  the host's time on the key ends (sampled rounds only;
                   the device holds the key and the program splits it)
              t_p  the page tables are built (numpy: the arguments' transfer
                   rides the call)
    t1      jit call returned (dispatch enqueued; NOT compute done)
    t_land  np.asarray(...) force returned — the round's one device
            sync: the tokens are on the host
    t_post  token commit / trie bookkeeping done

— and derives `t_dispatch` = t1-t0 (host assembly + enqueue),
`t_device_wait` = t_land-t1 (device compute + the copy to the host),
`t_host_post` = t_post-t_land. These aggregate to p50/p95 in histograms
and surface on `stats()["obs"]["round_decomp"]`; the serving cells read
the spans themselves (benchmarks/metrics/engine.py) — the baseline a
round-overlap dispatch A/B is held against. The three inner readings cut
the dispatch into the child spans `<kind>.assemble`, `.key`, `.put` and
`.enqueue`, and the dispatch and commit spans say in their `args` what
rode the round: steps, slots, the most steps it could run and which limit
set them; tokens committed, requests ended, and the seconds of the commit
spent inside the client's `on_token` (benchmarks/metrics/engine_dispatch.py
reads all of it). Since PR 53 every jit call the engine enqueues has a
number, `call` (`ServeEngine.dispatches` as the call took it): it rides the
span that brackets the call (`<kind>.enqueue`, `prefill.chunk`, the spec
round's two enqueue spans), the commit of its tokens (`<kind>.host_post`),
and the `engine.dispatch` annotation the ENGINE opens around the call with
`jax.profiler.TraceAnnotation` (not here: this package imports no jax), so
inside a profile every dispatch is one instant on both clocks. The host
phases that do not block on the device (`.dispatch`, `.host_post`,
`prefill.assemble`) also say the engine thread's CPU seconds, `cpu_s`, from
a second injected clock (`cpu_clock`, `time.thread_time`): wall minus
`cpu_s` is time the thread was not running. Under overlap="double"
(sampling/serve.py `_step_overlapped`) round N settles one step late, so
its t1 -> t_land window CONTAINS host work for other rounds; the engine
reports that overlapped span via `hidden_s` and it surfaces as the
`overlap_hidden` decomposition entry — the host time the overlap
actually hid, the A/B headline of
docs/SERVING.md "Round-overlap dispatch".

The module-level `flight_recorder()` singleton is the always-on crash
recorder for the training path: train/checkpoint/supervisor record into
it without plumbing, and crash paths (`DivergenceError`, SIGTERM drain,
serving chaos) call `dump_flight_recorder(rundir)` for postmortems.
"""

from __future__ import annotations

import collections
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
    "live",
    "STEP_SCOPES",
]

# The `jax.named_scope`s the training step program opens around its phases
# (training/train.py make_train_step; `lm_head_loss` inside ops/loss.py so
# every loss variant carries it), beside the model's own embed / block /
# attn / mlp / final_norm (models/gpt.py). Scopes are metadata: they name
# the ops in a device trace and change no instruction. Named once, here:
# docs/OBSERVABILITY.md lists them and tests/test_tracing.py pins them.
STEP_SCOPES = ("cast_params", "lm_head_loss", "grad_accum", "optimizer", "health")

# The recorders most recently built in this process, oldest first. TEMPORARY
# seam: benchmarks/metrics/*.py readers get only what the cell's `run` dict
# holds, and run after the cell's engine (its recorder's only owner) has gone
# out of scope, so the last few stay reachable here. It goes when a
# `benchmark` issue lets the cells hand `obs` to the readers. Bounded: a
# server that builds a recorder per engine keeps four rings at most.
_LIVE: tp.Deque["Observability"] = collections.deque(maxlen=4)


def live() -> tp.List["Observability"]:
    """The Observability objects last built in this process, newest last."""
    return list(_LIVE)


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
        cpu_clock: tp.Callable[[], float] = time.thread_time,
    ):
        self.clock = clock
        # the calling thread's CPU seconds: the engine reads it beside `clock`
        # at the ends of the host phases that say `cpu_s` (module docstring)
        self.cpu_clock = cpu_clock
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
        # A request's life and a round's load, observed by the engine at the
        # boundaries where they happen (sampling/serve.py `_req_phase`, `step`)
        h = self.metrics.histogram
        self.req_phase_s = {
            "req.queue": h("req_queue_s", "submit (or preemption) to admission"),
            "req.prefill": h("req_prefill_s", "admission to first token appended"),
            "req.decode": h("req_decode_s", "first to last token"),
        }
        self._h_round = h("round_s", "one scheduler round, envelope")
        self._h_round_chunks = h(
            "round_prefill_chunks", "prefill chunks that rode the round"
        )
        self._h_round_calls = h(
            "round_prefill_calls", "prefill programs enqueued in the round "
            "(chunks / calls: slots that rode a program)"
        )
        self._h_round_slots = h(
            "round_decode_slots", "slots holding a decoding request after the round"
        )
        self._h_round_steps = h(
            "round_decode_steps", "decode steps the round's program ran "
            "(of decode_chunk x round_group)"
        )
        # what the paged attention kernel's grid did with the round's tables
        self._c_blocks_swept = self.metrics.counter(
            "decode.blocks_swept", "grid steps (compute blocks) of one "
            "layer's paged-attention call, summed over decode steps"
        )
        self._c_blocks_live = self.metrics.counter(
            "decode.blocks_live", "of those, blocks holding a visible key "
            "(copied and computed; the rest cost a bare grid step)"
        )
        self._g_live_share = self.metrics.gauge(
            "decode.live_block_share", "blocks_live / blocks_swept of the "
            "last decode round"
        )
        # prefill programs enqueued and the slot-chunks that rode them
        self._c_prefill_calls = self.metrics.counter(
            "prefill.calls", "prefill programs enqueued"
        )
        self._c_prefill_chunks = self.metrics.counter(
            "prefill.chunks", "slot-chunks prefilled (chunks / calls rode "
            "a program; / (calls x width): how full it was)"
        )
        _LIVE.append(self)

    # -- round timing ---------------------------------------------------

    def record_round(
        self, kind: str, tid: str,
        t0: float, t1: float, t_land: float, t_post: float,
        hidden_s: float = 0.0,
        *,
        cuts: tp.Optional[tp.Tuple[float, tp.Optional[float], float]] = None,
        steps: tp.Optional[int] = None,
        slots: tp.Optional[int] = None,
        chunk: tp.Optional[int] = None,
        limit: tp.Optional[str] = None,
        tokens: tp.Optional[int] = None,
        finished: tp.Optional[int] = None,
        callback_s: tp.Optional[float] = None,
        call: tp.Optional[int] = None,
        bucket: tp.Optional[int] = None,
        cpu: tp.Optional[tp.Tuple[float, float, float, float]] = None,
        blocks: tp.Optional[tp.Tuple[int, int]] = None,
    ) -> None:
        """Record one engine round's boundary clock readings (see module
        docstring for the four-boundary semantics). Also emits the three
        phase spans into the flight recorder with explicit timestamps —
        no extra clock reads beyond the ones the engine already took.
        `hidden_s` is the slice of t1 -> t_land spent doing OTHER rounds'
        host work under round-overlap dispatch (the engine reads the clock
        once more as the settle force starts); it defaults to 0.0 so
        classic rounds record an honest zero.

        What rode the round, where the engine says it: `steps` the program
        ran, `slots` active in it, `chunk` the most steps it could run and
        `limit` the term that set them go on the dispatch span; `tokens`
        committed, requests `finished` and `callback_s` (seconds of the
        commit inside the client's `on_token`) on the host_post span.
        `cuts` = (t_a, t_k, t_p), the engine's readings inside t0 -> t1:
        the children `.assemble`, `.key` (t_k None: a greedy round, no key),
        `.put` (the page tables' build) and `.enqueue` (the one jit call,
        its numpy arguments' transfer with it) tile the dispatch span,
        recorded after it and named its children (`Tracer.complete`).

        `call` is the number of the round's jit call (module docstring): on
        `.enqueue`, with the `steps`, `slots` and page `bucket` its program
        ran at, and on `.host_post`, whose tokens that program made (a round
        under overlap settles a step late). `cpu` = `cpu_clock` read where
        t0, t1, t_land and t_post were: `cpu_s` of the dispatch and of the
        commit (not of the wait, which blocks on the device by design).
        `blocks` = (swept, live) of the round's paged-attention grid
        (`record_decode_blocks`' two integers), on the dispatch span as
        `blocks_swept` / `blocks_live`; the engine hands none where they
        would describe one of a program's several kernels."""
        self._h_dispatch.observe(t1 - t0)
        self._h_device.observe(t_land - t1)
        self._h_post.observe(t_post - t_land)
        self._h_hidden.observe(hidden_s)
        rode: tp.Dict[str, tp.Any] = {}  # the dispatch span's args, the commit's
        commit: tp.Dict[str, tp.Any] = {}
        if steps is not None:
            self._h_round_steps.observe(steps)
            rode.update(steps=steps, slots=slots, chunk=chunk, limit=limit)
        if blocks is not None:
            rode["blocks_swept"], rode["blocks_live"] = blocks
        if tokens is not None:
            commit.update(tokens=tokens, finished=finished, callback_s=callback_s)
        if cpu is not None:
            rode["cpu_s"], commit["cpu_s"] = cpu[1] - cpu[0], cpu[3] - cpu[2]
        if call is not None:
            commit["call"] = call
        complete = self.tracer.complete
        seq = complete(f"{kind}.dispatch", "round", tid, t0, t1 - t0, rode or None)
        if cuts is not None:
            t_a, t_k, t_p = cuts
            self._children(seq, "round", tid, t0, (
                (f"{kind}.assemble", t_a), (f"{kind}.key", t_k), (f"{kind}.put", t_p),
            ))
            complete(
                f"{kind}.enqueue", "round", tid, t_p, t1 - t_p,
                None if call is None else
                {"call": call, "steps": steps, "bucket": bucket, "slots": slots},
                parent=seq,
            )
        complete(f"{kind}.device_wait", "round", tid, t1, t_land - t1)
        complete(f"{kind}.host_post", "round", tid, t_land, t_post - t_land, commit or None)

    def record_prefill_assemble(
        self, tid: str, rid: int, t0: float, t_n: float,
        t_p: tp.Optional[float], t_end: float,
        cpu_s: tp.Optional[float] = None,
    ) -> None:
        """A prefill call's host time BEFORE its enqueue span
        (`prefill.chunk`) opens at `t_end`: the numpy chunk, starts and page
        bucket (t0 -> t_n: the span's self time), then the children
        `prefill.put` (the call's page-table rows, built in numpy) and, in
        a sampled call, `prefill.key` (the host's time on the key, from
        t_p: the program splits it, so two clock reads apart). `cpu_s`: the
        thread's CPU seconds over t0 -> t_end (`cpu_clock` at both ends)."""
        seq = self.tracer.complete(
            "prefill.assemble", "prefill", tid, t0, t_end - t0,
            None if cpu_s is None else {"cpu_s": cpu_s}, rid=rid,
        )
        self._children(seq, "prefill", tid, t_n, (
            ("prefill.put", t_end if t_p is None else t_p),
            ("prefill.key", None if t_p is None else t_end),
        ), rid)

    def _children(
        self, parent: int, cat: str, tid: str, t: float,
        parts: tp.Iterable[tp.Tuple[str, tp.Optional[float]]],
        rid: tp.Optional[int] = None,
    ) -> None:
        """Back-to-back child spans of `parent` from consecutive readings,
        the first starting at `t`: (name, the reading that ends it), a part
        whose reading is None did not happen."""
        for name, t_end in parts:
            if t_end is not None:
                self.tracer.complete(name, cat, tid, t, t_end - t, rid=rid, parent=parent)
                t = t_end

    def record_engine_round(self, dur_s: float, prefill_chunks: int,
                            prefill_calls: int, decode_slots: int) -> None:
        """One scheduler round's envelope (the `engine.round` span's own
        duration) and what rode it."""
        self._h_round.observe(dur_s)
        self._h_round_chunks.observe(prefill_chunks)
        self._h_round_calls.observe(prefill_calls)
        self._h_round_slots.observe(decode_slots)
        self._c_prefill_chunks.inc(prefill_chunks)
        self._c_prefill_calls.inc(prefill_calls)

    def record_decode_blocks(self, swept: int, live: int) -> None:
        """One decode round's paged-attention grid: blocks swept and blocks
        live, per layer call (sampling/serve.py `_count_blocks`)."""
        self._c_blocks_swept.inc(swept)
        self._c_blocks_live.inc(live)
        self._g_live_share.set(live / swept if swept else 0.0)

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
            "rounds": self._h_dispatch.n,
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
