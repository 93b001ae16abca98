"""Host-side span tracer with a bounded ring-buffer flight recorder.

JAX-free and clock-injected by design: the tracer never imports jax, never
touches device state, and reads time only through the callable handed to it
at construction — the same injectable-clock discipline the serving engine
uses (sampling/serve.py `clock=`), so tests drive it with a fake clock and
graftcheck GC012 has nothing to flag. Events are recorded as cheap tuples
into a `collections.deque(maxlen=...)`: when the ring fills, the OLDEST
events fall off and `dropped` counts them — a flight recorder keeps the
crash-adjacent tail, not the takeoff.

Export is Chrome trace-event JSON (the `{"traceEvents": [...]}` container),
loadable in Perfetto / chrome://tracing. Span begin/end pairs are emitted
as complete events (ph "X", ts/dur in microseconds), point events as
instants (ph "i"), and long-lived request lifecycles as async begin/end
pairs (ph "b"/"e") keyed by id so overlapping requests render as separate
tracks. Thread names ("engine", "server", "train", ...) become tid lanes
via metadata events (ph "M", name "thread_name").

Causality and identity: every event carries the request id it was given
(`rid=`; spans of one request share it) and its parent — the sequence
number of the innermost `span()` open on the same thread and lane when it
was recorded, which the tracer tracks by itself. A layer's self time is its
span's duration minus its children's. Ring tuple, positions fixed:
`(kind, name, cat, tid, t, dur, ident, args, rid, parent, seq)`; export
writes `rid` / `parent` / `seq` into `args`.

The off switch is `NULL_TRACER`: a shared singleton whose `span()` returns
one reusable no-op context manager and whose record methods are `pass`.
Instrumented code calls the tracer unconditionally and stays branch-free;
with NULL_TRACER in place the per-call cost is one attribute lookup and an
empty function body — sub-microsecond, zero clock reads, zero allocation.
"""

from __future__ import annotations

import json
import threading
import time
import typing as tp
from collections import deque

# Event kinds stored in the ring (first tuple field). Kept as one-char
# tags: the ring holds tens of thousands of tuples and these are compared
# on every export.
_COMPLETE = "X"
_INSTANT = "i"
_ASYNC_BEGIN = "b"
_ASYNC_END = "e"


class _SpanHandle:
    """Context manager for one open span; re-armed per `span()` call.

    Not reentrant and not thread-safe per instance — each `span()` call
    returns a fresh handle, so nesting and cross-thread use are safe at
    the Tracer level (the ring append is the only shared mutation, and
    deque.append is atomic under the GIL).
    """

    __slots__ = ("_tracer", "_name", "_cat", "_tid", "_rid", "_args", "t0",
                 "_seq", "_stack", "dur")

    def __init__(self, tracer: "Tracer", name: str, cat: str, tid: str,
                 rid: tp.Optional[int] = None, args: tp.Optional[dict] = None):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._tid = tid
        self._rid = rid
        self._args = args
        # set on entry / exit, for callers that also feed a histogram or
        # end a span of explicit readings where this one began
        self.t0 = 0.0
        self.dur = 0.0

    def __enter__(self) -> "_SpanHandle":
        tr = self._tracer
        self._seq = tr._next_seq()
        self._stack = tr._open_stack(self._tid)
        self._stack.append(self._seq)
        self.t0 = tr._clock()
        return self

    def __exit__(self, *exc) -> None:
        tr = self._tracer
        self.dur = tr._clock() - self.t0
        stack = self._stack
        stack.pop()
        tr._push(
            (_COMPLETE, self._name, self._cat, self._tid, self.t0, self.dur,
             None, self._args, self._rid, stack[-1] if stack else None, self._seq)
        )


class _NullSpan:
    """The no-op context manager NULL_TRACER hands out — one shared
    instance, no state, so `with tracer.span(...)` costs two empty calls
    when tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL_SPAN = _NullSpan()


class Tracer:
    """Bounded-ring span recorder. All timestamps come from the injected
    `clock` (seconds, monotonic-ish); export rebases them to the tracer's
    construction instant so Perfetto timelines start near zero."""

    def __init__(
        self,
        capacity: int = 16384,
        clock: tp.Callable[[], float] = time.perf_counter,
    ):
        self._clock = clock
        self._ring: tp.Deque[tuple] = deque(maxlen=capacity)
        self._capacity = capacity
        self._t_base = clock()
        self.dropped = 0
        self._seq = 0
        # open `span()`s per (thread, lane): the top is the parent of
        # whatever that thread records on that lane next
        self._open: tp.Dict[tp.Tuple[int, str], tp.List[int]] = {}

    # -- recording -------------------------------------------------------

    def _push(self, ev: tuple) -> None:
        if len(self._ring) == self._capacity:
            self.dropped += 1
        self._ring.append(ev)

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _open_stack(self, tid: str) -> tp.List[int]:
        return self._open.setdefault((threading.get_ident(), tid), [])

    def _record(self, kind, name, cat, tid, t, dur, ident, args, rid,
                parent: tp.Optional[int] = None) -> int:
        if parent is None:
            stack = self._open.get((threading.get_ident(), tid))
            parent = stack[-1] if stack else None
        seq = self._next_seq()
        self._push((kind, name, cat, tid, t, dur, ident, args, rid, parent, seq))
        return seq

    def span(self, name: str, cat: str = "", tid: str = "main",
             rid: tp.Optional[int] = None,
             args: tp.Optional[dict] = None) -> _SpanHandle:
        """Context manager measuring one host-side phase; `args` say what
        rode it (known at entry)."""
        return _SpanHandle(self, name, cat, tid, rid, args)

    def complete(
        self, name: str, cat: str, tid: str, start: float, dur: float,
        args: tp.Optional[dict] = None, rid: tp.Optional[int] = None,
        parent: tp.Optional[int] = None,
    ) -> int:
        """Record a span from explicit clock readings — for phases whose
        boundaries were already captured (the round decomposition reads
        the clock once per boundary and derives several spans). Returns the
        span's sequence number: handed back as `parent`, it makes a later
        span of explicit readings this one's child, whatever `span()` is
        open by then (absent, the innermost open one is the parent). Record
        a child AFTER its parent: where two spans start together, readers
        take the later entry as the inner one."""
        return self._record(_COMPLETE, name, cat, tid, start, dur, None, args, rid, parent)

    def instant(
        self, name: str, cat: str = "", tid: str = "main",
        args: tp.Optional[dict] = None, rid: tp.Optional[int] = None,
    ) -> None:
        """Point event (admission, eviction, shed, rollback, ...)."""
        self._record(_INSTANT, name, cat, tid, self._clock(), 0.0, None, args, rid)

    def async_begin(
        self, name: str, ident: tp.Union[str, int], cat: str = "",
        tid: str = "main", args: tp.Optional[dict] = None,
        t: tp.Optional[float] = None,
    ) -> None:
        """Open one track of a long-lived overlapping lifecycle (a request
        from submit to finish). `ident` pairs it with async_end and is the
        event's request id. `t` is a reading the caller already took from
        the same clock (the engine stamps request phases with the readings
        it hands its clients); absent, the tracer reads its own."""
        self._record(_ASYNC_BEGIN, name, cat, tid,
                     self._clock() if t is None else t, 0.0, ident, args, ident)

    def async_end(
        self, name: str, ident: tp.Union[str, int], cat: str = "",
        tid: str = "main", args: tp.Optional[dict] = None,
        t: tp.Optional[float] = None,
    ) -> None:
        self._record(_ASYNC_END, name, cat, tid,
                     self._clock() if t is None else t, 0.0, ident, args, ident)

    # -- introspection / export -----------------------------------------

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0

    def events(self) -> tp.List[tuple]:
        """Raw ring contents, oldest first: (kind, name, cat, tid, t, dur,
        ident, args, rid, parent, seq) — tests and the benchmark index the
        first eight."""
        return list(self._ring)

    def export(self) -> tp.List[dict]:
        """Chrome trace events (ts/dur in microseconds, rebased to the
        tracer's birth). tid strings map to stable integer lanes with
        `thread_name` metadata events so Perfetto labels them."""
        tids: tp.Dict[str, int] = {}
        out: tp.List[dict] = []
        for kind, name, cat, tid, t, dur, ident, args, rid, parent, seq in self._ring:
            lane = tids.setdefault(tid, len(tids) + 1)
            ev: tp.Dict[str, tp.Any] = {
                "name": name,
                "cat": cat or "obs",
                "ph": kind,
                "pid": 1,
                "tid": lane,
                "ts": round((t - self._t_base) * 1e6, 3),
            }
            if kind == _COMPLETE:
                ev["dur"] = round(dur * 1e6, 3)
            if kind == _INSTANT:
                ev["s"] = "t"  # thread-scoped instant
            if ident is not None:
                ev["id"] = ident
            args = dict(args) if args else {}
            if kind == _COMPLETE:
                args["seq"] = seq  # what a child's `parent` points at
            if parent is not None:
                args["parent"] = parent
            if rid is not None:
                args["rid"] = rid
            if args:
                ev["args"] = args
            out.append(ev)
        for tid, lane in tids.items():
            out.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 1,
                    "tid": lane,
                    "args": {"name": tid},
                }
            )
        return out

    def dump(self, path: str) -> str:
        """Write `{"traceEvents": [...]}` to `path`; returns the path."""
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"traceEvents": self.export()}, fh)
        return path


class _NullTracer:
    """Off switch. Shares the Tracer surface; every method is free."""

    __slots__ = ()

    dropped = 0

    def span(self, name: str, cat: str = "", tid: str = "main",
             rid: tp.Optional[int] = None,
             args: tp.Optional[dict] = None) -> _NullSpan:
        return _NULL_SPAN

    def complete(self, *a, **k) -> None:
        pass

    def instant(self, *a, **k) -> None:
        pass

    def async_begin(self, *a, **k) -> None:
        pass

    def async_end(self, *a, **k) -> None:
        pass

    def __len__(self) -> int:
        return 0

    def clear(self) -> None:
        pass

    def events(self) -> tp.List[tuple]:
        return []

    def export(self) -> tp.List[dict]:
        return []

    def dump(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"traceEvents": []}, fh)
        return path


NULL_TRACER = _NullTracer()
