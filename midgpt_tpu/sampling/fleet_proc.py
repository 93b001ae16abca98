"""Cross-process fleet serving: the replica socket transport and worker
protocol (docs/ROBUSTNESS.md "Cross-process fleet").

ROADMAP item 4a promotes the fleet's replica boundary (sampling/fleet.py)
from an object boundary to a real OS process boundary: each replica is a
worker process hosting one ServeEngine — its own CPU mesh, its own jit
cache, its own host-RAM SpillTier — and the FleetRouter drives it through
`ProcReplica`, which implements the exact duck-typed engine surface the
router already speaks (`submit`/`step`/`idle`/`finished`/counter attrs),
so the in-process path stays bit-identical and every r18 fleet test passes
unchanged. Deliberately NO `jax.distributed`: replicas share no arrays and
no collectives — everything that crosses the boundary is plain host data
over a socket (the GC015 wire contract, now literal). Workers are
CPU-only (tools/fleet_worker.py pins the platform): the spawning parent
holds JAX, and a chip belongs to one process at a time.

Wire format — length-prefixed, crc32-framed JSON + binary blobs:

    header:  magic "MGW1" | u32 payload_len | u32 crc32(payload)
    payload: u32 json_len | JSON bytes | blob bytes (concatenated)

ndarrays anywhere in a message tree are replaced by ``{"__blob__": i}``
descriptors (dtype/shape in the JSON header) and travel as raw bytes —
never pickled, never a live device array. The crc32 is verified BEFORE the
JSON is decoded: a truncated or bit-flipped frame raises `WireFrameError`
and is dropped with the connection, mirroring the SpillTier rule — a bad
frame degrades to a retried RPC (harvest marks make retries idempotent),
never into a decode.

Robustness weight lives in `ReplicaTransport`: per-RPC deadlines
(socket timeouts -> structured `TransportError`), connect/call retry on
the shared `robustness/backoff.py` schedule, a wire heartbeat (`last_ok`
on the injected clock) feeding the router's existing clock-injected health
checks, and chaos hooks (`arm_wire_corrupt` / `arm_wire_stall` /
`drop_conn`) for the `wire_corrupt` / `wire_stall` / `conn_drop` fault
kinds. A worker that stays unreachable past the retry budget raises
`ReplicaGoneError`; the router's consecutive-failure health check then
fires the same `_crash` failover path as an in-process engine death — a
`kill -9` of a worker looks exactly like r18's `engine_crash`, proven
token-for-token by the `proc_kill9` chaos gate.

Retry idempotence, per verb: `submit` carries a router-side `seq` the
worker dedups on (a retried admit never double-admits); `harvest` is a
high-water-mark read (`events_from` + `known_uids` — the request is the
ack); a retried `step` just runs an extra engine round, which greedy
batch-composition independence makes parity-neutral; `stats`/`conserve`
are pure reads. SIGTERM drains gracefully through the existing preempt
flag (robustness/preempt.py): the handler only flips the flag, the worker
loop notices it between RPCs, refuses new admissions with a non-retryable
backpressure reply, finishes its in-flight streams, and exits once idle
and disconnected. Spilled KV survives a drain — `spill_export` /
`spill_import` move `SpillTransferItem`s (checksums travel with their
pages, so take-side verification still covers the bytes end to end) and
the tier ledger extends with `received`/`transferred` buckets that keep
the conservation law closing across the boundary.

This module is import-light (no jax, no engine imports at module scope):
the frame codec, errors, and transport are unit-testable with nothing but
numpy + sockets; ProcReplica lazy-imports the engine types it mirrors.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import time
import typing as tp
import zlib

import numpy as np

from midgpt_tpu.robustness.backoff import retry_with_backoff

# -- structured errors (analysis/error_contracts.py registers the field
# contracts; GC016 enforces them at every raise site) -----------------------


class TransportError(ConnectionError):
    """One RPC attempt failed at the transport layer — connect refused,
    send/recv error, or the response did not land inside the per-RPC
    deadline. Retryable by construction: `ReplicaTransport.call` absorbs
    these on the shared backoff schedule and only escalates to
    `ReplicaGoneError` when the budget is spent."""

    def __init__(
        self,
        message: str,
        *,
        host: str,
        port: int,
        rpc: str,
        deadline_s: tp.Optional[float] = None,
    ):
        super().__init__(message)
        self.host = host
        self.port = port
        self.rpc = rpc
        self.deadline_s = deadline_s


class WireFrameError(ValueError):
    """A frame failed validation BEFORE its JSON was decoded — bad magic,
    truncated read, length overflow, or crc32 mismatch. The connection is
    dropped (a desynced stream cannot be trusted for the next frame) and
    the RPC retries on a fresh one; corrupt bytes never reach a decode."""

    def __init__(self, message: str, *, reason: str, nbytes: int = 0):
        super().__init__(message)
        self.reason = reason
        self.nbytes = nbytes


class ReplicaGoneError(ConnectionError):
    """The worker stayed unreachable past the transport's full retry
    budget. This is the wire's verdict that the replica is dead; the
    router's consecutive-failure health check turns it into the same
    failover `_crash` path an in-process engine death takes."""

    def __init__(
        self,
        message: str,
        *,
        host: str,
        port: int,
        rpc: str,
        attempts: int,
    ):
        super().__init__(message)
        self.host = host
        self.port = port
        self.rpc = rpc
        self.attempts = attempts


# -- frame codec ------------------------------------------------------------

_MAGIC = b"MGW1"
_HEADER = struct.Struct("<4sII")  # magic | payload_len | crc32(payload)
_JLEN = struct.Struct("<I")
# Sanity bound, not a resource budget: tiny-model KV pages are KBs; a
# length field past this is a desynced/corrupt stream, not a big message.
MAX_FRAME_BYTES = 1 << 28


def _pack_tree(obj: tp.Any, blobs: tp.List[np.ndarray]) -> tp.Any:
    """JSON-ify a message tree, lifting ndarrays out as indexed blobs."""
    if isinstance(obj, np.ndarray):
        # reshape back: ascontiguousarray promotes 0-d to 1-d, which would
        # silently change the shape a 0-d scalar lands with on the far side
        blobs.append(np.ascontiguousarray(obj).reshape(obj.shape))
        return {"__blob__": len(blobs) - 1}
    if isinstance(obj, dict):
        return {str(k): _pack_tree(v, blobs) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_pack_tree(v, blobs) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    return obj


def _unpack_tree(obj: tp.Any, blobs: tp.List[np.ndarray]) -> tp.Any:
    if isinstance(obj, dict):
        if set(obj) == {"__blob__"}:
            return blobs[obj["__blob__"]]
        return {k: _unpack_tree(v, blobs) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_unpack_tree(v, blobs) for v in obj]
    return obj


def encode_frame(obj: tp.Any) -> bytes:
    """Message tree -> one framed byte string (module docstring layout)."""
    blobs: tp.List[np.ndarray] = []
    tree = _pack_tree(obj, blobs)
    head = json.dumps(
        {
            "tree": tree,
            "blobs": [
                {"dtype": str(b.dtype), "shape": list(b.shape)} for b in blobs
            ],
        },
        separators=(",", ":"),
    ).encode("utf-8")
    payload = b"".join(
        [_JLEN.pack(len(head)), head] + [b.tobytes() for b in blobs]
    )
    return _HEADER.pack(_MAGIC, len(payload), zlib.crc32(payload)) + payload


def decode_frame(data: bytes) -> tp.Any:
    """One framed byte string -> message tree. Magic, length, and crc32
    are all verified before a single byte of JSON is parsed."""
    if len(data) < _HEADER.size:
        raise WireFrameError(
            f"frame truncated at {len(data)} bytes (header is "
            f"{_HEADER.size})",
            reason="truncated",
            nbytes=len(data),
        )
    magic, plen, crc = _HEADER.unpack_from(data)
    if magic != _MAGIC:
        raise WireFrameError(
            f"bad frame magic {magic!r}", reason="bad_magic", nbytes=len(data)
        )
    if plen > MAX_FRAME_BYTES:
        raise WireFrameError(
            f"frame length {plen} exceeds {MAX_FRAME_BYTES} — desynced or "
            "corrupt stream",
            reason="length",
            nbytes=len(data),
        )
    payload = data[_HEADER.size:]
    if len(payload) != plen:
        raise WireFrameError(
            f"frame payload truncated: {len(payload)} of {plen} bytes",
            reason="truncated",
            nbytes=len(data),
        )
    if zlib.crc32(payload) != crc:
        raise WireFrameError(
            "frame checksum mismatch — rejecting before decode",
            reason="checksum",
            nbytes=len(data),
        )
    (jlen,) = _JLEN.unpack_from(payload)
    if _JLEN.size + jlen > plen:
        raise WireFrameError(
            f"frame JSON header overruns payload ({jlen} bytes declared)",
            reason="length",
            nbytes=len(data),
        )
    head = json.loads(payload[_JLEN.size:_JLEN.size + jlen])
    blobs: tp.List[np.ndarray] = []
    off = _JLEN.size + jlen
    for desc in head.get("blobs", ()):
        dt = np.dtype(desc["dtype"])
        shape = tuple(int(s) for s in desc["shape"])
        count = int(np.prod(shape)) if shape else 1
        end = off + count * dt.itemsize
        if end > plen:
            raise WireFrameError(
                "frame blob section truncated", reason="truncated",
                nbytes=len(data),
            )
        # copy(): frombuffer views are read-only and entries may be
        # mutated after landing (e.g. SpillTier.corrupt_one)
        blobs.append(
            np.frombuffer(payload, dt, count, off).reshape(shape).copy()
        )
        off = end
    return _unpack_tree(head["tree"], blobs)


def _recv_exact(sock: socket.socket, n: int, what: str) -> bytes:
    """Read exactly n bytes. EOF before the first byte is a clean peer
    close (ConnectionError); EOF mid-read is a truncated frame."""
    chunks: tp.List[bytes] = []
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            if got == 0 and what == "header":
                raise ConnectionError("peer closed the connection")
            raise WireFrameError(
                f"connection closed mid-{what}: {got} of {n} bytes",
                reason="truncated",
                nbytes=got,
            )
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def read_frame_bytes(sock: socket.socket) -> bytes:
    """Read one raw frame off the socket (header validated enough to size
    the read; full verification happens in decode_frame)."""
    head = _recv_exact(sock, _HEADER.size, "header")
    magic, plen, _ = _HEADER.unpack(head)
    if magic != _MAGIC:
        raise WireFrameError(
            f"bad frame magic {magic!r}", reason="bad_magic",
            nbytes=len(head),
        )
    if plen > MAX_FRAME_BYTES:
        raise WireFrameError(
            f"frame length {plen} exceeds {MAX_FRAME_BYTES}",
            reason="length",
            nbytes=len(head),
        )
    return head + _recv_exact(sock, plen, "payload")


def read_frame(sock: socket.socket) -> tp.Any:
    return decode_frame(read_frame_bytes(sock))


def write_frame(sock: socket.socket, obj: tp.Any) -> int:
    data = encode_frame(obj)
    sock.sendall(data)
    return len(data)


# -- spill transfer payload (GC015 wire item) -------------------------------


@dataclasses.dataclass
class SpillTransferItem:
    """One spilled page crossing the process boundary: its full-prefix
    key, host-landed blocks (the blessed {k, v, k_scale, v_scale} shape),
    the ORIGINAL spill-time crc32 — preserved end to end so the take-side
    verification still covers transit AND residence — and the
    weights_version the KV was computed under."""

    key: tp.Tuple[int, ...]
    blocks: tp.Dict[str, np.ndarray]
    checksum: int
    weights_version: str


# -- router-side transport --------------------------------------------------


class ReplicaTransport:
    """One worker's socket endpoint: framed request/response RPCs with
    per-call deadlines, bounded reconnect/retry on the shared backoff
    schedule, a wire heartbeat, and the wire-level chaos hooks (module
    docstring)."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        rpc_deadline_s: float = 120.0,
        call_retries: int = 3,
        retry_base_s: float = 0.05,
        clock: tp.Callable[[], float] = time.perf_counter,
        sleep: tp.Callable[[float], None] = time.sleep,
        obs=None,
        obs_tid: str = "transport",
    ):
        if call_retries < 1:
            raise ValueError(f"call_retries must be >= 1, got {call_retries}")
        self.host = host
        self.port = port
        self.rpc_deadline_s = rpc_deadline_s
        self.call_retries = call_retries
        self.retry_base_s = retry_base_s
        self._clock = clock
        self._sleep = sleep
        self._sock: tp.Optional[socket.socket] = None
        self._seq = 0
        # wire heartbeat: injected-clock stamp of the last successful RPC
        # (FleetRouter's staleness check reads the same clock family)
        self.last_ok: tp.Optional[float] = None
        # counters
        self.rpc_count = 0
        self.wire_bytes = 0
        self.connects = 0
        self.retries = 0
        self.corrupt_frames = 0
        self.deadline_expiries = 0
        self.forced_drops = 0
        self._lat_s: tp.List[float] = []
        # chaos arms (wire_corrupt / wire_stall fault kinds)
        self._corrupt_next = False
        self._stall_next = False
        self._obs = obs
        self._obs_tid = obs_tid
        self._h_rpc = (
            None
            if obs is None
            else obs.metrics.histogram(
                "transport_rpc_s",
                "round-trip latency per fleet-transport RPC",
            )
        )

    # -- connection lifecycle ------------------------------------------

    def _ensure_conn(self, rpc: str, deadline_s: float) -> socket.socket:
        if self._sock is not None:
            return self._sock
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=deadline_s
            )
        except OSError as e:
            raise TransportError(
                f"connect to {self.host}:{self.port} failed for rpc "
                f"{rpc!r}: {e}",
                host=self.host,
                port=self.port,
                rpc=rpc,
                deadline_s=deadline_s,
            ) from e
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = sock
        self.connects += 1
        return sock

    def _drop_sock(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def close(self) -> None:
        self._drop_sock()

    @property
    def reconnects(self) -> int:
        return max(self.connects - 1, 0)

    # -- chaos hooks (robustness/faults.py kinds) ----------------------

    def drop_conn(self) -> None:
        """`conn_drop`: abruptly close the live connection; the next RPC
        must reconnect transparently (counted in `reconnects`)."""
        self.forced_drops += 1
        self._drop_sock()

    def arm_wire_corrupt(self) -> None:
        """`wire_corrupt`: flip a byte in the NEXT received frame before
        verification — the checksum must reject it pre-decode and the RPC
        must recover by retrying on a fresh connection."""
        self._corrupt_next = True

    def arm_wire_stall(self) -> None:
        """`wire_stall`: the NEXT RPC's response never lands inside its
        deadline (the request is sent, the read abandoned, the connection
        dropped — exactly what a deadline expiry leaves behind)."""
        self._stall_next = True

    # -- the RPC -------------------------------------------------------

    def call(
        self,
        op: str,
        payload: tp.Optional[tp.Dict[str, tp.Any]] = None,
        *,
        deadline_s: tp.Optional[float] = None,
        retries: tp.Optional[int] = None,
    ) -> tp.Dict[str, tp.Any]:
        """One request/response RPC. Transient transport failures retry on
        the shared backoff schedule (`robustness/backoff.py`); the `seq`
        assigned here is stable across those retries so side-effectful
        verbs dedup worker-side. Exhausting the budget raises
        `ReplicaGoneError`."""
        dl = self.rpc_deadline_s if deadline_s is None else deadline_s
        budget = self.call_retries if retries is None else retries
        self._seq += 1
        seq = self._seq
        self.rpc_count += 1
        t_start = self._clock()

        def attempt() -> tp.Dict[str, tp.Any]:
            sock = self._ensure_conn(op, dl)
            sock.settimeout(dl)
            req = dict(payload or {})
            req["op"] = op
            req["seq"] = seq
            try:
                self.wire_bytes += write_frame(sock, req)
                if self._stall_next:
                    self._stall_next = False
                    self.deadline_expiries += 1
                    self._drop_sock()
                    raise TransportError(
                        f"rpc {op!r} response did not land within {dl}s "
                        f"(wire stall)",
                        host=self.host,
                        port=self.port,
                        rpc=op,
                        deadline_s=dl,
                    )
                raw = read_frame_bytes(sock)
            except socket.timeout as e:
                self.deadline_expiries += 1
                self._drop_sock()
                raise TransportError(
                    f"rpc {op!r} exceeded its {dl}s deadline",
                    host=self.host,
                    port=self.port,
                    rpc=op,
                    deadline_s=dl,
                ) from e
            except WireFrameError:
                self.corrupt_frames += 1
                self._drop_sock()
                raise
            except OSError as e:
                self._drop_sock()
                raise TransportError(
                    f"rpc {op!r} transport failure: {e}",
                    host=self.host,
                    port=self.port,
                    rpc=op,
                    deadline_s=dl,
                ) from e
            self.wire_bytes += len(raw)
            if self._corrupt_next:
                self._corrupt_next = False
                flipped = bytearray(raw)
                flipped[-1] ^= 0xFF
                raw = bytes(flipped)
            try:
                reply = decode_frame(raw)
            except WireFrameError:
                # checksum/shape rejection AFTER a full read: the stream
                # itself is suspect — drop it and retry on a fresh one
                self.corrupt_frames += 1
                self._drop_sock()
                raise
            return reply

        def on_backoff(delay: float) -> None:
            self.retries += 1
            self._sleep(delay)

        try:
            reply = retry_with_backoff(
                attempt,
                retries=budget,
                base_s=self.retry_base_s,
                retry_on=(TransportError, WireFrameError),
                sleep=on_backoff,
            )
        except (TransportError, WireFrameError) as e:
            raise ReplicaGoneError(
                f"replica {self.host}:{self.port} unreachable after "
                f"{budget} attempt(s) on rpc {op!r}: {e}",
                host=self.host,
                port=self.port,
                rpc=op,
                attempts=budget,
            ) from e
        now = self._clock()
        self.last_ok = now
        self._lat_s.append(now - t_start)
        if self._h_rpc is not None:
            self._h_rpc.observe(now - t_start)
            self._obs.tracer.complete(
                f"transport.{op}", "rpc", self._obs_tid, t_start,
                now - t_start,
            )
        return reply

    # -- heartbeat + reporting -----------------------------------------

    def heartbeat_age(self, now: float) -> tp.Optional[float]:
        """Seconds since the last successful RPC on the injected clock
        (None before the first) — the wire heartbeat the router's
        staleness check consumes."""
        return None if self.last_ok is None else now - self.last_ok

    def _lat_pct(self, q: float) -> float:
        if not self._lat_s:
            return 0.0
        return float(np.percentile(np.asarray(self._lat_s), q))

    def stats(self) -> tp.Dict[str, tp.Any]:
        return {
            "rpc_count": self.rpc_count,
            "wire_bytes": self.wire_bytes,
            "connects": self.connects,
            "reconnects": self.reconnects,
            "retries": self.retries,
            "corrupt_frames": self.corrupt_frames,
            "deadline_expiries": self.deadline_expiries,
            "forced_drops": self.forced_drops,
            "rpc_p50_ms": round(self._lat_pct(50) * 1e3, 3),
            "rpc_p95_ms": round(self._lat_pct(95) * 1e3, 3),
        }


# -- router-side replica proxy ----------------------------------------------


class ProcReplica:
    """FleetRouter-facing proxy for one worker process. Implements the
    duck-typed engine surface the router drives (submit / step / idle /
    finished / counters), so `FleetRouter([ProcReplica(...), ...])` is the
    in-process fleet with the object boundary promoted to a process
    boundary — and nothing else changed.

    `step()` is one worker engine round plus a harvest: the worker's
    token events replay through the router's `on_token` relay and its
    durable finishes land in `self.finished`, both under high-water-mark
    idempotence so a retried RPC never duplicates either. RPC failures
    propagate as exceptions, which is exactly what the router's
    consecutive-failure health check counts — kill -9 detection IS the
    existing health machinery, fed by the wire."""

    is_proc = True

    def __init__(self, transport: ReplicaTransport):
        self.transport = transport
        hello = transport.call("hello")
        self.pid = int(hello["pid"])
        self.page_size = int(hello["page_size"])
        self.max_pages_per_slot = int(hello.get("max_pages_per_slot", 0))
        self.temperature = float(hello.get("temperature", 0.0))
        self.weights_version = str(hello.get("weights_version", "inline"))
        # truthy sentinel iff the worker engine runs its prefix trie — the
        # router validates `prefix_cache is None`, never dereferences it
        self.prefix_cache = True if hello.get("prefix_cache") else None
        self.on_token: tp.Optional[tp.Callable[[int, int, float], None]] = None
        self.finished: tp.Dict[int, tp.Any] = {}
        self._idle = True
        self._events_seen = 0
        # counters mirrored from the worker at every harvest (FleetRouter
        # stats/chaos summaries read these attribute names off engines)
        self.rounds = 0
        self.preemptions = 0
        self.shed = 0
        self.timeouts = 0
        self.cancelled = 0
        self.decode_kills = 0
        self.prefix_evictions = 0
        self.spill_readopted_pages = 0
        self._prefix_matched_tokens = 0
        self._prefix_matchable_tokens = 0
        self._hit_rate = 0.0
        self._spill_ledger: tp.Dict[str, int] = {}

    # -- engine surface the router drives ------------------------------

    def attach_spill(self, tier) -> None:
        """The worker owns its OWN tier (host RAM is per-process); the
        router-side shared tier only binds page_size here so replicas
        keep agreeing on the spill granule."""
        tier.set_page_size(self.page_size)
        self._router_spill = tier

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        eos_id: tp.Optional[int] = None,
        ttl_s: tp.Optional[float] = None,
    ) -> int:
        reply = self.transport.call(
            "submit",
            {
                "prompt": np.asarray(prompt, np.int32).reshape(-1),
                "max_new_tokens": int(max_new_tokens),
                "eos_id": None if eos_id is None else int(eos_id),
                "ttl_s": None if ttl_s is None else float(ttl_s),
            },
        )
        if reply.get("error") == "backpressure":
            from midgpt_tpu.sampling.serve import BackpressureError

            raise BackpressureError(
                str(reply.get("message", "replica shed the request")),
                needed_pages=reply.get("needed_pages"),
                backlog_pages=reply.get("backlog_pages"),
                budget_pages=reply.get("budget_pages"),
                retryable=bool(reply.get("retryable", False)),
            )
        self._raise_remote(reply, "submit")
        self._idle = False
        return int(reply["uid"])

    @property
    def idle(self) -> bool:
        return self._idle

    def step(self) -> None:
        reply = self.transport.call("step")
        self._raise_remote(reply, "step")
        self._apply_counters(reply)
        self._harvest()

    def run(self) -> None:
        """Drive the worker to idle — the ServeEngine.run() shape, for
        solo warm passes and reference drives outside a FleetRouter."""
        r = 0
        while not self.idle:
            self.step()
            r += 1
            if r >= 100_000:
                raise RuntimeError("proc replica run() did not converge")

    def _harvest(self) -> None:
        reply = self.transport.call(
            "harvest",
            {
                "events_from": self._events_seen,
                "known_uids": list(self.finished),
            },
        )
        self._raise_remote(reply, "harvest")
        for ruid, tok, t in reply.get("events", ()):
            self._events_seen += 1
            if self.on_token is not None:
                self.on_token(int(ruid), int(tok), float(t))
        if reply.get("finished"):
            from midgpt_tpu.sampling.serve import FinishedRequest

            for fin in reply["finished"]:
                uid = int(fin["uid"])
                self.finished[uid] = FinishedRequest(
                    uid,
                    np.asarray(fin["tokens"]),
                    [float(t) for t in fin.get("token_times", ())],
                    str(fin["status"]),
                )
        self._apply_counters(reply)

    def _apply_counters(self, reply: tp.Dict[str, tp.Any]) -> None:
        if "idle" in reply:
            self._idle = bool(reply["idle"])
        c = reply.get("counters")
        if not c:
            return
        self.rounds = int(c.get("rounds", self.rounds))
        self.preemptions = int(c.get("preemptions", self.preemptions))
        self.shed = int(c.get("shed", self.shed))
        self.timeouts = int(c.get("timeouts", self.timeouts))
        self.cancelled = int(c.get("cancelled", self.cancelled))
        self.decode_kills = int(c.get("decode_kills", self.decode_kills))
        self.prefix_evictions = int(
            c.get("prefix_evictions", self.prefix_evictions)
        )
        self.spill_readopted_pages = int(
            c.get("spill_readopted_pages", self.spill_readopted_pages)
        )
        self._prefix_matched_tokens = int(
            c.get("prefix_matched", self._prefix_matched_tokens)
        )
        self._prefix_matchable_tokens = int(
            c.get("prefix_matchable", self._prefix_matchable_tokens)
        )
        self._hit_rate = float(c.get("hit_rate", self._hit_rate))
        if "spill_ledger" in c:
            self._spill_ledger = dict(c["spill_ledger"])

    def prefix_stats(self) -> tp.Dict[str, float]:
        return {"hit_rate": self._hit_rate}

    def _raise_remote(self, reply: tp.Dict[str, tp.Any], op: str) -> None:
        if reply.get("error"):
            raise RuntimeError(
                f"worker pid {self.pid} rpc {op!r} failed remotely: "
                f"{reply.get('message', reply['error'])}"
            )

    # -- conservation across the boundary ------------------------------

    def assert_conserved(self, where: str = "") -> None:
        """Run the single-engine pool law AND the worker tier's ledger
        check IN the worker (the pool lives there), surfacing a violation
        as the same AssertionError the in-process path raises."""
        reply = self.transport.call("conserve", {"where": where})
        if not reply.get("ok"):
            raise AssertionError(
                f"worker pid {self.pid} conservation failed {where}: "
                f"{reply.get('error', 'unknown')}"
            )
        self._spill_ledger = dict(reply.get("spill_ledger", {}))

    def spill_ledger(self) -> tp.Dict[str, int]:
        return dict(self._spill_ledger)

    # -- spill-page transfer -------------------------------------------

    def export_spill(self) -> tp.List[SpillTransferItem]:
        """Pull every resident spilled page out of the worker's tier
        (counted `transferred` there); typically after a graceful drain,
        so surviving replicas can re-adopt the KV the drained worker
        paid to prefill."""
        reply = self.transport.call("spill_export")
        self._raise_remote(reply, "spill_export")
        return [
            SpillTransferItem(
                key=tuple(int(t) for t in d["key"]),
                blocks={k: np.asarray(v) for k, v in d["blocks"].items()},
                checksum=int(d["checksum"]),
                weights_version=str(d["weights_version"]),
            )
            for d in reply.get("items", ())
        ]

    def import_spill(self, items: tp.Sequence[SpillTransferItem]) -> int:
        reply = self.transport.call(
            "spill_import",
            {
                "items": [
                    {
                        "key": list(it.key),
                        "blocks": it.blocks,
                        "checksum": it.checksum,
                        "weights_version": it.weights_version,
                    }
                    for it in items
                ]
            },
        )
        self._raise_remote(reply, "spill_import")
        return int(reply.get("imported", 0))

    # -- lifecycle / chaos ---------------------------------------------

    def drain(self) -> tp.Dict[str, tp.Any]:
        """Graceful drain: the worker stops admitting (non-retryable
        backpressure on new submits), keeps serving step/harvest until
        its in-flight streams finish, and exits once idle after the
        router disconnects — the SIGTERM path, driven explicitly."""
        return self.transport.call("drain")

    def kill9(self) -> None:
        """`proc_kill9`: SIGKILL the worker process — no drain, no flush,
        no goodbye. Detection and failover must come entirely from the
        health checks riding the wire."""
        os.kill(self.pid, signal.SIGKILL)

    def drop_conn(self) -> None:
        self.transport.drop_conn()

    def arm_wire_corrupt(self) -> None:
        self.transport.arm_wire_corrupt()

    def arm_wire_stall(self) -> None:
        self.transport.arm_wire_stall()

    def _evict_shared_prefix_fault(self) -> None:
        reply = self.transport.call("evict_prefix")
        self._raise_remote(reply, "evict_prefix")

    def stats(self) -> tp.Dict[str, tp.Any]:
        reply = self.transport.call("stats")
        self._raise_remote(reply, "stats")
        out = dict(reply.get("stats", {}))
        out["spill"] = reply.get("spill", {})
        out["compile_counts"] = reply.get("compile_counts", {})
        out["transport"] = self.transport.stats()
        return out

    def compile_counts(self) -> tp.Dict[str, tp.Any]:
        reply = self.transport.call("stats")
        self._raise_remote(reply, "stats")
        return dict(reply.get("compile_counts", {}))

    def close(self, kill: bool = False) -> None:
        try:
            self.transport.call("bye", retries=1, deadline_s=5.0)
        except (ReplicaGoneError, OSError):
            pass
        self.transport.close()
        if kill:
            try:
                os.kill(self.pid, signal.SIGKILL)
            except (OSError, ProcessLookupError):
                pass

    def on_router_crash(self) -> None:
        """FleetRouter._crash hook: a replica the health checks declared
        dead gets its transport torn down and — belt and braces — its
        process SIGKILLed, so a half-alive worker cannot keep serving a
        router that already failed its streams over."""
        self.transport.close()
        try:
            os.kill(self.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            pass


# -- worker side ------------------------------------------------------------


def build_worker_engine(spec: tp.Dict[str, tp.Any]):
    """Spec -> (ServeEngine, SpillTier). Same-seed workers build
    bit-identical params (GPT.init under the spec's PRNG seed), which is
    what makes cross-process failover replays token-for-token exact."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT, GPTConfig
    from midgpt_tpu.sampling.fleet import SpillTier
    from midgpt_tpu.sampling.serve import ServeEngine

    cfg = GPTConfig(**spec["model"])
    params = GPT.init(cfg, jax.random.PRNGKey(int(spec.get("seed", 0))))
    kw = dict(spec.get("engine", {}))
    dtype_name = kw.pop("cache_dtype", "float32")
    dtypes = {
        "float32": jnp.float32,
        "bfloat16": jnp.bfloat16,
        "int8": jnp.int8,
    }
    eng = ServeEngine(
        cfg,
        params,
        temperature=0.0,
        prefix_cache=True,
        cache_dtype=dtypes[dtype_name],
        **kw,
    )
    tier = SpillTier()
    eng.attach_spill(tier)
    return eng, tier


def parent_jax_config() -> tp.Dict[str, tp.Any]:
    """The parent-process jax config knobs that change numerics, to mirror
    into worker specs: params init (threefry) and matmul precision must
    agree across the boundary or greedy parity is fiction (pinned by the
    cross-process parity gate in tests/test_fleet_proc.py)."""
    import jax

    out: tp.Dict[str, tp.Any] = {
        "jax_threefry_partitionable": bool(
            jax.config.jax_threefry_partitionable
        ),
    }
    prec = jax.config.jax_default_matmul_precision
    if prec is not None:
        out["jax_default_matmul_precision"] = prec
    return out


class _WorkerState:
    """Everything one worker process serves RPCs against."""

    def __init__(self, eng, tier):
        self.eng = eng
        self.tier = tier
        self.events: tp.List[tp.Tuple[int, int, float]] = []
        self.submit_replies: tp.Dict[int, tp.Dict[str, tp.Any]] = {}
        self.draining = False
        eng.on_token = self._on_token

    def _on_token(self, uid: int, tok: int, t: float) -> None:
        self.events.append((int(uid), int(tok), float(t)))

    def counters(self) -> tp.Dict[str, tp.Any]:
        eng = self.eng
        return {
            "rounds": eng.rounds,
            "preemptions": eng.preemptions,
            "shed": eng.shed,
            "timeouts": eng.timeouts,
            "cancelled": eng.cancelled,
            "decode_kills": eng.decode_kills,
            "prefix_evictions": eng.prefix_evictions,
            "spill_readopted_pages": eng.spill_readopted_pages,
            "prefix_matched": eng._prefix_matched_tokens,
            "prefix_matchable": eng._prefix_matchable_tokens,
            "hit_rate": eng.prefix_stats()["hit_rate"],
            "spill_ledger": self.tier.ledger(),
        }

    def handle(self, req: tp.Dict[str, tp.Any]) -> tp.Dict[str, tp.Any]:
        op = req.get("op", "")
        fn = getattr(self, f"_op_{op}", None)
        if fn is None:
            return {"error": "exception", "message": f"unknown op {op!r}"}
        try:
            return fn(req)
        except Exception as e:  # structured remote error, never a hang
            return {"error": "exception", "message": f"{type(e).__name__}: {e}"}

    # -- verbs ---------------------------------------------------------

    def _op_hello(self, req) -> tp.Dict[str, tp.Any]:
        return {
            "pid": os.getpid(),
            "page_size": self.eng.page_size,
            "max_pages_per_slot": self.eng.max_pages_per_slot,
            "temperature": self.eng.temperature,
            "prefix_cache": self.eng.prefix_cache is not None,
            "weights_version": getattr(self.eng, "weights_version", "inline"),
        }

    def _op_submit(self, req) -> tp.Dict[str, tp.Any]:
        from midgpt_tpu.sampling.serve import BackpressureError

        seq = req.get("seq")
        if seq in self.submit_replies:  # retried RPC: never double-admit
            return self.submit_replies[seq]
        if self.draining:
            reply: tp.Dict[str, tp.Any] = {
                "error": "backpressure",
                "message": "worker is draining (SIGTERM) — not admitting",
                "needed_pages": None,
                "backlog_pages": None,
                "budget_pages": None,
                "retryable": False,
            }
        else:
            try:
                uid = self.eng.submit(
                    np.asarray(req["prompt"], np.int32),
                    int(req["max_new_tokens"]),
                    req.get("eos_id"),
                    ttl_s=req.get("ttl_s"),
                )
                reply = {"uid": int(uid), "idle": self.eng.idle}
            except BackpressureError as e:
                reply = {
                    "error": "backpressure",
                    "message": str(e),
                    "needed_pages": e.needed_pages,
                    "backlog_pages": e.backlog_pages,
                    "budget_pages": e.budget_pages,
                    "retryable": e.retryable,
                }
        self.submit_replies[seq] = reply
        return reply

    def _op_step(self, req) -> tp.Dict[str, tp.Any]:
        if not self.eng.idle:
            self.eng.step()
        return {"idle": self.eng.idle, "counters": self.counters()}

    def _op_harvest(self, req) -> tp.Dict[str, tp.Any]:
        known = set(req.get("known_uids", ()))
        fins = []
        for uid, fr in self.eng.finished.items():
            if uid in known:
                continue
            fins.append(
                {
                    "uid": int(uid),
                    "tokens": np.asarray(fr.tokens),
                    "token_times": [float(t) for t in fr.token_times],
                    "status": fr.status,
                }
            )
        start = int(req.get("events_from", 0))
        return {
            "events": [list(e) for e in self.events[start:]],
            "finished": fins,
            "idle": self.eng.idle,
            "counters": self.counters(),
        }

    def _op_stats(self, req) -> tp.Dict[str, tp.Any]:
        return {
            "stats": _jsonable(self.eng.stats()),
            "spill": self.tier.stats(),
            "compile_counts": self.eng.compile_stats(),
            "counters": self.counters(),
        }

    def _op_conserve(self, req) -> tp.Dict[str, tp.Any]:
        from midgpt_tpu.sampling import ops

        where = str(req.get("where", ""))
        try:
            ops.assert_conserved(self.eng, where)
            self.tier.assert_ledger(where)
        except AssertionError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "spill_ledger": self.tier.ledger()}

    def _op_spill_export(self, req) -> tp.Dict[str, tp.Any]:
        items = self.tier.export_entries()
        return {
            "items": [
                {
                    "key": list(it.key),
                    "blocks": it.blocks,
                    "checksum": it.checksum,
                    "weights_version": it.weights_version,
                }
                for it in items
            ]
        }

    def _op_spill_import(self, req) -> tp.Dict[str, tp.Any]:
        items = [
            SpillTransferItem(
                key=tuple(int(t) for t in d["key"]),
                blocks={k: np.asarray(v) for k, v in d["blocks"].items()},
                checksum=int(d["checksum"]),
                weights_version=str(d["weights_version"]),
            )
            for d in req.get("items", ())
        ]
        return {"imported": self.tier.import_entries(items)}

    def _op_evict_prefix(self, req) -> tp.Dict[str, tp.Any]:
        self.eng._evict_shared_prefix_fault()
        return {"idle": self.eng.idle}

    def _op_drain(self, req) -> tp.Dict[str, tp.Any]:
        self.draining = True
        return {"draining": True, "idle": self.eng.idle}

    def _op_bye(self, req) -> tp.Dict[str, tp.Any]:
        return {"bye": True}


def _jsonable(obj: tp.Any) -> tp.Any:
    """Engine stats() dicts hold numpy scalars/arrays and arbitrary
    nesting; coerce to the frame codec's tree shape."""
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


def run_worker(
    spec: tp.Dict[str, tp.Any],
    *,
    port: int = 0,
    announce: tp.Optional[tp.Callable[[int], None]] = None,
) -> None:
    """Worker process main loop (tools/fleet_worker.py calls this after
    pinning the jax platform). Binds, announces the port, then serves one
    router connection at a time. SIGTERM routes through the preempt flag
    (the handler only flips it — GC014); the loop notices between RPCs,
    stops admitting, and exits once drained and disconnected. Exits too
    when the parent process disappears — an orphaned worker must not
    squat on a CPU forever."""
    from midgpt_tpu.robustness import preempt

    eng, tier = build_worker_engine(spec)
    preempt.install_handlers()
    state = _WorkerState(eng, tier)
    parent = os.getppid()
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", port))
    srv.listen(1)
    srv.settimeout(0.25)
    if announce is not None:
        announce(srv.getsockname()[1])
    try:
        while True:
            if preempt.requested():
                state.draining = True
            if state.draining and eng.idle:
                return
            if os.getppid() != parent:
                return  # orphaned: the router process is gone
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            with conn:
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                conn.settimeout(None)
                saw_bye = _serve_conn(conn, state, preempt)
            if saw_bye and state.draining and eng.idle:
                return
    finally:
        srv.close()


def _serve_conn(conn: socket.socket, state: _WorkerState, preempt) -> bool:
    """Serve frames on one connection until the peer disconnects or says
    bye. A corrupt inbound frame drops the connection (the router's
    transport retries on a fresh one). Returns True on explicit bye."""
    while True:
        if preempt.requested():
            state.draining = True
        try:
            req = read_frame(conn)
        except (ConnectionError, OSError):
            return False
        except WireFrameError:
            return False
        reply = state.handle(req)
        reply["seq"] = req.get("seq")
        try:
            write_frame(conn, reply)
        except (OSError, ConnectionError):
            return False
        if req.get("op") == "bye":
            return True


# -- spawning helpers (chaos/bench/tests) -----------------------------------


def worker_script_path() -> str:
    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..")
    )
    return os.path.join(root, "tools", "fleet_worker.py")


def _popen_worker(spec: tp.Dict[str, tp.Any]):
    root = os.path.dirname(os.path.dirname(worker_script_path()))
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, worker_script_path(), "--spec-json",
         json.dumps(spec)],
        stdout=subprocess.PIPE,
        env=env,
        text=True,
    )


def spawn_worker(spec: tp.Dict[str, tp.Any]) -> tp.Tuple[tp.Any, int]:
    """Popen a worker with `spec`, block until it announces its port on
    stdout ("PORT <n>"), return (Popen, port). Stderr passes through so
    worker tracebacks land in the driver's log, never on the one-line
    JSON stdout contract (the worker's stdout is a pipe)."""
    proc = _popen_worker(spec)
    return proc, _await_port(proc)


def spawn_workers(
    spec: tp.Dict[str, tp.Any], n: int
) -> tp.List[tp.Tuple[tp.Any, int]]:
    """Spawn `n` workers CONCURRENTLY: all Popens first, then collect the
    port announcements — the expensive part of worker startup (jax import
    + engine build) overlaps instead of serializing."""
    procs = [_popen_worker(spec) for _ in range(n)]
    return [(p, _await_port(p)) for p in procs]


def _await_port(proc) -> int:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"fleet worker exited (rc={proc.poll()}) before announcing "
                "its port"
            )
        if line.startswith("PORT "):
            return int(line.split()[1])


def connect_replica(port: int, **transport_kw) -> ProcReplica:
    return ProcReplica(
        ReplicaTransport("127.0.0.1", port, **transport_kw)
    )
