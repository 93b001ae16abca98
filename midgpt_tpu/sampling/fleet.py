"""Fleet serving: N ServeEngine replicas behind a prefix-affinity router,
with health-checked failover and a verified host-RAM KV spill tier
(docs/ROBUSTNESS.md "Fleet serving & failover").

One engine in one process is a single point of failure: an engine crash
drops every accepted stream, and every trie eviction discards KV that cost
real prefill FLOPs to build. This module extends the single-engine
robustness machinery (supervisor/chaos faults/backoff, PRs 3/6/12) from
*one engine surviving faults* to *a fleet surviving the loss of an engine*,
with three cooperating pieces:

  * `FleetRouter` — schedules arrivals by TRIE AFFINITY: the first
    page_size tokens of the prompt (the only shareable granule, see
    prefix_cache.py) rendezvous-hash over the alive replicas, so requests
    sharing a system prompt land on the replica already holding its pages
    and the fleet-wide prefix hit rate does not dilute toward 1/N.
    Rendezvous (highest-random-weight) hashing keeps the mapping stable
    when a replica dies: only the dead replica's keys move.
  * Health-checked FAILOVER — the router steps each replica inside a
    try/except with clock-injected heartbeats; a replica whose step raises
    `max_consecutive_failures` times in a row, or whose heartbeat goes
    stale past `heartbeat_timeout_s`, is marked dead. Its already-finished
    results are harvested, and its accepted-but-unfinished streams are
    resubmitted to survivors through the bounded `PageHandoffQueue`
    retry path (sampling/disagg.py — the general page-transport
    primitive). Resubmission replays the ORIGINAL prompt with the FULL
    budget: greedy streams are batch-composition-independent (the
    engine's founding parity invariant, tests/test_serving.py), so a
    failed-over stream reproduces the exact tokens the dead replica would
    have served — the chaos gate parity-checks every stream, survivors
    AND failovers, against a fault-free single-engine pass. Delivery on
    the `on_token` hook is therefore at-least-once across a failover
    (already-streamed tokens replay); terminal results in `finished` are
    exactly-once.
  * `SpillTier` — a host-RAM tier under every replica's trie: refcount-0
    pages spill their content to host memory on eviction (int8 pages
    travel quantized with their scales — 2x cheaper) instead of being
    discarded, keyed by the page's FULL token prefix (KV is
    position-dependent: the same page content at a different depth is
    different KV). Each spilled page carries a crc32 checksum verified on
    re-adoption and the weights_version it was computed under: a corrupt
    or stale page is discarded and the tokens re-prefill — the PR 3
    verified-checkpoint discipline applied to KV, so a flipped bit can
    never poison a decode. Re-adoption rides the pow2-bucketed adoption
    scatter (`pages.adopt_pages`). The tier is SHARED fleet-wide: KV
    content depends only on tokens and weights, not on which replica
    computed it, so a failed-over stream re-prefills from pages its dead
    replica spilled.

Graceful degradation, never a crash: when every surviving replica sheds an
admission the router raises an aggregated, retryable `BackpressureError`
(`submit_retry` wraps it in the shared bounded backoff schedule,
robustness/backoff.py), and a failover the survivors refuse past the
queue's retry budget becomes a terminal "shed" finish — structured
outcomes at every exhaustion point.

Conservation extends across tiers (`assert_fleet_conserved`): every alive
replica obeys the single-engine pool law (ops.assert_conserved), and the
spill tier's ledger closes — resident + readopted + corrupt_discarded +
capacity_dropped + stale_discarded == total_spilled. The fleet chaos
scenarios (robustness/chaos_serve.py: engine_crash / handoff_stall /
spill_corrupt) assert both after every drain.

Cross-process fleets (sampling/fleet_proc.py, docs/ROBUSTNESS.md
"Cross-process fleet"): a replica may be a `ProcReplica` — a proxy for a
worker PROCESS hosting the engine behind the framed socket transport.
The router drives it through the same duck-typed surface, so everything
above holds unchanged; what this module adds for that mode is (a) the
wire-level fault kinds (`proc_kill9` / `conn_drop` / `wire_corrupt` /
`wire_stall`) fired from `step()` against proc replicas — kill -9
detection deliberately flows through the SAME consecutive-failure health
path as an in-process engine death, fed by `ReplicaGoneError` off the
wire; (b) spill-page transfer (`SpillTier.export_entries` /
`import_entries`) whose `transferred`/`received` buckets keep the ledger
law closing when pages cross a process boundary; and (c) per-replica
dispatch in `assert_fleet_conserved`, which runs the pool law INSIDE the
worker (over the `conserve` RPC) for proc replicas.
"""

from __future__ import annotations

import dataclasses
import time
import typing as tp
import zlib

import numpy as np

from midgpt_tpu.robustness import faults
from midgpt_tpu.robustness.backoff import retry_with_backoff
from midgpt_tpu.sampling.disagg import (
    HandoffRetryExhausted,
    PageHandoffQueue,
)
from midgpt_tpu.sampling.pages import split_pages, take_pages
from midgpt_tpu.sampling.serve import (
    BackpressureError,
    FinishedRequest,
    ServeEngine,
)


class _SpillEntry:
    """One spilled page: single-page host blocks ('k'/'v' (L, H, ps, C)
    and, int8 pools, 'k_scale'/'v_scale' (L, H, ps)), the crc32 of their
    bytes, the weights_version the KV was computed under, and an LRU
    stamp."""

    __slots__ = ("blocks", "checksum", "weights_version", "stamp", "nbytes")

    def __init__(self, blocks, checksum, weights_version, stamp):
        self.blocks = blocks
        self.checksum = checksum
        self.weights_version = weights_version
        self.stamp = stamp
        self.nbytes = sum(b.nbytes for b in blocks.values())


def _blocks_crc(blocks: tp.Dict[str, np.ndarray]) -> int:
    crc = 0
    for key in sorted(blocks):
        crc = zlib.crc32(blocks[key].tobytes(), crc)
    return crc


class SpillTier:
    """Host-RAM spill tier for evicted trie pages (module docstring).

    Entries key on the page's full token prefix, so `peek_run`/`take_run`
    walk exactly the pages an admission's trie match stopped short of.
    Checksums are verified at TAKE (the moment the bytes would enter a
    decode), never at peek — a corrupt entry truncates the run, is counted
    `corrupt_discarded`, and the affected tokens re-prefill. The ledger
    `total_spilled == resident + readopted + corrupt_discarded +
    capacity_dropped + stale_discarded` is the cross-tier half of the
    fleet conservation invariant (`assert_fleet_conserved`).

    Chaos hooks (robustness/faults.py): `arm_stall` models a wedged
    host transport — the NEXT consult that would return pages refuses
    instead (counted `stall_fallbacks`; the caller re-prefills, correct
    but slower); `corrupt_one` flips a byte in the most recently spilled
    resident entry so the checksum discipline is exercised end to end."""

    def __init__(
        self,
        *,
        capacity_bytes: tp.Optional[int] = None,
        clock: tp.Callable[[], float] = time.perf_counter,
    ):
        self._entries: tp.Dict[tp.Tuple[int, ...], _SpillEntry] = {}
        self.capacity_bytes = capacity_bytes
        self._clock = clock
        self._tick = 0
        self._stall_armed = False
        # ledger counters (every spilled page ends in exactly one bucket)
        self.total_spilled = 0
        self.readopted = 0
        self.corrupt_discarded = 0
        self.capacity_dropped = 0
        self.stale_discarded = 0
        # cross-process transfer buckets (fleet_proc.py): pages that
        # entered/left this tier over the wire rather than via spill/take
        self.received = 0
        self.transferred = 0
        # non-ledger visibility counters
        self.duplicate_skips = 0
        self.stall_fallbacks = 0
        self.spilled_bytes = 0
        self.readopted_bytes = 0

    # -- spill side (prefix_cache.on_evict) ----------------------------

    def spill(self, cache, prefix: tp.Tuple[int, ...], page: int,
              weights_version: str) -> bool:
        """Land `page`'s pool content on the host under `prefix` (the
        page's full token prefix from PrefixCache.on_evict). Called while
        the page's device bytes are still intact — eviction frees the page
        AFTER the hook returns. int8 pools spill quantized: the int8
        columns plus their per-page scales, half the bytes of a bf16
        page."""
        key = tuple(int(t) for t in prefix)
        existing = self._entries.get(key)
        if existing is not None:
            if existing.weights_version == weights_version:
                # same tokens + same weights => same KV; keep the resident
                self.duplicate_skips += 1
                return False
            # stale duplicate from before a hot swap: replace it
            del self._entries[key]
            self.stale_discarded += 1
        (blocks,) = split_pages(take_pages(cache, [page]))
        self._tick += 1
        entry = _SpillEntry(
            blocks, _blocks_crc(blocks), weights_version, self._tick
        )
        self._entries[key] = entry
        self.total_spilled += 1
        self.spilled_bytes += entry.nbytes
        self._enforce_capacity()
        return True

    def _enforce_capacity(self) -> None:
        if self.capacity_bytes is None:
            return
        while (
            len(self._entries) > 1
            and sum(e.nbytes for e in self._entries.values())
            > self.capacity_bytes
        ):
            key = min(self._entries, key=lambda k: self._entries[k].stamp)
            del self._entries[key]
            self.capacity_dropped += 1

    # -- re-adopt side (ServeEngine._readopt_from_spill) ---------------

    def peek_run(self, prompt, start_page: int, limit: int,
                 weights_version: str) -> int:
        """How many consecutive pages starting at page depth `start_page`
        of `prompt` are resident under `weights_version` (checksums NOT
        verified — that happens at take). An armed stall refuses the first
        consult that would return pages, then clears: the caller falls
        back to plain re-prefill, which is the stall's whole failure
        mode — slower, never wrong."""
        ps = self._require_ps()
        n = 0
        for j in range(limit):
            key = tuple(int(t) for t in prompt[: (start_page + j + 1) * ps])
            e = self._entries.get(key)
            if e is None or e.weights_version != weights_version:
                break
            n += 1
        if n and self._stall_armed:
            self._stall_armed = False
            self.stall_fallbacks += 1
            return 0
        return n

    def take_run(self, prompt, start_page: int, n: int,
                 weights_version: str) -> tp.List[tp.Dict[str, np.ndarray]]:
        """Move up to `n` consecutive pages out of the tier (move-on-take:
        the caller owns them; re-eviction re-spills). Each page's crc32 is
        verified here — a mismatch discards THAT entry, truncates the run,
        and counts `corrupt_discarded`: corrupt bytes never reach a
        decode, the tokens simply re-prefill."""
        ps = self._require_ps()
        out: tp.List[tp.Dict[str, np.ndarray]] = []
        for j in range(n):
            key = tuple(int(t) for t in prompt[: (start_page + j + 1) * ps])
            e = self._entries.pop(key, None)
            if e is None:
                break
            if e.weights_version != weights_version:
                self.stale_discarded += 1
                break
            if _blocks_crc(e.blocks) != e.checksum:
                self.corrupt_discarded += 1
                break
            self.readopted += 1
            self.readopted_bytes += e.nbytes
            out.append(e.blocks)
        return out

    # page_size is bound once, at the first attach (ServeEngine
    # attach_spill): spill keys are exact multiples of it, and a tier
    # shared across replicas requires them to agree.
    _ps: int = 0

    def set_page_size(self, ps: int) -> None:
        if self._ps and self._ps != ps:
            raise ValueError(
                f"spill tier already bound to page_size={self._ps}, "
                f"got {ps}"
            )
        self._ps = ps

    def _require_ps(self) -> int:
        if not self._ps:
            raise RuntimeError(
                "spill tier consulted before any engine attached it "
                "(ServeEngine.attach_spill binds page_size)"
            )
        return self._ps

    # -- chaos hooks ---------------------------------------------------

    def arm_stall(self) -> None:
        self._stall_armed = True

    def corrupt_one(self) -> bool:
        """Flip a byte in the most recently spilled resident entry's K
        block WITHOUT updating its checksum — the take-side verification
        must catch it. Returns False when nothing is resident (the fault
        stays armed until something is)."""
        if not self._entries:
            return False
        key = max(self._entries, key=lambda k: self._entries[k].stamp)
        e = self._entries[key]
        k = e.blocks["k"].copy()
        flat = k.view(np.uint8).reshape(-1)
        flat[0] ^= 0xFF
        e.blocks["k"] = k
        return True

    # -- cross-process transfer (fleet_proc.py) ------------------------

    def export_entries(self):
        """Move every resident entry out of this tier for wire transfer
        (typically a draining worker handing its spilled KV to survivors).
        Move-on-export like take_run: the pages leave this ledger through
        the `transferred` bucket and re-enter the receiver's through
        `received` — both sides' conservation laws keep closing. Checksums
        travel UNVERIFIED and UNCHANGED: the receiver's take-side check
        then covers transit and residence with one number."""
        from midgpt_tpu.sampling.fleet_proc import SpillTransferItem

        items = [
            SpillTransferItem(
                key=key,
                blocks=e.blocks,
                checksum=e.checksum,
                weights_version=e.weights_version,
            )
            for key, e in sorted(
                self._entries.items(), key=lambda kv: kv[1].stamp
            )
        ]
        self._entries.clear()
        self.transferred += len(items)
        return items

    def import_entries(self, items) -> int:
        """Land wire-transferred entries in this tier, preserving each
        page's ORIGINAL spill-time checksum (a bit flipped in transit is
        caught by the normal take_run verification — corrupt KV degrades
        to re-prefill, never poisons a decode). A resident duplicate under
        the same weights_version wins (`duplicate_skips`); a stale one is
        replaced (`stale_discarded`). Returns the number imported."""
        imported = 0
        for it in items:
            key = tuple(int(t) for t in it.key)
            self.received += 1
            imported += 1
            existing = self._entries.get(key)
            if existing is not None:
                if existing.weights_version == it.weights_version:
                    # resident copy is equivalent: the incoming page goes
                    # straight to the discard bucket it would reach anyway
                    self.duplicate_skips += 1
                    self.stale_discarded += 1
                    continue
                del self._entries[key]
                self.stale_discarded += 1
            self._tick += 1
            self._entries[key] = _SpillEntry(
                dict(it.blocks), int(it.checksum), it.weights_version,
                self._tick,
            )
        self._enforce_capacity()
        return imported

    # -- accounting ----------------------------------------------------

    def resident_count(self) -> int:
        return len(self._entries)

    def resident_bytes(self) -> int:
        return sum(e.nbytes for e in self._entries.values())

    def ledger(self) -> tp.Dict[str, int]:
        return {
            "total_spilled": self.total_spilled,
            "resident": len(self._entries),
            "readopted": self.readopted,
            "corrupt_discarded": self.corrupt_discarded,
            "capacity_dropped": self.capacity_dropped,
            "stale_discarded": self.stale_discarded,
            "received": self.received,
            "transferred": self.transferred,
        }

    def assert_ledger(self, where: str = "") -> None:
        """Pages in == pages accounted for. Sources: spilled locally or
        received over the wire. Sinks: resident, readopted, one of the
        discard buckets, or transferred away. Identical to the pre-proc
        law when both transfer buckets are zero."""
        led = self.ledger()
        total = (
            led["resident"]
            + led["readopted"]
            + led["corrupt_discarded"]
            + led["capacity_dropped"]
            + led["stale_discarded"]
            + led["transferred"]
        )
        assert total == led["total_spilled"] + led["received"], (
            f"spill ledger violated {where}: {led} "
            f"(buckets sum to {total})"
        )

    def stats(self) -> tp.Dict[str, int]:
        return {
            **self.ledger(),
            "resident_bytes": self.resident_bytes(),
            "spilled_bytes": self.spilled_bytes,
            "readopted_bytes": self.readopted_bytes,
            "duplicate_skips": self.duplicate_skips,
            "stall_fallbacks": self.stall_fallbacks,
        }


@dataclasses.dataclass
class FailoverItem:
    """One accepted stream crossing replicas after a crash: the ORIGINAL
    prompt and FULL budget (greedy batch-independence makes the replay
    bit-identical). Rides PageHandoffQueue with empty blocks — the pages
    re-prefill from the shared spill tier / survivor trie at the
    destination, so nothing is gathered from the dead replica."""

    uid: int  # fleet uid
    prompt: np.ndarray  # (T0,) int32
    max_new_tokens: int
    eos_id: tp.Optional[int]
    deadline: tp.Optional[float]
    blocks: tp.Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    n_pages: int = 0


@dataclasses.dataclass
class _Stream:
    """Router-side record of an accepted stream: everything needed to
    replay it on a survivor if its replica dies."""

    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: tp.Optional[int]
    deadline: tp.Optional[float]
    replica: int
    replica_uid: int


class FleetRouter:
    """N ServeEngine replicas behind prefix-affinity routing with
    health-checked failover (module docstring).

    The router OWNS its engines: it overwrites their `on_token` hooks (to
    translate replica uids to fleet uids) and attaches the shared spill
    tier to each. Engines must be greedy (temperature 0 — failover parity
    is the contract), prefix-cached (the trie is both the affinity target
    and the spill source), and agree on page_size."""

    def __init__(
        self,
        engines: tp.Sequence[ServeEngine],
        *,
        clock: tp.Callable[[], float] = time.perf_counter,
        spill: tp.Optional[SpillTier] = None,
        heartbeat_timeout_s: tp.Optional[float] = None,
        max_consecutive_failures: int = 3,
        failover_retries: int = 512,
        on_token: tp.Optional[tp.Callable[[int, int, float], None]] = None,
        on_finish: tp.Optional[tp.Callable[[FinishedRequest], None]] = None,
    ):
        engines = list(engines)
        if not engines:
            raise ValueError("a fleet needs at least one replica")
        for i, eng in enumerate(engines):
            if eng.prefix_cache is None:
                raise ValueError(
                    f"replica {i} has no prefix cache — the trie is the "
                    "router's affinity target and the spill tier's source"
                )
            if eng.temperature != 0.0:
                raise ValueError(
                    "FleetRouter is greedy-only: failover replays a stream "
                    "on a survivor and bit-parity is the contract"
                )
        ps = engines[0].page_size
        if any(e.page_size != ps for e in engines):
            raise ValueError("replicas must agree on page_size")
        self.engines = engines
        self.page_size = ps
        self.alive = [True] * len(engines)
        self._clock = clock
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.max_consecutive_failures = max_consecutive_failures
        self.on_token = on_token
        self.on_finish = on_finish
        self.spill = spill if spill is not None else SpillTier(clock=clock)
        for i, eng in enumerate(engines):
            eng.attach_spill(self.spill)
            eng.on_token = self._make_token_relay(i)
        # failover transport: same bounded-retry page queue as disagg —
        # blocks are empty, so only the retry discipline rides (base_s=0:
        # the router tick is the pacing, like the disagg pipeline tick).
        self.failover_queue = PageHandoffQueue(
            retries=failover_retries, base_s=0.0, clock=clock
        )
        self.finished: tp.Dict[int, FinishedRequest] = {}
        self._pending: tp.Dict[int, _Stream] = {}
        self._by_replica: tp.Dict[tp.Tuple[int, int], int] = {}
        self._uid = 0
        self.rounds = 0
        now = clock()
        self._heartbeat = [now] * len(engines)
        self._failures = [0] * len(engines)
        # counters
        self.failovers = 0  # replica deaths
        self.failed_over_streams = 0
        self.router_shed = 0  # submit-time total refusals (all replicas)
        self.shed_streams = 0  # failovers terminally shed past the budget
        self.crash_log: tp.List[tp.Dict[str, tp.Any]] = []
        # cross-process replicas (fleet_proc.ProcReplica marks itself):
        # the wire-level fault kinds in step() only target these, and
        # their deaths are counted separately for the serve_fleet profile
        self._proc_idx = [
            i
            for i, eng in enumerate(engines)
            if getattr(eng, "is_proc", False)
        ]
        self.proc_failovers = 0

    # -- admission -----------------------------------------------------

    def submit(
        self,
        prompt: tp.Sequence[int],
        max_new_tokens: int,
        eos_id: tp.Optional[int] = None,
        ttl_s: tp.Optional[float] = None,
    ) -> int:
        """Place a request on the affinity replica, spilling over to the
        other survivors least-loaded-first. When EVERY survivor sheds,
        raises one aggregated BackpressureError (retryable iff any
        replica's shed was) — the fleet's graceful-degradation front
        door."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        deadline = None if ttl_s is None else self._clock() + ttl_s
        uid = self._uid
        self._place(uid, prompt, max_new_tokens, eos_id, deadline)
        self._uid += 1
        return uid

    def submit_retry(
        self,
        prompt: tp.Sequence[int],
        max_new_tokens: int,
        eos_id: tp.Optional[int] = None,
        ttl_s: tp.Optional[float] = None,
        *,
        retries: int = 8,
        base_s: float = 0.0,
    ) -> int:
        """`submit` under the shared bounded backoff schedule
        (robustness/backoff.py). The "sleep" between attempts steps the
        fleet once — capacity frees as replicas finish work, so waiting
        IS progress. Non-retryable sheds propagate immediately; the final
        failure re-raises the aggregated BackpressureError."""
        return retry_with_backoff(
            lambda: self.submit(prompt, max_new_tokens, eos_id, ttl_s),
            retries=retries,
            base_s=base_s,
            retry_on=(BackpressureError,),
            sleep=lambda _delay: self.step(),
            should_retry=lambda e: getattr(e, "retryable", False),
        )

    def _place(self, uid, prompt, max_new_tokens, eos_id, deadline) -> None:
        now = self._clock()
        ttl = None if deadline is None else max(deadline - now, 0.0)
        errs: tp.List[BackpressureError] = []
        for i in self._route_order(prompt):
            try:
                ruid = self.engines[i].submit(
                    prompt, max_new_tokens, eos_id, ttl_s=ttl
                )
            except BackpressureError as e:
                errs.append(e)
                continue
            self._pending[uid] = _Stream(
                uid, prompt, max_new_tokens, eos_id, deadline, i, ruid
            )
            self._by_replica[(i, ruid)] = uid
            return
        self.router_shed += 1
        retryable = any(e.retryable for e in errs) if errs else False
        first = errs[0] if errs else None
        raise BackpressureError(
            f"all {sum(self.alive)} surviving replicas shed the request"
            + (f" (affinity replica: {errs[0]})" if errs else ""),
            needed_pages=getattr(first, "needed_pages", None),
            backlog_pages=getattr(first, "backlog_pages", None),
            budget_pages=getattr(first, "budget_pages", None),
            retryable=retryable,
        )

    def _route_order(self, prompt) -> tp.List[int]:
        """Affinity replica first (rendezvous hash of the first full page
        — the only granule the trie can share), then the remaining
        survivors least-loaded first. Prompts without a full shareable
        page have no affinity and go least-loaded."""
        alive = [i for i, a in enumerate(self.alive) if a]
        if not alive:
            raise RuntimeError("no alive replicas in the fleet")
        load = {i: 0 for i in alive}
        for st in self._pending.values():
            if st.replica in load:
                load[st.replica] += 1
        rest = sorted(alive, key=lambda i: (load[i], i))
        aff = self._affinity(prompt, alive)
        if aff is None:
            return rest
        return [aff] + [i for i in rest if i != aff]

    def _affinity(self, prompt, alive: tp.List[int]) -> tp.Optional[int]:
        ps = self.page_size
        if len(prompt) < ps + 1:  # match caps at len(prompt) - 1 tokens
            return None
        key = np.asarray(prompt[:ps], np.int64).tobytes()
        return max(
            alive,
            key=lambda i: zlib.crc32(key + i.to_bytes(4, "little")),
        )

    # -- the fleet round -----------------------------------------------

    @property
    def idle(self) -> bool:
        return (
            not self._pending
            and not len(self.failover_queue)
            and all(
                eng.idle
                for i, eng in enumerate(self.engines)
                if self.alive[i]
            )
        )

    def run(self, max_rounds: int = 100_000) -> tp.Dict[int, FinishedRequest]:
        start = self.rounds
        while not self.idle:
            if self.rounds - start >= max_rounds:
                raise RuntimeError(
                    f"fleet failed to drain within {max_rounds} rounds"
                )
            self.step()
        return self.finished

    def step(self) -> None:
        """One fleet round: fire fleet-level chaos faults, step every
        alive replica under the health checks, harvest finishes, drain
        the failover queue onto survivors."""
        self.rounds += 1
        if sum(self.alive) > 1 and faults.should_fire(
            "engine_crash", step=self.rounds
        ):
            self._crash(self._crash_victim(), reason="fault")
        if faults.should_fire("handoff_stall", step=self.rounds):
            self.spill.arm_stall()
        if self.spill.resident_count() > 0 and faults.should_fire(
            "spill_corrupt", step=self.rounds
        ):
            self.spill.corrupt_one()
        self._fire_proc_faults()
        for i, eng in enumerate(self.engines):
            if not self.alive[i]:
                continue
            now = self._clock()
            if eng.idle:
                self._heartbeat[i] = now
                continue
            try:
                eng.step()
            except Exception:
                self._failures[i] += 1
                if self._failures[i] >= self.max_consecutive_failures:
                    self._crash(i, reason="consecutive_failures")
                continue
            self._heartbeat[i] = now
            self._failures[i] = 0
            if (
                self.heartbeat_timeout_s is not None
                and self._clock() - self._heartbeat[i]
                > self.heartbeat_timeout_s
            ):
                self._crash(i, reason="heartbeat_stale")
        self._harvest()
        self._drain_failover()

    def _fire_proc_faults(self) -> None:
        """The wire-level fault kinds (robustness/faults.py "cross-process
        fleet" section), targeting the busiest alive proc replica so the
        fault lands under real traffic. `proc_kill9` SIGKILLs the worker
        and deliberately does NOT mark it dead here: detection must flow
        through the same health checks as any other replica death — step
        RPCs fail with ReplicaGoneError until the consecutive-failure
        threshold fires `_crash`. The other three arm transport-level
        chaos the RPC retry path must absorb transparently."""
        procs = [i for i in self._proc_idx if self.alive[i]]
        if not procs:
            return
        victim = self._busiest(procs)
        if sum(self.alive) > 1 and faults.should_fire(
            "proc_kill9", step=self.rounds
        ):
            self.engines[victim].kill9()
        if faults.should_fire("conn_drop", step=self.rounds):
            self.engines[victim].drop_conn()
        if faults.should_fire("wire_corrupt", step=self.rounds):
            self.engines[victim].arm_wire_corrupt()
        if faults.should_fire("wire_stall", step=self.rounds):
            self.engines[victim].arm_wire_stall()

    def _busiest(self, candidates: tp.List[int]) -> int:
        load = {i: 0 for i in candidates}
        for st in self._pending.values():
            if st.replica in load:
                load[st.replica] += 1
        return max(sorted(load), key=lambda i: load[i])

    def _crash_victim(self) -> int:
        """The engine_crash fault's target: the alive replica holding the
        most accepted streams (maximal failover work; deterministic
        low-index tie-break)."""
        return self._busiest([i for i, a in enumerate(self.alive) if a])

    def _crash(self, i: int, *, reason: str) -> None:
        """Mark replica `i` dead and fail its streams over: harvest what
        it already finished (those results are durable), push every
        accepted-but-unfinished stream onto the failover queue for
        resubmission to survivors. The dead replica's pool dies with it —
        conservation is per-ALIVE-replica — but its spilled pages live on
        in the shared tier, so the replays re-prefill cheaper."""
        if not self.alive[i]:
            return
        self.alive[i] = False
        self.failovers += 1
        if getattr(self.engines[i], "is_proc", False):
            self.proc_failovers += 1
        self.crash_log.append(
            {"replica": i, "round": self.rounds, "reason": reason}
        )
        # proc replicas: tear the transport down and make sure the worker
        # process is gone — a half-alive worker must not keep serving a
        # router that already failed its streams over
        closer = getattr(self.engines[i], "on_router_crash", None)
        if closer is not None:
            closer()
        self._harvest_engine(i)
        moved = sorted(
            (st for st in self._pending.values() if st.replica == i),
            key=lambda st: st.uid,
        )
        for st in moved:
            del self._pending[st.uid]
            del self._by_replica[(i, st.replica_uid)]
            self.failover_queue.push(
                FailoverItem(
                    uid=st.uid,
                    prompt=st.prompt,
                    max_new_tokens=st.max_new_tokens,
                    eos_id=st.eos_id,
                    deadline=st.deadline,
                )
            )
            self.failed_over_streams += 1

    def _harvest(self) -> None:
        for i in range(len(self.engines)):
            if self.alive[i]:
                self._harvest_engine(i)

    def _harvest_engine(self, i: int) -> None:
        eng = self.engines[i]
        done = [
            st
            for st in self._pending.values()
            if st.replica == i and st.replica_uid in eng.finished
        ]
        for st in done:
            fr = eng.finished[st.replica_uid]
            out = FinishedRequest(st.uid, fr.tokens, fr.token_times, fr.status)
            self.finished[st.uid] = out
            del self._pending[st.uid]
            del self._by_replica[(i, st.replica_uid)]
            if self.on_finish is not None:
                self.on_finish(out)

    def _drain_failover(self) -> None:
        while True:
            item = self.failover_queue.pop()
            if item is None:
                break
            if item.deadline is not None and (
                item.deadline - self._clock() <= 0
            ):
                self._terminal(item, "timeout")
                continue
            try:
                self._place(
                    item.uid, item.prompt, item.max_new_tokens,
                    item.eos_id, item.deadline,
                )
            except BackpressureError:
                try:
                    self.failover_queue.requeue(item)
                except HandoffRetryExhausted:
                    # survivors refused past the bounded budget: terminal
                    # structured shed, never a silent drop or a spin
                    self._terminal(item, "shed")
                    self.shed_streams += 1
                break

    def _terminal(self, item: FailoverItem, status: str) -> None:
        out = FinishedRequest(item.uid, item.prompt, [], status)
        self.finished[item.uid] = out
        if self.on_finish is not None:
            self.on_finish(out)

    def _make_token_relay(self, i: int):
        def relay(ruid: int, tok: int, t: float) -> None:
            uid = self._by_replica.get((i, ruid))
            if uid is not None and self.on_token is not None:
                self.on_token(uid, tok, t)

        return relay

    # -- reporting -----------------------------------------------------

    def prefix_hit_rate(self) -> float:
        """Fleet-wide trie hit rate: Σ matched / Σ matchable tokens over
        EVERY replica (dead ones served real traffic before dying). The
        number affinity routing exists to protect — random routing over N
        replicas dilutes a template workload toward 1/N of the
        single-engine rate."""
        matched = sum(e._prefix_matched_tokens for e in self.engines)
        matchable = sum(e._prefix_matchable_tokens for e in self.engines)
        return matched / matchable if matchable else 0.0

    def transport_stats(self) -> tp.Optional[tp.Dict[str, tp.Any]]:
        """Wire-level rollup over the proc replicas (None for a pure
        in-process fleet): summed volume/recovery counters, mean p50 and
        worst p95 latency — the serve_fleet profile's transport fields."""
        if not self._proc_idx:
            return None
        per = [self.engines[i].transport.stats() for i in self._proc_idx]
        out: tp.Dict[str, tp.Any] = {
            k: sum(s[k] for s in per)
            for k in (
                "rpc_count", "wire_bytes", "connects", "reconnects",
                "retries", "corrupt_frames", "deadline_expiries",
                "forced_drops",
            )
        }
        out["rpc_p50_ms"] = round(
            sum(s["rpc_p50_ms"] for s in per) / len(per), 3
        )
        out["rpc_p95_ms"] = max(s["rpc_p95_ms"] for s in per)
        return out

    def stats(self) -> tp.Dict[str, tp.Any]:
        return {
            "fleet_size": len(self.engines),
            "alive": sum(self.alive),
            "rounds": self.rounds,
            "failovers": self.failovers,
            "proc_failovers": self.proc_failovers,
            "failed_over_streams": self.failed_over_streams,
            "router_shed": self.router_shed,
            "shed_streams": self.shed_streams,
            "prefix_hit_rate": self.prefix_hit_rate(),
            "failover_queue": self.failover_queue.stats(),
            "spill": self.spill.stats(),
            "transport": self.transport_stats(),
            "crash_log": list(self.crash_log),
            "replicas": [
                {
                    "alive": self.alive[i],
                    "rounds": eng.rounds,
                    "preemptions": eng.preemptions,
                    "shed": eng.shed,
                    "spill_readopted_pages": eng.spill_readopted_pages,
                    "prefix_hit_rate": eng.prefix_stats()["hit_rate"],
                }
                for i, eng in enumerate(self.engines)
            ],
        }


def assert_fleet_conserved(router: FleetRouter, where: str = "") -> None:
    """The cross-tier conservation law (ISSUE 14): every ALIVE replica
    obeys the single-engine pool law (free + trie-held + live-slot-only
    == num_pages - 1, ops.assert_conserved — a dead replica's pool died
    with it), and the shared spill tier's ledger closes (every page ever
    spilled is resident, readopted, or accounted discarded). Chaos
    scenarios assert this after every drain, including the spill-corrupt
    discard paths.

    Cross-process replicas run the pool law INSIDE the worker (the pages
    live there) over the `conserve` RPC — the law closes ACROSS the
    process boundary, with the worker-side verdict surfacing as the same
    AssertionError the in-process path raises."""
    from midgpt_tpu.sampling import ops

    for i, eng in enumerate(router.engines):
        if not router.alive[i]:
            continue
        if getattr(eng, "is_proc", False):
            eng.assert_conserved(f"{where} fleet replica {i}")
        else:
            ops.assert_conserved(eng, f"{where} fleet replica {i}")
    router.spill.assert_ledger(where)
