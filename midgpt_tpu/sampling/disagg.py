"""Disaggregated prefill/decode serving: two ServeEngine roles bridged by a
page-handoff queue (docs/SERVING.md "Mesh-sharded serving").

Prefill and decode want opposite machines: prefill is compute-bound batch
work (long chunks, few slots), decode is HBM-bound latency work (many
slots, short chunks). A monolithic engine time-slices both on one set of
chips and each interferes with the other's SLO (FastUSP's multi-level
split, PAPERS.md). Disaggregation runs a prefill-heavy engine instance and
a decode-heavy one — on the two rows of a (data=2, tp) serving mesh
(parallel/serve_tp.role_submeshes), or unsharded side by side on the CPU
test mesh — and moves each request between them exactly once, at the
prefill/decode boundary.

The handoff rides machinery previous PRs already built, which is why it is
small:

  * chunked prefill makes the prefill role preemptible (a request never
    holds the engine longer than one chunk), and `max_new_tokens=1` makes
    "prefill + first token" a complete ServeEngine request — the prefill
    role needs no new scheduler states;
  * the prefix-cache trie already expresses "these pages hold tokens
    0..n": at prefill finish the request's complete prompt pages sit in
    the trie, `match` hands them (referenced) to the handoff, and on the
    decode side `release(..., n_shared=0)` donates the adopted copies back
    into the DECODE trie, so the decode engine's ordinary admission path
    re-matches them and skips prompt re-prefill — the decode role needs no
    new admission states either;
  * page content moves as a host-gathered block (`pages.take_pages`) and
    lands through one jitted scatter (`pages.adopt_pages`, donated pool,
    oob-padded page indices like every engine scatter), so the adopt is
    one compiled program per (page-count bucket, dtype) — the same
    bucketing discipline that keeps the serving jits' compile set
    mix-independent. The pool's format is sampling/pages.py's alone.

Greedy parity: the decode role's prompt is `prompt + [first_token]`; its
prefill recomputes exactly the positions the handoff did not ship and its
first host-side argmax reproduces the monolithic engine's second token
(prefill-logits/decode-step parity is the engine's founding invariant,
tests/test_sampling.py), so a disaggregated greedy stream is token-for-
token the monolithic stream (pinned by tests/test_tp_serving.py). The
queue is lossy-safe in both directions: a handoff that cannot get decode
pool pages degrades to plain re-prefill on the decode side (correct, just
slower), and a timed-out request propagates its timeout status.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
import typing as tp

import numpy as np

from midgpt_tpu.models.gpt import GPTConfig, GPTParams
from midgpt_tpu.obs import DISABLED_SNAPSHOT, Observability
from midgpt_tpu.robustness.backoff import backoff_delays
from midgpt_tpu.obs.trace import NULL_TRACER
from midgpt_tpu.sampling.pages import adopt_pages, take_pages
from midgpt_tpu.sampling.serve import (
    BackpressureError,
    FinishedRequest,
    ServeEngine,
)


@dataclasses.dataclass
class HandoffItem:
    """One request crossing the prefill->decode boundary: identity and
    budget, the prefill role's first token (with its wall-clock time, so
    TTFT survives the handoff), and the host-gathered content of its
    complete prompt pages."""

    uid: int  # DisaggServe uid
    prompt: np.ndarray  # (T0,) int32
    first_token: int
    first_time: float
    max_new_tokens: int  # ORIGINAL budget (decode role gets it minus 1)
    eos_id: tp.Optional[int]
    deadline: tp.Optional[float]
    blocks: tp.Dict[str, np.ndarray]  # page content, as pages.take_pages gives it
    n_pages: int


class HandoffRetryExhausted(RuntimeError):
    """A queued page-transport item was refused by its destination more
    times than the queue's bounded retry budget allows. Structured like
    BackpressureError: `uid` identifies the stream, `attempts` the spent
    budget, so a router can convert it into a terminal shed instead of
    retrying forever (graceful degradation, never a silent drop)."""

    def __init__(self, message: str, *, uid: int, attempts: int):
        super().__init__(message)
        self.uid = uid
        self.attempts = attempts


class PageHandoffQueue:
    """FIFO of page-transport items with transfer accounting and a bounded
    retry-with-backoff schedule — the general page-transport primitive:
    disagg's prefill->decode handoff and the fleet router's failover
    resubmission (sampling/fleet.py) both ride it. Host-side and
    process-local here (all roles live in one process on the test mesh);
    the counters are the interface a cross-host transport would have to
    honor — bytes_copied is the KV traffic the transport actually moves,
    the number to weigh against the prompt re-prefill FLOPs it saves.

    Items are duck-typed: anything with `uid`, `n_pages`, and `blocks`
    queues (HandoffItem, fleet.FailoverItem). Retry state lives ON the
    item (`_handoff_attempts`, `_not_before`), so requeue backs an item
    off on the SAME exponential schedule every transient-failure path in
    the repo uses (robustness/backoff.py: base_s * 2**attempt), and a
    destination that keeps refusing raises the structured
    HandoffRetryExhausted instead of spinning — ad-hoc unbounded
    front-requeue loops are gone."""

    def __init__(
        self,
        *,
        retries: int = 32,
        base_s: float = 0.0,
        clock: tp.Callable[[], float] = time.perf_counter,
    ):
        if retries < 1:
            raise ValueError(f"retries must be >= 1, got {retries}")
        self._q: tp.Deque[tp.Any] = collections.deque()
        self.retries = retries
        self.base_s = base_s
        self._clock = clock
        self.enqueued = 0
        self.dequeued = 0
        self.pages_copied = 0
        self.bytes_copied = 0
        self.retry_exhausted = 0

    def __len__(self) -> int:
        return len(self._q)

    def push(self, item) -> None:
        self.enqueued += 1
        self.pages_copied += item.n_pages
        self.bytes_copied += sum(b.nbytes for b in item.blocks.values())
        item._handoff_attempts = 0
        item._not_before = 0.0
        self._q.append(item)

    def pop(self, now: tp.Optional[float] = None):
        """The next ready item, or None when the queue is empty or its head
        is still inside a backoff window (FIFO order is preserved — a
        backed-off head shields the items behind it, which would only be
        refused by the same full destination)."""
        if not self._q:
            return None
        item = self._q[0]
        if getattr(item, "_not_before", 0.0) > (
            self._clock() if now is None else now
        ):
            return None
        self.dequeued += 1
        return self._q.popleft()

    def requeue(self, item) -> None:
        """Return a refused item to the FRONT (it keeps its place) with the
        next exponential delay stamped on it. Raises HandoffRetryExhausted
        once the item has been refused `retries` times — the caller owns
        the terminal disposition (disagg: fallback re-prefill happened
        earlier; fleet: terminal shed)."""
        self.dequeued -= 1
        attempts = getattr(item, "_handoff_attempts", 0) + 1
        item._handoff_attempts = attempts
        if attempts >= self.retries:
            self.retry_exhausted += 1
            raise HandoffRetryExhausted(
                f"handoff uid={item.uid} refused {attempts} times "
                f"(budget {self.retries})",
                uid=item.uid,
                attempts=attempts,
            )
        # attempts-th delay of the shared schedule: base_s * 2**(attempts-1)
        delay = next(
            itertools.islice(
                backoff_delays(self.retries, self.base_s), attempts - 1, None
            ),
            0.0,
        )
        item._not_before = self._clock() + delay
        self._q.appendleft(item)

    def stats(self) -> tp.Dict[str, int]:
        return {
            "depth": len(self._q),
            "enqueued": self.enqueued,
            "dequeued": self.dequeued,
            "pages_copied": self.pages_copied,
            "bytes_copied": self.bytes_copied,
            "retry_exhausted": self.retry_exhausted,
        }


class DisaggServe:
    """A prefill-role ServeEngine and a decode-role ServeEngine joined by a
    PageHandoffQueue (module docstring).

    `mesh`, when given, must carry data >= 2: role r lives on
    `role_submeshes(mesh)[r]` — row 0 prefill, row 1 decode — so the two
    roles occupy disjoint devices and each is tp-sharded across its row.
    With mesh=None both roles run unsharded (the CPU parity
    configuration). `engine_kw` is shared by both roles;
    `prefill_kw`/`decode_kw` override per role (the point of
    disaggregation: e.g. a long prefill_chunk on the prefill role, more
    slots on the decode role). Greedy only (temperature=0): the handoff
    carries no RNG stream, and parity with a monolithic engine is the
    contract."""

    def __init__(
        self,
        config: GPTConfig,
        params: GPTParams,
        *,
        mesh=None,
        prefill_kw: tp.Optional[tp.Dict[str, tp.Any]] = None,
        decode_kw: tp.Optional[tp.Dict[str, tp.Any]] = None,
        clock: tp.Callable[[], float] = time.perf_counter,
        obs: tp.Optional[Observability] = None,
        **engine_kw,
    ):
        kinds = config.model().cache_kinds(config)
        if len(kinds) > 1:
            raise NotImplementedError(
                "DisaggServe: disaggregated prefill (a hand-off of pages of ONE kind, owned through "
                f"the prefix trie) is not wired for a model with {len(kinds)} kinds of cache "
                f"({', '.join(k.name for k in kinds)}: several kinds of page, or a STATE kind, whose row no page hand-off carries)"
            )
        if engine_kw.get("temperature", 0.0) != 0.0:
            raise ValueError("DisaggServe is greedy-only (module docstring)")
        if engine_kw.pop("prefix_cache", True) is not True:
            raise ValueError(
                "DisaggServe requires the prefix cache: the trie IS the "
                "handoff's page-ownership ledger"
            )
        pf_mesh = dec_mesh = None
        if mesh is not None:
            from midgpt_tpu.parallel.serve_tp import role_submeshes

            roles = role_submeshes(mesh)
            if len(roles) < 2:
                raise ValueError(
                    "disaggregation needs a mesh with data >= 2 (one row "
                    "per role); got data="
                    f"{int(mesh.shape['data'])}"
                )
            pf_mesh, dec_mesh = roles[0], roles[1]
        self._clock = clock
        # One shared Observability, two tid lanes: both roles' round spans
        # land in the same flight recorder under "prefill"/"decode" thread
        # names, with the handoff spans on a third "disagg" lane — the
        # Perfetto view IS the pipeline diagram.
        self.obs = obs
        self._trace = obs.tracer if obs is not None else NULL_TRACER
        self.prefill = ServeEngine(
            config, params, prefix_cache=True, clock=clock, mesh=pf_mesh,
            obs=obs, obs_tid="prefill",
            **{**engine_kw, **(prefill_kw or {})},
        )
        self.decode = ServeEngine(
            config, params, prefix_cache=True, clock=clock, mesh=dec_mesh,
            obs=obs, obs_tid="decode",
            **{**engine_kw, **(decode_kw or {})},
        )
        # Bounded transport: a decode role that refuses the same item 512
        # ticks in a row is wedged, and the structured exhaustion below
        # converts the stream to a terminal shed instead of spinning the
        # pipeline forever (base_s=0: the pipeline tick IS the pacing).
        self.queue = PageHandoffQueue(retries=512, base_s=0.0, clock=clock)
        self.finished: tp.Dict[int, FinishedRequest] = {}
        # disagg uid -> (prompt, max_new, eos, deadline), keyed twice over
        # the role engines' own uid spaces while a request is inside one.
        self._pf_pending: tp.Dict[int, tp.Tuple[int, np.ndarray, int,
                                                tp.Optional[int],
                                                tp.Optional[float]]] = {}
        self._dec_pending: tp.Dict[int, HandoffItem] = {}
        self._uid = 0
        # Handoffs that could not get decode-pool pages and fell back to
        # plain re-prefill on the decode role (correct, just slower).
        self.fallback_reprefills = 0
        # prefill<->decode pool-capacity moves (ops.py re-role decisions)
        self.re_roles = 0

    # -- public surface ------------------------------------------------

    def submit(
        self,
        prompt: tp.Sequence[int],
        max_new_tokens: int,
        eos_id: tp.Optional[int] = None,
        ttl_s: tp.Optional[float] = None,
    ) -> int:
        """Queue a request on the PREFILL role (budget 1: prefill + first
        token is a complete request there). Backpressure propagates —
        shedding happens at the front door, not mid-pipeline."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        deadline = None if ttl_s is None else self._clock() + ttl_s
        pf_uid = self.prefill.submit(prompt, 1, eos_id=None, ttl_s=ttl_s)
        uid = self._uid
        self._uid += 1
        self._pf_pending[pf_uid] = (uid, prompt, max_new_tokens, eos_id, deadline)
        return uid

    @property
    def idle(self) -> bool:
        return (
            not self._pf_pending
            and not self._dec_pending
            and not len(self.queue)
            and self.prefill.idle
            and self.decode.idle
        )

    def run(self) -> tp.Dict[int, FinishedRequest]:
        while not self.idle:
            self.step()
        return self.finished

    def step(self) -> None:
        """One pipeline tick: advance prefill, drain its finishes into the
        handoff queue, adopt queued handoffs into the decode role, advance
        decode, drain its finishes. The two engine step()s are independent
        device programs on disjoint (sub)meshes — a real deployment
        overlaps them; the host loop here interleaves them, which is
        enough for every invariant the tests pin."""
        if not self.prefill.idle:
            self.prefill.step()
        self._drain_prefill()
        self._drain_queue()
        if not self.decode.idle:
            self.decode.step()
        self._drain_decode()

    def stats(self) -> tp.Dict[str, tp.Any]:
        return {
            "queue": self.queue.stats(),
            "fallback_reprefills": self.fallback_reprefills,
            "re_roles": self.re_roles,
            "prefill": self.prefill.stats(),
            "decode": self.decode.stats(),
            # shared across both roles (one Observability, two tid lanes)
            "obs": (
                DISABLED_SNAPSHOT if self.obs is None else self.obs.snapshot()
            ),
        }

    def rebalance(self, n_pages: int, *, src: str = "prefill",
                  dst: str = "decode") -> tp.Dict[str, tp.Any]:
        """Move `n_pages` of pool capacity from the `src` role to the
        `dst` role via two live resizes (sampling/ops.py resize_pool) —
        the re-role actuator of the model-ops policy loop. Shrink-first:
        if the src role cannot give the pages up without dropping its
        resident working set, the retryable PoolResizeError propagates
        BEFORE anything changed; the dst grow that follows cannot fail.
        Each role keeps its own pool and devices — re-roling moves page
        BUDGET, not pages in flight (those still cross on the handoff
        queue's adoption scatter)."""
        roles = {"prefill": self.prefill, "decode": self.decode}
        if src not in roles or dst not in roles or src == dst:
            raise ValueError(f"rebalance src/dst must be distinct roles "
                             f"from {sorted(roles)}, got {src!r}->{dst!r}")
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        shrink = roles[src].resize(roles[src].allocator.num_pages - n_pages)
        grow = roles[dst].resize(roles[dst].allocator.num_pages + n_pages)
        self.re_roles += 1
        self._trace.instant(
            "ops.re_role", "ops", "disagg",
            args={"src": src, "dst": dst, "pages": n_pages},
        )
        return {"src": src, "dst": dst, "pages": n_pages,
                "src_resize": shrink, "dst_resize": grow}

    # -- internals -----------------------------------------------------

    def _finish(self, fr: FinishedRequest) -> None:
        self.finished[fr.uid] = fr

    def _drain_prefill(self) -> None:
        done = [u for u in self._pf_pending if u in self.prefill.finished]
        for pf_uid in done:
            uid, prompt, max_new, eos_id, deadline = self._pf_pending.pop(pf_uid)
            fr = self.prefill.finished[pf_uid]
            if fr.status != "ok":
                self._finish(
                    FinishedRequest(uid, fr.tokens, fr.token_times, fr.status)
                )
                continue
            first = int(fr.tokens[len(prompt)])
            first_time = fr.token_times[0]
            if max_new == 1 or (eos_id is not None and first == eos_id):
                self._finish(
                    FinishedRequest(
                        uid,
                        np.append(prompt, np.int32(first)),
                        [first_time],
                        "ok",
                    )
                )
                continue
            item = self._gather_pages(
                uid, prompt, first, first_time, max_new, eos_id, deadline
            )
            self.queue.push(item)
            self._trace.instant(
                "handoff.push", "disagg", "disagg",
                args={
                    "uid": uid,
                    "n_pages": item.n_pages,
                    "bytes": sum(b.nbytes for b in item.blocks.values()),
                },
            )

    def _gather_pages(
        self, uid, prompt, first, first_time, max_new, eos_id, deadline
    ) -> HandoffItem:
        """Reference the request's complete prompt pages out of the
        prefill trie, land their content on the host, and drop the refs
        (the entries stay in the PREFILL trie for future shared-template
        hits — the handoff copies, it does not steal)."""
        with self._trace.span("handoff.gather", "disagg", "disagg"):
            pc = self.prefill.prefix_cache
            mr = pc.match(prompt, max_tokens=len(prompt) - 1)
            n = len(mr.pages)
            blocks: tp.Dict[str, np.ndarray] = {}
            if n:
                blocks = take_pages(self.prefill.cache, mr.pages)
                ps = self.prefill.page_size
                self.prefill.pool.free(
                    0, pc.release(prompt[: n * ps], mr.pages, n)
                )
        return HandoffItem(
            uid=uid, prompt=prompt, first_token=first, first_time=first_time,
            max_new_tokens=max_new, eos_id=eos_id, deadline=deadline,
            blocks=blocks, n_pages=n,
        )

    def _drain_queue(self) -> None:
        while True:
            item = self.queue.pop()
            if item is None:
                break
            if item.deadline is not None:
                remaining = item.deadline - self._clock()
                if remaining <= 0:
                    self._finish(
                        FinishedRequest(
                            item.uid,
                            np.append(item.prompt, np.int32(item.first_token)),
                            [item.first_time],
                            "timeout",
                        )
                    )
                    continue
            else:
                remaining = None
            dec_prompt = np.append(item.prompt, np.int32(item.first_token))
            try:
                dec_uid = self.decode.submit(
                    dec_prompt, item.max_new_tokens - 1, item.eos_id,
                    ttl_s=remaining,
                )
            except BackpressureError:
                try:
                    self.queue.requeue(item)
                except HandoffRetryExhausted:
                    # wedged decode role: terminal shed, never a spin
                    self._finish(
                        FinishedRequest(
                            item.uid,
                            np.append(item.prompt, np.int32(item.first_token)),
                            [item.first_time],
                            "shed",
                        )
                    )
                break  # decode role is full; retry next tick
            with self._trace.span("handoff.adopt", "disagg", "disagg"):
                self._adopt(item)
            self._dec_pending[dec_uid] = item

    def _adopt(self, item: HandoffItem) -> None:
        """Allocate decode-pool pages, scatter the handed-off content into
        them, and donate them to the DECODE trie at refcount 0 — from here
        the decode engine's ordinary admission match finds them and skips
        the prompt prefill. Falls back to nothing (plain re-prefill) when
        the decode pool cannot free enough pages."""
        n = item.n_pages
        if n == 0:
            return
        eng = self.decode
        dst = eng.pool.alloc(0, n)
        if dst is None:
            # Reclaim unreferenced trie pages, the engine's own pressure
            # valve, then retry once.
            eng.pool.free(
                0, eng.prefix_cache.evict(n - eng.allocator.free_count)
            )
            dst = eng.pool.alloc(0, n)
        if dst is None:
            self.fallback_reprefills += 1
            self._trace.instant(
                "handoff.fallback_reprefill", "disagg", "disagg",
                args={"uid": item.uid},
            )
            return
        eng.cache = adopt_pages(eng.mesh, eng.cache, dst, item.blocks)
        ps = eng.page_size
        eng.pool.free(
            0, eng.prefix_cache.release(item.prompt[: n * ps], dst, 0)
        )

    def _drain_decode(self) -> None:
        done = [u for u in self._dec_pending if u in self.decode.finished]
        for dec_uid in done:
            item = self._dec_pending.pop(dec_uid)
            fr = self.decode.finished[dec_uid]
            # fr.tokens is (prompt + first) + the decode role's generation —
            # exactly the monolithic stream.
            self._finish(
                FinishedRequest(
                    item.uid,
                    fr.tokens,
                    [item.first_time] + list(fr.token_times),
                    fr.status,
                )
            )
