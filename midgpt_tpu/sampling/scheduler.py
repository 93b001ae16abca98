"""Pluggable serving scheduler policies (admission, ordering, preemption).

PR 1 inlined three policy decisions in the `ServeEngine` round loop: which
queued request claims a freed slot (`_admit`), which running slot is
preempted when the page pool runs dry (`_ensure_pages`/`_evict`), and when
`submit` refuses a request outright (backpressure shedding). This module
extracts them behind a `Scheduler` interface so serving policy is a host-
side plug — the page table, lengths and active mask stay plain jit inputs,
so SWAPPING POLICIES NEVER TOUCHES A COMPILED PROGRAM (pinned by
tests/test_scheduler.py with the tests/test_recompile_pins.py counter
methodology). The extraction is also what mesh-sharded serving and prefix
caching (ROADMAP items 1-2) hook into: both need to reorder admission and
choose eviction victims without re-opening the engine's round loop.

Two policies ship:

  * `FCFSScheduler` — the PR 1 behavior, bit-for-bit: admit the queue head,
    evict the youngest, shed only on the `max_backlog_pages` budget. The
    default; every existing serving/spec/quant parity test runs through it
    unchanged (tests/test_serving.py, tests/test_spec.py,
    tests/test_quant_cache.py).
  * `SLOScheduler` — deadline-aware: admission is earliest-deadline-first,
    preemption picks the victim with the MOST deadline slack (an urgent
    request keeps its pages; a request with an hour to spare re-prefills),
    and admission sheds requests whose deadline is already infeasible
    (closer than `min_headroom_s`) — refusing work it cannot finish in time
    instead of burning pool pages on it (load shedding). Shed decisions are
    reported via `BackpressureError.retryable=False` so the async front
    door (sampling/server.py) fails them fast instead of retrying.

Deadlock-freedom is the ENGINE's invariant, not the policy's: the engine
only ever offers preemption candidates strictly younger (later
`admit_order`) than the slot that needs pages, so the oldest running
request always makes progress no matter what a policy returns. A policy
returning a non-candidate is a contract violation and raises.

**Deferred-effect semantics under round-overlap dispatch** (sampling/
serve.py `_step_overlapped`, docs/SERVING.md "Round-overlap dispatch"):
policy decisions are HOST decisions and only ever take effect at the next
dispatch boundary, never mid-flight. With overlap off that boundary is the
same round; with overlap="double" the engine dispatches round N+1 BEFORE
running round N's host phase, so a request this policy admits (or a victim
it selects) during round N's host phase first appears in (disappears from)
round N+2's dispatched batch — the one-round-late boundary the engine's
`dispatch_log` records and tests/test_overlap.py pins for both shipped
policies. Policies need no awareness of this: the interface below is
unchanged, the engine alone decides when a decision lands on the device,
and an eviction of a slot with an in-flight dispatch simply discards that
slot's un-settled tokens (recompute preemption regenerates them
bit-exactly).

With the cross-request prefix cache on (sampling/prefix_cache.py), the
backpressure accounting policies see is refcount-aware: the engine's
`_backlog_pages` charges a trie-shared page ONCE no matter how many
queued/running requests will map it, and unreferenced trie pages are
charged nothing because the engine reclaims them on demand BEFORE asking a
policy for a preemption victim (`_ensure_pages`). Policies themselves are
unchanged — eviction candidates are still slots, never trie nodes, so a
policy can never evict a shared prefix out from under a co-reader.
"""

from __future__ import annotations

import typing as tp

if tp.TYPE_CHECKING:  # import cycle: serve.py imports this module
    from midgpt_tpu.sampling.serve import Request, _Slot


class Scheduler:
    """Host-side serving policy. Stateless by default; implementations may
    keep statistics but must not touch device state — scheduling decisions
    feed the engine's page table and queue order only, which are plain jit
    inputs (the zero-new-compiled-programs contract,
    tests/test_scheduler.py)."""

    name = "base"

    def select_admit(
        self, queue: tp.Sequence["Request"], now: float
    ) -> tp.Optional[int]:
        """Index into `queue` of the request to admit into a freed slot,
        or None to deliberately leave the slot empty this round."""
        raise NotImplementedError

    def select_victim(
        self,
        requester: "_Slot",
        candidates: tp.Sequence["_Slot"],
        now: float,
    ) -> tp.Optional["_Slot"]:
        """Which of `candidates` to preempt so `requester` can grow.

        `candidates` holds only running slots strictly younger than
        `requester` (the engine's deadlock-freedom invariant — see module
        docstring); it is never empty. Return None to defer `requester`
        instead of preempting anyone."""
        raise NotImplementedError

    def shed_reason(
        self,
        need_pages: int,
        deadline: tp.Optional[float],
        engine,
        now: float,
    ) -> tp.Optional[tp.Tuple[str, bool]]:
        """Admission control, called by `ServeEngine.submit` before a
        request enters the queue. None admits; `(reason, retryable)`
        sheds — the engine raises `BackpressureError(reason,
        retryable=retryable, ...)`."""
        raise NotImplementedError

    # Shared backpressure-budget check: every policy sheds when the
    # worst-case committed page demand would exceed `max_backlog_pages`
    # (the PR 3 bound; None = unbounded, the pre-TTL behavior).
    def _over_budget(self, need_pages: int, engine) -> tp.Optional[tp.Tuple[str, bool]]:
        if engine.max_backlog_pages is None:
            return None
        backlog = engine._backlog_pages()
        if backlog + need_pages > engine.max_backlog_pages:
            return (
                f"admission refused: request needs {need_pages} worst-case "
                f"pages on top of a committed backlog of {backlog} "
                f"(budget {engine.max_backlog_pages}) — the pool is "
                "oversubscribed; shed load or retry after requests finish",
                True,  # retryable: capacity frees as requests finish
            )
        return None


class FCFSScheduler(Scheduler):
    """The PR 1 policy, extracted verbatim: first-come-first-served
    admission (queue head), youngest-first preemption, budget-only
    shedding. Behavior preservation is pinned token-for-token by the
    pre-existing serving parity suite (tests/test_serving.py,
    tests/test_spec.py, tests/test_quant_cache.py) running through this
    default policy."""

    name = "fcfs"

    def select_admit(self, queue, now):
        return 0 if queue else None

    def select_victim(self, requester, candidates, now):
        return max(candidates, key=lambda s: s.admit_order)

    def shed_reason(self, need_pages, deadline, engine, now):
        return self._over_budget(need_pages, engine)


class SLOScheduler(Scheduler):
    """Deadline-urgency scheduling: serve the requests whose SLO is at
    risk, shed the ones that are already lost.

    * **Admission order** — earliest deadline first; deadline-less requests
      rank last; ties fall back to FCFS (queue position).
    * **Preemption** — among the (strictly younger) candidates, evict the
      slot with the MOST deadline slack, ties youngest-first. An urgent
      request near its deadline keeps its pages; the recompute cost of
      preemption lands on whoever can best absorb it.
    * **Load shedding** — beyond the backpressure budget (retryable, like
      FCFS), and additionally any request whose deadline is nearer than
      `min_headroom_s` (non-retryable: waiting only makes it later). A
      request shed at submit costs zero pool pages and zero prefill work —
      the error-budget lever; the engine counts it as `shed`.
    """

    name = "slo"

    def __init__(self, min_headroom_s: float = 0.0):
        self.min_headroom_s = min_headroom_s

    @staticmethod
    def _slack(deadline: tp.Optional[float], now: float) -> float:
        return float("inf") if deadline is None else deadline - now

    def select_admit(self, queue, now):
        if not queue:
            return None
        return min(
            range(len(queue)),
            key=lambda i: (self._slack(queue[i].deadline, now), i),
        )

    def select_victim(self, requester, candidates, now):
        return max(
            candidates,
            key=lambda s: (self._slack(s.request.deadline, now), s.admit_order),
        )

    def shed_reason(self, need_pages, deadline, engine, now):
        over = self._over_budget(need_pages, engine)
        if over is not None:
            return over
        if deadline is not None and deadline - now < self.min_headroom_s:
            return (
                f"admission refused: deadline headroom {deadline - now:.3f}s "
                f"is below the {self.min_headroom_s:.3f}s service floor — "
                "the SLO is already infeasible, shedding instead of burning "
                "pool pages on a request that cannot finish in time",
                False,  # waiting cannot make a past-due deadline feasible
            )
        return None


def set_backlog_budget(engine, pages: tp.Optional[int]) -> tp.Optional[int]:
    """Retune the engine's `max_backlog_pages` shed threshold live (None
    disables the budget). This is the shed-threshold actuator of the
    model-ops policy loop (sampling/ops.py ModelOps): the budget is pure
    host-side admission state, so moving it never touches a compiled
    program — the same guarantee as swapping scheduler policies. Returns
    the previous budget."""
    prev = engine.max_backlog_pages
    engine.max_backlog_pages = pages
    return prev
