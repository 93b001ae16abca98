"""Zero-downtime model ops: blue/green weight hot-swap, elastic KV pool
resize, and the SLO-driven policy controller (docs/ROBUSTNESS.md
"Zero-downtime model ops").

Production serving means deploys under traffic. This module is the first
subsystem allowed to mutate a live engine's *identity* — its weights, its
pool geometry, its role — so every operation is built around the existing
invariants rather than around speed:

  * **Hot-swap** (`stage_hot_swap` / `maybe_flip_swap`) is blue/green at
    round granularity: the new params are validated (tree structure, leaf
    shapes/dtypes, config) against the live ones and re-homed onto the
    live params' shardings BEFORE staging, so a same-shape swap is a pure
    pointer flip — zero new compiled programs (params are *traced* args
    of the serving jits; only shapes/dtypes/shardings are compile keys,
    tests/test_recompile_pins.py). While a swap is staged, admissions
    pause; in-flight streams finish on the old weights; the flip happens
    at the first round boundary with no live slot. The KV pool and the
    prefix trie survive untouched (their content keys on prompt tokens,
    which are weight-independent; post-flip hits replay old-weight K/V —
    exactly the pages a restarted engine would recompute, see
    docs/ROBUSTNESS.md for the staleness contract).
  * **Resize** (`resize_pool`) moves the resident working set — live slot
    pages plus every referenced trie page — into a freshly allocated pool
    through the same pow2-bucketed gather/adoption scatter that the
    disagg handoff uses (sampling/pages.py `PagePool.migrate`), then remaps
    slot page lists and trie entries onto the new physical ids. Shrink
    REFUSES with a structured, retryable `PoolResizeError` rather than
    evicting below the resident working set (the backpressure discipline,
    serve.py `BackpressureError`); unreferenced trie pages are LRU-evicted
    to fit. Page conservation (free + trie + live-only == num_pages - 1)
    is asserted before and after the migration.
  * **ModelOps** is a clock-injected controller (GC012: no wall-clock
    reads outside the injected callable) that consumes the signals the
    obs layer already surfaces — free-page fraction, backlog pages,
    shed_frac, p95 TTFT when the caller has one — and
    emits grow/shrink/re-role/shed-threshold decisions, observable as
    `ops.decision` tracer instants and Prometheus gauges.

Chaos gates: robustness/chaos_serve.py `hot_swap_mid_decode` (verified
checkpoint flipped mid-trace, zero drops, bit-parity on both sides of the
flip) and `pool_resize` (grow-then-shrink mid-trace, conservation at every
boundary, parity vs a no-resize pass, int8 scales migrating with pages).
"""
from __future__ import annotations

import dataclasses
import math
import time
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.sampling.pages import pow2_bucket as _pow2_bucket
from midgpt_tpu.sampling.serve import ServeEngine


class HotSwapError(RuntimeError):
    """A staged weight swap was rejected BEFORE touching the live engine.

    Structured fields (callers never string-parse):

      reason     "tree_structure" | "shape" | "dtype" | "config" |
                 "draft_missing" | "draft_unexpected" | "swap_pending"
      path       offending leaf path ("" when not leaf-specific)
      expected   live engine's value for the mismatched property
      got        candidate's value
    """

    def __init__(
        self,
        message: str,
        *,
        reason: str,
        path: str = "",
        expected: tp.Any = None,
        got: tp.Any = None,
    ):
        super().__init__(message)
        self.reason = reason
        self.path = path
        self.expected = expected
        self.got = got


class PoolResizeError(RuntimeError):
    """A live pool resize was refused — shrinking below the resident
    working set would have to drop referenced pages, which is a data-loss
    decision the caller must make (finish/evict streams), not the resizer.

    Structured fields (the BackpressureError discipline, serve.py):

      requested_pages   the num_pages the caller asked for
      resident_pages    distinct pages that MUST survive (live slots +
                        referenced trie entries), i.e. the floor is
                        resident_pages + 1 (sink)
      num_pages         the pool's current num_pages
      requested_slots / live_slots   set for slot-count refusals
      retryable         True — retry after streams drain or evictions
    """

    def __init__(
        self,
        message: str,
        *,
        requested_pages: int,
        resident_pages: int,
        num_pages: int,
        requested_slots: tp.Optional[int] = None,
        live_slots: tp.Optional[int] = None,
        retryable: bool = True,
    ):
        super().__init__(message)
        self.requested_pages = requested_pages
        self.resident_pages = resident_pages
        self.num_pages = num_pages
        self.requested_slots = requested_slots
        self.live_slots = live_slots
        self.retryable = retryable


# ---------------------------------------------------------------------------
# Blue/green weight hot-swap
# ---------------------------------------------------------------------------


def _leaf_paths(tree) -> tp.List[tp.Tuple[str, tp.Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]


def _validate_swap_tree(old, new, *, what: str) -> None:
    if jax.tree_util.tree_structure(old) != jax.tree_util.tree_structure(new):
        raise HotSwapError(
            f"hot-swap rejected: {what} tree structure differs from the "
            "live engine's (different model family / qkv layout?)",
            reason="tree_structure",
            expected=str(jax.tree_util.tree_structure(old)),
            got=str(jax.tree_util.tree_structure(new)),
        )
    for (path, o), (_, n) in zip(_leaf_paths(old), _leaf_paths(new)):
        if tuple(o.shape) != tuple(np.shape(n)):
            raise HotSwapError(
                f"hot-swap rejected: {what} leaf {path} has shape "
                f"{tuple(np.shape(n))}, live engine has {tuple(o.shape)} — "
                "same-shape swaps only (a different architecture is a new "
                "engine, not a swap)",
                reason="shape",
                path=path,
                expected=tuple(o.shape),
                got=tuple(np.shape(n)),
            )
        n_dtype = jnp.asarray(n).dtype if not hasattr(n, "dtype") else n.dtype
        if jnp.dtype(o.dtype) != jnp.dtype(n_dtype):
            raise HotSwapError(
                f"hot-swap rejected: {what} leaf {path} has dtype {n_dtype}, "
                f"live engine has {o.dtype} — a dtype change is a recompile, "
                "not a zero-downtime swap",
                reason="dtype",
                path=path,
                expected=str(o.dtype),
                got=str(n_dtype),
            )


def stage_hot_swap(
    engine: ServeEngine,
    params,
    *,
    draft_params=None,
    version: str = "inline",
    config=None,
) -> tp.Dict[str, tp.Any]:
    """Validate + stage a blue/green weight swap on `engine`.

    Rejections raise `HotSwapError` without perturbing the engine. On
    success the candidate params are device_put onto the live params'
    shardings (the sharding is a compile key of the serving jits — this is
    what makes the flip zero-recompile on both single-chip and mesh
    engines) and staged; `maybe_flip_swap` flips at the first round
    boundary with no live slot (immediately, for an idle engine). While
    staged, `_admit` pauses so queued arrivals deterministically take the
    NEW weights.
    """
    if engine._staged_swap is not None:
        raise HotSwapError(
            "hot-swap rejected: a swap is already staged "
            f"(version {engine._staged_swap['version']!r}) and has not "
            "flipped yet",
            reason="swap_pending",
            expected=None,
            got=version,
        )
    if config is not None:
        live_cfg = engine.config
        if config != live_cfg:
            raise HotSwapError(
                "hot-swap rejected: model config differs from the live "
                "engine's — a config change is a new engine, not a swap",
                reason="config",
                expected=live_cfg,
                got=config,
            )
    _validate_swap_tree(engine.params, params, what="params")
    if draft_params is not None and engine.draft_params is None:
        raise HotSwapError(
            "hot-swap rejected: draft params offered but the live engine "
            "has no draft model configured",
            reason="draft_unexpected",
        )
    if draft_params is None and engine.draft_params is not None:
        # Target-only swap on a speculative engine is legal — the draft
        # only PROPOSES; the rejection sampler guarantees the committed
        # distribution is the (new) target's regardless of draft staleness.
        pass
    if draft_params is not None:
        _validate_swap_tree(engine.draft_params, draft_params, what="draft_params")

    params = jax.tree.map(
        lambda o, n: jax.device_put(n, o.sharding), engine.params, params
    )
    if draft_params is not None:
        draft_params = jax.tree.map(
            lambda o, n: jax.device_put(n, o.sharding),
            engine.draft_params,
            draft_params,
        )
    engine._staged_swap = {
        "params": params,
        "draft_params": draft_params,
        "version": version,
        "staged_round": engine.rounds,
        "staged_at": engine._clock(),
        "in_flight_at_stage": sorted(
            s.request.uid for s in engine.slots if s is not None
        ),
    }
    engine._trace.instant(
        "ops.hot_swap_staged",
        "ops",
        engine._obs_tid,
        args={
            "version": version,
            "in_flight": len(engine._staged_swap["in_flight_at_stage"]),
        },
    )
    summary = {
        "staged": True,
        "version": version,
        "staged_round": engine.rounds,
        "in_flight_at_stage": list(engine._staged_swap["in_flight_at_stage"]),
    }
    # Idle engines flip immediately — nothing to drain.
    summary["flipped"] = maybe_flip_swap(engine)
    return summary


def maybe_flip_swap(engine: ServeEngine) -> bool:
    """Flip a staged swap iff no old-side stream remains in flight: no
    slot live AND no recompute-preempted stream waiting in the queue (its
    committed tokens came from the old weights — resuming it on the new
    ones would hand back a stream that matches neither version). That is
    the round boundary where blue/green is a pure pointer exchange.
    Called by `ServeEngine.step` between expiry and admission; returns
    True when the flip happened."""
    st = engine._staged_swap
    if st is None:
        return False
    if any(s is not None for s in engine.slots):
        return False
    if any(q.uid in engine._resumed_uids for q in engine.queue):
        return False
    old_version = engine.weights_version
    engine.params = st["params"]
    if st["draft_params"] is not None:
        engine.draft_params = st["draft_params"]
    engine.weights_version = st["version"]
    engine._staged_swap = None
    engine.hot_swaps += 1
    record = {
        "staged_round": st["staged_round"],
        "flip_round": engine.rounds,
        "swap_latency_s": engine._clock() - st["staged_at"],
        "in_flight_at_stage": st["in_flight_at_stage"],
        "served_uids_at_flip": sorted(engine.finished),
        "from_version": old_version,
        "version": st["version"],
    }
    engine.swap_history.append(record)
    engine._trace.instant(
        "ops.hot_swap",
        "ops",
        engine._obs_tid,
        args={
            "version": st["version"],
            "from_version": old_version,
            "flip_round": engine.rounds,
        },
    )
    if engine.obs is not None:
        engine.obs.metrics.counter(
            "ops_hot_swaps", "completed blue/green weight flips"
        ).inc()
    return True


# ---------------------------------------------------------------------------
# Elastic pool resize
# ---------------------------------------------------------------------------


def assert_conserved(engine: ServeEngine, where: str) -> None:
    """The serving-wide page conservation law (`PagePool.conserved`: free +
    trie-held + live-slot-only == num_pages - 1, page 0 being the sink).
    Resize asserts it on BOTH sides of a migration."""
    assert engine.pool.conserved(engine.slots), (
        f"page conservation violated {where}: {engine.pool.ledger(engine.slots)}"
    )


def resize_pool(
    engine: ServeEngine,
    num_pages: tp.Optional[int] = None,
    *,
    max_slots: tp.Optional[int] = None,
) -> tp.Dict[str, tp.Any]:
    """Grow/shrink the live pool to `num_pages` (and/or the slot count to
    `max_slots`) by migrating the resident working set into a new pool.

    Runs between rounds on the engine thread (the async front door routes
    it through the driver loop, server.py). Protocol:

      1. Refuse (PoolResizeError, retryable) if the resident working set —
         live slot pages + referenced trie pages — cannot fit, or if live
         slots exceed the requested slot count.
      2. LRU-evict unreferenced trie pages that no longer fit.
      3. `PagePool.migrate`: gather resident pages (pow2 bucket,
         sink-padded), scatter into the new pool with the adoption jit
         (int8 scales ride along), install pool + allocator; then remap
         slot page lists + trie entries to the new ids.
      4. Conservation asserted on both sides.

    The new pool's first decode/prefill round compiles the page-bucket
    programs for the new num_pages (a program key); an identical resize
    replays from the jit cache — pinned in tests/test_recompile_pins.py.
    """
    old_total = engine.allocator.num_pages
    if num_pages is None:
        num_pages = old_total
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (sink + 1), got {num_pages}")
    live_slots = [s for s in engine.slots if s is not None]
    if max_slots is not None and max_slots < 1:
        raise ValueError(f"max_slots must be >= 1, got {max_slots}")
    if max_slots is not None and len(live_slots) > max_slots:
        raise PoolResizeError(
            f"resize refused: {len(live_slots)} live slots exceed the "
            f"requested max_slots={max_slots} — drain or cancel streams "
            "first (retryable)",
            requested_pages=num_pages,
            resident_pages=0,
            num_pages=old_total,
            requested_slots=max_slots,
            live_slots=len(live_slots),
        )

    pc = engine.prefix_cache
    live = engine.pool.live_pages(live_slots)[0]
    referenced = set() if pc is None else pc.referenced_pages()
    # Slot-shared pages (pages[:n_shared]) are referenced trie entries by
    # construction, so |live ∪ referenced| = |live − held| + |referenced|.
    resident = live | referenced
    if num_pages - 1 < len(resident):
        raise PoolResizeError(
            f"resize refused: requested num_pages={num_pages} holds "
            f"{num_pages - 1} pages but the resident working set is "
            f"{len(resident)} pages (live slots + referenced trie entries) "
            "— shrinking would drop live data; drain streams or evict "
            "first (retryable)",
            requested_pages=num_pages,
            resident_pages=len(resident),
            num_pages=old_total,
        )
    assert_conserved(engine, "before resize")

    trie_evicted = 0
    if pc is not None:
        held = pc.pages_held()
        overflow = len(live | held) - (num_pages - 1)
        if overflow > 0:
            # Only unreferenced entries are evictable; the resident check
            # above guarantees there are at least `overflow` of them.
            freed = engine.prefix_cache.evict(overflow)
            engine.pool.free(0, freed)
            trie_evicted = len(freed)
            assert trie_evicted == overflow, (
                f"resize eviction shortfall: wanted {overflow}, "
                f"evicted {trie_evicted}"
            )

    held = set() if pc is None else pc.pages_held()
    old_ids = sorted(live | held)
    n_migrate = len(old_ids)
    mapping = engine.pool.migrate(num_pages, old_ids)
    for s in live_slots:
        s.pages[0][:] = [mapping[p] if p >= 0 else -1 for p in s.pages[0]]
    if pc is not None:
        pc.remap_pages(mapping)
    if max_slots is not None and max_slots != engine.max_slots:
        # Live slots keep their _Slot objects; the page table is rebuilt
        # from engine.slots every round, so compaction is free. A new
        # max_slots is a program shape key — bounded, caller-chosen.
        engine.slots = live_slots + [None] * (max_slots - len(live_slots))
        engine.max_slots = max_slots
    assert_conserved(engine, "after resize")

    engine.resizes += 1
    record = {
        "round": engine.rounds,
        "from_pages": old_total,
        "to_pages": num_pages,
        "pages_migrated": n_migrate,
        "trie_pages_evicted": trie_evicted,
        "max_slots": engine.max_slots,
        "gather_bucket": _pow2_bucket(n_migrate) if n_migrate else 0,
    }
    engine.resize_history.append(record)
    engine._trace.instant(
        "ops.resize", "ops", engine._obs_tid,
        args={k: v for k, v in record.items()},
    )
    if engine.obs is not None:
        engine.obs.metrics.counter(
            "ops_resizes", "completed live pool resizes"
        ).inc()
        engine.obs.metrics.gauge(
            "ops_pool_pages", "current pool num_pages"
        ).set(float(num_pages))
    return record


# ---------------------------------------------------------------------------
# SLO-driven policy controller
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class OpsDecision:
    """One controller tick's outcome. kind is one of "none" | "grow" |
    "shrink" | "shed_threshold" | "re_role"; `applied` is False when the
    target refused (e.g. PoolResizeError on a shrink — recorded in
    `error`, retryable next tick) or when the controller runs advisory
    (`apply=False`)."""

    kind: str
    reason: str
    args: tp.Dict[str, tp.Any] = dataclasses.field(default_factory=dict)
    applied: bool = False
    error: tp.Optional[str] = None


class ModelOps:
    """Clock-injected SLO policy loop over a ServeEngine or a DisaggServe.

    Consumes only signals the engine already exposes (free-page fraction,
    backlog pages, shed fraction, handoff queue depth) plus an optional
    caller-measured `ttft_p95_ms` (a front end feeds its own window),
    and emits at most ONE decision per tick:

      grow            free pages below `low_free_frac`, TTFT over budget,
                      or shed fraction over budget -> resize the pool up
                      by `grow_frac`.
      shrink          free pages above `high_free_frac` with an idle
                      backlog -> resize down by `shrink_frac` (refusals
                      are recorded, not raised — retryable next tick).
      shed_threshold  persistent shedding with a healthy pool -> loosen
                      `max_backlog_pages` (scheduler.set_backlog_budget).
      re_role         disagg targets: deep handoff backlog -> move pool
                      pages prefill->decode (DisaggServe.rebalance);
                      starved prefill with an idle queue -> the reverse.

    A "none" tick touches no device state and dispatches no program —
    obs-on controller ticks are zero-recompile-pinned
    (tests/test_recompile_pins.py). Decisions surface as `ops.decision`
    tracer instants and `ops_*` Prometheus gauges.
    """

    def __init__(
        self,
        target,
        *,
        clock: tp.Callable[[], float] = time.perf_counter,
        obs=None,
        low_free_frac: float = 0.15,
        high_free_frac: float = 0.85,
        grow_frac: float = 0.5,
        shrink_frac: float = 0.25,
        min_interval_s: float = 0.0,
        ttft_budget_ms: tp.Optional[float] = None,
        shed_budget_frac: float = 0.25,
        handoff_backlog_high: int = 4,
        rebalance_pages: int = 4,
        apply: bool = True,
    ):
        self.target = target
        self._clock = clock
        self._disagg = hasattr(target, "prefill") and hasattr(target, "decode")
        if obs is None:
            obs = getattr(target, "obs", None)
        self.obs = obs
        self.low_free_frac = low_free_frac
        self.high_free_frac = high_free_frac
        self.grow_frac = grow_frac
        self.shrink_frac = shrink_frac
        self.min_interval_s = min_interval_s
        self.ttft_budget_ms = ttft_budget_ms
        self.shed_budget_frac = shed_budget_frac
        self.handoff_backlog_high = handoff_backlog_high
        self.rebalance_pages = rebalance_pages
        self.apply = apply
        self._last_tick: tp.Optional[float] = None
        self.decisions: tp.List[OpsDecision] = []

    # -- signal helpers --------------------------------------------------

    @staticmethod
    def _free_frac(eng) -> float:
        cap = eng.allocator.num_pages - 1
        return eng.allocator.free_count / max(1, cap)

    @staticmethod
    def _shed_frac(eng) -> float:
        return eng.shed / max(1, eng.shed + eng._uid)

    def _gauges(self, prefix: str, eng) -> None:
        if self.obs is None:
            return
        m = self.obs.metrics
        m.gauge(
            f"ops_{prefix}free_page_frac", "free pages / allocatable pages"
        ).set(self._free_frac(eng))
        m.gauge(
            f"ops_{prefix}backlog_pages", "worst-case page demand of live work"
        ).set(float(eng._backlog_pages()))
        m.gauge(
            f"ops_{prefix}shed_frac", "shed submits / total submits"
        ).set(self._shed_frac(eng))

    def _record(self, decision: OpsDecision) -> OpsDecision:
        self.decisions.append(decision)
        if self.obs is not None and decision.kind != "none":
            self.obs.tracer.instant(
                "ops.decision", "ops", "ops",
                args={
                    "kind": decision.kind,
                    "reason": decision.reason,
                    "applied": decision.applied,
                    **{k: v for k, v in decision.args.items()
                       if isinstance(v, (int, float, str, bool))},
                },
            )
            self.obs.metrics.counter(
                f"ops_decisions_{decision.kind}",
                f"controller '{decision.kind}' decisions",
            ).inc()
        return decision

    # -- tick ------------------------------------------------------------

    def tick(self, *, ttft_p95_ms: tp.Optional[float] = None) -> OpsDecision:
        now = self._clock()
        if (
            self._last_tick is not None
            and now - self._last_tick < self.min_interval_s
        ):
            return self._record(OpsDecision(kind="none", reason="interval"))
        self._last_tick = now
        if self._disagg:
            return self._tick_disagg()
        return self._tick_engine(self.target, ttft_p95_ms)

    def _tick_engine(self, eng, ttft_p95_ms) -> OpsDecision:
        self._gauges("", eng)
        cap = eng.allocator.num_pages - 1
        free_frac = self._free_frac(eng)
        shed_frac = self._shed_frac(eng)
        ttft_over = (
            self.ttft_budget_ms is not None
            and ttft_p95_ms is not None
            and ttft_p95_ms > self.ttft_budget_ms
        )
        if free_frac < self.low_free_frac or ttft_over:
            new_pages = 1 + int(math.ceil(cap * (1.0 + self.grow_frac)))
            reason = "ttft_over_budget" if ttft_over else "free_pages_low"
            d = OpsDecision(
                kind="grow", reason=reason,
                args={"from_pages": cap + 1, "to_pages": new_pages,
                      "free_frac": free_frac},
            )
            if self.apply:
                eng.resize(new_pages)
                d.applied = True
            return self._record(d)
        if shed_frac > self.shed_budget_frac and eng.max_backlog_pages is not None:
            from midgpt_tpu.sampling.scheduler import set_backlog_budget

            new_budget = int(eng.max_backlog_pages * 1.5) + 1
            d = OpsDecision(
                kind="shed_threshold", reason="shed_frac_over_budget",
                args={"from_budget": eng.max_backlog_pages,
                      "to_budget": new_budget, "shed_frac": shed_frac},
            )
            if self.apply:
                set_backlog_budget(eng, new_budget)
                d.applied = True
            return self._record(d)
        if free_frac > self.high_free_frac and eng._backlog_pages() == 0:
            new_pages = 1 + max(1, int(math.ceil(cap * (1.0 - self.shrink_frac))))
            if new_pages < cap + 1:
                d = OpsDecision(
                    kind="shrink", reason="free_pages_high",
                    args={"from_pages": cap + 1, "to_pages": new_pages,
                          "free_frac": free_frac},
                )
                if self.apply:
                    try:
                        eng.resize(new_pages)
                        d.applied = True
                    except PoolResizeError as e:
                        d.error = str(e)
                return self._record(d)
        return self._record(OpsDecision(kind="none", reason="in_band"))

    def _tick_disagg(self) -> OpsDecision:
        d = self.target
        self._gauges("prefill_", d.prefill)
        self._gauges("decode_", d.decode)
        depth = d.queue.stats()["depth"]
        if self.obs is not None:
            self.obs.metrics.gauge(
                "ops_handoff_depth", "prefill->decode handoff queue depth"
            ).set(float(depth))
        if depth > self.handoff_backlog_high:
            dec = OpsDecision(
                kind="re_role", reason="handoff_backlog_deep",
                args={"src": "prefill", "dst": "decode",
                      "pages": self.rebalance_pages, "depth": depth},
            )
            if self.apply:
                try:
                    d.rebalance(self.rebalance_pages, src="prefill", dst="decode")
                    dec.applied = True
                except PoolResizeError as e:
                    dec.error = str(e)
            return self._record(dec)
        if (
            depth == 0
            and self._free_frac(d.prefill) < self.low_free_frac
            and self._free_frac(d.decode) > self.high_free_frac
        ):
            dec = OpsDecision(
                kind="re_role", reason="prefill_starved",
                args={"src": "decode", "dst": "prefill",
                      "pages": self.rebalance_pages, "depth": depth},
            )
            if self.apply:
                try:
                    d.rebalance(self.rebalance_pages, src="decode", dst="prefill")
                    dec.applied = True
                except PoolResizeError as e:
                    dec.error = str(e)
            return self._record(dec)
        return self._record(OpsDecision(kind="none", reason="in_band"))
