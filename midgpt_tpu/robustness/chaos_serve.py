"""Serving chaos scenarios: inject one of the serving fault kinds into a
seeded trace and assert the engine DEGRADES instead of breaking.

The training chaos harness (tools/chaos_run.py + robustness/faults.py)
proves recovery end to end by running the real supervisor against injected
failures. This module is the serving twin: `run_serving_chaos` runs the
same seeded request trace twice — once fault-free for reference, once with
a fault plan armed — and checks the three degradation invariants the chaos
gate (tests/test_chaos_serve.py, `chaos_run.py --serve`) enforces:

  1. **Alive** — the engine (and, for client faults, the async front door)
     finishes the trace; no fault kind may crash the process.
  2. **Conserved** — every pool page is back on the free list afterwards
     (`free_count == num_pages - 1`), whatever was shed/killed/poisoned.
  3. **Isolated** — greedy token streams of UNAFFECTED requests are
     bit-identical to the fault-free run (greedy determinism pin,
     tests/test_chaos_serve.py). "Affected" is fault-specific and
     engine-reported: `poisoned_uids` for poisoned_page, non-"ok" statuses
     for sheds/timeouts/cancels. kill_mid_decode affects NOBODY — its
     recovery is recompute preemption, which is parity-preserving — so
     there every request must match. kill_overlapped_round is its
     round-overlap twin (docs/SERVING.md "Round-overlap dispatch"): the
     engine runs with `overlap="double"`, the fault drops the IN-FLIGHT
     dispatched round's handle un-settled mid host phase, and the same
     recompute-preemption path must regenerate every lost token — the
     reference pass stays un-overlapped, so the parity check also re-proves
     that overlap itself is bit-exact.

Two model-ops scenarios ride the same harness (sampling/ops.py,
docs/ROBUSTNESS.md "Zero-downtime model ops") with a THREE-sided parity
check instead of invariant 3's two-sided one:

  * `hot_swap_mid_decode@k` — verified-checkpoint weights (saved and
    restored through the real training/checkpoint.py manifest path) are
    staged at round k and flip blue/green: zero streams dropped, streams
    finished before the flip bit-match a fault-free OLD-weights pass,
    streams admitted after bit-match a fault-free NEW-weights pass, pool +
    trie conserved across the flip.
  * `pool_resize@j,pool_resize@k` — the pool grows then shrinks mid-trace
    (engine `resize_plan`), on an int8 cache so the scale side buffers
    must migrate with their pages: conservation holds at every boundary
    (asserted inside resize_pool AND after the drain) and EVERY stream is
    bit-identical to a no-resize pass — a resize affects nobody.

The fleet scenarios (sampling/fleet.py, `_run_fleet_chaos`) run the trace
through TWO replicas behind a FleetRouter with its shared host-RAM spill
tier and extend all three invariants across replicas and tiers:
`engine_crash@k` kills the busiest replica at router round k — zero
accepted streams drop, failover replays bit-match the single-engine
reference; `handoff_stall` / `spill_corrupt` hit the spill path — a
stalled transport falls back to re-prefill and a corrupt page is caught
by the take-side checksum, either way never a token mismatch — with
cross-tier page conservation (assert_fleet_conserved) after the drain.

The cross-process kinds (`proc_kill9` / `conn_drop` / `wire_corrupt` /
`wire_stall`, `_run_proc_fleet_chaos`) run the same fleet gate with the
replica boundary promoted to real worker PROCESSES behind the framed
socket transport (sampling/fleet_proc.py): a hard `kill -9` of a worker
mid-decode must be detected purely through the wire and produce the exact
engine_crash failover story — zero drops, cross-process bit-parity,
ledgers closing across the boundary — while the pure wire faults must be
absorbed by the transport's checksum/deadline/retry machinery invisibly.

Faults are deterministic for a seeded trace: round-keyed kinds fire on the
engine's round counter (`kill_mid_decode@7` = round 7), slow_client keys on
the victim uid, submit_storm keys on the arrival index at which the burst
lands. This module is import-light glue; the faults it arms live in the one
registry every chaos path shares.

Every scenario runs its fault pass under a flight recorder and leaves a
postmortem (`flight_recorder.json` + `.prom`): in `trace_dir` when the
caller gave one, and in a fresh temp dir — path appended to the failing
AssertionError — when an invariant breaks without one.
"""

from __future__ import annotations

import asyncio
import subprocess
import tempfile
import typing as tp

import numpy as np

from midgpt_tpu.obs import Observability
from midgpt_tpu.robustness import faults

# Storm burst: how many clone requests the submit_storm fault slams into
# the engine at its arrival index (sized to overrun the default backlog
# budget below several times over).
STORM_SIZE = 8
# Backlog budget armed for storm scenarios — small enough that the burst
# MUST shed, big enough that the base trace admits.
STORM_BACKLOG_PAGES = 24
# Grow-then-shrink targets for the pool_resize scenario, applied in plan
# order from the 29-page base geometry below. 43/37 are fresh geometries:
# pool size is a program-key dim and the recompile pins count from
# pristine/warm baselines in the same pytest process (see _engine).
RESIZE_TARGETS = [43, 37]


def _tiny_cfg():
    from midgpt_tpu.models.gpt import GPTConfig

    return GPTConfig(
        block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32
    )


def _tiny_model(seed: int):
    import jax

    from midgpt_tpu.models.gpt import GPT

    cfg = _tiny_cfg()
    return cfg, GPT.init(cfg, jax.random.PRNGKey(seed))


def _trace(cfg, seed: int, n_requests: int, shared: bool = False):
    rng = np.random.default_rng(seed)
    out = []
    # `shared` (the evict_shared_prefix scenario): template-heavy traffic —
    # two 16-token system prompts with short unique tails — so the prefix
    # trie holds HOT shared nodes for the fault to flush. Drawn only in
    # shared mode: the plain scenarios' seeded traces must stay the exact
    # RNG stream their step-keyed fault plans were tuned against.
    templates = [
        rng.integers(0, cfg.vocab_size, 16).astype(np.int32) for _ in range(2)
    ] if shared else []
    for i in range(n_requests):
        if shared:
            tail = rng.integers(
                0, cfg.vocab_size, int(rng.integers(2, 6))
            ).astype(np.int32)
            prompt = np.concatenate([templates[i % 2], tail])
            m = int(rng.integers(6, 16))
        else:
            # draw order (t0, m, prompt) is load-bearing: the plain
            # scenarios' step-keyed fault plans were tuned against it
            t0 = int(rng.integers(4, 24))
            m = int(rng.integers(6, 16))
            prompt = rng.integers(0, cfg.vocab_size, t0).astype(np.int32)
        out.append((prompt, m))
    return out


def _engine(
    cfg, params, *, max_backlog_pages=None, clock=None, prefix=False,
    obs=None, cache_dtype=None, overlap="off",
):
    import jax.numpy as jnp

    from midgpt_tpu.sampling.serve import ServeEngine

    kw: tp.Dict[str, tp.Any] = {}
    if clock is not None:
        kw["clock"] = clock
    if obs is not None:
        kw["obs"] = obs
    return ServeEngine(
        cfg,
        params,
        max_slots=3,
        page_size=8,
        # NOT 25: the pool size is a program-key dim, and the recompile pin
        # (tests/test_recompile_pins.py) counts compiles of the 25-page f32
        # program set from a pristine baseline — chaos runs in the same
        # pytest process must not pre-warm that exact geometry.
        num_pages=29,
        prefill_chunk=16,
        decode_chunk=4,
        temperature=0.0,
        cache_dtype=jnp.float32 if cache_dtype is None else cache_dtype,
        max_backlog_pages=max_backlog_pages,
        prefix_cache=prefix,
        overlap=overlap,
        **kw,
    )


def _run_plain(eng, trace, storm: bool):
    """Drive the engine synchronously. Returns (uid -> trace index,
    n_storm_shed). With `storm`, each arrival consults the submit_storm
    fault (step = arrival index) and, when it fires, slams STORM_SIZE
    clones of that request in at once — the admitted ones compete for the
    pool like real duplicate traffic, the rest must shed."""
    from midgpt_tpu.sampling.serve import BackpressureError

    uid_to_idx: tp.Dict[int, int] = {}
    storm_shed = 0
    for idx, (prompt, m) in enumerate(trace):
        if storm and faults.should_fire("submit_storm", step=idx):
            for _ in range(STORM_SIZE):
                try:
                    eng.submit(prompt, m)  # clones: excluded from parity
                except BackpressureError:
                    storm_shed += 1
        try:
            uid_to_idx[eng.submit(prompt, m)] = idx
        except BackpressureError:
            storm_shed += 1
    eng.run()
    return uid_to_idx, storm_shed


def _run_trickle(eng, trace, arrival_stride: int = 2):
    """Drive the engine with STAGGERED arrivals — one submission every
    `arrival_stride` rounds — instead of _run_plain's upfront burst, so a
    mid-trace model op deterministically has traffic on BOTH sides of its
    boundary (the hot-swap gate needs post-flip admissions). Greedy
    streams are batch-composition-independent, so parity against an
    upfront-submitted reference pass is still exact — the same property
    the preemption/disagg parity gates lean on, pinned end to end in
    tests/test_chaos_serve.py (hot-swap and pool-resize gates)."""
    uid_to_idx: tp.Dict[int, int] = {}
    pending = list(enumerate(trace))
    r = 0
    while pending or not eng.idle:
        if pending and r % arrival_stride == 0:
            idx, (prompt, m) = pending.pop(0)
            uid_to_idx[eng.submit(prompt, m)] = idx
        eng.step()
        r += 1
        assert r < 10_000, "trickle drive did not converge"
    return uid_to_idx


def _run_server(eng, trace):
    """Drive the engine through the async front door, one consumer task
    per request, collecting delivered tokens (what a client actually saw —
    the thing slow-client sheds must not corrupt for anyone else)."""
    from midgpt_tpu.sampling.server import AsyncServeServer

    delivered: tp.Dict[int, tp.List[int]] = {}
    uid_to_idx: tp.Dict[int, int] = {}

    async def main():
        # The bound is what ONE step can hand a client that has read all it
        # was sent: the first token and a decode chunk. A step lower and a
        # healthy client is shed unless its consumer wins a race INSIDE the
        # step (the first token read before the chunk lands); between steps
        # the loop runs the consumers before it starts the next one.
        server = AsyncServeServer(
            eng, max_buffered_tokens=1 + eng.decode_chunk, submit_retries=1,
            idle_poll_s=0.001,
        )
        driver = asyncio.create_task(server.run())

        async def consume(uid):
            delivered[uid] = []
            async for tok in server.stream(uid):
                delivered[uid].append(tok)

        consumers = []
        for idx, (prompt, m) in enumerate(trace):
            uid = await server.submit(prompt, m)
            uid_to_idx[uid] = idx
            consumers.append(asyncio.create_task(consume(uid)))
        await asyncio.gather(*consumers)
        await server.drain()
        await driver

    asyncio.run(main())
    return uid_to_idx, delivered


# -- shared scenario scaffolding (one builder, one postmortem policy) ------


def _reference_pass(cfg, params, trace, *, prefix=False, cache_dtype=None):
    """Fault-free pass -> {trace index: full reference token array}. Also
    warms every jit shape, so the fault pass's timings/timeouts cannot
    hinge on compile stalls. Clears the registry first: a previously armed
    plan must never leak into a reference."""
    faults.clear()
    ref = _engine(cfg, params, prefix=prefix, cache_dtype=cache_dtype)
    ref_uids, _ = _run_plain(ref, trace, storm=False)
    return {
        idx: np.asarray(ref.finished[uid].tokens)
        for uid, idx in ref_uids.items()
    }


def _armed_engine(cfg, params, fault_plan, **engine_kw):
    """Arm `fault_plan` and build the engine-under-fault with its flight
    recorder — the ONE construction point every scenario shares (each
    fault kind used to re-spell this pair). Returns (eng, obs, armed)."""
    faults.clear()
    armed = faults.activate_plan(fault_plan)
    obs = Observability()
    eng = _engine(cfg, params, obs=obs, **engine_kw)
    return eng, obs, armed


def _run_scenario(obs, trace_dir, body):
    """Run `body()` — the fault pass PLUS its invariant checks — under the
    postmortem policy: dump the flight recorder into `trace_dir` when the
    caller asked for one, and on ANY failure even without one (fresh temp
    dir, path appended to the exception) so a broken invariant always
    leaves a loadable trace. Returns body's summary with "trace" set."""
    try:
        summary = body()
    except BaseException as e:
        d = trace_dir or tempfile.mkdtemp(prefix="midgpt_chaos_postmortem_")
        path = obs.dump(d)
        e.args = tuple(
            [f"{e.args[0]}\n[flight recorder: {path}]"] + list(e.args[1:])
        ) if e.args else (f"[flight recorder: {path}]",)
        raise
    summary["trace"] = None if trace_dir is None else obs.dump(trace_dir)
    return summary


def _assert_drained_conserved(eng) -> int:
    """Invariant 2 (+ serviceability): engine drained, every page either
    free or retained by the trie with zero live references. Returns the
    trie page count for the summary."""
    assert eng.idle, "engine left work behind"
    trie_pages = (
        0 if eng.prefix_cache is None else eng.prefix_cache.page_count()
    )
    assert eng.pool.conserved(eng.slots), f"page leak: {eng.pool.ledger(eng.slots)}"
    if eng.prefix_cache is not None:
        dangling = eng.prefix_cache.referenced_page_count()
        assert dangling == 0, f"{dangling} trie refcount(s) outlived the drain"
    return trie_pages


def run_serving_chaos(
    fault_plan: str, *, seed: int = 0, n_requests: int = 5,
    trace_dir: tp.Optional[str] = None,
) -> tp.Dict[str, tp.Any]:
    """Run the scenario (module docstring); returns the summary dict that
    `chaos_run.py --serve` emits as its JSON line. Raises AssertionError
    when a degradation invariant breaks — that IS the chaos verdict.

    The fault pass always runs under a flight recorder (midgpt_tpu/obs/):
    with `trace_dir` the Chrome trace + .prom metrics land there
    unconditionally; without one they land in a temp dir only when an
    invariant fails (the path rides the AssertionError)."""
    if any(
        k in fault_plan
        for k in ("proc_kill9", "conn_drop", "wire_corrupt", "wire_stall")
    ):
        return _run_proc_fleet_chaos(
            fault_plan, seed=seed, n_requests=n_requests, trace_dir=trace_dir
        )
    if any(
        k in fault_plan
        for k in ("engine_crash", "handoff_stall", "spill_corrupt")
    ):
        return _run_fleet_chaos(
            fault_plan, seed=seed, n_requests=n_requests, trace_dir=trace_dir
        )
    if "hot_swap_mid_decode" in fault_plan:
        return _run_hot_swap_chaos(
            fault_plan, seed=seed, n_requests=n_requests, trace_dir=trace_dir
        )
    if "pool_resize" in fault_plan:
        return _run_pool_resize_chaos(
            fault_plan, seed=seed, n_requests=n_requests, trace_dir=trace_dir
        )
    cfg, params = _tiny_model(seed)
    uses_server = "slow_client" in fault_plan
    uses_storm = "submit_storm" in fault_plan
    # The trie-flush fault needs a trie: both passes run with the prefix
    # cache ON over a template-shared trace, so the reference pass also
    # proves the cache itself is parity-clean before the flush is judged.
    uses_prefix = "evict_shared_prefix" in fault_plan
    # The overlap-kill fault needs an in-flight dispatched round to drop:
    # only the fault pass runs double-buffered — the reference stays plain,
    # so invariant 3 doubles as an overlap-on-vs-off greedy parity check.
    uses_overlap = "kill_overlapped_round" in fault_plan
    trace = _trace(cfg, seed + 1, n_requests, shared=uses_prefix)

    ref_tokens = _reference_pass(cfg, params, trace, prefix=uses_prefix)
    eng, obs, armed = _armed_engine(
        cfg, params, fault_plan,
        max_backlog_pages=STORM_BACKLOG_PAGES if uses_storm else None,
        prefix=uses_prefix,
        overlap="double" if uses_overlap else "off",
    )

    def body() -> tp.Dict[str, tp.Any]:
        delivered: tp.Optional[tp.Dict[int, tp.List[int]]] = None
        storm_shed = 0
        if uses_server:
            uid_to_idx, delivered = _run_server(eng, trace)
        else:
            uid_to_idx, storm_shed = _run_plain(eng, trace, storm=uses_storm)
        fired = faults.fired_counts()
        faults.clear()

        _assert_drained_conserved(eng)

        # -- invariant 3: unaffected greedy streams are bit-identical ----
        affected = set(eng.poisoned_uids)
        statuses: tp.Dict[str, int] = {}
        parity_checked = parity_ok = 0
        for uid, idx in uid_to_idx.items():
            fr = eng.finished.get(uid)
            assert fr is not None, f"request {uid} vanished"
            statuses[fr.status] = statuses.get(fr.status, 0) + 1
            if fr.status != "ok":
                affected.add(uid)  # shed/timeout/slow_client: partial by design
            if uid in affected:
                continue
            parity_checked += 1
            if np.array_equal(np.asarray(fr.tokens), ref_tokens[idx]):
                parity_ok += 1
            if delivered is not None:
                # What the client consumed must be a prefix of the reference
                # generation — streaming may trail the engine, never diverge.
                prompt_len = len(trace[idx][0])
                got = np.asarray(delivered[uid], np.int32)
                want = ref_tokens[idx][prompt_len:prompt_len + len(got)]
                assert np.array_equal(got, want), (
                    f"delivered stream diverged for request {uid}"
                )
        assert parity_ok == parity_checked, (
            f"greedy parity broke on {parity_checked - parity_ok} unaffected "
            f"request(s)"
        )
        assert sum(fired.values()) >= min(1, len(armed)), "no armed fault fired"
        if fired.get("kill_overlapped_round"):
            assert eng.overlap_kills >= 1, (
                "overlap kill fired but no in-flight round was ever dropped"
            )

        return {
            "mode": "serve",
            "fault_plan": fault_plan,
            "faults_fired": fired,
            "n_requests": n_requests,
            "statuses": statuses,
            "overlap_mode": eng.overlap,
            "overlap_kills": eng.overlap_kills,
            "shed": eng.shed + storm_shed,
            "timeouts": eng.timeouts,
            "cancelled": eng.cancelled,
            "decode_kills": eng.decode_kills,
            "preemptions": eng.preemptions,
            "poisoned": len(eng.poisoned_uids),
            "parity_checked": parity_checked,
            "parity_ok": parity_ok,
            "pages_conserved": True,
            "prefix_cache": eng.prefix_cache is not None,
            "prefix_reclaimed": eng.prefix_evictions,
            "prefix_hit_rate": eng.prefix_stats()["hit_rate"],
        }

    return _run_scenario(obs, trace_dir, body)


# -- fleet scenarios (sampling/fleet.py) -----------------------------------


def _fleet_router(cfg, params, obs, n_replicas: int = 2):
    """The fleet-under-fault: `n_replicas` prefix-cached greedy engines
    behind a FleetRouter with its shared spill tier (the router attaches
    it). Same per-replica shape as _engine except the pool: 31 is a fresh
    program-key geometry — not 25 (recompile-pin baseline),
    29 (single-engine chaos), or 43/37 (resize targets)."""
    import jax.numpy as jnp

    from midgpt_tpu.sampling.fleet import FleetRouter
    from midgpt_tpu.sampling.serve import ServeEngine

    engines = [
        ServeEngine(
            cfg,
            params,
            max_slots=3,
            page_size=8,
            num_pages=31,
            prefill_chunk=16,
            decode_chunk=4,
            temperature=0.0,
            cache_dtype=jnp.float32,
            prefix_cache=True,
            obs=obs,
            obs_tid=f"replica{i}",
        )
        for i in range(n_replicas)
    ]
    return FleetRouter(engines)


def _run_fleet_chaos(fault_plan, *, seed, n_requests, trace_dir):
    """Fleet degradation gate (docs/ROBUSTNESS.md "Fleet serving &
    failover"): run the shared-template trace through a 2-replica fleet
    with `fault_plan` armed and assert the three invariants extended
    across replicas and tiers —

      1. Alive: the FLEET finishes the trace; killing a replica mid-trace
         (engine_crash) drops ZERO accepted streams — they fail over.
      2. Conserved, cross-tier: every alive replica obeys the pool law
         and the spill ledger closes (assert_fleet_conserved), including
         through the spill_corrupt discard path.
      3. Bit-identical: EVERY stream — survivors and failover replays —
         matches a fault-free single-engine reference pass. A corrupt or
         stalled spill page may cost a re-prefill, never a token.

    The spill-path kinds (handoff_stall / spill_corrupt) need resident
    spilled pages to bite on, which organic pressure only produces at
    pool sizes that make the trace nondeterministically tight. Instead
    the scenario STAGES the tier: the first request runs alone, then
    every replica's trie is force-flushed (the same reclaim the
    evict_shared_prefix fault models), spilling its pages to the host
    tier deterministically; the remaining same-template requests then
    consult the tier on admission — where the armed stall refuses the
    first useful run and the armed corruption is caught by the take-side
    checksum."""
    from midgpt_tpu.sampling.fleet import assert_fleet_conserved

    cfg, params = _tiny_model(seed)
    trace = _trace(cfg, seed + 1, n_requests, shared=True)
    ref_tokens = _reference_pass(cfg, params, trace, prefix=True)

    faults.clear()
    armed = faults.activate_plan(fault_plan)
    obs = Observability()
    router = _fleet_router(cfg, params, obs)
    stage_spill = any(
        k in fault_plan for k in ("handoff_stall", "spill_corrupt")
    )

    def body() -> tp.Dict[str, tp.Any]:
        uid_to_idx: tp.Dict[int, int] = {}
        pending = list(enumerate(trace))
        if stage_spill and pending:
            idx, (prompt, m) = pending.pop(0)
            uid_to_idx[router.submit(prompt, m)] = idx
            router.run()
            for i, rep in enumerate(router.engines):
                if router.alive[i]:
                    rep._evict_shared_prefix_fault()
        r = 0
        while pending or not router.idle:
            if pending:
                idx, (prompt, m) = pending.pop(0)
                # trickled one per round (like _run_trickle): a mid-trace
                # crash deterministically finds accepted streams in flight
                uid_to_idx[router.submit_retry(prompt, m)] = idx
            router.step()
            r += 1
            assert r < 10_000, "fleet drive did not converge"
        fired = faults.fired_counts()
        faults.clear()

        # -- invariant 2, extended across replicas AND tiers -------------
        assert_fleet_conserved(router, "after drain")
        for i, rep in enumerate(router.engines):
            if router.alive[i]:
                _assert_drained_conserved(rep)

        # -- invariants 1 + 3: zero drops, every stream bit-identical ----
        statuses: tp.Dict[str, int] = {}
        parity_checked = parity_ok = 0
        for uid, idx in uid_to_idx.items():
            fr = router.finished.get(uid)
            assert fr is not None, f"accepted stream {uid} vanished"
            statuses[fr.status] = statuses.get(fr.status, 0) + 1
            assert fr.status == "ok", (
                f"accepted stream {uid} dropped with status {fr.status!r}"
            )
            parity_checked += 1
            if np.array_equal(np.asarray(fr.tokens), ref_tokens[idx]):
                parity_ok += 1
        assert parity_ok == parity_checked, (
            f"greedy parity broke on {parity_checked - parity_ok} "
            f"stream(s) vs the fault-free single-engine pass"
        )
        assert sum(fired.values()) >= min(1, len(armed)), "no armed fault fired"
        if fired.get("engine_crash"):
            assert router.failovers >= 1, "crash fired but nobody died"
            assert router.failed_over_streams >= 1, (
                "crash fired with no accepted streams to fail over — "
                "the gate proved nothing"
            )
        if fired.get("handoff_stall"):
            assert router.spill.stall_fallbacks >= 1, (
                "stall armed but no consult ever fell back to re-prefill"
            )
        if fired.get("spill_corrupt"):
            assert router.spill.corrupt_discarded >= 1, (
                "corruption armed but never caught by the take-side checksum"
            )

        return {
            "mode": "serve",
            "fault_plan": fault_plan,
            "faults_fired": fired,
            "n_requests": n_requests,
            "statuses": statuses,
            "shed": sum(e.shed for e in router.engines),
            "timeouts": sum(e.timeouts for e in router.engines),
            "cancelled": sum(e.cancelled for e in router.engines),
            "decode_kills": sum(e.decode_kills for e in router.engines),
            "preemptions": sum(e.preemptions for e in router.engines),
            "poisoned": 0,
            "parity_checked": parity_checked,
            "parity_ok": parity_ok,
            "pages_conserved": True,
            "prefix_cache": True,
            "prefix_reclaimed": sum(
                e.prefix_evictions for e in router.engines
            ),
            "prefix_hit_rate": router.prefix_hit_rate(),
            "fleet_size": len(router.engines),
            "alive": sum(router.alive),
            "failovers": router.failovers,
            "failed_over_streams": router.failed_over_streams,
            "dropped_streams": 0,
            "spill": router.spill.stats(),
        }

    return _run_scenario(obs, trace_dir, body)


# -- cross-process fleet scenarios (sampling/fleet_proc.py) ----------------


def proc_worker_spec(seed: int, *, cpu_devices: int = 1) -> tp.Dict[str, tp.Any]:
    """Worker spec matching the chaos fleet geometry: the same tiny model
    at the same seed (same-seed GPT.init on the same pinned CPU backend =>
    bit-identical params in every process, the foundation of cross-process
    greedy parity — pinned end to end by tests/test_fleet_proc.py) and the
    31-page fleet pool. Workers have their OWN jit
    caches, so 31 collides with nothing in the parent (the program-key
    geometry ledger in _fleet_router's docstring is per-process)."""
    import dataclasses as _dc

    from midgpt_tpu.sampling.fleet_proc import parent_jax_config

    return {
        "model": _dc.asdict(_tiny_cfg()),
        "seed": seed,
        "engine": {
            "max_slots": 3,
            "page_size": 8,
            "num_pages": 31,
            "prefill_chunk": 16,
            "decode_chunk": 4,
            "cache_dtype": "float32",
        },
        "cpu_devices": cpu_devices,
        "jax_config": parent_jax_config(),
    }


def _proc_reference_pass(port, trace):
    """Fault-free single-engine pass driven over the wire on an
    already-spawned worker (same spec, same pinned CPU backend as the
    fleet workers) -> {trace index: full token array}. Running the
    reference in-parent would compare across BACKENDS whenever the parent
    sits on the real TPU (chaos_run.py without JAX_PLATFORMS=cpu) —
    worker-vs-worker keeps the parity claim about the process boundary,
    not about TPU-vs-CPU matmul bit patterns. Upfront submission (vs the
    fleet drive's trickle) is fine: greedy streams are
    batch-composition-independent, the same property every other parity
    gate leans on (tests/test_fleet_proc.py runs this gate non-slow)."""
    from midgpt_tpu.sampling.fleet_proc import connect_replica

    faults.clear()
    rep = connect_replica(port)
    uid_to_idx = {}
    for idx, (prompt, m) in enumerate(trace):
        uid_to_idx[rep.submit(prompt, m)] = idx
    r = 0
    while not rep.idle:
        rep.step()
        r += 1
        assert r < 10_000, "proc reference drive did not converge"
    ref = {
        idx: np.asarray(rep.finished[uid].tokens)
        for uid, idx in uid_to_idx.items()
    }
    rep.close()
    return ref


def _run_proc_fleet_chaos(fault_plan, *, seed, n_requests, trace_dir):
    """Cross-process fleet degradation gate (docs/ROBUSTNESS.md
    "Cross-process fleet"): the _run_fleet_chaos invariants with the
    replica boundary promoted to a real OS process boundary — two worker
    PROCESSES (fleet_proc.spawn_worker, each its own jax backend and jit
    cache) behind a FleetRouter speaking the framed socket transport.

      1. Alive: `proc_kill9` SIGKILLs the busiest worker mid-decode and
         the fleet still finishes every accepted stream — detection flows
         purely through the wire (ReplicaGoneError -> consecutive-failure
         health check -> the same _crash failover as engine_crash), zero
         drops, bounded requeue then structured shed.
      2. Conserved, across the process boundary: alive workers run the
         pool law + spill ledger IN-process over the `conserve` RPC
         (assert_fleet_conserved dispatches), and the router-side tier
         ledger closes.
      3. Bit-identical: every stream — survivors and failover replays —
         matches a fault-free single-engine reference served by its own
         worker process (_proc_reference_pass), proving params, prefill,
         and decode agree bit-for-bit across process boundaries.

    The wire kinds (`conn_drop` / `wire_corrupt` / `wire_stall`) must be
    absorbed by the transport invisibly: same zero-drop, same parity, plus
    the per-kind transport counter proving the fault actually bit
    (reconnects / corrupt_frames / deadline_expiries).

    The router process must also compile NOTHING: the parent's jit census
    (ServeEngine.compile_stats) is snapshotted up front and pinned
    unchanged after the drive — the whole scenario runs without a single
    parent-process engine program. Pinned by tests/test_fleet_proc.py
    (kill-and-survive representative + slow wire-kind scenarios)."""
    from midgpt_tpu.sampling.fleet import FleetRouter, assert_fleet_conserved
    from midgpt_tpu.sampling.fleet_proc import connect_replica, spawn_workers
    from midgpt_tpu.sampling.serve import ServeEngine

    cfg = _tiny_cfg()
    trace = _trace(cfg, seed + 1, n_requests, shared=True)
    compiles_before = ServeEngine.compile_stats()
    spec = proc_worker_spec(seed)
    procs = []
    try:
        # all three workers (reference + 2 replicas) spawn CONCURRENTLY:
        # jax import + engine build overlap, and the fleet workers keep
        # warming while the reference pass drives worker 0
        procs = spawn_workers(spec, 3)
        ref_tokens = _proc_reference_pass(procs[0][1], trace)
        procs[0][0].kill()

        faults.clear()
        armed = faults.activate_plan(fault_plan)
        obs = Observability()
        replicas = [
            connect_replica(port, retry_base_s=0.05, obs=obs)
            for _, port in procs[1:]
        ]
        router = FleetRouter(replicas)

        def body() -> tp.Dict[str, tp.Any]:
            uid_to_idx: tp.Dict[int, int] = {}
            pending = list(enumerate(trace))
            r = 0
            while pending or not router.idle:
                if pending:
                    idx, (prompt, m) = pending.pop(0)
                    # trickled one per round (like _run_fleet_chaos): the
                    # round-keyed kill deterministically lands mid-decode
                    uid_to_idx[router.submit_retry(prompt, m)] = idx
                router.step()
                r += 1
                # wider guard than the in-process drive: kill -9 detection
                # costs max_consecutive_failures failed rounds first
                assert r < 20_000, "proc fleet drive did not converge"
            fired = faults.fired_counts()
            faults.clear()

            # -- invariant 2, across the process boundary ---------------
            assert_fleet_conserved(router, "after proc drain")

            # -- invariants 1 + 3: zero drops, bit-parity cross-process -
            statuses: tp.Dict[str, int] = {}
            parity_checked = parity_ok = 0
            for uid, idx in uid_to_idx.items():
                fr = router.finished.get(uid)
                assert fr is not None, f"accepted stream {uid} vanished"
                statuses[fr.status] = statuses.get(fr.status, 0) + 1
                assert fr.status == "ok", (
                    f"accepted stream {uid} dropped with status "
                    f"{fr.status!r}"
                )
                parity_checked += 1
                if np.array_equal(np.asarray(fr.tokens), ref_tokens[idx]):
                    parity_ok += 1
            assert parity_ok == parity_checked, (
                f"greedy parity broke on {parity_checked - parity_ok} "
                "stream(s) vs the fault-free in-process reference"
            )
            assert sum(fired.values()) >= min(1, len(armed)), (
                "no armed fault fired"
            )
            transport = router.transport_stats()
            if fired.get("proc_kill9"):
                assert router.proc_failovers >= 1, (
                    "kill -9 fired but the wire never reported the death"
                )
                assert router.failed_over_streams >= 1, (
                    "kill -9 fired with no accepted streams to fail over "
                    "— the gate proved nothing"
                )
            if fired.get("conn_drop"):
                assert transport["reconnects"] >= 1, (
                    "connection dropped but no RPC ever reconnected"
                )
            if fired.get("wire_corrupt"):
                assert transport["corrupt_frames"] >= 1, (
                    "frame corruption armed but the checksum never "
                    "rejected one"
                )
            if fired.get("wire_stall"):
                assert transport["deadline_expiries"] >= 1, (
                    "stall armed but no RPC deadline ever expired"
                )

            # -- recompile pin: the router process compiled nothing -----
            compiles_after = ServeEngine.compile_stats()
            assert compiles_after == compiles_before, (
                f"router process compiled programs for proc replicas: "
                f"{compiles_before} -> {compiles_after}"
            )

            return {
                "mode": "serve",
                "fault_plan": fault_plan,
                "faults_fired": fired,
                "n_requests": n_requests,
                "statuses": statuses,
                "shed": router.router_shed,
                "timeouts": sum(e.timeouts for e in router.engines),
                "cancelled": sum(e.cancelled for e in router.engines),
                "decode_kills": sum(e.decode_kills for e in router.engines),
                "preemptions": sum(e.preemptions for e in router.engines),
                "poisoned": 0,
                "parity_checked": parity_checked,
                "parity_ok": parity_ok,
                "pages_conserved": True,
                "prefix_cache": True,
                "prefix_reclaimed": sum(
                    e.prefix_evictions for e in router.engines
                ),
                "prefix_hit_rate": router.prefix_hit_rate(),
                "fleet_size": len(router.engines),
                "alive": sum(router.alive),
                "failovers": router.failovers,
                "failed_over_streams": router.failed_over_streams,
                "dropped_streams": 0,
                "spill": router.spill.stats(),
                "procs": True,
                "proc_failovers": router.proc_failovers,
                "worker_pids": [rep.pid for rep in replicas],
                "transport": transport,
                "router_compiles_delta": 0,
            }

        return _run_scenario(obs, trace_dir, body)
    finally:
        faults.clear()
        for proc, _port in procs:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass


# -- model-ops scenarios (sampling/ops.py) ---------------------------------


def _verified_swap_weights(cfg, seed: int, root_dir: str):
    """Fresh weights through the REAL verified-checkpoint path: init at a
    different seed, save with the manifest-stamping CheckpointManager,
    restore via `restore_for_sampling`'s latest-verified-step path — the
    exact pipeline a production deploy would hand the hot-swap. Returns
    (restored params, step, "<step>:<sha12>" weights version)."""
    import os
    import types

    import jax

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.sampling.engine import restore_for_sampling
    from midgpt_tpu.training.checkpoint import CheckpointManager

    ckpt_dir = os.path.join(root_dir, "swap_ckpt")
    mgr = CheckpointManager(ckpt_dir, save_interval_steps=1)
    mgr.save(7, {"params": GPT.init(cfg, jax.random.PRNGKey(seed + 101))},
             force=True)
    mgr.wait()
    version = mgr.weights_version(7)
    mgr.close()
    assert version is not None, "manifest missing after save barrier"
    # fsdp_min_size past any leaf size -> fully replicated shardings, which
    # stage_hot_swap then re-homes onto the live engine's own layout.
    shim = types.SimpleNamespace(
        model_config=cfg, fsdp_min_size=1 << 60, param_dtype="float32"
    )
    restored, step = restore_for_sampling(ckpt_dir, shim)
    return restored, step, version


def _run_hot_swap_chaos(
    fault_plan: str, *, seed: int, n_requests: int,
    trace_dir: tp.Optional[str],
) -> tp.Dict[str, tp.Any]:
    """Blue/green weight flip mid-trace (module docstring): three passes —
    fault-free on the OLD weights, fault-free on the NEW (restored)
    weights, then the fault pass — and a per-stream parity check against
    whichever side of the flip served it (`served_uids_at_flip`). Pinned
    by tests/test_chaos_serve.py::
    test_chaos_hot_swap_mid_decode_blue_green_parity."""
    cfg, params_old = _tiny_model(seed)
    root = trace_dir or tempfile.mkdtemp(prefix="midgpt_chaos_swap_")
    params_new, step, version = _verified_swap_weights(cfg, seed, root)
    trace = _trace(cfg, seed + 1, n_requests)

    ref_old = _reference_pass(cfg, params_old, trace)
    ref_new = _reference_pass(cfg, params_new, trace)
    eng, obs, armed = _armed_engine(cfg, params_old, fault_plan)
    eng.swap_source = lambda: {
        "params": params_new, "version": version, "config": cfg,
    }

    def body() -> tp.Dict[str, tp.Any]:
        uid_to_idx = _run_trickle(eng, trace)
        fired = faults.fired_counts()
        faults.clear()
        assert sum(fired.values()) >= min(1, len(armed)), "no armed fault fired"
        assert eng.hot_swaps == 1, f"swap never flipped ({eng.hot_swaps=})"
        assert eng.weights_version == version, (
            f"weights_version {eng.weights_version!r} != {version!r}"
        )
        _assert_drained_conserved(eng)

        swap = eng.swap_history[0]
        old_uids = set(swap["served_uids_at_flip"])
        statuses: tp.Dict[str, int] = {}
        parity = {"old": 0, "new": 0}
        for uid, idx in uid_to_idx.items():
            fr = eng.finished.get(uid)
            assert fr is not None, f"request {uid} dropped across the flip"
            statuses[fr.status] = statuses.get(fr.status, 0) + 1
            assert fr.status == "ok", (
                f"request {uid} degraded to {fr.status!r} — a hot swap must "
                "drop zero streams"
            )
            side = "old" if uid in old_uids else "new"
            want = (ref_old if side == "old" else ref_new)[idx]
            assert np.array_equal(np.asarray(fr.tokens), want), (
                f"greedy parity broke for request {uid} ({side}-weights side "
                "of the flip)"
            )
            parity[side] += 1
        assert parity["old"] and parity["new"], (
            f"flip landed outside the trace ({parity}) — tune the fault round"
        )
        return {
            "mode": "serve",
            "fault_plan": fault_plan,
            "faults_fired": fired,
            "n_requests": n_requests,
            "statuses": statuses,
            "weights_version": eng.weights_version,
            "checkpoint_step": step,
            "swap": {
                "staged_round": swap["staged_round"],
                "flip_round": swap["flip_round"],
                "in_flight_at_stage": len(swap["in_flight_at_stage"]),
                "swap_latency_s": swap["swap_latency_s"],
            },
            "parity_old_side": parity["old"],
            "parity_new_side": parity["new"],
            "dropped": 0,
            "pages_conserved": True,
        }

    return _run_scenario(obs, trace_dir, body)


def _run_pool_resize_chaos(
    fault_plan: str, *, seed: int, n_requests: int,
    trace_dir: tp.Optional[str],
) -> tp.Dict[str, tp.Any]:
    """Grow-then-shrink pool resize mid-trace (module docstring), on an
    int8 cache so the scale side buffers must migrate with their pages:
    conservation at every boundary and EVERY stream bit-identical to the
    no-resize reference — a resize affects nobody."""
    import jax.numpy as jnp

    cfg, params = _tiny_model(seed)
    trace = _trace(cfg, seed + 1, n_requests)

    ref_tokens = _reference_pass(cfg, params, trace, cache_dtype=jnp.int8)
    eng, obs, armed = _armed_engine(cfg, params, fault_plan,
                                    cache_dtype=jnp.int8)
    eng.resize_plan = list(RESIZE_TARGETS)

    def body() -> tp.Dict[str, tp.Any]:
        # Trickle arrivals: the grow-then-shrink plan spans two fault
        # rounds, so the trace must still be live at BOTH (upfront
        # submission can drain a small trace before the shrink round).
        uid_to_idx = _run_trickle(eng, trace)
        fired = faults.fired_counts()
        faults.clear()
        n_fired = sum(fired.values())
        assert n_fired >= min(1, len(armed)), "no armed fault fired"
        # resize_pool asserts conservation before AND after each migration;
        # this is the post-drain re-check.
        assert eng.resizes == n_fired, (
            f"{n_fired} pool_resize firings but {eng.resizes} resizes"
        )
        _assert_drained_conserved(eng)
        assert eng.cache.quantized and eng.cache.k_scale is not None

        statuses: tp.Dict[str, int] = {}
        parity_ok = 0
        for uid, idx in uid_to_idx.items():
            fr = eng.finished.get(uid)
            assert fr is not None, f"request {uid} dropped across a resize"
            statuses[fr.status] = statuses.get(fr.status, 0) + 1
            assert np.array_equal(np.asarray(fr.tokens), ref_tokens[idx]), (
                f"greedy parity broke for request {uid} across a live resize"
            )
            parity_ok += 1
        return {
            "mode": "serve",
            "fault_plan": fault_plan,
            "faults_fired": fired,
            "n_requests": n_requests,
            "statuses": statuses,
            "cache_dtype": "int8",
            "resizes": eng.resize_history,
            "pages_migrated": sum(
                r["pages_migrated"] for r in eng.resize_history
            ),
            "final_num_pages": eng.allocator.num_pages,
            "parity_checked": parity_ok,
            "parity_ok": parity_ok,
            "pages_conserved": True,
        }

    return _run_scenario(obs, trace_dir, body)
