"""Fleet serving primitives (sampling/fleet.py): the host-RAM spill
tier's checksum/version/ledger discipline, the PageHandoffQueue
bounded-retry transport it shares with disagg, and the FleetRouter's
affinity / health-check / failover policies. Router policy runs against
duck-typed fake replicas — the policies are pure host-side scheduling, a
model would only slow the assertions down; two tests at the end run real
engines (burst absorption; hit rate and re-adoption). The fault gates (crash
parity, corrupt-spill discard, cross-tier conservation) live in
test_chaos_serve.py, the cross-process ones in test_fleet_proc.py."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.robustness import faults
from midgpt_tpu.sampling.disagg import (
    HandoffRetryExhausted,
    PageHandoffQueue,
)
from midgpt_tpu.sampling.fleet import (
    FleetRouter,
    SpillTier,
    _blocks_crc,
    assert_fleet_conserved,
)
from midgpt_tpu.sampling.serve import BackpressureError, FinishedRequest


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# -- spill tier -----------------------------------------------------------

PS = 8


def _fake_cache(n_pages, *, quantized=False, seed=0):
    """The slice of the KV pool SpillTier.spill reads: k/v with the page
    axis at 2, optional per-page scales with the page axis at 1."""
    rng = np.random.default_rng(seed)
    ns = types.SimpleNamespace(
        k=jnp.asarray(rng.standard_normal((2, 2, n_pages, PS, 4)),
                      jnp.float32),
        v=jnp.asarray(rng.standard_normal((2, 2, n_pages, PS, 4)),
                      jnp.float32),
        k_scale=None,
        v_scale=None,
    )
    if quantized:
        ns.k_scale = jnp.asarray(
            rng.standard_normal((2, n_pages, PS)), jnp.float32
        )
        ns.v_scale = jnp.asarray(
            rng.standard_normal((2, n_pages, PS)), jnp.float32
        )
    return ns


def _spill_prompt(cache, tier, prompt, pages, version="v0"):
    """Spill `pages` pool pages as the consecutive page-prefixes of
    `prompt` (what PrefixCache.on_evict hands the tier)."""
    for depth, page in enumerate(pages):
        tier.spill(cache, tuple(prompt[: (depth + 1) * PS]), page, version)


def test_spill_roundtrip_closes_ledger():
    """Pages spilled under a prompt's page-prefixes come back bit-exact
    via peek_run/take_run, and every counter lands in exactly one ledger
    bucket (the cross-tier half of assert_fleet_conserved)."""
    cache = _fake_cache(4)
    tier = SpillTier()
    tier.set_page_size(PS)
    prompt = list(range(100, 100 + 3 * PS))
    _spill_prompt(cache, tier, prompt, [1, 2])
    assert tier.resident_count() == 2
    assert tier.peek_run(prompt, 0, 3, "v0") == 2  # run stops at depth 2
    got = tier.take_run(prompt, 0, 2, "v0")
    assert len(got) == 2
    np.testing.assert_array_equal(
        got[0]["k"], np.asarray(cache.k[:, :, 1])
    )
    np.testing.assert_array_equal(
        got[1]["v"], np.asarray(cache.v[:, :, 2])
    )
    # move-on-take: the tier no longer holds them
    assert tier.resident_count() == 0
    assert tier.readopted == 2
    tier.assert_ledger("roundtrip")


def test_spill_quantized_blocks_carry_scales():
    """int8 pools spill quantized — the per-page scales must travel with
    the columns or re-adoption would decode garbage."""
    cache = _fake_cache(4, quantized=True)
    tier = SpillTier()
    tier.set_page_size(PS)
    prompt = list(range(2 * PS))
    _spill_prompt(cache, tier, prompt, [3])
    (blocks,) = tier.take_run(prompt, 0, 1, "v0")
    assert set(blocks) == {"k", "v", "k_scale", "v_scale"}
    np.testing.assert_array_equal(
        blocks["k_scale"], np.asarray(cache.k_scale[:, 3])
    )
    tier.assert_ledger("quantized")


def test_spill_checksum_catches_corruption():
    """A flipped byte between spill and take is caught by the crc32
    verify: the entry is discarded (never handed to a decode), the run
    truncates, and the discard is ledgered."""
    cache = _fake_cache(4)
    tier = SpillTier()
    tier.set_page_size(PS)
    prompt = list(range(3 * PS))
    _spill_prompt(cache, tier, prompt, [1, 2])
    assert tier.corrupt_one()  # targets the most recent spill (depth 1)
    got = tier.take_run(prompt, 0, 2, "v0")
    assert len(got) == 1  # depth 0 fine, depth 1 discarded -> truncated
    assert tier.corrupt_discarded == 1
    assert tier.resident_count() == 0  # the corrupt entry is GONE
    tier.assert_ledger("corrupt")


def test_spill_stall_refuses_once_then_recovers():
    """An armed stall refuses the first consult that would return pages
    (the caller re-prefills — slower, never wrong), then clears."""
    cache = _fake_cache(4)
    tier = SpillTier()
    tier.set_page_size(PS)
    prompt = list(range(2 * PS))
    _spill_prompt(cache, tier, prompt, [1])
    tier.arm_stall()
    assert tier.peek_run(prompt, 0, 1, "v0") == 0
    assert tier.stall_fallbacks == 1
    assert tier.peek_run(prompt, 0, 1, "v0") == 1  # cleared
    tier.assert_ledger("stall")


def test_spill_capacity_drops_oldest():
    cache = _fake_cache(6)
    entry_bytes = 2 * np.asarray(cache.k[:, :, 0]).nbytes
    tier = SpillTier(capacity_bytes=2 * entry_bytes)
    tier.set_page_size(PS)
    prompt = list(range(4 * PS))
    _spill_prompt(cache, tier, prompt, [1, 2, 3])
    assert tier.resident_count() == 2
    assert tier.capacity_dropped == 1
    # the OLDEST (depth 0) was dropped: the run now starts broken
    assert tier.peek_run(prompt, 0, 3, "v0") == 0
    assert tier.peek_run(prompt, 1, 2, "v0") == 2
    tier.assert_ledger("capacity")


def test_spill_version_discipline():
    """Weights-version rules: a duplicate under the same version is
    skipped (same tokens + same weights => same KV), a duplicate across a
    hot swap replaces the stale entry, and a take under the wrong version
    discards instead of re-adopting another model's KV."""
    cache = _fake_cache(4)
    tier = SpillTier()
    tier.set_page_size(PS)
    prompt = list(range(2 * PS))
    _spill_prompt(cache, tier, prompt, [1], version="v0")
    _spill_prompt(cache, tier, prompt, [1], version="v0")
    assert tier.duplicate_skips == 1 and tier.total_spilled == 1
    _spill_prompt(cache, tier, prompt, [2], version="v1")  # post-swap
    assert tier.stale_discarded == 1
    assert tier.peek_run(prompt, 0, 1, "v0") == 0
    assert tier.take_run(prompt, 0, 1, "v0") == []
    assert tier.stale_discarded == 2
    assert tier.resident_count() == 0
    tier.assert_ledger("version")


def test_spill_page_size_binds_once():
    tier = SpillTier()
    with pytest.raises(RuntimeError, match="before any engine"):
        tier.peek_run([0] * 16, 0, 1, "v0")
    tier.set_page_size(8)
    tier.set_page_size(8)  # idempotent
    with pytest.raises(ValueError, match="already bound"):
        tier.set_page_size(16)


# -- the shared page-transport queue --------------------------------------


def _item(uid=7, n_pages=2):
    return types.SimpleNamespace(
        uid=uid, n_pages=n_pages, blocks={"k": np.zeros(4, np.float32)}
    )


def test_handoff_queue_backoff_schedule_and_exhaustion():
    """The failover/disagg transport: a refused item returns to the FRONT
    under the shared exponential backoff (robustness/backoff.py), shields
    the items behind it, and raises the structured HandoffRetryExhausted
    past the bounded budget instead of spinning."""
    clock = _FakeClock()
    q = PageHandoffQueue(retries=3, base_s=1.0, clock=clock)
    q.push(_item(uid=7))
    q.push(_item(uid=8))
    it = q.pop()
    assert it.uid == 7
    q.requeue(it)  # attempt 1: delay base_s * 2**0
    assert q.pop() is None  # backed off, and uid=8 is shielded behind it
    clock.t += 1.0
    it = q.pop()
    assert it.uid == 7  # kept its place
    q.requeue(it)  # attempt 2: delay 2.0
    clock.t += 2.0
    it = q.pop()
    with pytest.raises(HandoffRetryExhausted) as ei:
        q.requeue(it)  # attempt 3 == budget
    assert ei.value.uid == 7 and ei.value.attempts == 3
    assert q.retry_exhausted == 1
    assert q.pop().uid == 8  # the queue keeps serving
    assert q.stats()["enqueued"] == 2


def test_handoff_queue_rejects_zero_retries():
    with pytest.raises(ValueError, match="retries"):
        PageHandoffQueue(retries=0)


# -- router policy, against fake replicas ---------------------------------


class _FakeEngine:
    """Duck-typed stand-in for ServeEngine: just enough surface for the
    router's admission/health/failover policy (capacity-bounded submit,
    deterministic finish after `steps_to_finish` rounds, injectable step
    failures and clock stalls)."""

    def __init__(self, *, capacity=4, steps_to_finish=2, page_size=8,
                 clock=None, retryable_shed=True):
        self.prefix_cache = object()  # router requires a trie
        self.temperature = 0.0
        self.page_size = page_size
        self.capacity = capacity
        self.steps_to_finish = steps_to_finish
        self.retryable_shed = retryable_shed
        self.on_token = None
        self.finished = {}
        self.active = {}
        self.spill = None
        self._uid = 0
        self.fail_steps = 0  # raise in step() this many times
        self.stall_s = 0.0  # advance `clock` by this much per step
        self._clock = clock
        # stats() surface
        self.rounds = 0
        self.preemptions = 0
        self.shed = 0
        self.spill_readopted_pages = 0
        self._prefix_matched_tokens = 0
        self._prefix_matchable_tokens = 0

    def attach_spill(self, spill):
        self.spill = spill
        spill.set_page_size(self.page_size)

    def prefix_stats(self):
        return {"hit_rate": 0.0}

    def submit(self, prompt, max_new_tokens, eos_id=None, ttl_s=None):
        if len(self.active) >= self.capacity:
            self.shed += 1
            raise BackpressureError(
                "fake full", needed_pages=1, backlog_pages=self.capacity,
                budget_pages=self.capacity, retryable=self.retryable_shed,
            )
        uid = self._uid
        self._uid += 1
        self.active[uid] = [
            np.asarray(prompt, np.int32), int(max_new_tokens),
            self.steps_to_finish,
        ]
        return uid

    @property
    def idle(self):
        return not self.active

    def step(self):
        self.rounds += 1
        if self.fail_steps > 0:
            self.fail_steps -= 1
            raise RuntimeError("injected replica failure")
        if self.stall_s and self._clock is not None:
            self._clock.t += self.stall_s
        for uid in [u for u, rec in self.active.items()
                    if rec[2] <= 1]:
            prompt, m, _ = self.active.pop(uid)
            # deterministic "generation": prompt echoed + counted tokens
            toks = np.concatenate(
                [prompt, np.arange(m, dtype=np.int32)]
            )
            self.finished[uid] = FinishedRequest(uid, toks, [0.0] * m, "ok")
        for rec in self.active.values():
            rec[2] -= 1


def _prompt(template, tail):
    return np.asarray(list(template) + list(tail), np.int32)


def test_router_affinity_is_deterministic_and_rendezvous_stable():
    """The rendezvous property failover depends on: a prompt's affinity
    replica is a pure function of its first page, and when a replica dies
    only ITS prompts remap — every other prompt keeps its replica, so the
    surviving tries stay hot."""
    clock = _FakeClock()
    router = FleetRouter(
        [_FakeEngine() for _ in range(3)], clock=clock
    )
    prompts = [
        _prompt(range(t * 50, t * 50 + 8), [1, 2, 3]) for t in range(6)
    ]
    full = [router._affinity(p, [0, 1, 2]) for p in prompts]
    assert full == [router._affinity(p, [0, 1, 2]) for p in prompts]
    dead = full[0]
    survivors = [i for i in range(3) if i != dead]
    for p, aff in zip(prompts, full):
        remapped = router._affinity(p, survivors)
        if aff != dead:
            assert remapped == aff  # rendezvous: unaffected keys stay put
        else:
            assert remapped in survivors
    # prompts shorter than a full shareable page have no affinity
    assert router._affinity(np.arange(8, dtype=np.int32), [0, 1, 2]) is None


def test_router_places_by_affinity_then_least_loaded():
    clock = _FakeClock()
    router = FleetRouter([_FakeEngine(capacity=8) for _ in range(2)],
                         clock=clock)
    p = _prompt(range(8), [9, 9])
    aff = router._affinity(p, [0, 1])
    for _ in range(3):  # same template -> same replica, every time
        uid = router.submit(p, 4)
        assert router._pending[uid].replica == aff
    # affinity replica full: spillover to the other survivor, not a shed
    router.engines[aff].capacity = 3
    uid = router.submit(p, 4)
    assert router._pending[uid].replica == 1 - aff


def test_router_failover_zero_drops_on_consecutive_failures():
    """The health-check path: a replica that keeps throwing is declared
    dead at max_consecutive_failures; its accepted streams replay on the
    survivor with the ORIGINAL prompt and full budget, and finish with
    the same deterministic output — zero drops, no duplicates."""
    clock = _FakeClock()
    router = FleetRouter(
        [_FakeEngine(capacity=8, clock=clock) for _ in range(2)],
        clock=clock, max_consecutive_failures=2,
    )
    uids = [router.submit(_prompt(range(t, t + 8), [1]), 3)
            for t in (0, 100, 200)]
    victim = router._pending[uids[0]].replica
    expected = {
        u: np.concatenate([router._pending[u].prompt,
                           np.arange(3, dtype=np.int32)])
        for u in uids
    }
    router.engines[victim].fail_steps = 2
    done = router.run()
    assert set(done) == set(uids)
    assert router.failovers == 1
    assert router.alive[victim] is False
    assert router.crash_log[0]["reason"] == "consecutive_failures"
    moved = sum(1 for u in uids
                if router._pending.get(u) is None)  # all drained
    assert moved == 3 and router.failed_over_streams >= 1
    for u in uids:
        assert done[u].status == "ok"
        np.testing.assert_array_equal(done[u].tokens, expected[u])


def test_router_heartbeat_staleness_crashes_the_wedged_replica():
    """A replica whose rounds stop returning within heartbeat_timeout_s
    is declared dead even though step() never raised — the wedged-host
    failure mode consecutive-failure counting cannot see."""
    clock = _FakeClock()
    router = FleetRouter(
        [_FakeEngine(capacity=8, clock=clock) for _ in range(2)],
        clock=clock, heartbeat_timeout_s=5.0,
    )
    uid = router.submit(_prompt(range(8), [1]), 3)
    victim = router._pending[uid].replica
    router.engines[victim].stall_s = 50.0  # each round eats 50 "seconds"
    done = router.run()
    assert router.alive[victim] is False
    assert router.crash_log[0]["reason"] == "heartbeat_stale"
    assert done[uid].status == "ok"  # failed over, not dropped


def test_router_aggregated_shed_is_structured_and_retryable():
    clock = _FakeClock()
    router = FleetRouter(
        [_FakeEngine(capacity=0, clock=clock) for _ in range(2)],
        clock=clock,
    )
    with pytest.raises(BackpressureError) as ei:
        router.submit(_prompt(range(8), [1]), 4)
    assert ei.value.retryable is True  # any retryable replica => retryable
    assert router.router_shed == 1
    router2 = FleetRouter(
        [_FakeEngine(capacity=0, retryable_shed=False)], clock=clock,
    )
    with pytest.raises(BackpressureError) as ei:
        router2.submit(_prompt(range(8), [1]), 4)
    assert ei.value.retryable is False


def test_router_failover_past_budget_is_terminal_shed():
    """When every survivor refuses a failed-over stream past the bounded
    retry budget, the stream terminates with a structured "shed" status —
    a graceful-degradation verdict the client can see, never a silent
    drop or an infinite requeue spin."""
    clock = _FakeClock()
    eng0 = _FakeEngine(capacity=1, clock=clock)
    eng1 = _FakeEngine(capacity=0, clock=clock)  # survivor always refuses
    router = FleetRouter(
        [eng0, eng1], clock=clock, max_consecutive_failures=1,
        failover_retries=3,
    )
    # place on eng0 regardless of affinity (eng1 has no room)
    uid = router.submit(_prompt(range(8), [1]), 3)
    assert router._pending[uid].replica == 0
    eng0.fail_steps = 1  # first step kills it
    done = router.run()
    assert done[uid].status == "shed"
    assert router.shed_streams == 1
    assert router.failover_queue.retry_exhausted == 1


def test_router_requires_greedy_and_prefix_cache():
    eng = _FakeEngine()
    eng.temperature = 0.7
    with pytest.raises(ValueError, match="greedy"):
        FleetRouter([eng])
    eng2 = _FakeEngine()
    eng2.prefix_cache = None
    with pytest.raises(ValueError, match="prefix cache"):
        FleetRouter([eng2])
    with pytest.raises(ValueError, match="page_size"):
        FleetRouter([_FakeEngine(page_size=8), _FakeEngine(page_size=16)])


# -- real engines: the availability story ---------------------------------


def _real_engines():
    """(config, factory) of prefix-cached greedy replicas at the fleet's
    31-page pool geometry (chaos_serve._fleet_router's)."""
    import jax

    from midgpt_tpu.models.gpt import GPT, GPTConfig
    from midgpt_tpu.sampling.serve import ServeEngine

    cfg = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2,
                    n_embd=32)
    params = GPT.init(cfg, jax.random.PRNGKey(0))

    def mk(**kw):
        return ServeEngine(
            cfg, params, max_slots=3, page_size=8, num_pages=31,
            prefill_chunk=16, decode_chunk=4, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=True, **kw,
        )

    return cfg, mk


def test_fleet_absorbs_burst_a_single_engine_sheds():
    """The acceptance story of fleet admission: under a bounded
    admission budget (max_backlog_pages), a burst that a single engine
    must shed fits the fleet's aggregate budget — the affinity replica
    refuses and the request spills over to the other survivor instead of
    bouncing to the client. The fleet then drains every admitted stream
    with pages conserved on every replica and the spill ledger closed."""
    cfg, mk_engine = _real_engines()
    rng = np.random.default_rng(0)
    template = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    burst = [
        (np.concatenate([template,
                         rng.integers(0, cfg.vocab_size, 4).astype(np.int32)]),
         8)
        for _ in range(4)
    ]  # worst case ceil((12+8)/8) = 3 pages each

    def mk():
        return mk_engine(max_backlog_pages=7)  # fits 2 bursts of 3 pages, not 3

    single = mk()
    admitted, shed = 0, 0
    for p, m in burst:
        try:
            single.submit(p, m)
            admitted += 1
        except BackpressureError as e:
            assert e.retryable
            shed += 1
    assert shed >= 1, "the burst must overrun one engine's budget"

    router = FleetRouter([mk(), mk()])
    uids = [router.submit(p, m) for p, m in burst]  # all admitted
    done = router.run()
    assert all(done[u].status == "ok" for u in uids)
    assert router.router_shed == 0
    assert len({router.finished[u].tokens.tobytes() for u in uids}) >= 1
    assert_fleet_conserved(router, "burst")


def test_fleet_hit_rate_is_not_diluted_and_readoption_is_token_invisible():
    """What affinity routing and the shared spill tier are FOR: a
    template-heavy trace through one prefix-cached engine and through a
    2-replica fleet, both taking the same mid-trace trie flush (a pressure
    spike reclaiming every unreferenced page). Random routing would dilute
    the fleet's trie hit rate toward 1/N of the single engine's; rendezvous
    affinity keeps each template on the replica that holds its pages, and
    where the single engine loses the flushed KV and re-prefills, the
    replicas spill it to the host tier and the second half re-adopts it. So
    the fleet's hit rate is no lower than the single engine's, pages did
    come back from the tier, and not one token differs."""
    cfg, mk = _real_engines()
    rng = np.random.default_rng(1)
    templates = [rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
                 for _ in range(2)]
    trace = [
        (np.concatenate([templates[i % 2],
                         rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(3, 9))).astype(np.int32)]),
         int(rng.integers(8, 13)))
        for i in range(8)
    ]
    half = len(trace) // 2

    def two_halves(target, replicas):
        uids = [target.submit(p, m) for p, m in trace[:half]]
        target.run()
        for rep in replicas:
            rep._evict_shared_prefix_fault()  # the shared mid-trace flush
        uids += [target.submit(p, m) for p, m in trace[half:]]
        target.run()
        return [np.asarray(target.finished[u].tokens) for u in uids]

    single = mk()
    single_tokens = two_halves(single, [single])
    router = FleetRouter([mk(), mk()])
    fleet_tokens = two_halves(router, router.engines)

    for i, (a, b) in enumerate(zip(single_tokens, fleet_tokens)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    assert sum(e.spill_readopted_pages for e in router.engines) >= 1
    single_hit = single.prefix_stats()["hit_rate"]
    assert single_hit > 0.0
    assert router.prefix_hit_rate() >= single_hit, (router.prefix_hit_rate(), single_hit)
    assert_fleet_conserved(router, "after the two halves")


def test_blocks_crc_is_order_and_content_sensitive():
    a = {"k": np.arange(8, dtype=np.float32),
         "v": np.arange(8, 16).astype(np.float32)}
    b = {k: v.copy() for k, v in a.items()}
    assert _blocks_crc(a) == _blocks_crc(b)
    b["k"][0] += 1
    assert _blocks_crc(a) != _blocks_crc(b)
