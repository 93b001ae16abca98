"""Continuous-batching serving engine (sampling/serve.py): scheduler
behavior (admission, lazy page growth, eviction/preemption, EOS), and
greedy token parity with the fixed-batch engine on mixed-length traces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.sampling.engine import generate
from midgpt_tpu.sampling.pages import PageAllocator
from midgpt_tpu.sampling.serve import ServeEngine

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _trace(seed=0, lengths=(5, 23, 11, 37, 3), max_new=(10, 12, 20, 8, 15)):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
        for n, m in zip(lengths, max_new)
    ]


def test_page_allocator():
    a = PageAllocator(8)  # pages 1..7 allocatable, 0 is the sink
    assert a.free_count == 7
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got
    assert a.alloc(5) is None and a.free_count == 4  # failed alloc is a no-op
    a.free(got)
    assert a.free_count == 7
    with pytest.raises(AssertionError):
        a.free([0])  # the sink must never enter the free list


def test_submit_rejects_oversized_requests(params):
    eng = ServeEngine(CFG, params, max_slots=2, cache_dtype=jnp.float32)
    with pytest.raises(ValueError, match="block_size"):
        eng.submit(np.zeros(60, np.int32), 10)
    small = ServeEngine(
        CFG, params, max_slots=1, num_pages=3, cache_dtype=jnp.float32
    )
    with pytest.raises(ValueError, match="pages"):
        small.submit(np.zeros(30, np.int32), 30)


@pytest.mark.slow
def test_serve_greedy_parity_with_generate(params):
    """The acceptance pin: a continuous-batched greedy run reproduces
    engine.generate token-for-token for every request in a mixed-length
    trace — admissions, chunked prefill, and slot churn included
    (more slots than requests is deliberate: requests overlap/rotate)."""
    trace = _trace()
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, prefill_chunk=16,
        decode_chunk=8, temperature=0.0, cache_dtype=jnp.float32,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    assert set(done) == set(uids)
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(
            done[u].tokens, np.asarray(ref[0]), err_msg=f"request {u}"
        )


def test_serve_parity_under_eviction(params):
    """A pool too small for the working set forces recompute-style
    preemption (evict youngest, re-queue with generated tokens folded into
    the prompt); outputs must STILL match the un-preempted reference."""
    trace = _trace()[:3]
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=10,
        temperature=0.0, cache_dtype=jnp.float32,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(
            done[u].tokens, np.asarray(ref[0]), err_msg=f"request {u}"
        )


def test_serve_decode_time_eviction_of_active_slot(params):
    """Regression: mid-decode page growth for an OLDER slot evicts the
    youngest slot, which can sit at a LATER index of the same decode
    round's loop. The round must skip the freed slot (it re-queues and
    re-prefills) rather than dereference None — and parity must survive
    the preemption. Short prompts make eviction fire during decode, not
    prefill (test_serve_parity_under_eviction covers the prefill case)."""
    rng = np.random.default_rng(3)
    trace = [
        (rng.integers(0, CFG.vocab_size, 8).astype(np.int32), 40)
        for _ in range(3)
    ]
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=10,
        temperature=0.0, cache_dtype=jnp.float32,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    assert set(done) == set(uids)
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(
            done[u].tokens, np.asarray(ref[0]), err_msg=f"request {u}"
        )


def test_serve_eos_frees_slot_early(params):
    """EOS finishes a request mid-chunk; its pages return to the pool and
    its tokens stop at the EOS."""
    p = _trace()[0][0]
    probe = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    u = probe.submit(p, 10)
    full = probe.run()[u].tokens
    eos = int(full[len(p) + 2])  # a token we know greedy decoding emits

    eng = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    u2 = eng.submit(p, 10, eos_id=eos)
    out = eng.run()[u2].tokens
    assert out[-1] == eos and len(out) == len(p) + 3
    assert eng.allocator.free_count == eng.allocator.num_pages - 1
    assert eng.idle


def test_serve_pages_grow_lazily(params):
    """Admission must NOT reserve worst-case pages: right after the first
    prefill chunk, a long-prompt request holds only the pages that chunk
    touched."""
    rng = np.random.default_rng(1)
    p = rng.integers(0, CFG.vocab_size, 40).astype(np.int32)
    eng = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, page_size=8,
        prefill_chunk=16, temperature=0.0, cache_dtype=jnp.float32,
    )
    eng.submit(p, 8)
    eng._admit()
    eng._prefill_round()  # 16 of 40 prompt tokens -> 2 pages
    slot = eng.slots[0]
    assert slot.prompt_pos == 16 and len(slot.pages[0]) == 2


def test_serve_interleaves_prefill_with_decode(params):
    """A long prompt admitted while another request decodes must not stall
    it: each round advances the prompt by at most one chunk AND decodes the
    running slot."""
    rng = np.random.default_rng(2)
    short = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
    long_p = rng.integers(0, CFG.vocab_size, 48).astype(np.int32)
    eng = ServeEngine(
        CFG, params, max_slots=2, num_pages=33, page_size=8,
        prefill_chunk=16, decode_chunk=4, temperature=0.0,
        cache_dtype=jnp.float32,
    )
    u_short = eng.submit(short, 12)
    eng.step()  # short prefills (one chunk) + first decode chunk
    produced_before = len(eng.slots[0].generated)
    assert produced_before > 0
    u_long = eng.submit(long_p, 4)
    eng.step()  # long's chunk 1 of 3 interleaves with short's decode
    long_slot = next(
        s for s in eng.slots if s is not None and s.request.uid == u_long
    )
    assert long_slot.prompt_pos == 16  # exactly one chunk of prefill
    assert len(eng.slots[0].generated) > produced_before  # short kept going
    done = eng.run()
    for u, (p, m) in ((u_short, (short, 12)), (u_long, (long_p, 4))):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(done[u].tokens, np.asarray(ref[0]))


def test_serve_stochastic_sampling_runs(params):
    """temperature > 0 exercises the keyed sampling path (no parity claim —
    different key stream than generate); output must be in-vocab and the
    right length."""
    trace = _trace()[:2]
    eng = ServeEngine(
        CFG, params, max_slots=2, num_pages=17, temperature=0.8, top_k=20,
        seed=7, cache_dtype=jnp.float32,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        out = done[u].tokens
        assert len(out) == len(p) + m
        assert (out >= 0).all() and (out < CFG.vocab_size).all()


def test_first_tokens_come_from_the_prefill_program_alone(params, monkeypatch):
    """An engine without `on_first_logits` pulls no logits: every first token
    is the prefill program's own sample, and no sampling function runs
    eagerly between a round's programs (`sample_logits` sees tracers only)."""
    from midgpt_tpu.sampling import serve

    real = serve.sample_logits

    def traced_only(logits, *args):
        assert isinstance(logits, jax.core.Tracer), "sample_logits called eagerly in a serving round"
        return real(logits, *args)

    monkeypatch.setattr(serve, "sample_logits", traced_only)
    trace = _trace()
    # top_p: a setting no other test compiles, so the prefill program is traced under the patch
    make = lambda: ServeEngine(
        CFG, params, max_slots=3, num_pages=25, page_size=8, prefill_chunk=16, decode_chunk=4,
        temperature=0.8, top_p=0.9, seed=3, cache_dtype=jnp.float32,
    )
    eng = make()
    assert eng.prefill_width == 3
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    assert eng.preemptions == 0 and set(done) == set(uids)
    assert (eng.first_tokens, eng.first_logit_pulls) == (len(trace), 0)
    stats = eng.stats()
    assert (stats["first_tokens"], stats["first_logit_pulls"]) == (len(trace), 0)
    for (p, m), u in zip(trace, uids):
        out = done[u].tokens
        assert len(out) == len(p) + m and (out >= 0).all() and (out < CFG.vocab_size).all()
    # one seed gives one stream (journal / replay need no more)
    again = make()
    for p, m in trace:
        again.submit(p, m)
    for u, r in again.run().items():
        np.testing.assert_array_equal(r.tokens, done[u].tokens)


class FakeClock:
    """Injectable engine clock (satellite): TTL tests advance time
    explicitly instead of racing wall-clock sleeps on the 1-core host."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_serve_request_ttl_timeout(params):
    """Satellite (robustness PR): a deadline-expired request is finished
    with status='timeout' (partial tokens returned, pages freed) instead of
    occupying the pool forever — queued and running requests alike. Driven
    entirely by the injectable clock: zero sleeps, zero flakiness."""
    clock = FakeClock()
    eng = ServeEngine(
        CFG, params, max_slots=1, page_size=8, num_pages=17,
        prefill_chunk=16, cache_dtype=jnp.float32, clock=clock,
    )
    p = np.arange(5, dtype=np.int32)
    u_dead = eng.submit(p, 8, ttl_s=5.0)
    u_live = eng.submit(p, 8)  # no TTL: immune to the clock jump
    clock.advance(10.0)  # u_dead expires while still queued
    done = eng.run()
    assert done[u_dead].status == "timeout"
    assert len(done[u_dead].tokens) == len(p)  # nothing generated
    assert done[u_live].status == "ok"
    assert len(done[u_live].tokens) == len(p) + 8
    assert eng.timeouts == 1
    assert eng.allocator.free_count == eng.allocator.num_pages - 1  # all freed

    # running slot: expire mid-generation -> partial tokens, pages freed
    clock2 = FakeClock()
    eng2 = ServeEngine(
        CFG, params, max_slots=1, page_size=8, num_pages=17,
        prefill_chunk=16, decode_chunk=1, cache_dtype=jnp.float32,
        clock=clock2,
    )
    u = eng2.submit(p, 12, ttl_s=60.0)
    for _ in range(3):
        eng2.step()  # prefill + a couple of decode rounds, well inside TTL
    slot = next(s for s in eng2.slots if s is not None)
    n_before = len(slot.generated)
    assert 0 < n_before < 12
    clock2.advance(61.0)  # sail past the deadline, deterministically
    eng2.step()
    assert eng2.slots[0] is None and u in eng2.finished
    assert eng2.finished[u].status == "timeout"
    assert len(eng2.finished[u].tokens) == len(p) + n_before
    assert eng2.timeouts == 1
    assert eng2.allocator.free_count == eng2.allocator.num_pages - 1


def test_serve_backpressure_admission(params):
    """Satellite (robustness PR): beyond max_backlog_pages, submit raises
    BackpressureError instead of growing the queue without bound; capacity
    frees as requests finish."""
    from midgpt_tpu.sampling.serve import BackpressureError

    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, num_pages=17,
        prefill_chunk=16, cache_dtype=jnp.float32, max_backlog_pages=4,
    )
    p = np.arange(10, dtype=np.int32)  # 10 + 6 tokens -> 2 pages worst case
    u1 = eng.submit(p, 6)
    u2 = eng.submit(p, 6)
    with pytest.raises(BackpressureError, match="backlog"):
        eng.submit(p, 6)
    done = eng.run()
    assert done[u1].status == "ok" and done[u2].status == "ok"
    u3 = eng.submit(p, 6)  # backlog drained: admission works again
    assert eng.run()[u3].status == "ok"


def test_backpressure_error_structured_fields(params):
    """Satellite: BackpressureError carries the retry ergonomics as fields
    (needed/backlog/budget pages, retry_after_pages, retryable) so the
    async server backs off on data instead of string-parsing messages."""
    from midgpt_tpu.sampling.serve import BackpressureError

    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, num_pages=17,
        prefill_chunk=16, cache_dtype=jnp.float32, max_backlog_pages=4,
    )
    p = np.arange(10, dtype=np.int32)  # 10 + 6 tokens -> 2 pages worst case
    eng.submit(p, 6)
    eng.submit(p, 6)
    with pytest.raises(BackpressureError) as ei:
        eng.submit(p, 6)
    e = ei.value
    assert e.needed_pages == 2
    assert e.backlog_pages == 4
    assert e.budget_pages == 4
    assert e.retry_after_pages == 2  # pages that must free before retry
    assert e.retryable  # capacity sheds are retryable (deadline sheds not)
    assert eng.shed == 1


def _co_resident_pair(params, **kw):
    """Two-slot engine plus a long victim prompt and a short bystander
    prompt; returns (eng, p_victim, p_bystander)."""
    rng = np.random.default_rng(11)
    p_victim = rng.integers(0, CFG.vocab_size, 48).astype(np.int32)
    p_by = rng.integers(0, CFG.vocab_size, 7).astype(np.int32)
    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, num_pages=33,
        prefill_chunk=16, decode_chunk=4, temperature=0.0,
        cache_dtype=jnp.float32, **kw,
    )
    return eng, p_victim, p_by


def _assert_bystander_exact(eng, u_by, p_by, m_by, params):
    ref = generate(CFG, params, jnp.asarray(p_by)[None], m_by, temperature=0.0)
    np.testing.assert_array_equal(
        eng.finished[u_by].tokens, np.asarray(ref[0]),
        err_msg="cancellation perturbed a co-resident slot",
    )
    assert eng.allocator.free_count == eng.allocator.num_pages - 1


def test_cancel_during_prefill_conserves_pages(params):
    """Satellite: client disconnect while the victim is STILL MID-PROMPT —
    its chunk-held pages return to the pool, nothing was generated, and the
    co-resident decode stream is untouched."""
    eng, p_victim, p_by = _co_resident_pair(params)
    u_by = eng.submit(p_by, 10)
    u_victim = eng.submit(p_victim, 8)
    eng.step()  # victim prefilled one chunk of three; bystander decodes
    slot = next(
        s for s in eng.slots if s is not None and s.request.uid == u_victim
    )
    assert slot.prefilling and slot.pages[0], "victim must be mid-prefill"
    assert eng.cancel(u_victim)
    assert eng.finished[u_victim].status == "cancelled"
    assert len(eng.finished[u_victim].tokens) == len(p_victim)  # prompt only
    eng.run()
    _assert_bystander_exact(eng, u_by, p_by, 10, params)
    assert not eng.cancel(u_victim)  # already finished: no-op


def test_cancel_during_decode_conserves_pages(params):
    """Satellite: disconnect mid-DECODE — partial tokens recorded, pages
    freed, bystander exact."""
    eng, p_victim, p_by = _co_resident_pair(params)
    u_by = eng.submit(p_by, 12)
    u_victim = eng.submit(p_victim[:9], 20)
    for _ in range(4):
        eng.step()
    slot = next(
        s for s in eng.slots if s is not None and s.request.uid == u_victim
    )
    n_gen = len(slot.generated)
    assert 0 < n_gen < 20, "victim must be mid-decode"
    assert eng.cancel(u_victim)
    fr = eng.finished[u_victim]
    assert fr.status == "cancelled" and len(fr.tokens) == 9 + n_gen
    # the delivered prefix is exactly the greedy stream (no corruption)
    ref = generate(CFG, params, jnp.asarray(p_victim[:9])[None], 20,
                   temperature=0.0)
    np.testing.assert_array_equal(fr.tokens, np.asarray(ref[0])[: 9 + n_gen])
    eng.run()
    _assert_bystander_exact(eng, u_by, p_by, 12, params)


def test_cancel_during_spec_rounds_conserves_pages(params):
    """Satellite: disconnect between SPECULATIVE verify rounds of a
    self-draft engine — rollback bookkeeping must not leak the victim's
    pages nor perturb the co-resident stream (greedy spec serving is
    token-identical to generate, tests/test_spec.py)."""
    from midgpt_tpu.sampling.spec import self_draft

    dcfg, dparams = self_draft(CFG, params, 1)
    eng, p_victim, p_by = _co_resident_pair(
        params,
        draft_params=dparams, draft_config=dcfg, draft_shares_cache=True,
        spec_k_max=4, spec_k_min=4, spec_adapt=False,
    )
    u_by = eng.submit(p_by, 12)
    u_victim = eng.submit(p_victim[:9], 20)
    for _ in range(4):
        eng.step()
    slot = next(
        s for s in eng.slots if s is not None and s.request.uid == u_victim
    )
    assert len(slot.generated) > 0, "victim must be mid-speculation"
    assert eng._spec_rounds > 0, "engine must actually be speculating"
    assert eng.cancel(u_victim)
    eng.run()
    assert eng.finished[u_victim].status == "cancelled"
    _assert_bystander_exact(eng, u_by, p_by, 12, params)


def test_cancel_queued_request(params):
    """Cancelling a request that never reached a slot frees nothing but
    still records the terminal status (and FCFS admission skips it)."""
    eng = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, cache_dtype=jnp.float32,
    )
    p = np.arange(5, dtype=np.int32)
    u1 = eng.submit(p, 6)
    u2 = eng.submit(p, 6)  # queued behind u1 (one slot)
    assert eng.cancel(u2)
    assert eng.finished[u2].status == "cancelled"
    done = eng.run()
    assert done[u1].status == "ok"
    assert eng.cancelled == 1
    assert eng.allocator.free_count == eng.allocator.num_pages - 1
