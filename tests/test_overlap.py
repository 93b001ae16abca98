"""Round-overlap dispatch pins (docs/SERVING.md "Round-overlap dispatch"):
every overlap mode is bit-exact vs the classic engine, the double-buffered
scheduler boundary is one round late BY CONSTRUCTION (policy decisions made
during round N's host phase first reach round N+2's dispatch — pinned via
`engine.dispatch_log` for FCFS and SLO), fused groups handle EOS and budget
edges inside the group, an in-flight victim's un-settled tokens are
discarded without perturbing anyone, and the hung-step watchdog stays armed
across the overlapped settle. Compile-count pins live in
tests/test_recompile_pins.py; the chaos gate (kill_overlapped_round) in
tests/test_chaos_serve.py.

Geometry discipline: 39 pages — a fresh program-key pool (not 25/31/51/57/
61/71, the recompile-pin baselines, nor 27/29/33/41, the tool/chaos/serving
geometries), so nothing here pre-warms a pinned program set.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.robustness.errors import StepHangError
from midgpt_tpu.robustness.watchdog import StepWatchdog
from midgpt_tpu.sampling.engine import generate
from midgpt_tpu.sampling.scheduler import FCFSScheduler, SLOScheduler
from midgpt_tpu.sampling.serve import ServeEngine

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _eng(params, overlap="off", round_group=1, cache_dtype=jnp.float32, **kw):
    return ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=39,
        prefill_chunk=16, decode_chunk=8, temperature=0.0,
        cache_dtype=cache_dtype, overlap=overlap, round_group=round_group,
        **kw,
    )


def _trace(seed=0, lengths=(25, 34, 47), max_new=(9, 17, 17)):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
        for n, m in zip(lengths, max_new)
    ]


def _ref(params, prompt, max_new):
    return np.asarray(
        generate(CFG, params, jnp.asarray(prompt)[None], max_new,
                 temperature=0.0)[0]
    )


def _assert_conserved(eng):
    trie = 0 if eng.prefix_cache is None else eng.prefix_cache.page_count()
    assert eng.allocator.free_count + trie == eng.allocator.num_pages - 1, (
        f"page leak: {eng.allocator.free_count} free + {trie} trie of "
        f"{eng.allocator.num_pages - 1} allocatable"
    )


# ----------------------------------------------------------------------
# the tentpole parity pin: every mode is bit-exact
# ----------------------------------------------------------------------


def test_overlap_modes_bit_exact_vs_generate(params):
    """off / double / group:2 on the same mixed trace all reproduce
    `generate` token-for-token — overlap restructures WHEN host work runs
    and how many rounds one dispatch carries, never what is computed. The
    group budget edge rides along: max_new=9 leaves 8 decode-side tokens,
    under one fused group's span, so emission must stop at the budget
    inside the group."""
    trace = _trace()
    for overlap, rg in (("off", 1), ("double", 1), ("group", 2)):
        eng = _eng(params, overlap, rg)
        uids = [eng.submit(p, m) for p, m in trace]
        done = eng.run()
        for (p, m), u in zip(trace, uids):
            np.testing.assert_array_equal(
                done[u].tokens, _ref(params, p, m),
                err_msg=f"mode {overlap}:{rg}, request {u}",
            )
            assert len(done[u].tokens) == len(p) + m
        assert eng.stats()["overlap_mode"] == overlap
        assert eng.stats()["round_group"] == rg
        _assert_conserved(eng)


@pytest.mark.slow
def test_overlap_wide_matrix_bit_exact(params):
    """The wide acceptance matrix: group:4 and double x {int8 cache,
    speculative draft, prefix cache, tp=2 sharded decode} all stay
    bit-exact vs the classic engine on the same trace."""
    from midgpt_tpu.parallel.serve_tp import make_serve_mesh
    from midgpt_tpu.sampling.spec import self_draft

    trace = _trace(seed=3)
    dcfg, dparams = self_draft(CFG, params, 1)
    variants = [
        dict(),  # f32 group:4
        dict(cache_dtype="int8"),
        dict(prefix_cache=True),
        dict(draft_params=dparams, draft_config=dcfg,
             draft_shares_cache=True, spec_k_max=4, spec_k_min=4,
             spec_adapt=False),
        dict(mesh=make_serve_mesh(tp_size=2)),
    ]
    for i, kw in enumerate(variants):
        spec = "draft_params" in kw
        # spec mode keeps its own draft/verify rounds: "double" falls back
        # to the classic order (serve.py step()) and "group" fuses nothing
        # through the verify path — the mode must still be SAFE to set.
        modes = (("double", 1),) if spec else (("group", 4), ("double", 1))
        base = _eng(params, "off", 1, **kw)
        uids = [base.submit(p, m) for p, m in trace]
        want = {u: np.asarray(base.run()[u].tokens) for u in uids}
        for overlap, rg in modes:
            eng = _eng(params, overlap, rg, **kw)
            uids2 = [eng.submit(p, m) for p, m in trace]
            done = eng.run()
            for u0, u1 in zip(uids, uids2):
                np.testing.assert_array_equal(
                    done[u1].tokens, want[u0],
                    err_msg=f"variant {i}, mode {overlap}:{rg}",
                )
            _assert_conserved(eng)


def test_eos_at_group_interior_stops_exactly(params):
    """EOS fired INSIDE a fused group (not at its edge) must stop the
    stream at exactly the same token as the classic engine: the in-program
    deactivation masks the remaining scan steps and the host discards
    nothing it should keep. The eos token is picked from the reference
    stream so greedy decoding deterministically hits it mid-group."""
    p, m = _trace(seed=7, lengths=(25,), max_new=(17,))[0]
    ref = _ref(params, p, m)
    eos_tok = int(ref[len(p) + 5])  # greedy emits this 6 tokens in
    outs = {}
    for overlap, rg in (("off", 1), ("group", 2), ("double", 1)):
        eng = _eng(params, overlap, rg)
        u = eng.submit(p, m, eos_id=eos_tok)
        outs[(overlap, rg)] = np.asarray(eng.run()[u].tokens)
        _assert_conserved(eng)
    want = outs[("off", 1)]
    assert len(want) < len(p) + m, "eos never fired — test staged wrong"
    for k, got in outs.items():
        np.testing.assert_array_equal(got, want, err_msg=f"mode {k}")


# ----------------------------------------------------------------------
# the one-round-late scheduler boundary (dispatch_log pins)
# ----------------------------------------------------------------------


@pytest.mark.parametrize("make_sched", [FCFSScheduler, SLOScheduler],
                         ids=["fcfs", "slo"])
def test_double_admission_lands_two_dispatches_late(params, make_sched):
    """The deferred-effect pin (sampling/scheduler.py docstring): under
    overlap="double", round N+1's dispatch is enqueued BEFORE round N's
    host phase runs, so a request admitted during that host phase first
    appears in round N+2's dispatch — for any policy, because the
    boundary is the engine's, not the scheduler's. The classic engine
    admits before it dispatches, so there the same arrival lands one
    round later, not two. B's prompt fits ONE prefill chunk, so it is
    decode-ready in the same host phase that admits it — the dispatch
    distance measured is purely the policy boundary, not prefill time."""
    trace = _trace(seed=11, lengths=(25, 12), max_new=(33, 9))
    (pa, ma), (pb, mb) = trace
    first_round = {}
    for overlap in ("double", "off"):
        eng = _eng(params, overlap, 1, scheduler=make_sched())
        ua = eng.submit(pa, ma)
        for _ in range(3):
            eng.step()
        r0 = eng.rounds
        assert any(ua in uids for _, uids in eng.dispatch_log)
        ub = eng.submit(pb, mb)
        eng.step()
        eng.step()
        log = list(eng.dispatch_log)
        first_round[overlap] = min(
            r for r, uids in log if ub in uids
        ) - r0
        done = eng.run()
        for (p, m), u in zip(trace, (ua, ub)):
            np.testing.assert_array_equal(done[u].tokens, _ref(params, p, m))
        _assert_conserved(eng)
    assert first_round["double"] == 2, (
        f"double-buffered admission landed {first_round['double']} rounds "
        "late, want exactly 2 (the one-round-late policy boundary)"
    )
    assert first_round["off"] == 1, (
        "classic admission must stay same-round-visible (admit precedes "
        f"dispatch), got {first_round['off']}"
    )


def test_inflight_victim_tokens_discarded_without_collateral(params):
    """Cancelling a slot whose round is still IN FLIGHT discards its
    un-settled tokens (identity mismatch at settle) and touches nobody
    else: the survivor stays bit-exact and every page comes home."""
    (pa, ma), (pc, mc) = _trace(seed=13, lengths=(25, 12), max_new=(17, 33))
    eng = _eng(params, "double", 1)
    ua = eng.submit(pa, ma)
    uc = eng.submit(pc, mc)
    for _ in range(8):
        eng.step()
        if eng._inflight is not None and uc in dict(eng.dispatch_log).get(
            eng.rounds, ()
        ):
            break
    else:
        pytest.fail("victim never entered an in-flight dispatch")
    assert eng.cancel(uc)
    done = eng.run()
    assert done[uc].status == "cancelled"
    assert len(done[uc].tokens) < len(pc) + mc  # partial by design
    np.testing.assert_array_equal(done[ua].tokens, _ref(params, pa, ma))
    _assert_conserved(eng)


# ----------------------------------------------------------------------
# watchdog stays armed across the overlapped settle
# ----------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_watchdog_armed_through_overlap_is_invisible(params):
    """An armed-but-never-expiring watchdog changes nothing under
    overlap="double" — bit-exact streams — and every overlapped settle
    goes through its sync funnel (syncs counted, zero expiries)."""
    wd = StepWatchdog(60.0, poll_s=0.001)
    eng = _eng(params, "double", 1, watchdog=wd)
    trace = _trace(seed=17, lengths=(25, 34), max_new=(17, 9))
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        np.testing.assert_array_equal(done[u].tokens, _ref(params, p, m))
    assert wd.syncs >= 2, "overlapped settles bypassed the watchdog funnel"
    assert wd.expiries == 0
    _assert_conserved(eng)


def test_watchdog_bounds_a_hung_overlapped_settle(params):
    """A settle that never lands (the hung-device model: the in-flight
    handle's device arrays hang on materialization) must end in
    StepHangError via the armed watchdog — labeled as the overlap sync —
    not in a wedged server. The hang is injected by swapping the handle's
    unforced outputs for objects whose __array__ parks forever."""
    clock = _FakeClock()
    wd = StepWatchdog(5.0, clock=clock, poll_s=0.001)
    eng = _eng(params, "double", 1, watchdog=wd)
    p, m = _trace(seed=19, lengths=(25,), max_new=(33,))[0]
    eng.submit(p, m)
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None

    class _Hang:
        def __array__(self, dtype=None, copy=None):
            clock.t = 100.0
            threading.Event().wait()

    eng._inflight = dataclasses.replace(
        eng._inflight, toks=_Hang(), emitted=_Hang()
    )
    with pytest.raises(StepHangError) as ei:
        eng.step()  # next step settles the (hung) in-flight round
    assert "serve.overlap_sync" in str(ei.value)
    assert wd.expiries == 1
