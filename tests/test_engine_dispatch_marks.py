"""Every engine dispatch on both clocks (PR 53): the number a jit call takes
(`ServeEngine.dispatches`) is on the span that brackets the call, on the
`engine.dispatch` annotation the engine opens around it and on the commit of
its tokens; the host phases that do not block on the device say the thread's
CPU seconds from an injected clock; the kernel grid's census rides the round's
dispatch span. With obs off: no annotation, neither clock read more often than
before, the same tokens and the same dispatch log. CPU runs give counts and
structure, never a time."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.obs import Observability
from midgpt_tpu.sampling.serve import ServeEngine
from midgpt_tpu.sampling.spec import self_draft

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = GPTConfig(block_size=64, vocab_size=64, n_layer=2, n_head=2, n_embd=32)


class StepClock:
    """Each read returns the time, then advances it: every read is visible."""

    def __init__(self, step=0.001):
        self.t, self.step, self.calls = 0.0, step, 0

    def __call__(self):
        self.calls += 1
        self.t += self.step
        return self.t


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture
def marks(monkeypatch):
    """`jax.profiler.TraceAnnotation` replaced by a recorder: [(name, kwargs,
    the engine clock's time at entry)], the clock set by the test (`.clock`)."""
    opened = []

    class Annotation:
        clock = None

        def __init__(self, name, **kw):
            self.item = [name, kw, None]

        def __enter__(self):
            self.item[2] = Annotation.clock.t if Annotation.clock else None
            opened.append(tuple(self.item))

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    opened_of = lambda: [m for m in opened if m[0] == "engine.dispatch"]
    opened_of.set_clock = lambda c: setattr(Annotation, "clock", c)
    return opened_of


def _engine(params, num_pages, clock, obs=None, **over):
    kw = dict(max_slots=2, page_size=8, num_pages=num_pages, prefill_chunk=8, decode_chunk=4,
              temperature=0.0, cache_dtype=jnp.float32, clock=clock, obs=obs)
    kw.update(over)
    return ServeEngine(CFG, params, **kw)


def _serve(eng):
    uids = [eng.submit(np.arange(1, 12 + 3 * i, dtype=np.int32), 10 + i) for i in range(4)]
    while not eng.idle:
        eng.step()
    return [eng.finished[u].tokens.tolist() for u in uids]


def _with_call(events, *names):
    return {e[7]["call"]: e for e in events if e[1] in names and e[7] and "call" in e[7]}


@pytest.mark.parametrize("overlap,group", [("off", 1), ("group", 2), ("double", 2)])
def test_call_is_consecutive_and_the_same_on_span_annotation_and_commit(params, marks, overlap, group):
    """Prefill and decode calls take consecutive numbers from 1; each number is
    on exactly one bracketing span (`prefill.chunk` / `decode.enqueue`), on the
    annotation opened INSIDE that span, and, a decode call's, on the one
    `decode.host_post` that committed its tokens (a step late under double)."""
    clock = StepClock()
    marks.set_clock(clock)
    obs = Observability(clock=clock, cpu_clock=StepClock())
    eng = _engine(params, 17, clock, obs, overlap=overlap, round_group=group)
    _serve(eng)
    calls = [kw["call"] for _, kw, _ in marks()]
    assert calls == list(range(1, len(calls) + 1)) and len(calls) == eng.dispatches == eng.stats()["dispatches"]
    evs = obs.tracer.events()
    prefills, enqueues = _with_call(evs, "prefill.chunk"), _with_call(evs, "decode.enqueue")
    commits = _with_call(evs, "decode.host_post")
    assert sorted([*prefills, *enqueues]) == calls and not set(prefills) & set(enqueues)
    assert len(prefills) == eng.prefill_calls and set(commits) == set(enqueues)
    assert len(commits) == sum(e[1] == "decode.host_post" for e in evs)  # no commit without its call
    at = {kw["call"]: t for _, kw, t in marks()}
    for call, span in {**prefills, **enqueues}.items():
        assert span[4] <= at[call] <= span[4] + span[5]  # the mark is an instant of its span
    by_seq = {e[10]: e for e in evs if e[0] == "X"}
    for call, enq in enqueues.items():
        rode = by_seq[enq[9]][7]  # the round's decode.dispatch
        assert by_seq[enq[9]][1] == "decode.dispatch"
        assert (enq[7]["steps"], enq[7]["slots"]) == (rode["steps"], rode["slots"])
        assert enq[7]["bucket"] in (1, 2, 4, 8)  # pages: the one width every kind's table of the round has
        assert commits[call][7]["tokens"] <= rode["steps"] * rode["slots"]
        assert commits[call][4] >= enq[4] + enq[5]  # the commit follows its program's call
    for span in prefills.values():
        assert span[7]["width"] == eng.prefill_width and 1 <= span[7]["rows"] <= span[7]["width"]
    firsts = [e for e in evs if e[1] == "prefill.first_token"]  # whose program's token a first token is
    assert len(firsts) == 4 and all(prefills[e[7]["call"]][4] + prefills[e[7]["call"]][5] <= e[4] for e in firsts)
    if overlap == "double":  # round N settles after round N + 1 was enqueued
        order = sorted((e[4], e[1], e[7]["call"]) for e in [*enqueues.values(), *commits.values()])
        late = [c for (_, n0, c), (_, n1, _) in zip(order, order[1:]) if n0 == n1 == "decode.enqueue"]
        assert late  # two enqueues in a row: the first one's commit came after the second call


@pytest.mark.parametrize("shared", [True, False])
def test_draft_verify_and_logits_calls_are_dispatches_too(params, marks, shared):
    """A speculative engine: the draft and the verify program take two
    consecutive numbers (on `spec.draft_enqueue` / `spec.verify_enqueue`; the
    commit carries the verify's), a SEPARATE draft model's prefill call is a
    dispatch of its own (the number after its target's, no span of its own),
    and `next_logits` counts as well: execution k is dispatch k, no exception."""
    clock = StepClock()
    obs = Observability(clock=clock, cpu_clock=StepClock())
    dcfg, dparams = self_draft(CFG, params, 1) if shared else (CFG, params)
    eng = _engine(params, 33, clock, obs, draft_params=dparams, draft_config=dcfg,
                  draft_shares_cache=shared, spec_k_max=2, spec_k_min=2, spec_adapt=False)
    eng.submit(np.arange(1, 12, dtype=np.int32), 9)
    while not any(s is not None and not s.prefilling for s in eng.slots):
        eng.step()
    before = eng.dispatches
    assert eng.next_logits() and eng.dispatches == before + 1
    while not eng.idle:
        eng.step()
    calls = [kw["call"] for _, kw, _ in marks()]
    assert calls == list(range(1, eng.dispatches + 1))
    evs = obs.tracer.events()
    prefills = _with_call(evs, "prefill.chunk")
    drafts, verifies = _with_call(evs, "spec.draft_enqueue"), _with_call(evs, "spec.verify_enqueue")
    assert drafts and sorted(verifies) == [c + 1 for c in sorted(drafts)]
    assert set(_with_call(evs, "spec.host_post")) == set(verifies)
    owned = {*prefills, *drafts, *verifies}
    unowned = set(calls) - owned
    # the logits call, and with a model of its own the draft's prefill after each target's
    assert unowned == {before + 1} | (set() if shared else {c + 1 for c in prefills})


def test_cpu_seconds_come_from_the_injected_cpu_clock(params, marks):
    """`cpu_s` on `decode.dispatch`, `decode.host_post` and `prefill.assemble`
    is the difference of two reads of `cpu_clock` (one step of the fake apart:
    nothing else reads it between a phase's ends), four reads a decode round
    and two a prefill call; the spans that block on the device say none."""
    cpu = StepClock(step=0.01)
    obs = Observability(clock=StepClock(), cpu_clock=cpu)
    eng = _engine(params, 17, StepClock(), obs)
    _serve(eng)
    evs = obs.tracer.events()
    timed = [e for e in evs if e[1] in ("decode.dispatch", "decode.host_post", "prefill.assemble")]
    assert timed and all(e[7]["cpu_s"] == pytest.approx(0.01) for e in timed)
    rounds = sum(e[1] == "decode.dispatch" for e in evs)
    assert cpu.calls == 4 * rounds + 2 * eng.prefill_calls
    blocked = [e for e in evs if e[1] in ("decode.device_wait", "prefill.first_token")]
    assert blocked and not any("cpu_s" in (e[7] or {}) for e in blocked)


@pytest.mark.parametrize("overlap,group,parent_reads", [("off", 1, 42), ("group", 2, 26), ("double", 2, 32)])
def test_obs_off_opens_no_annotation_and_reads_no_clock_more(params, marks, overlap, group, parent_reads):
    """With obs off the count is the only new work: no annotation is opened,
    the engine's clock is read as often as before PR 24 / PR 36 (the pins of
    tests/test_tracing.py, same scenario), no CPU clock exists to be read, and
    tokens, dispatch log and dispatch count equal the obs-on engine's."""
    clock = StepClock()
    off = _engine(params, 17, clock, overlap=overlap, round_group=group)
    toks_off = _serve(off)
    assert clock.calls == parent_reads and marks() == [] and off.obs is None
    on = _engine(params, 17, StepClock(), Observability(clock=StepClock(), cpu_clock=StepClock()),
                 overlap=overlap, round_group=group)
    assert _serve(on) == toks_off
    assert list(on.dispatch_log) == list(off.dispatch_log)
    assert on.dispatches == off.dispatches == len(marks()) > 0


@pytest.mark.parametrize("whole", [True, False])
def test_block_census_rides_the_dispatch_span(params, marks, monkeypatch, whole):
    """`blocks_swept` / `blocks_live` on each round's `decode.dispatch` are the
    increments `_count_blocks` gave the two counters in that round (the census
    runs where the kernel runs: steered here, the CPU engine gathers); a family
    whose `kernel_sweep` is one of several kernels keeps the counters and says
    nothing on the span."""
    monkeypatch.setattr(GPT, "kernel_sweep_whole", whole)
    obs = Observability(clock=StepClock(), cpu_clock=StepClock())
    eng = _engine(params, 17, StepClock(), obs)
    real, steps = eng._count_blocks, []

    def census(*a, **k):
        counters = lambda: [obs.snapshot()["counters"][c] for c in ("decode.blocks_swept", "decode.blocks_live")]
        eng.attn_impl, before = "kernel", counters()
        try:
            return real(*a, **k)
        finally:
            eng.attn_impl = "gather"
            steps.append(tuple(b - a for a, b in zip(before, counters())))

    eng._count_blocks = census
    _serve(eng)
    rode = [e[7] for e in obs.tracer.events() if e[1] == "decode.dispatch"]
    assert len(rode) == len(steps) > 0 and all(swept >= live > 0 for swept, live in steps)
    if whole:
        assert [(r["blocks_swept"], r["blocks_live"]) for r in rode] == steps
    else:
        assert not any("blocks_swept" in r or "blocks_live" in r for r in rode)


def test_only_trinity_has_a_second_decode_kernel():
    from midgpt_tpu.models import dots3, mimo_v2, ouro, pangu_ultra, trinity

    whole = {m.__name__: m.kernel_sweep_whole for m in
             (GPT, ouro.Ouro, pangu_ultra.PanguUltra, mimo_v2.MimoV2, dots3.Dots3, trinity.Trinity)}
    assert whole == {"GPT": True, "Ouro": True, "PanguUltra": True, "MimoV2": True, "Dots3": True, "Trinity": False}


def _bench(*rel):
    path = os.path.join(ROOT, "benchmarks", *rel)
    spec = importlib.util.spec_from_file_location("marks_" + rel[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmarks_reader_joins_the_hand_made_trace():
    """benchmarks/metrics/engine_device_calls.py on benchmarks/fixtures/
    engine_calls_small.json gives the hand-worked values of its .expected.json
    (the thorough cases, each refusal among them, are benchmarks/tests/
    test_engine_device_calls.py: `python -m pytest benchmarks/tests -q`)."""
    reduce, reader = _bench("reduce.py"), _bench("metrics", "engine_device_calls.py")
    with open(os.path.join(ROOT, "benchmarks", "fixtures", "engine_calls_small.json")) as f:
        fx = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "fixtures", "engine_calls_small.expected.json")) as f:
        want = json.load(f)["metrics"]
    log = []
    got = reader.summarize(reduce, [tuple(m) for m in fx["marks"]], [tuple(m) for m in fx["modules"]],
                           {k: [tuple(iv) for iv in v] for k, v in fx["leaf_busy"].items()},
                           [tuple(e) for e in fx["events"]], fx["lo"], fx["hi"], log.append)
    assert got == {k: pytest.approx(v) for k, v in want.items() if k != "host.offcpu_share"}
    four = sum(got[k] for k in got if k.startswith("starved."))
    assert four == pytest.approx(got["engine.host_starved_share"])  # the four shares are the starved time
    assert len(log) == 3 and all(line.startswith("engine_device_calls: ") for line in log)
