"""The measurement inside the program (PR 24): causality and identity in the
tracer, a request's life as async tracks in the serving engine, named phases
in the training step, seconds per compiled program in set-up — and the
benchmark readers (benchmarks/metrics/) that turn each into a per-layer
metric, run here on hand-made `run` dicts and in a CPU rehearsal of two cells.
CPU runs give counts and structure, never a time."""

import contextlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu import obs as obs_mod
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.obs import STEP_SCOPES, Observability
from midgpt_tpu.obs.trace import NULL_TRACER, Tracer
from midgpt_tpu.sampling.serve import ServeEngine
from midgpt_tpu.utils import compile_cache
from rehearsal_tree import run_rehearsal

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


def _engine(params, num_pages, clock, obs=None, on_token=None, **over):
    kw = dict(max_slots=2, page_size=8, num_pages=num_pages, prefill_chunk=8, decode_chunk=4,
              temperature=0.0, cache_dtype=jnp.float32, clock=clock, obs=obs, on_token=on_token)
    kw.update(over)
    return ServeEngine(CFG, params, **kw)


def _serve(eng):
    uids = [eng.submit(np.arange(1, 12 + 3 * i, dtype=np.int32), 10 + i) for i in range(4)]
    while not eng.idle:
        eng.step()
    return uids, [eng.finished[u].tokens.tolist() for u in uids]


# ---------------------------------------------------------------------------
# A. tracer: the ring tuple keeps its head; rid and parent are recorded
# ---------------------------------------------------------------------------


def test_ring_tuple_head_unchanged_and_rid_parent_appended():
    tr = Tracer(capacity=16, clock=StepClock(1.0))
    with tr.span("engine.round", "round", "engine"):
        with tr.span("prefill.chunk", "prefill", "engine", rid=7):
            tr.instant("admitted", "lifecycle", "engine", args={"uid": 7})
        tr.complete("decode.dispatch", "round", "engine", 2.5, 0.5)
        tr.async_begin("req.queue", 7, "request", "engine", t=1.25)
        tr.instant("elsewhere", "x", "server")  # another lane: no parent
    evs = {e[1]: e for e in tr.events()}
    # positions 0-7 as before: kind, name, cat, tid, start, dur, ident, args
    assert evs["prefill.chunk"][:8] == ("X", "prefill.chunk", "prefill", "engine", 3.0, 2.0, None, None)
    assert evs["decode.dispatch"][:8] == ("X", "decode.dispatch", "round", "engine", 2.5, 0.5, None, None)
    assert evs["req.queue"][:8] == ("b", "req.queue", "request", "engine", 1.25, 0.0, 7, None)
    assert all(len(e) == 11 for e in evs.values())
    rnd, chunk = evs["engine.round"], evs["prefill.chunk"]
    assert rnd[9] is None and chunk[8] == 7
    assert chunk[9] == rnd[10]  # the chunk's parent is the round's sequence number
    assert evs["admitted"][9] == chunk[10]  # innermost open span wins
    assert evs["decode.dispatch"][9] == rnd[10] and evs["req.queue"][9] == rnd[10]
    assert evs["req.queue"][8] == 7  # an async track's id is its request id
    assert evs["elsewhere"][9] is None
    exported = {e["name"]: e for e in tr.export() if e["ph"] != "M"}
    assert exported["prefill.chunk"]["args"] == {"seq": chunk[10], "parent": rnd[10], "rid": 7}
    assert exported["admitted"]["args"] == {"uid": 7, "parent": chunk[10]}
    assert exported["engine.round"]["args"] == {"seq": rnd[10]}


def test_complete_takes_an_explicit_parent_and_a_span_its_args():
    """A span of explicit readings recorded AFTER the round can still name the
    span it tiles (PR 36): `complete` hands back its sequence number and takes
    one as `parent`, whatever `span()` is open; `span(args=...)` says what rode it."""
    tr = Tracer(capacity=16, clock=StepClock(1.0))
    with tr.span("engine.round", "round", "engine"):
        seq = tr.complete("decode.dispatch", "round", "engine", 2.0, 1.0, {"steps": 4})
        tr.complete("decode.assemble", "round", "engine", 2.0, 0.25, parent=seq)
        with tr.span("prefill.chunk", "prefill", "engine", 7, {"rows": 3}) as sp:
            pass
    evs = {e[1]: e for e in tr.events()}
    assert evs["decode.dispatch"][10] == seq and evs["decode.dispatch"][7] == {"steps": 4}
    assert evs["decode.assemble"][9] == seq                      # not the open engine.round
    assert evs["decode.dispatch"][9] == evs["engine.round"][10]  # absent: the open span, as before
    assert evs["prefill.chunk"][7] == {"rows": 3} and evs["prefill.chunk"][4] == sp.t0
    names = [e[1] for e in tr.events()]
    assert names.index("decode.assemble") > names.index("decode.dispatch")  # a child after its parent
    exported = {e["name"]: e for e in tr.export() if e["ph"] != "M"}
    assert exported["decode.assemble"]["args"] == {"seq": evs["decode.assemble"][10], "parent": seq}
    assert exported["prefill.chunk"]["args"]["rows"] == 3
    assert NULL_TRACER.complete("a", "b", "c", 0.0, 1.0, parent=3) is None


def test_null_tracer_takes_the_new_arguments_and_stays_empty():
    with NULL_TRACER.span("prefill.chunk", "prefill", "engine", 7, {"rows": 1}):
        NULL_TRACER.async_begin("req.queue", 7, "request", "engine", None, 1.0)
        NULL_TRACER.async_end("req.queue", 7, "request", "engine", t=2.0)
        NULL_TRACER.complete("a", "b", "c", 0.0, 1.0, rid=7)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []


def test_live_keeps_the_last_recorders_reachable_for_a_late_reader():
    a = Observability()
    b = Observability()
    assert obs_mod.live()[-2:] == [a, b]
    ident = id(b)
    del a, b  # their owner is gone; a reader that runs afterwards still finds them
    assert id(obs_mod.live()[-1]) == ident
    for _ in range(6):
        Observability()
    assert len(obs_mod.live()) == 4  # bounded


# ---------------------------------------------------------------------------
# B. serving engine: a request's life, and nothing when obs is off
# ---------------------------------------------------------------------------


def _legs(events):
    """{uid: [(name, begin, end, end_args, begin_args)]} from async pairs."""
    open_, out = {}, {}
    for e in events:
        if e[0] == "b":
            open_[(e[6], e[1])] = (e[4], e[7])
        elif e[0] == "e":
            t0, bargs = open_.pop((e[6], e[1]))
            out.setdefault(e[6], []).append((e[1], t0, e[4], e[7], bargs))
    assert not open_, f"async tracks left open: {open_}"
    return out


def test_request_queue_plus_prefill_is_time_to_first_token(params):
    clock = StepClock()
    obs = Observability(clock=clock)
    first, submit = {}, {}
    eng = _engine(params, 17, clock, obs, on_token=lambda uid, tok, t: first.setdefault(uid, t))
    real_submit = eng.submit

    def stamped(prompt, n):
        uid = real_submit(prompt, n)
        submit[uid] = eng.queue[-1].t_submit  # the reading submit() took
        return uid

    eng.submit = stamped
    uids, _ = _serve(eng)
    legs = _legs(obs.tracer.events())
    for uid in uids:
        names = [leg[0] for leg in legs[uid]]
        assert names == ["req.queue", "req.prefill", "req.decode"]
        (_, q0, q1, _, _), (_, p0, p1, pargs, _), (_, d0, d1, dargs, _) = legs[uid]
        assert q0 == submit[uid] and q1 == p0 and p1 == d0 == first[uid]
        assert (q1 - q0) + (p1 - p0) == pytest.approx(first[uid] - submit[uid], abs=1e-12)
        n_prompt = 11 + 3 * uids.index(uid)
        assert pargs["prompt_tokens"] == n_prompt and pargs["prefix_skipped"] == 0
        assert pargs["chunks"] == -(-n_prompt // 8) and pargs["rounds"] >= pargs["chunks"]
        assert dargs == {"tokens_out": 10 + uids.index(uid), "status": "ok"}
    hist = eng.stats()["obs"]["histograms"]
    assert hist["req_queue_s"]["n"] == hist["req_prefill_s"]["n"] == hist["req_decode_s"]["n"] == 4
    assert hist["round_s"]["n"] == eng.rounds
    assert hist["round_prefill_chunks"]["n"] == eng.rounds and hist["round_decode_slots"]["max"] <= 2
    # chunks ride a round's prefill program together: never more programs than chunks
    assert hist["round_prefill_calls"]["n"] == eng.rounds and hist["round_prefill_calls"]["max"] == 1
    counters = eng.stats()["obs"]["counters"]
    assert counters["prefill.calls"] == eng.prefill_calls < eng.prefill_chunks == counters["prefill.chunks"]
    assert eng._req_open == {}
    # the first-token span is its request's; a prefill.chunk span is one CALL's
    # enqueue, shared by the slots that rode it: it carries the first row's uid
    evs = obs.tracer.events()
    assert {e[8] for e in evs if e[1] == "prefill.first_token"} == set(uids)
    assert {e[8] for e in evs if e[1] == "prefill.chunk"} <= set(uids)
    assert sum(e[1] == "prefill.chunk" for e in evs) == eng.prefill_calls
    # one first-token span a request, each closing on a token the device sampled; no hook, so no logits came over
    assert sum(e[1] == "prefill.first_token" for e in evs) == eng.first_tokens == len(uids)
    assert (eng.stats()["first_tokens"], eng.stats()["first_logit_pulls"]) == (len(uids), 0)


def test_preempted_request_gets_a_second_queue_leg(params):
    clock = StepClock()
    obs = Observability(clock=clock)
    eng = _engine(params, 7, clock, obs)  # 6 usable pages: the younger slot is evicted
    uids, _ = _serve(eng)
    assert eng.preemptions >= 1
    legs = _legs(obs.tracer.events())
    victims = [u for u in uids if [leg[0] for leg in legs[u]].count("req.queue") > 1]
    assert len(victims) == eng.preemptions
    for u in victims:
        second = [leg for leg in legs[u] if leg[0] == "req.queue"][1]
        assert second[4] == {"resumed": True}
        cut = [leg for leg in legs[u] if (leg[3] or {}).get("status") == "preempted"]
        assert len(cut) == 1 and cut[0][2] == second[1]  # one leg ends where the queue leg begins
    assert eng._req_open == {}


@pytest.mark.parametrize("num_pages,parent_reads", [(17, 42), (7, 50)])
def test_obs_off_reads_the_clock_as_before_and_emits_the_same_tokens(params, num_pages, parent_reads):
    """With obs off the request spans cost no clock read: the engine reads its
    injected clock exactly as often as the commit before PR 24 did over the
    same rounds (42 / 50 reads: counted there with this scenario, the second
    with one preemption), and obs on changes no token."""
    clock = StepClock()
    eng = _engine(params, num_pages, clock)
    _, toks_off = _serve(eng)
    assert clock.calls == parent_reads
    _, toks_on = _serve(_engine(params, num_pages, StepClock(), Observability(clock=StepClock())))
    assert toks_on == toks_off


@pytest.mark.parametrize("overlap,num_pages,parent_reads", [
    ("group", 17, 26), ("group", 7, 36), ("double", 17, 32), ("double", 7, 42)])
def test_obs_off_reads_the_clock_as_before_on_the_grouped_paths(params, overlap, num_pages, parent_reads):
    """The same pin for the dispatch the two overlap modes share (round_group 2):
    the reads counted on the parent of PR 36 with this scenario, and obs on
    changes no token."""
    clock = StepClock()
    _, toks_off = _serve(_engine(params, num_pages, clock, overlap=overlap, round_group=2))
    assert clock.calls == parent_reads
    _, toks_on = _serve(_engine(params, num_pages, StepClock(), Observability(clock=StepClock()),
                                overlap=overlap, round_group=2))
    assert toks_on == toks_off


# ---------------------------------------------------------------------------
# B2. what rode a round, and what its dispatch and commit are made of (PR 36)
# ---------------------------------------------------------------------------


def _args_of(events, name):
    return [e[7] for e in events if e[1] == name]


@pytest.mark.parametrize("overlap,group", [("off", 1), ("group", 2), ("double", 2)])
def test_round_args_count_the_tokens_the_client_got(params, overlap, group):
    """On every decode path the commits' `tokens` sum to what `on_token`
    delivered after each request's first (that one comes out of the prefill
    call), `finished` to the requests, `callback_s` to the clock's steps inside
    the client's callback, and `steps` feeds the one new histogram."""
    clock = StepClock()
    obs = Observability(clock=clock)
    got = {}
    eng = _engine(params, 17, clock, obs, overlap=overlap, round_group=group,
                  on_token=lambda uid, tok, t: got.setdefault(uid, []).append(tok))
    uids, _ = _serve(eng)
    evs = obs.tracer.events()
    rounds, commits = _args_of(evs, "decode.dispatch"), _args_of(evs, "decode.host_post")
    assert len(rounds) == len(commits) > 0
    delivered = sum(len(v) for v in got.values())
    assert sum(c["tokens"] for c in commits) == delivered - len(uids)
    assert sum(c["finished"] for c in commits) == len(uids)
    for c in commits:  # a fake clock step a callback: two reads a token
        assert c["callback_s"] == pytest.approx(c["tokens"] * clock.step)
    for r, c in zip(rounds, commits):
        assert r["chunk"] == 4 * group and 1 <= r["steps"] <= r["chunk"] and 1 <= r["slots"] <= 2
        assert c["tokens"] <= r["steps"] * r["slots"]
        assert r["limit"] in (("chunk", "remaining", "block") if overlap == "off" else ("chunk", "need"))
    hist = eng.stats()["obs"]["histograms"]["round_decode_steps"]
    assert hist["n"] == len(rounds) and hist["max"] == max(r["steps"] for r in rounds)
    assert hist["mean"] == pytest.approx(sum(r["steps"] for r in rounds) / len(rounds))


@pytest.mark.parametrize("overlap,want", [
    ("off", {"steps": 2, "slots": 3, "chunk": 4, "limit": "remaining", "tokens": 6, "finished": 0}),
    # the grouped dispatch runs what its NEEDIEST slot wants and masks the rest
    ("group", {"steps": 8, "slots": 3, "chunk": 8, "limit": "chunk", "tokens": 3 + 8 + 8, "finished": 1}),
])
def test_a_hand_built_round_says_its_steps_and_why(params, overlap, want):
    """One slot with 3 tokens left among slots with 18: the classic round runs
    the largest power of two under the FEWEST left (2 steps, `remaining`), for
    all three slots; the grouped one its chunk, masking the short slot."""
    clock = StepClock()
    obs = Observability(clock=clock)
    eng = _engine(params, 33, clock, obs, max_slots=3, overlap=overlap, round_group=2 if overlap == "group" else 1)
    for max_new in (4, 19, 19):  # one prefill call ends all three prompts: 3, 18, 18 left
        eng.submit(np.arange(1, 7, dtype=np.int32), max_new)
    eng.step()
    evs = obs.tracer.events()
    (rode,), (commit,) = _args_of(evs, "decode.dispatch"), _args_of(evs, "decode.host_post")
    assert rode.pop("cpu_s") >= 0  # PR 53: the dispatch span also says the thread's CPU seconds
    assert {**rode, **{k: commit[k] for k in ("tokens", "finished")}} == want
    if overlap == "off":  # the tail: 3 left -> 2, then 1 step, then the others alone at the chunk
        eng.step(), eng.step()
        assert [(a["steps"], a["limit"], a["slots"]) for a in _args_of(obs.tracer.events(), "decode.dispatch")] == [
            (2, "remaining", 3), (1, "remaining", 3), (4, "chunk", 2)]
    else:
        while not eng.idle:
            eng.step()
        assert _args_of(obs.tracer.events(), "decode.dispatch")[-1]["limit"] == "need"  # 18 = 8 + 8 + 2


@pytest.mark.parametrize("overlap", ["off", "group", "double"])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_dispatch_children_tile_their_parent_and_the_block_census_waits(params, overlap, temperature):
    """`decode.assemble` / `key` / `put` / `enqueue` are children of their
    round's `decode.dispatch` (its `seq`), back to back from its start to its
    end, `decode.key` only where a key was split; and the kernel-grid census
    (instrumentation) runs after t1, not inside the dispatch it used to be timed as."""
    clock = StepClock()
    obs = Observability(clock=clock)
    eng = _engine(params, 17, clock, obs, temperature=temperature, overlap=overlap, round_group=2)
    census_at = []
    real = eng._count_blocks
    eng._count_blocks = lambda *a, **k: (census_at.append(clock.t), real(*a, **k))[1]
    _serve(eng)
    evs = obs.tracer.events()
    parents = [e for e in evs if e[1] == "decode.dispatch"]
    assert len(parents) == len(census_at) > 0
    names = ["decode.assemble"] + ["decode.key"] * (temperature > 0) + ["decode.put", "decode.enqueue"]
    for parent, t_census in zip(parents, census_at):
        kids = [e for e in evs if e[9] == parent[10]]
        assert [k[1] for k in kids] == names and all(k[10] > parent[10] for k in kids)
        assert kids[0][4] == parent[4]
        for a, b in zip(kids, kids[1:]):
            assert a[4] + a[5] == pytest.approx(b[4], abs=1e-12)
        assert sum(k[5] for k in kids) == pytest.approx(parent[5], abs=1e-12)
        assert t_census >= parent[4] + parent[5] - 1e-12  # after t1
    assert not any(e[1] == "decode.key" for e in evs) or temperature > 0


def test_gap_at_a_dispatchs_start_goes_to_the_child_not_the_parent():
    """`reduce.attribute_gaps` gives a gap to the latest-STARTED span over its
    midpoint and breaks a tie of starts by the order of the list: the child
    `decode.assemble` starts with its parent and is recorded after it."""
    reduce = _load("reduce.py")
    obs = Observability(clock=StepClock())
    obs.record_round("decode", "engine", 1.0, 1.004, 1.010, 1.012,
                     cuts=(1.001, 1.002, 1.003), steps=4, slots=2, chunk=4, limit="chunk",
                     tokens=8, finished=0, callback_s=0.001)
    spans_ns = [(e[1], int(e[4] * 1e9), int(e[5] * 1e9)) for e in obs.tracer.events()]
    busy = lambda a, b: [0, int(a * 1e9), int((b - a) * 1e9)]
    ops = [busy(0.9, 1.0002), busy(1.0008, 1.0022), busy(1.0028, 1.0101), busy(1.0119, 1.1)]
    gaps = reduce.attribute_gaps(ops, spans_ns, int(0.9e9), int(1.1e9))
    assert set(gaps) == {"decode.assemble", "decode.put", "decode.host_post"}
    assert gaps["decode.assemble"] == pytest.approx(600_000, abs=2)


@pytest.mark.parametrize("max_slots,width", [(1, 1), (16, 16)])
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_prefill_assemble_ends_where_the_enqueue_span_opens(params, max_slots, width, temperature):
    clock = StepClock()
    obs = Observability(clock=clock)
    eng = _engine(params, 16 * 2 + 1, clock, obs, max_slots=max_slots, prefill_chunk=16, temperature=temperature)
    assert eng.prefill_width == width
    for _ in range(16):
        eng.submit(np.arange(1, 6, dtype=np.int32), 2)
    while not eng.idle:
        eng.step()
    evs = obs.tracer.events()
    by_start = lambda name: sorted((e for e in evs if e[1] == name), key=lambda e: e[4])
    assembles, chunks = by_start("prefill.assemble"), by_start("prefill.chunk")
    assert len(assembles) == len(chunks) == eng.prefill_calls
    for a, c in zip(assembles, chunks):
        assert a[4] + a[5] == pytest.approx(c[4], abs=1e-12) and a[8] == c[8] and a[9] == c[9]
        kids = [e for e in evs if e[9] == a[10]]
        assert [k[1] for k in kids] == ["prefill.put"] + ["prefill.key"] * (temperature > 0)
        assert kids[0][4] > a[4]  # the numpy arrays are the span's own time
        assert kids[-1][4] + kids[-1][5] == pytest.approx(c[4], abs=1e-12)
    rode = [c[7] for c in chunks]
    assert rode[0] == {"rows": width, "tokens": 5 * width, "bucket": 1, "call": 1, "width": width}  # PR 53: + call, width
    assert all(r["rows"] <= width for r in rode)
    assert sum(r["rows"] for r in rode) == eng.prefill_chunks == 16
    assert sum(r["tokens"] for r in rode) == eng.prefilled_tokens == 16 * 5


# ---------------------------------------------------------------------------
# C. training: scopes are metadata; the feed has spans
# ---------------------------------------------------------------------------


def _lower_step(tmp_path, fsdp_mode):
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.train import init_state, make_train_step
    from test_train import tiny_config

    cfg = tiny_config(tmp_path, g_accum_iters=2, compute_dtype="bfloat16", fsdp_mode=fsdp_mode)
    mesh = make_mesh(cfg.mesh)
    params, opt_state, specs, optimizer = init_state(cfg, mesh)
    step, *_ = make_train_step(cfg, optimizer, mesh, specs)
    x = make_global_batch(np.zeros((2, 8, 32), np.int32), mesh, batch_spec())
    return step.lower(params, opt_state, x, x, jax.random.PRNGKey(0))


@pytest.mark.parametrize("fsdp_mode", ["gspmd", "shard_map"])
def test_step_program_names_every_scope_and_gains_no_operation(fsdp_mode, tmp_path, monkeypatch):
    """Under either collective schedule (the compiler's, and the authored one
    the four-chip cell takes): step_phases.py reads the same scopes off both."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    lowered = _lower_step(tmp_path, fsdp_mode)
    n_ops = sum(" = " in line for line in lowered.as_text().splitlines())
    locs = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    step_phases = _reader("step_phases.py")
    seen = {step_phases.innermost_scope(loc) for loc in locs}
    assert set(STEP_SCOPES) <= seen, f"scopes the lowered step does not name: {set(STEP_SCOPES) - seen}"
    assert {"attn", "mlp", "embed", "final_norm"} <= seen
    # backward ops of the loss keep the scope (no custom backward rule drops
    # it). The shard_map body is a function of its own in the lowered text:
    # its locations start at the body, so the loss's cotangent sums read
    # `lm_head_loss/add_any` there.
    backward = "transpose(jvp(lm_head_loss))" if fsdp_mode == "gspmd" else "lm_head_loss/add_any"
    assert any(backward in loc for loc in locs)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = sum(" = " in line for line in _lower_step(tmp_path, fsdp_mode).as_text().splitlines())
    assert n_ops == bare


def test_feed_spans_are_opened_where_the_work_is(tmp_path):
    from midgpt_tpu.data.dataset import TokenDataset
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from test_train import tiny_config

    (np.arange(4000) % 17).astype(np.uint16).tofile(tmp_path / "train.bin")
    (np.arange(400) % 17).astype(np.uint16).tofile(tmp_path / "val.bin")
    tr = obs_mod.flight_recorder().tracer
    n0 = len(tr.events())
    x, _ = TokenDataset(str(tmp_path), seed=1).batch("train", 0, 32, 8, 1)
    make_global_batch(x, make_mesh(tiny_config(tmp_path).mesh), batch_spec())
    names = [e[1] for e in tr.events()[n0:]]
    assert names == ["data.batch", "data.put"]


# ---------------------------------------------------------------------------
# D. set-up: seconds per compiled program
# ---------------------------------------------------------------------------


def test_compile_cache_stats_show_a_program_under_its_name():
    import jax.monitoring
    from jax._src import monitoring as _mon

    stats = compile_cache.CompileCacheStats(dir="unused")
    listener = stats._on_duration  # one bound-method object: unregister finds it by identity
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        @jax.jit
        def tiny_named_program_pr24(x):
            return x * 2 + 1

        tiny_named_program_pr24(jnp.ones((3,))).block_until_ready()
    finally:
        _mon.unregister_event_duration_listener(listener)
    cost = stats.programs["tiny_named_program_pr24"]  # jit(f) and f fold into one row
    assert cost.calls == 1 and cost.compile_or_load_s > 0 and cost.trace_s > 0 and cost.lower_s > 0
    t = stats.totals()
    assert t["programs"] >= 1 and t["compile_or_load_s"] >= cost.compile_or_load_s
    lines = stats.summary().splitlines()
    assert lines[0] == "compile_cache: dir=unused requests=0 hits=0 writes=0"  # chip_smoke.py parses this
    assert any("tiny_named_program_pr24" in ln and "x1" in ln for ln in lines[2:])


# ---------------------------------------------------------------------------
# E. readers, on hand-made run dicts
# ---------------------------------------------------------------------------


def _load(rel):
    path = os.path.join(ROOT, "benchmarks", rel)
    spec = importlib.util.spec_from_file_location("pr24_" + os.path.basename(rel)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return _load(os.path.join("metrics", name))


def _run_dict(kind, **over):
    logs = []
    run = {"kind": kind, "spans": [], "window_s": 10.0, "counters": {"traced_steps": 2},
           "samples": {"ttft_s": [0.3]}, "trace_summary": None, "log": lambda *a: logs.append(" ".join(map(str, a))),
           "load": lambda fn: _load(fn), "logs": logs}
    run.update(over)
    return run


def _trace_summary(ops, infos):
    """One device; ops = [(name, start_ns, dur_ns)], infos = {name: info dict}."""
    names = sorted({n for n, _, _ in ops})
    idx = {n: i for i, n in enumerate(names)}
    dev_ops = sorted(([idx[n], s, d] for n, s, d in ops), key=lambda o: (o[1], -o[2]))
    busy = sum(d for n, _, d in ops if not n.startswith("while"))
    return {"trace": {"names": names, "info": {str(idx[n]): v for n, v in infos.items()}},
            "devices": [{"ops": dev_ops}], "n_devices": 1, "busy_ns_mean": busy, "window_ns": 10_000_000}


STEP_OPS = [("while.1", 0, 9_000_000), ("fusion.1", 0, 2_000_000), ("attn.3", 2_000_000, 1_000_000),
            ("fusion.2", 3_000_000, 3_000_000), ("fusion.3", 6_000_000, 1_000_000),
            ("fusion.4", 7_000_000, 1_500_000), ("copy.1", 8_500_000, 500_000),
            ("fusion.5", 9_000_000, 1_000_000)]
STEP_TEXT = """HloModule jit_step, is_scheduled=true
%body (p: f32[8]) -> f32[8] {
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kOutput, metadata={op_name="jit(step)/jit(main)/while/body/jvp(block)/mlp/dot_general" source_file="x.py" source_line=1}
  %attn.3 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/while/body/transpose(jvp(block))/attn/pallas_call"}
  %fusion.2 = bf16[8]{0} fusion(%attn.3), kind=kOutput, metadata={op_name="jit(step)/jit(main)/while/body/transpose(jvp(lm_head_loss))/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, metadata={op_name="jit(step)/jit(main)/while/body/jvp(lm_head_loss)/checkpoint/rematted_computation/exp"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, metadata={op_name="jit(step)/jit(main)/while/body/grad_accum/add"}
  ROOT %copy.1 = f32[8]{0} copy(%fusion.4)
}
ENTRY %main (a: f32[8]) -> f32[8] {
  %while.1 = f32[8]{0} while(%a), condition=%cond, body=%body
  ROOT %fusion.5 = f32[8]{0} fusion(%while.1), kind=kLoop, metadata={op_name="jit(step)/jit(main)/optimizer/mul"}
}
"""


class _FakeRuntime:
    def __init__(self, text):
        self.text = text

    def step_program_text(self):
        return self.text


def _with_runtime(monkeypatch, text):
    train = importlib.import_module("midgpt_tpu.training.train")
    monkeypatch.setattr(train, "_LAST_RUNTIME", None if text is None else _FakeRuntime(text))


def test_step_phases_reader_splits_exclusive_time_by_innermost_scope(monkeypatch):
    _with_runtime(monkeypatch, STEP_TEXT)
    run = _run_dict("train", trace_summary=_trace_summary(STEP_OPS, {}))
    out = _reader("step_phases.py").read(run)
    assert out == pytest.approx({"step.attn_ms": 0.5, "step.mlp_ms": 1.0, "step.lm_head_loss_ms": 2.0,
                                 "step.optimizer_ms": 0.5, "step.unattributed_ms": 1.0})
    assert sum(out.values()) == pytest.approx(run["trace_summary"]["busy_ns_mean"] / 1e6 / 2)


@pytest.mark.parametrize("text,says", [
    (re.sub(r"lm_head_loss|optimizer", "mlp", STEP_TEXT), "stale cache?"),
    (STEP_TEXT.replace("%fusion.", "%other_fusion."), "is not the traced program"),
    (None, "no last_runtime()"),
])
def test_step_phases_reader_reports_nothing_and_says_why(monkeypatch, text, says):
    _with_runtime(monkeypatch, text)
    run = _run_dict("train", trace_summary=_trace_summary(STEP_OPS, {}))
    assert _reader("step_phases.py").read(run) is None
    assert any(says in line for line in run["logs"])


def test_step_program_text_names_the_running_programs_ops(tmp_path):
    """The runtime's own text: instruction names with the scope path in their
    metadata, from the same avals as the loop's call."""
    from test_train import tiny_config

    train = importlib.import_module("midgpt_tpu.training.train")
    (np.arange(4000) % 17).astype(np.uint16).tofile(tmp_path / "train.bin")
    (np.arange(400) % 17).astype(np.uint16).tofile(tmp_path / "val.bin")
    rt = train.make_runtime(tiny_config(tmp_path, g_accum_iters=2))
    assert train.last_runtime() is rt
    reader = _reader("step_phases.py")
    scopes = {reader.innermost_scope(v) for v in dict(reader._INSTRUCTION.findall(rt.step_program_text())).values()}
    assert {"attn", "mlp", "lm_head_loss", "optimizer"} <= scopes


def test_engine_requests_reader_on_a_hand_made_window():
    reader = _reader("engine_requests.py")
    obs = Observability(clock=StepClock())
    tr = obs.tracer
    tr.complete("engine.round", "round", "engine", 99.0, 0.9)  # the last round before the window
    spans = [("engine.round", 100.0, 1.0), ("engine.admit", 100.0, 0.1), ("engine.prefill", 100.1, 0.3),
             ("prefill.chunk", 100.15, 0.1), ("decode.dispatch", 100.5, 0.1), ("decode.device_wait", 100.6, 0.2),
             ("engine.round", 101.0, 2.0), ("engine.prefill", 101.5, 1.0)]
    for n, s, d in spans:
        tr.complete(n, "x", "engine", s, d)
    tr.async_begin("req.queue", 1, t=99.95)     # submitted as the window opened (after the last round)
    tr.async_end("req.queue", 1, t=100.0)
    tr.async_begin("req.prefill", 1, t=100.0)
    tr.async_end("req.prefill", 1, t=101.0, args={"prompt_tokens": 5})
    tr.async_begin("req.queue", 2, t=98.0)      # submitted before the window: not in the set
    tr.async_end("req.queue", 2, t=100.0)
    tr.async_begin("req.prefill", 2, t=100.0)
    tr.async_end("req.prefill", 2, t=100.4, args={"prompt_tokens": 5})
    tr.async_begin("req.queue", 3, t=100.2)     # preempted before its first token: legs are summed
    tr.async_end("req.queue", 3, t=100.3)
    tr.async_begin("req.prefill", 3, t=100.3)
    tr.async_end("req.prefill", 3, t=100.5, args={"status": "preempted"})
    tr.async_begin("req.queue", 3, t=100.5, args={"resumed": True})
    tr.async_end("req.queue", 3, t=100.9)
    tr.async_begin("req.prefill", 3, t=100.9)
    tr.async_end("req.prefill", 3, t=102.0, args={"prompt_tokens": 5})
    tr.async_begin("req.queue", 4, t=101.0)     # first token after the window: not in the set
    tr.async_end("req.queue", 4, t=101.1)
    tr.async_begin("req.prefill", 4, t=101.1)
    tr.async_end("req.prefill", 4, t=111.0, args={"prompt_tokens": 5})
    out = reader.read(_run_dict("serve", spans=spans, window_s=10.0))
    assert out["engine.round_ms_p50"] == pytest.approx(1500.0)
    # self time: 1.0 - (0.1 + 0.3 + 0.1 + 0.2) = 0.3 and 2.0 - 1.0 = 1.0
    assert out["engine.round_self_ms_p50"] == pytest.approx(650.0)
    assert out["req.queue_ms_mean"] == pytest.approx(1e3 * (0.05 + 0.5) / 2)
    assert out["req.prefill_ms_mean"] == pytest.approx(1e3 * (1.0 + 1.3) / 2)


def test_engine_requests_reader_without_tracks_reports_rounds_only():
    for _ in range(4):
        Observability()  # none of the recorders within reach holds a req.* track
    run = _run_dict("serve", spans=[("engine.round", 1.0, 0.5)])
    out = _reader("engine_requests.py").read(run)
    assert set(out) == {"engine.round_ms_p50", "engine.round_self_ms_p50"}
    assert any("req.* left out" in line for line in run["logs"])
    run = _run_dict("serve")
    assert _reader("engine_requests.py").read(run) == {}
    assert any("no engine.round span" in line for line in run["logs"])


def _dispatch_window(obs, with_args):
    """Two decode rounds and two prefill calls inside a window, a third round
    after it; as this program records them, or (`with_args` False) as the
    parent of PR 36 did: the same phase spans with no args and no children."""
    tr = obs.tracer
    rounds = [  # t0, (t_a, t_k, t_p), t1, t_land, t_post, rode, commit
        (100.0, (100.001, 100.0015, 100.003), 100.004, 100.010, 100.012,
         dict(steps=4, slots=2, chunk=8, limit="remaining"), dict(tokens=8, finished=1, callback_s=0.0005)),
        (101.0, (101.002, 101.003, 101.005), 101.006, 101.020, 101.024,
         dict(steps=8, slots=2, chunk=8, limit="chunk"), dict(tokens=16, finished=0, callback_s=0.0015)),
        (200.0, (200.001, 200.002, 200.003), 200.004, 200.010, 200.011,  # after the window
         dict(steps=1, slots=1, chunk=8, limit="remaining"), dict(tokens=1, finished=1, callback_s=0.0)),
    ]
    for t0, cuts, t1, t_land, t_post, rode, commit in rounds:
        if with_args:
            obs.record_round("decode", "engine", t0, t1, t_land, t_post, cuts=cuts, **rode, **commit)
        else:
            obs.record_round("decode", "engine", t0, t1, t_land, t_post)
    for t0, t_end, rows in ((100.5, 100.52, 3), (101.5, 101.53, 1)):
        tr.complete("prefill.chunk", "prefill", "engine", t_end, 0.001,
                    {"rows": rows, "tokens": 5 * rows, "bucket": 1} if with_args else None, rid=1)
        if with_args:
            obs.record_prefill_assemble("engine", 1, t0, t0 + 0.005, t0 + 0.015, t_end)
    return [(e[1], e[4], e[5]) for e in tr.events() if e[0] == "X" and e[4] < 150.0]


def test_engine_dispatch_reader_on_a_hand_made_window():
    reader = _reader("engine_dispatch.py")
    Observability()  # an older recorder without the window's spans is passed over
    spans = _dispatch_window(Observability(clock=StepClock()), with_args=True)
    Observability()  # and so is a newer one
    run = _run_dict("serve", spans=spans, counters={"max_slots": 2})
    out = reader.read(run)
    assert {k: v for k, v in out.items() if v is not None} == pytest.approx({
        "decode.steps_per_round_mean": 6.0,             # (4 + 8) / 2: the third round is outside
        "decode.round_fill": 100.0 * 24 / (2 * 16),     # tokens over max_slots x chunk, summed
        "decode.tail_limited_share": 50.0,              # `remaining` AND short of the chunk
        "decode.host_ms_per_token": (4 + 6 + 2 + 4) / 24,
        "decode.dispatch_ms_p50": 5.0, "decode.host_post_ms_p50": 3.0,
        "decode.assemble_ms_p50": 1.5, "decode.key_ms_p50": 0.75,
        "decode.put_ms_p50": 1.75, "decode.enqueue_ms_p50": 1.0,
        "decode.callback_share": 100.0 * 0.002 / 0.006,
        "prefill.assemble_ms_p50": 25.0, "prefill.rows_per_call_mean": 2.0,
    })
    assert any("decode rounds in the window: 2; steps {4: 1, 8: 1}" in line for line in run["logs"])
    assert reader.read(_run_dict("train")) is None and reader.read(_run_dict("serve")) is None


THIRTEEN = (
    "decode.steps_per_round_mean", "decode.round_fill", "decode.tail_limited_share", "decode.host_ms_per_token",
    "decode.dispatch_ms_p50", "decode.host_post_ms_p50", "decode.assemble_ms_p50", "decode.key_ms_p50",
    "decode.put_ms_p50", "decode.enqueue_ms_p50", "decode.callback_share", "prefill.assemble_ms_p50",
    "prefill.rows_per_call_mean")


@pytest.mark.parametrize("overlap", ["off", "group"])
def test_engine_dispatch_reader_reads_all_thirteen_off_a_sampled_engine(params, overlap):
    """PR 37 took the key split and the puts off the host; their spans are still
    stamped in a sampled round (`decode.key`: two clock reads apart; `decode.put`:
    the page tables' build), so the reader has a NUMBER for each of its thirteen
    metrics off a real engine's window, none left out."""
    clock = StepClock()
    obs = Observability(clock=clock)
    _serve(_engine(params, 17, clock, obs, temperature=0.8, overlap=overlap, round_group=2,
                   on_token=lambda uid, tok, t: None))
    spans = [(e[1], e[4], e[5]) for e in obs.tracer.events() if e[0] == "X"]
    run = _run_dict("serve", spans=spans, counters={"max_slots": 2})
    out = _reader("engine_dispatch.py").read(run)
    assert set(out) == set(THIRTEEN)
    assert all(isinstance(out[k], float) for k in THIRTEEN), {k: out[k] for k in THIRTEEN if out[k] is None}
    assert out["decode.key_ms_p50"] == pytest.approx(1.0)  # one step of the clock: the host does nothing there
    assert not [line for line in run["logs"] if "left out" in line]


_DISPATCH_COUNT = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp, numpy as np
from midgpt_tpu.utils import compile_cache
stats = compile_cache.enable()
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.sampling import serve
from midgpt_tpu.sampling.spec import self_draft
cfg = GPTConfig(block_size=64, vocab_size=64, n_layer=2, n_head=2, n_embd=32)
params = jax.jit(lambda k: GPT.init(cfg, k))(jax.random.PRNGKey(0))  # one program: no eager split of the model's own
dcfg, dparams = self_draft(cfg, params, 1)
rounds = 0
for kw in ({}, {"overlap": "group", "round_group": 2}, {"overlap": "double", "round_group": 2},
           {"draft_config": dcfg, "draft_params": dparams}):
    eng = serve.ServeEngine(cfg, params, max_slots=2, page_size=8, num_pages=17, prefill_chunk=8, decode_chunk=4,
                            temperature=0.8, cache_dtype=jnp.float32, **kw)
    for i in range(4):
        eng.submit(np.arange(1, 12 + 3 * i, dtype=np.int32), 10 + i)
    while not eng.idle:
        eng.step()
    rounds += eng.rounds
programs = (serve._serve_prefill_chunk, serve._serve_decode_chunk, serve._serve_decode_group,
            serve._spec_draft_chunk, serve._spec_verify_chunk)
print(json.dumps({"programs": {f: c.calls for f, c in stats.programs.items()}, "rounds": rounds,
                  "labels": sorted(label for p in programs for label in p._compiled)}))
"""

# What the script above gave on the PARENT of PR 37 (commit bef1e18), whose table also held `_threefry_split` x2 (the
# two- and the three-way split) and `_unstack` x2, each a program of its own that the host dispatched before every
# sampled call: the serving programs the same traffic compiled there, by label, and how many a jitted function
# (a draft model's prefill program has its target's label)
PARENT_COMPILE_SET = [
    "serve_decode_chunk n_steps=1 page_table=4 pool=float32", "serve_decode_chunk n_steps=2 page_table=4 pool=float32",
    "serve_decode_chunk n_steps=4 page_table=4 pool=float32",
    "serve_decode_group n_steps=2 round_group=2 page_table=4 pool=float32",
    "serve_decode_group n_steps=4 round_group=2 page_table=4 pool=float32",
    "serve_prefill_chunk page_table_row=1 tokens=8 pool=float32", "serve_prefill_chunk page_table_row=2 tokens=8 pool=float32",
    "serve_prefill_chunk page_table_row=4 tokens=8 pool=float32",
    "spec_draft_chunk k_steps=1 page_table=4 pool=float32", "spec_draft_chunk k_steps=1 page_table=8 pool=float32",
    "spec_draft_chunk k_steps=2 page_table=4 pool=float32", "spec_draft_chunk k_steps=4 page_table=4 pool=float32",
    "spec_verify_chunk page_table=4 drafts=2 pool=float32", "spec_verify_chunk page_table=8 drafts=2 pool=float32",
]
PARENT_COMPILES = {"_serve_prefill_chunk": 6, "_serve_decode_chunk": 3, "_serve_decode_group": 2,
                   "_spec_draft_chunk": 4, "_spec_verify_chunk": 4}


def test_a_sampled_round_dispatches_its_one_program_and_compiles_the_parents_set(tmp_path):
    """A count, CPU: fresh engines (classic, group, double, speculative) served to
    the end at temperature 0.8 in a process of their own leave in
    `compile_cache.current()`'s table no `_threefry_split` and no `_unstack`
    compiled (the host splits no key and unpacks none: the programs do, and a
    split traced inside one compiles nothing of its own), and the serving
    programs compiled are the parent's, label for label and count for count:
    numpy arguments made no second entry."""
    out = subprocess.run(
        [sys.executable, "-c", _DISPATCH_COUNT], capture_output=True, text=True, check=False, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.splitlines()[-1])
    compiled = {f: n for f, n in got["programs"].items() if n}
    assert got["rounds"] > 40
    assert not {"_threefry_split", "_unstack"} & set(compiled), compiled
    assert got["labels"] == PARENT_COMPILE_SET
    assert {f: n for f, n in compiled.items() if f.startswith(("_serve_", "_spec_"))} == PARENT_COMPILES


def test_engine_dispatch_reader_leaves_out_what_the_parent_cannot_say():
    """On a program whose spans carry no args and no children (the parent of
    PR 36, which the driver runs these readers over): the two medians that
    need neither, a line for everything left out, and no exception."""
    reader = _reader("engine_dispatch.py")
    spans = _dispatch_window(Observability(clock=StepClock()), with_args=False)
    run = _run_dict("serve", spans=spans, counters={"max_slots": 2})
    out = reader.read(run)
    assert {k: v for k, v in out.items() if v is not None} == pytest.approx(
        {"decode.dispatch_ms_p50": 5.0, "decode.host_post_ms_p50": 3.0})
    said = " ".join(run["logs"])
    for name in ("decode.assemble", "prefill.assemble", "decode.steps_per_round_mean", "decode.round_fill",
                 "decode.callback_share", "decode.host_ms_per_token", "prefill.rows_per_call_mean"):
        assert name in said, name
    for _ in range(4):
        Observability()  # the window's recorder is out of reach: the span medians still come
    run = _run_dict("serve", spans=spans, counters={"max_slots": 2})
    assert reader.read(run)["decode.dispatch_ms_p50"] == pytest.approx(5.0)
    assert any("no live recorder holds the window's spans" in line for line in run["logs"])


def test_serve_prefill_reader_shares():
    reader = _reader("serve_prefill.py")
    spans = [("prefill.first_token", 1.0, 0.25), ("prefill.first_token", 2.0, 0.25), ("prefill.chunk", 3.0, 9.0)]
    events = [("/device:TPU:0", "jit__serve_prefill_chunk(123)", 0, 3_000), ("/device:TPU:0", "jit__serve_decode_chunk(9)", 3_000, 5_000),
              ("/device:TPU:0", "jit__serve_prefill_chunk(123)", 9_000, 4_000)]  # the last one is half outside
    assert reader.prefill_share(events, 0, 11_000) == pytest.approx(100.0 * 5_000 / 10_000)
    assert reader.prefill_share([], 0, 10) is None
    # a run with no xplane file on disk (or no TPU plane in it): the span share only, and a log line
    run = _run_dict("serve", spans=spans, trace_summary={"lo": 0, "hi": 10})
    reader.TRACE_DIR = "/nonexistent/trace"
    assert reader.read(run) == pytest.approx({"prefill.first_token_sync_share": 5.0})
    assert any("no XLA Modules line" in line for line in run["logs"])
    assert reader.read(_run_dict("train")) is None


def test_train_feed_reader_sums_a_steps_batch_and_puts():
    reader = _reader("train_feed.py")
    tr = obs_mod.flight_recorder().tracer
    base = 5_000_000.0  # far from any real perf_counter reading in this ring
    for i, (b, p1, p2) in enumerate([(0.010, 0.001, 0.001), (0.020, 0.002, 0.002), (0.030, 0.003, 0.003)]):
        t = base + i
        tr.complete("data.batch", "data", "train", t, b)
        tr.complete("data.put", "data", "train", t + 0.1, p1)
        tr.complete("data.put", "data", "train", t + 0.2, p2)
    tr.complete("data.batch", "data", "train", base + 50.0, 9.0)  # outside the window
    out = reader.read(_run_dict("train", spans=[("bench.data", base, 0.01)], window_s=10.0))
    assert out == pytest.approx({"train.feed_ms_p50": 24.0})
    run = _run_dict("train", spans=[("bench.data", base + 1000.0, 0.01)])
    assert reader.read(run) is None and any("left out" in line for line in run["logs"])


def test_setup_programs_reader(monkeypatch):
    reader = _reader("setup_programs.py")
    stats = compile_cache.CompileCacheStats(dir="d")
    stats._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.5, fun_name="step")
    stats._on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25, fun_name="jit(step)")
    stats._on_duration("/jax/core/compile/backend_compile_duration", 4.0, fun_name="jit(step)")
    stats._on_duration("/jax/core/compile/backend_compile_duration", 1.0, fun_name="jit(_serve_decode_chunk)")
    stats._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.75)
    monkeypatch.setattr(compile_cache, "_CURRENT", stats)
    run = _run_dict("train")
    assert reader.read(run) == {"setup.compile_or_load_s": 5.0, "setup.trace_lower_s": 0.75, "setup.programs": 2.0}
    assert any("4.75 s  step  x1" in line for line in run["logs"])
    monkeypatch.setattr(compile_cache, "_CURRENT", None)
    run = _run_dict("serve")
    assert reader.read(run) is None and any("left out" in line for line in run["logs"])


# ---------------------------------------------------------------------------
# the harness lists the new names where a CPU can produce them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell,names", [
    ("serve_124m_sample", {"req.queue_ms_mean", "req.prefill_ms_mean", "engine.round_ms_p50",
                           "engine.round_self_ms_p50", "prefill.first_token_sync_share",
                           "setup.compile_or_load_s",
                           "setup.trace_lower_s", "setup.programs",
                           # PR 36: the host side of a round from inside
                           *THIRTEEN,
                           # PR 53: the one reading of engine_device_calls.py that needs no device trace
                           "host.offcpu_share"}),
    ("train_124m", {"train.feed_ms_p50", "setup.compile_or_load_s", "setup.trace_lower_s", "setup.programs",
                    "step.attn_ms", "step.mlp_ms", "step.lm_head_loss_ms", "step.optimizer_ms",
                    "step.unattributed_ms"}),
    # PR 46: a family with kinds of attention layer, read by the configuration's `metrics` group
    ("serve_trinity_mini_reason", {"serve.attn_global_ms", "serve.attn_window_ms", "serve.attn_gate_ms",
                                   "serve.moe_route_ms", "serve.moe_experts_ms", "serve.moe_shared_ms",
                                   "serve.lm_head_ms", "serve.model_unattributed_ms", "serve.moe_experts_touched",
                                   "serve.moe_load_max_over_mean", "kv.global_pool_fill",
                                   "kv.window_tokens_per_slot_max", "engine.occupancy", "setup.programs"}),
    # PR 51: a family whose full layers select what they attend to, read by the same group and two new readers
    ("serve_dots3_note_longctx", {"serve.attn_sparse_ms", "serve.dsa_index_ms", "serve.dsa_topk_ms", "serve.attn_select_ms",
                                  "serve.attn_window_ms", "serve.attn_gate_ms", "serve.moe_route_ms", "serve.moe_experts_ms",
                                  "serve.moe_shared_ms", "serve.lm_head_ms", "serve.model_unattributed_ms",
                                  "dsa_index_sweep_ms_per_token", "select_decode_attention_ms_per_token",
                                  "serve.dsa_selected_share", "kv.index_bytes_per_token", "kv.window_tokens_per_slot_max",
                                  "kv.latent_pool_fill", "kv.latent_bytes_per_token", "serve.moe_experts_touched",
                                  "serve.moe_visits_per_expert_touched", "engine.occupancy", "setup.programs"}),
])
def test_rehearsal_lists_the_new_metrics(cell, names, tmp_path):
    proc = run_rehearsal(tmp_path, cell, seconds="2", timeout=600)  # a tree of its own: tests/rehearsal_tree.py
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"]
    assert names <= set(last["would_report"]), sorted(names - set(last["would_report"]))


def test_decode_round_records_the_kernels_blocks(params, monkeypatch):
    """`decode.blocks_swept` / `decode.blocks_live` / `decode.live_block_share`
    on a hand-built round: the engine's host arithmetic with the kernel's own
    block width and live rule (kernels/attention_template.py), recorded only
    where the kernel runs."""
    import midgpt_tpu.sampling.serve as serve_mod
    from midgpt_tpu.kernels.attention_template import block_pages

    obs = Observability()
    eng = _engine(params, 33, StepClock(), obs)
    lengths, active = np.asarray([40, 3]), np.asarray([True, False])
    eng._count_blocks(lengths, active, 8, 1, n_steps=4)  # CPU: the gather lowering
    assert obs.snapshot()["counters"]["decode.blocks_swept"] == 0

    eng.attn_impl = "kernel"  # what a TPU engine resolves to
    _, n_kv, _, ps, lanes = eng.cache.k.shape
    assert block_pages(n_kv, lanes, 4, ps, 8, 1) == 8  # an 8-page table: one block
    eng._count_blocks(lengths, active, 8, 1, n_steps=4)
    snap = obs.snapshot()
    # 2 slots x 1 block x 4 steps; the inactive slot's one visible key keeps its block live
    assert snap["counters"]["decode.blocks_swept"] == 8
    assert snap["counters"]["decode.blocks_live"] == 8
    assert snap["gauges"]["decode.live_block_share"] == 1.0

    # the same round cut into 2-page blocks, as a wider pool row would cut it
    monkeypatch.setattr(serve_mod, "block_pages", lambda *a: 2)
    eng._count_blocks(lengths, active, 8, 1, n_steps=1, n_rows=3)
    snap = obs.snapshot()
    # 4 blocks a slot; rows see 41..43 keys -> blocks 0-2 live; inactive: 1..3 -> block 0
    assert snap["counters"]["decode.blocks_swept"] == 8 + 8
    assert snap["counters"]["decode.blocks_live"] == 8 + 3 + 1
    assert snap["gauges"]["decode.live_block_share"] == 0.5
    assert "decode_blocks_live 12" in obs.metrics.to_prometheus()  # the .prom beside a dump


# ---------------------------------------------------------------------------
# PR 46: the readers of a family with kinds of attention layer
# ---------------------------------------------------------------------------

KINDS_TEXT = """
  %attn_global.10 = (f32[8,4]{1,0:T(8,128)S(1)}, f32[8,1]{1,0}) custom-call(%q, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/attn/attn_global/pallas_call"}
  %pallas_call.38 = f32[8,4]{1,0:T(8,128)S(1)} get-tuple-element(%attn_global.10), index=0, metadata={op_name="jit(f)/while/body/attn/attn_global/pallas_call"}
  %bitcast.7 = f32[2,4,4]{2,1,0} bitcast(%pallas_call.38)
  %multiply_reduce_fusion.19 = f32[4]{0} fusion(%bitcast.7), kind=kLoop, calls=%fused.1, metadata={op_name="jit(f)/while/body/attn/attn_global/reduce_sum"}
  %divide_convert_fusion.7 = bf16[4]{0} fusion(%multiply_reduce_fusion.19), kind=kLoop, calls=%fused.2, metadata={op_name="jit(f)/while/body/attn/attn_global/convert_element_type"}
  %fusion.9 = bf16[4]{0} fusion(%divide_convert_fusion.7, %w), kind=kLoop, calls=%fused.3, metadata={op_name="jit(f)/while/body/attn/attn_global/attn_gate/mul"}
  %fusion.10 = f32[4]{0} fusion(%pallas_call.38), kind=kLoop, calls=%fused.4, metadata={op_name="jit(f)/while/body/mlp/moe_route/add"}
  %attn_window.40 = bf16[8,4]{1,0} custom-call(%q, %pool), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/attn/attn_window/pallas_call"}
  %fusion.11 = bf16[4]{0} fusion(%attn_window.40), kind=kLoop, calls=%fused.5, metadata={op_name="jit(f)/while/body/attn/attn_window/mul"}
"""


def test_a_split_kernels_merge_is_found_after_it_and_nothing_else_is():
    """serve_kinds_scopes.combine_of: from a kernel that gives float32
    partials, along its users while float32 flows, inside its own scope, up to
    the cast back; a kernel that finalizes in itself (the stream's type) has
    none; an op of another scope that reads the partials is not the merge."""
    kinds = _reader("serve_kinds_scopes.py")
    scope_of = {"attn_global.10": "attn_global", "pallas_call.38": "attn_global", "multiply_reduce_fusion.19": "attn_global",
                "divide_convert_fusion.7": "attn_global", "fusion.9": "attn_gate", "fusion.10": "moe_route",
                "attn_window.40": "attn_window", "fusion.11": "attn_window"}
    got = kinds.combine_of(KINDS_TEXT, {"attn_global.10": "attn_global", "attn_window.40": "attn_window"}, scope_of)
    assert got == {k: "attn_global" for k in ("pallas_call.38", "bitcast.7", "multiply_reduce_fusion.19", "divide_convert_fusion.7")}


def test_the_trace_coverage_line_says_where_the_trace_has_no_ops():
    kinds = _reader("serve_kinds_scopes.py")
    run = _run_dict("serve")
    ms = 1_000_000
    mods = [(0, 40 * ms, "jit__serve_decode_chunk(1)"), (50 * ms, 90 * ms, "jit__serve_prefill_chunk(2)")]
    by_prog = {"decode": [[0, 0, 10 * ms], [1, 30 * ms, 10 * ms]], "prefill": [[2, 50 * ms, 40 * ms]], "other": [[3, 95 * ms, ms]]}
    kinds._coverage(run, {"lo": 0, "hi": 100 * ms}, {"name": "/device:TPU:0"}, mods, by_prog, {"prefill": "_serve_prefill_chunk", "decode": "_serve_decode_chunk"})
    (line,) = run["logs"]
    assert "4 op events" in line and "decode 1 (40 ms; ops 20 ms" in line and "prefill 1 (40 ms" in line
    assert "4 stretches over 2 ms with no op, 39 ms in all" in line and "20.0@10" in line
