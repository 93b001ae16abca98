"""metrics/engine_device_calls.py on a small hand-made trace
(fixtures/engine_calls_small.json, the arithmetic in its .expected.json): the
join of marks, executions and spans; the device plane moved onto the host
plane's clock; each refusal; the idle time split by overlap where the midpoint
rule names another owner; both sums. JAX-free:

    python -m pytest benchmarks/tests -q
"""

import copy
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*rel):
    path = os.path.join(HERE, *rel)
    spec = importlib.util.spec_from_file_location("t_" + rel[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


reduce = _load("reduce.py")
reader = _load("metrics", "engine_device_calls.py")
with open(os.path.join(HERE, "fixtures", "engine_calls_small.json")) as f:
    FIXTURE = json.load(f)
with open(os.path.join(HERE, "fixtures", "engine_calls_small.expected.json")) as f:
    WANT = json.load(f)
PLANE = "/device:TPU:0"
EARLY = 1_000_000  # the fixture's device plane sits this many ns early against its host plane


def summarize(fx, log=None):
    lines = [] if log is None else log
    out = reader.summarize(reduce, [tuple(m) for m in fx["marks"]], [tuple(m) for m in fx["modules"]],
                           {k: [tuple(iv) for iv in v] for k, v in fx["leaf_busy"].items()},
                           [tuple(e) for e in fx["events"]], fx["lo"], fx["hi"], lines.append)
    return out


def changed(**over):
    fx = copy.deepcopy(FIXTURE)
    fx.update(over)
    return fx


def test_the_join_gives_the_hand_worked_metrics_and_says_what_it_joined():
    log = []
    got = summarize(FIXTURE, log)
    want = {k: v for k, v in WANT["metrics"].items() if k != "host.offcpu_share"}
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-9), k
    joined, idle, table = log
    assert "6 engine.dispatch marks (calls 6..11) joined to 6 executions" in joined
    assert "1 execution(s) from before the profiler dropped, 0 dispatch(es) cut" in joined
    assert "2 prefill + 2 decode calls" in joined and "{'jit_convert_element_type': 1}" in joined
    assert "the device plane moved +1000 us onto the host plane's clock" in joined and "+-100 us" in joined
    assert "launch 1.4" in idle and "starved 5.6" in idle and "inside executions 0.1" in idle
    assert "prefill rows 1 of 4 bucket 2: 1 x 0.400" in table and "decode steps 2 bucket 4: 1 x 0.600" in table


def test_both_sums_close_and_the_parts_are_the_hand_worked_nanoseconds():
    """launch + starved + inside executions = what the leaf ops say is idle; the
    spans' shares = starved: each side computed its own way (interval algebra on
    one, the sweep over the spans on the other)."""
    fx = FIXTURE
    marks = [tuple(m) for m in fx["marks"]]
    events = [tuple(e) for e in fx["events"]]
    spans_by_call = {e[7]["call"]: e for e in events if e[1] in reader.SPAN_PROGRAMS and "call" in (e[7] or {})}
    offset, _ = reader.clock_offset_ns(marks, spans_by_call)
    assert offset == pytest.approx(500_000)  # call 6's mark is 40 us late: the median does not move
    mods = [tuple(m[1:]) for m in fx["modules"]]
    landed = {7: 2_300_000, 8: 4_000_000, 10: 7_600_000}  # first token of call 7; commits of calls 8 and 10
    pairs, head, shift, half = reader.join(marks, [m for m in mods if reader.is_engine_program(m[0])],
                                           spans_by_call, landed)
    assert head == 1 and [c for c, _, _ in pairs] == [6, 7, 8, 9, 10, 11] and all(ex for _, _, ex in pairs)
    assert {"shift": shift, "half_width": half} == WANT["plane_shift_ns"]  # [+900, +1100] us: the midpoint
    mods = [(n, s + shift, d) for n, s, d in mods]
    pairs = [(c, m, (ex[0], ex[1] + shift, ex[2])) for c, m, ex in pairs]
    leaf = [(a + shift, b + shift) for a, b in fx["leaf_busy"][PLANE]]
    spans = [(int(e[4] * 1e9 + offset), e[10], int((e[4] + e[5]) * 1e9 + offset), e[1]) for e in events]
    idle = reader.idle_split(reduce, pairs, mods, leaf, spans, fx["lo"], fx["hi"])
    for k, v in WANT["idle_ns"].items():
        assert idle[k] == pytest.approx(v, abs=2), k
    assert idle["launch"] + idle["starved"] + idle["residual"] == pytest.approx(idle["idle_leaf"], abs=2)
    assert sum(idle["by_span"].values()) == pytest.approx(idle["starved"], abs=40)  # a nanosecond a boundary


def test_split_by_overlap_where_the_midpoint_rule_names_another_owner():
    """The case PR 53 exists for: the gap [3900, 5700) runs decode.device_wait ->
    decode.host_post -> engine.round -> engine.expire -> ... -> prefill.chunk and
    its launch; `reduce.attribute_gaps` gives all 1,800 us to decode.host_post
    (its midpoint), and with the gap after call 10 charges that span 3,850 us for
    the 2,900 it lasts. By overlap it is charged what of it the device idled under."""
    events = [tuple(e) for e in FIXTURE["events"]]
    spans_ns = [(e[1], int(e[4] * 1e9) + 500_000, int(e[5] * 1e9)) for e in events]
    ops = [[0, a + EARLY, b - a] for a, b in FIXTURE["leaf_busy"][PLANE]]
    old = reduce.attribute_gaps(ops, spans_ns, FIXTURE["lo"], FIXTURE["hi"])
    assert old["decode.host_post"] == pytest.approx(WANT["midpoint_rule_host_post_ns"], abs=40)
    lasted = sum(int(e[5] * 1e9) for e in events if e[1] == "decode.host_post")
    assert lasted == pytest.approx(WANT["host_post_spans_ns"], abs=4) and old["decode.host_post"] > lasted
    got = summarize(FIXTURE)
    window = FIXTURE["hi"] - FIXTURE["lo"]
    assert got["starved.host_post_share"] * window / 100 <= lasted + 4
    assert got["starved.host_post_share"] == pytest.approx(29.0)
    # an instant goes to the INNERMOST span open: a child that starts with its parent wins the tie
    by = reader.split_by_innermost([(0, 100)], [(0, 1, 100, "parent"), (0, 2, 40, "child"), (60, 3, 80, "late")])
    assert by == {"child": 40, "parent": 40, "late": 20}
    # spans that do not nest (a wait that outlasts its round): the latest started still wins, none is counted twice
    by = reader.split_by_innermost([(0, 50), (70, 100)], [(0, 1, 90, "wait"), (30, 2, 200, "next round")])
    assert by == {"wait": 30, "next round": 20 + 30}
    assert reader.split_by_innermost([(5, 9)], []) == {None: 4}


def _refusal(fx):
    with pytest.raises(reader.Refusal) as e:
        summarize(fx)
    return str(e.value)


def test_each_refusal():
    marks, modules, events = FIXTURE["marks"], FIXTURE["modules"], FIXTURE["events"]
    assert "no engine.dispatch mark" in _refusal(changed(marks=[]))
    assert "not consecutive" in _refusal(changed(marks=marks[:2] + marks[3:]))
    no_calls = [e[:7] + [{k: v for k, v in (e[7] or {}).items() if k != "call"}] + e[8:] for e in events]
    assert "no span carries the `call`" in _refusal(changed(events=no_calls))
    # an execution missing INSIDE the trace (call 8's): the counts agree again (the one from before the profiler
    # makes up for it) and the calls pair with the wrong programs under every head tried
    assert "call 7 is a prefill.chunk on the host and ran jit__serve_decode_chunk(2)" in _refusal(
        changed(modules=modules[:3] + modules[4:]))
    # the same count, two executions swapped in kind: call 7 is a prefill on the host
    swapped = copy.deepcopy(modules)
    swapped[2][1], swapped[3][1] = swapped[3][1], swapped[2][1]
    assert "call 7 is a prefill.chunk on the host and ran jit__serve_decode_chunk(2)" in _refusal(changed(modules=swapped))
    # the right programs in the right order, and NO shift of the device plane puts every execution after its
    # mark and before its landing: call 8's execution starts 50 us before any shift the landings allow
    early = copy.deepcopy(modules)
    early[3][2] -= 500_000
    assert "no shift of the device plane puts every execution after its mark (+1150.0 us at the least) and before " \
           "its tokens' landing (+1100.0 us at the most)" in _refusal(changed(modules=early))
    # nothing landed in the trace: the device plane cannot be anchored
    no_landing = [e for e in events if e[1] not in ("decode.host_post", "prefill.first_token")]
    assert "cannot be anchored" in _refusal(changed(events=no_landing))


def test_executions_the_trace_cut_at_either_end_are_trimmed_by_count_not_by_the_clock():
    """One execution more than marks: the surplus is at the head (a dispatch made before the
    profiler ran), whatever the two planes' clocks say of it. A program no dispatch was counted
    for INSIDE the trace makes one execution too many as well: then the programs stop matching
    their spans' kinds, or no shift fits, and the reader refuses."""
    modules = FIXTURE["modules"]
    late = copy.deepcopy(modules)
    late[0][2] = 200_000  # the execution from before the profiler: as stored it even starts AFTER the first mark
    assert summarize(changed(modules=late))["prefill.call_device_ms_p50"] == pytest.approx(0.7)
    extra = modules + [[PLANE, "jit__serve_decode_logits(4)", 7_000_000, 100_000]]
    assert "ran jit__serve_" in _refusal(changed(modules=extra))


def test_a_dispatch_the_traces_end_cut_is_trimmed_and_its_launch_runs_to_the_windows_end():
    got, log = summarize(changed(modules=FIXTURE["modules"][:-1]), log := []), log
    assert "1 execution(s) from before the profiler dropped, 1 dispatch(es) cut by the trace's end" in log[0]
    assert got["engine.launch_idle_share"] == pytest.approx(13.5)  # [10800, 11000) either way


def test_sums_that_do_not_close_leave_the_split_out_and_say_so():
    """Leaf ops outside every module execution (a trace whose module line lost
    events): the parts add up to more idle time than the leaf ops left."""
    fx = changed(leaf_busy={PLANE: FIXTURE["leaf_busy"][PLANE] + [[7_000_000, 7_400_000]]})
    log = []
    got = summarize(fx, log)
    assert any("does NOT close" in line for line in log)
    assert got["prefill.call_device_ms_p50"] == pytest.approx(0.7)  # the join stands
    assert not any(k.startswith(("starved.", "engine.launch_idle", "engine.host_starved")) for k in got)


def test_a_cell_that_cannot_have_a_metric_leaves_it_out_and_says_why():
    events = copy.deepcopy(FIXTURE["events"])
    for e in events:
        if e[1] == "decode.dispatch":  # a family with two decode kernels: no census on the span
            e[7] = {k: v for k, v in e[7].items() if not k.startswith("blocks_")}
    log = []
    got = summarize(changed(events=events), log)
    assert got["decode.live_block_share"] is None
    assert any("decode.live_block_share left out" in line for line in log)
    assert got["prefill.call_device_ms_p50"] == pytest.approx(0.7)  # the rest of the join stands


def _run(**over):
    said = []
    run = {"kind": "serve", "spans": [tuple(s) for s in FIXTURE["window_spans"]], "window_s": FIXTURE["window_s"],
           "trace_summary": None, "log": said.append, "load": lambda fn: _load(*fn.split("/"))}
    run.update(over)
    return run, said


def test_read_reports_the_cpu_share_without_a_trace_and_nothing_where_nothing_is_said(monkeypatch):
    """`read` finds the window's events in the newest live recorder (obs.live(),
    the seam engine_dispatch.py uses); an untraced serve run reports
    host.offcpu_share alone; a program whose spans say no `cpu_s` (the parent),
    a training run and a run with no recorder report nothing and do not raise."""
    sys.path.insert(0, os.path.dirname(HERE))
    from midgpt_tpu import obs as obs_mod

    recorder = obs_mod.Observability(capacity=64)
    for e in FIXTURE["events"]:
        recorder.tracer.complete(e[1], e[2], e[3], e[4], e[5], e[7])
    run, said = _run()
    assert reader.read(run) == {"host.offcpu_share": pytest.approx(WANT["metrics"]["host.offcpu_share"])}
    assert reader.read(_run(kind="train")[0]) is None and reader.read(_run(spans=[])[0]) is None

    parent = obs_mod.Observability(capacity=64)  # the parent's spans: no cpu_s, no call
    for e in FIXTURE["events"]:
        parent.tracer.complete(e[1], e[2], e[3], e[4], e[5],
                               {k: v for k, v in (e[7] or {}).items() if k not in ("cpu_s", "call")} or None)
    run, said = _run()
    assert reader.read(run) == {"host.offcpu_share": None}
    assert any("say no cpu_s" in line for line in said)

    monkeypatch.setattr(obs_mod, "_LIVE", type(obs_mod._LIVE)(maxlen=4))
    run, said = _run()
    assert reader.read(run) is None and any("no live recorder" in line for line in said)


def _ticking_window(n_spans, every):
    """A window of `n_spans` host_post spans of 1 ms on a clock that ticks at 10 ms: one span in
    `every` was running when a tick fell (cpu_s 0.01), the others read 0."""
    from midgpt_tpu import obs as obs_mod

    recorder = obs_mod.Observability(capacity=n_spans + 8)
    spans = []
    for k in range(n_spans):
        recorder.tracer.complete("decode.host_post", "round", "engine", 0.01 * k, 0.001,
                                 {"cpu_s": 0.01 if k % every == 0 else 0.0})
        spans.append(("decode.host_post", 0.01 * k, 0.001))
    return _run(spans=spans, window_s=0.01 * n_spans)


def test_a_cpu_clock_that_ticks_is_counted_in_ticks_and_too_few_of_them_are_noise():
    """The chip machines' kernel counts a thread's CPU time in jiffies of 10 ms (my chip runs,
    PR 53): a phase reads 0 or a multiple of 0.01, and the window's sum is a count of ticks. The
    share is reported from MIN_TICKS of them, with what it is good to in the log; from fewer it
    is left out and the line says so."""
    sys.path.insert(0, os.path.dirname(HERE))
    run, said = _ticking_window(3000, 10)  # 300 ticks = 3.0 s on the CPU for 3.0 s of spans
    assert reader.read(run) == {"host.offcpu_share": pytest.approx(0.0, abs=1e-9)}
    assert any("ticks at 10 ms here: 300 ticks" in line and "+-5.8 points" in line for line in said)
    run, said = _ticking_window(1000, 12)  # 84 ticks
    assert reader.read(run) == {"host.offcpu_share": None}
    assert any("84 ticks" in line and "left out as noise" in line for line in said)
