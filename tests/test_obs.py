"""Unified observability layer (midgpt_tpu/obs/): fake-clock tracer and
metrics units, the Chrome-trace export contract that tools/trace_view.py
and Perfetto consume, round-decomposition arithmetic, the engine-level
span catalog on a CPU mesh, the obs-on == obs-off greedy bit-parity
pin, and the chaos-path flight-recorder dump.

Pool geometry note: engine tests use num_pages=33 — disjoint from the
25-page pristine recompile-pin geometry and the 29/31-page tp/warm-pin
geometries (tests/test_recompile_pins.py); the obs-toggle compile pin
itself lives there with the other pins.
"""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import midgpt_tpu.obs as obs_mod
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.obs import (
    NULL_TRACER,
    Observability,
    Tracer,
    dump_flight_recorder,
    flight_recorder,
)
from midgpt_tpu.obs.metrics import Histogram, MetricsRegistry
from midgpt_tpu.obs.trace import _NULL_SPAN
from midgpt_tpu.robustness.chaos_serve import run_serving_chaos
from midgpt_tpu.sampling.serve import ServeEngine

_TOOLS = pathlib.Path(__file__).resolve().parents[1] / "tools"


def _load_trace_view():
    spec = importlib.util.spec_from_file_location(
        "trace_view", _TOOLS / "trace_view.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class FakeClock:
    """Deterministic injected clock: each call returns the current time
    then advances by `step` — so every clock read is visible in the
    expected timestamps below."""

    def __init__(self, start=100.0, step=1.0):
        self.t = start
        self.step = step

    def __call__(self):
        t = self.t
        self.t += self.step
        return t


# ---------------------------------------------------------------------------
# Tracer units (JAX-free, fake clock)
# ---------------------------------------------------------------------------


def test_span_nesting_records_both_levels_with_real_durations():
    clock = FakeClock(start=100.0, step=1.0)
    tr = Tracer(capacity=8, clock=clock)  # _t_base = 100.0
    with tr.span("outer", "phase", "engine"):  # t0 = 101
        with tr.span("inner", "phase", "engine"):  # t0 = 102
            pass  # inner exit reads 103
    # outer exit reads 104
    evs = tr.events()
    assert [(e[1], e[4], e[5]) for e in evs] == [
        ("inner", 102.0, 1.0),
        ("outer", 101.0, 3.0),  # closes after inner: completion order
    ]
    assert all(e[0] == "X" and e[2] == "phase" and e[3] == "engine" for e in evs)


def test_export_rebases_to_birth_and_assigns_tid_lanes():
    clock = FakeClock(start=50.0, step=1.0)
    tr = Tracer(capacity=8, clock=clock)  # birth at t=50
    tr.complete("round", "round", "engine", 52.0, 0.5)
    tr.instant("rollback", "fault", "train")
    tr.async_begin("request", "uid-7", "lifecycle", "server")
    tr.async_end("request", "uid-7", "lifecycle", "server")
    out = tr.export()
    by_name = {e["name"]: e for e in out if e["ph"] != "M"}
    # complete: ts/dur microseconds rebased to the tracer's birth
    assert by_name["round"]["ph"] == "X"
    assert by_name["round"]["ts"] == pytest.approx(2e6)
    assert by_name["round"]["dur"] == pytest.approx(0.5e6)
    # instant: thread-scoped
    assert by_name["rollback"]["ph"] == "i" and by_name["rollback"]["s"] == "t"
    # async pair shares an id, and b comes before e
    pair = [e for e in out if e.get("id") == "uid-7"]
    assert [e["ph"] for e in pair] == ["b", "e"]
    # tid strings became distinct integer lanes with thread_name metadata
    lanes = {e["args"]["name"]: e["tid"] for e in out if e["ph"] == "M"}
    assert set(lanes) == {"engine", "train", "server"}
    assert len(set(lanes.values())) == 3
    assert by_name["round"]["tid"] == lanes["engine"]
    assert by_name["rollback"]["tid"] == lanes["train"]


def test_ring_keeps_the_tail_and_counts_drops():
    tr = Tracer(capacity=4, clock=FakeClock())
    for i in range(6):
        tr.instant(f"i{i}")
    assert len(tr) == 4
    assert tr.dropped == 2
    # flight-recorder semantics: the OLDEST events fell off
    assert [e[1] for e in tr.events()] == ["i2", "i3", "i4", "i5"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_dump_is_loadable_by_trace_view(tmp_path):
    tv = _load_trace_view()
    tr = Tracer(capacity=8, clock=FakeClock())
    with tr.span("engine.round", "round", "engine"):
        pass
    path = tr.dump(str(tmp_path / "flight_recorder.json"))
    evs = tv.load_trace(tv.find_trace(str(tmp_path)))
    assert any(e["name"] == "engine.round" for e in evs)
    # raw json is the Chrome container
    with open(path, encoding="utf-8") as fh:
        assert set(json.load(fh)) == {"traceEvents"}


def test_trace_view_rejects_non_trace_json(tmp_path):
    tv = _load_trace_view()
    bad = tmp_path / "not_a_trace.json"
    bad.write_text(json.dumps({"hello": 1}))
    with pytest.raises(ValueError):
        tv.load_trace(str(bad))


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x", "y", "z") as s:
        assert s is _NULL_SPAN  # one shared handle, no allocation
    NULL_TRACER.complete("a", "b", "c", 0.0, 1.0)
    NULL_TRACER.instant("a")
    NULL_TRACER.async_begin("a", "id")
    NULL_TRACER.async_end("a", "id")
    assert len(NULL_TRACER) == 0
    assert NULL_TRACER.events() == [] and NULL_TRACER.export() == []
    assert NULL_TRACER.dropped == 0


# ---------------------------------------------------------------------------
# Metrics units
# ---------------------------------------------------------------------------


def test_histogram_nearest_rank_percentiles():
    h = Histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["n"] == 100
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == 50.0  # nearest-rank: ceil(0.5*100)-1 -> sorted[49]
    assert s["p95"] == 95.0
    assert s["max"] == 100.0


def test_histogram_empty_summary_is_zeros():
    assert Histogram("empty").summary() == {
        "n": 0, "mean": 0.0, "p50": 0.0, "p95": 0.0, "max": 0.0,
    }


def test_histogram_reservoir_is_bounded_but_counts_exact():
    h = Histogram("lat", maxlen=8)
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["n"] == 100  # exact count survives the bounded reservoir
    assert s["max"] == 99.0
    assert s["p50"] >= 92.0  # percentiles come from the recent tail


def test_registry_create_or_get_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("rounds", "help text")
    c.inc()
    c.inc(2.0)
    assert reg.counter("rounds") is c  # create-or-get, no reset
    reg.gauge("backlog").set(7)
    reg.histogram("lat").observe(0.25)
    snap = reg.snapshot()
    assert snap["counters"] == {"rounds": 3.0}
    assert snap["gauges"] == {"backlog": 7.0}
    assert snap["histograms"]["lat"]["n"] == 1
    json.dumps(snap)  # the unified stats payload must stay serializable


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("rounds_decomposed", "rounds seen").inc(3)
    reg.gauge("backlog.pages").set(2)  # dot must sanitize to underscore
    reg.histogram("round_dispatch_s").observe(0.002)
    text = reg.to_prometheus()
    assert "# TYPE rounds_decomposed counter\nrounds_decomposed 3" in text
    assert "# TYPE backlog_pages gauge\nbacklog_pages 2" in text
    assert '# TYPE round_dispatch_s summary' in text
    assert 'round_dispatch_s{quantile="0.5"} 0.002' in text
    assert "round_dispatch_s_count 1" in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# Observability bundle: round decomposition + dump
# ---------------------------------------------------------------------------


def test_record_round_decomposition_arithmetic():
    obs = Observability(clock=FakeClock())
    # four boundary readings: dispatch 2 ms, device wait 8 ms, post 1 ms
    obs.record_round("decode", "engine", 10.000, 10.002, 10.010, 10.011)
    d = obs.round_decomp()
    assert d["rounds"] == 1
    assert d["dispatch"]["mean_ms"] == pytest.approx(2.0)
    assert d["device_wait"]["p50_ms"] == pytest.approx(8.0)
    assert d["host_post"]["max_ms"] == pytest.approx(1.0)
    # the three phase spans landed in the ring with the EXPLICIT boundary
    # timestamps — record_round must not read the clock again
    evs = obs.tracer.events()
    assert [(e[1], e[4], e[5]) for e in evs] == [
        ("decode.dispatch", 10.000, pytest.approx(0.002)),
        ("decode.device_wait", 10.002, pytest.approx(0.008)),
        ("decode.host_post", 10.010, pytest.approx(0.001)),
    ]
    assert all(e[2] == "round" and e[3] == "engine" for e in evs)
    snap = obs.snapshot()
    assert snap["enabled"] is True
    assert snap["spans"] == 3 and snap["spans_dropped"] == 0
    assert snap["round_decomp"]["rounds"] == 1


def test_observability_dump_writes_trace_and_prom(tmp_path):
    tv = _load_trace_view()
    obs = Observability(clock=FakeClock())
    obs.record_round("decode", "engine", 1.0, 2.0, 3.0, 4.0)
    path = obs.dump(str(tmp_path))
    assert path == str(tmp_path / "flight_recorder.json")
    evs = tv.load_trace(path)
    assert {e["name"] for e in evs} >= {
        "decode.dispatch", "decode.device_wait", "decode.host_post",
    }
    prom = (tmp_path / "flight_recorder.prom").read_text()
    assert "round_dispatch_s_count 1" in prom


def test_global_flight_recorder_lazy_and_dump_none(tmp_path, monkeypatch):
    monkeypatch.setattr(obs_mod, "_FLIGHT", None)
    # never touched -> no file, no empty lie
    assert dump_flight_recorder(str(tmp_path)) is None
    assert not list(tmp_path.iterdir())
    fr = flight_recorder()
    assert flight_recorder() is fr  # singleton
    fr.tracer.instant("supervisor.rollback", "fault", "train")
    path = dump_flight_recorder(str(tmp_path))
    tv = _load_trace_view()
    assert any(
        e["name"] == "supervisor.rollback" for e in tv.load_trace(path)
    )


# ---------------------------------------------------------------------------
# Engine-level: span catalog, nesting, and the obs-toggle parity pin
# ---------------------------------------------------------------------------

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _trace(seed=0, n=4):
    rng = np.random.default_rng(seed)
    lens = rng.integers(4, 30, size=n)
    return (
        [rng.integers(1, CFG.vocab_size, size=int(l)).astype(np.int32)
         for l in lens],
        [int(b) for b in rng.integers(5, 14, size=n)],
    )


def _run(params, obs):
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=33,
        prefill_chunk=8, decode_chunk=8, temperature=0.0,
        cache_dtype=jnp.float32, obs=obs,
    )
    prompts, budgets = _trace()
    uids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    done = eng.run()
    return eng, [done[u].tokens.tolist() for u in uids]


def test_engine_emits_span_catalog_and_rounds_contain_decode(params):
    """A served trace carries the documented span catalog
    (docs/OBSERVABILITY.md) and every decode phase span is time-contained
    in an engine.round envelope — one shared clock, four boundary reads."""
    obs = Observability()
    eng, toks = _run(params, obs)
    assert all(len(t) > 0 for t in toks)
    evs = obs.tracer.events()  # (kind, name, cat, tid, t, dur, ident, args)
    names = {e[1] for e in evs}
    assert {
        "engine.round", "engine.expire", "engine.admit", "engine.prefill",
        "prefill.chunk", "prefill.first_token",
        "decode.dispatch", "decode.device_wait", "decode.host_post",
        "admitted", "finish",
    } <= names
    rounds = sorted(
        (e[4], e[4] + e[5]) for e in evs
        if e[0] == "X" and e[1] == "engine.round"
    )
    assert rounds
    phases = [
        (e[4], e[4] + e[5]) for e in evs
        if e[0] == "X" and e[1].startswith("decode.")
    ]
    assert phases
    for t0, t1 in phases:
        assert any(r0 <= t0 and t1 <= r1 for r0, r1 in rounds), (
            f"decode span [{t0}, {t1}] outside every engine.round envelope"
        )
    # unified stats schema: one decomposition per DECODE round (prefill-
    # only rounds get an engine.round envelope but no decode dispatch)
    st = eng.stats()["obs"]
    assert st["enabled"] is True
    decomp = st["round_decomp"]
    # three phase spans a round and, under the dispatch, its children
    # (assemble / put / enqueue: a greedy round splits no key)
    assert decomp["rounds"] == len(phases) // 6 > 0
    assert decomp["rounds"] == sum(e[1] == "decode.dispatch" for e in evs)
    assert decomp["rounds"] <= len(rounds)
    assert decomp["device_wait"]["n"] == decomp["rounds"]
    assert decomp["dispatch"]["p95_ms"] >= 0.0


def test_obs_toggle_preserves_greedy_token_streams(params):
    """The acceptance pin: wiring an Observability through the engine
    changes zero emitted tokens — instrumentation reads clocks and appends
    tuples, it never touches scheduling state or device buffers."""
    eng_off, base = _run(params, None)
    assert eng_off.stats()["obs"] == {"enabled": False}
    _, traced = _run(params, Observability())
    assert traced == base


def test_serving_chaos_leaves_loadable_dump(tmp_path):
    """Crash-path artifact: a chaos run with a trace_dir leaves a
    Chrome-trace flight recorder (plus .prom metrics) for the FAULT pass,
    fault instant included."""
    s = run_serving_chaos("kill_mid_decode@6", seed=0, trace_dir=str(tmp_path))
    assert s["mode"] == "serve"
    assert s["parity_ok"] == s["parity_checked"] > 0
    assert s["trace"] == str(tmp_path / "flight_recorder.json")
    tv = _load_trace_view()
    evs = tv.load_trace(tv.find_trace(str(tmp_path)))
    names = {e["name"] for e in evs}
    assert "fault.kill_mid_decode" in names
    assert "engine.round" in names
    assert (tmp_path / "flight_recorder.prom").exists()
