"""metrics/prefill_attention.py on a small hand-made ring and trace
(fixtures/prefill_attention_small.json, the arithmetic in its `expected`): which
device events are the prefill program's template calls, which prefill calls
are the traced extension's, and what a program without the scope reports.
JAX-free:

    python -m pytest benchmarks/tests -q
"""

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
reader = _load("metrics", "prefill_attention.py")
with open(os.path.join(HERE, "fixtures", "prefill_attention_small.json")) as f:
    FX = json.load(f)
WANT = FX["expected"]


def run_record(events, trace=True, log=None):
    """A run record whose recorder ring is `events` (None: no live recorder)."""
    import types

    summary = dict(FX["summary"], trace=FX["trace"])
    summary["devices"] = [{"ops": [tuple(o) for o in d["ops"]]} for d in summary["devices"]]
    ring = types.SimpleNamespace(recorder_events=lambda run: events)
    return {"kind": "serve", "spans": [tuple(s) for s in FX["window_spans"]],
            "trace_summary": summary if trace else None,
            "load": lambda fn: reduce if fn == "reduce.py" else ring,
            "log": (log if log is not None else []).append}


def test_only_the_custom_calls_named_prefill_attn_are_the_kernel():
    """Decode's `closed_call`, the write's `kv_write` and an XLA fusion that
    carries the scope's name are not."""
    which = reduce.matching(FX["trace"], reader.NAME, reader.INFO)
    assert sorted(FX["trace"]["names"][i] for i in which) == ["prefill_attn.3", "prefill_attn.41"]
    summary = dict(FX["summary"], trace=FX["trace"])
    assert reduce.kernel_time(summary, FX["trace"], reader.NAME, reader.INFO) == (WANT["kernel_ns"], WANT["kernel_calls"])


def test_the_traced_calls_start_after_the_windows_last_prefill_span():
    traced = reader.traced_chunk_args(FX["events"], [s for n, s, _ in FX["window_spans"] if n == reader.SPAN])
    assert [a["call"] for a in traced] == [7, 9]
    assert sum(a["tokens"] for a in traced) == WANT["traced_tokens"]


def test_read_gives_the_hand_worked_metric():
    log = []
    got = reader.read(run_record(FX["events"], log=log))
    assert got == {"prefill_attention_ms_per_token": pytest.approx(WANT["ms_per_token"], rel=1e-12)}
    assert len(log) == 1 and "3 events named prefill_attn.<n>" in log[0] and "50 prompt tokens in 2 prefill calls" in log[0]
    assert reader.read(run_record(FX["events"], trace=False)) is None  # an untraced run


def test_a_program_without_the_scope_reports_nothing():
    """The parent of PR 54: no event of the name (run.py drops a None). And a
    training cell, and a run with no recorder."""
    run = run_record(FX["events"])
    run["trace_summary"]["trace"] = dict(FX["trace"], names=[n.replace("prefill_attn", "fusion") for n in FX["trace"]["names"]])
    assert reader.read(run) is None
    assert reader.read({"kind": "train", "spans": []}) is None
    assert reader.read(run_record(None)) is None
