"""The per-layer table's budget (benchmarks/README.md, "The table's budget"):
`BENCHMARK.json` may hold CAP per-layer entries and no more, and a name that a
`benchmark` PR retired is neither declared nor still computed by a reader (a
traced run would log it as "computed but not declared"). The files are read as
text; the readers that lost keys are also run on made-up records. JAX-free:

    python -m pytest benchmarks/tests -q
"""

import importlib.util
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAP = 128  # the contract's: 1 to 128 metrics of single layers
# retired name -> the entries that keep its reading (PERF.md section 3)
RETIRED = {
    "data.batch_ms_p50": ["train.feed_ms_p50"],
    "fsdp.collective_ms_per_step": ["fsdp.exposed_collective_ms_per_step", "fsdp.grad_reduce_exposed_ms_per_step"],
    "flash_attention_tile_share": ["flash_attention_roofline"],  # and make_runtime's `flash attention:` line
    "serve.ttft_ms_mean": ["req.prefill_ms_mean", "req.queue_ms_mean", "serve.ttft_ms_p50"],
    "engine.round_host_ms_p50.serve": ["decode.dispatch_ms_p50", "decode.host_post_ms_p50", "engine.round_self_ms_p50"],
    "prefill.chunk_ms_p50": ["prefill.assemble_ms_p50", "starved.dispatch_share", "prefill.call_device_ms_p50"],
    "prefill.one_row_call_device_ms_p50": ["prefill.call_device_ms_p50", "prefill.rows_per_call_mean",
                                           "prefill.device_us_per_token"],
    "engine.launch_ms_p50": ["engine.launch_idle_share"],
}


def _text(*rel):
    with open(os.path.join(HERE, *rel)) as f:
        return f.read()


BENCH = json.loads(_text("..", "BENCHMARK.json"))
READERS = {fn: _text("metrics", fn) for fn in sorted(os.listdir(os.path.join(HERE, "metrics"))) if fn.endswith(".py")}
FIXTURES = {fn: _text("fixtures", fn) for fn in sorted(os.listdir(os.path.join(HERE, "fixtures")))}


def _reader(fn):
    spec = importlib.util.spec_from_file_location("table_" + fn[:-3], os.path.join(HERE, "metrics", fn))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_table_is_within_the_cap_and_names_each_entry_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) <= CAP, f"{len(names)} per-layer entries: the cap is {CAP}; retire one first (README, the table's budget)"
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    assert all(set(m.get("workloads", cells)) <= cells for m in BENCH["per_layer"])


@pytest.mark.parametrize("name", sorted(RETIRED))
def test_a_retired_name_is_neither_declared_nor_a_key_of_a_reader(name):
    declared = {m["name"] for m in BENCH["per_layer"]}
    assert name not in declared
    assert set(RETIRED[name]) <= declared, "what keeps the reading has to stay declared"
    key = re.compile(r"""["']""" + re.escape(name) + r"""["']""")  # a docstring may name it in backticks
    still = [fn for fn, text in READERS.items() if key.search(text)]
    assert not still, f"{name} is retired and {still} still compute(s) it"
    expecting = [fn for fn, text in FIXTURES.items() if key.search(text)]
    assert not expecting, f"{name} is retired and fixtures {expecting} still expect it"


def test_the_readers_that_lost_keys_report_what_stays_and_nothing_else():
    pct = lambda xs, q: sorted(xs)[min(len(xs) - 1, int(len(xs) * q / 100))]
    train = {"kind": "train", "samples": {"step_s": [0.2, 0.1, 0.4]}, "chips": 4, "counters": {"traced_steps": 2},
             "spans": [("bench.data", 0.0, 0.001), ("bench.put", 0.001, 0.002)],
             "trace_summary": {"collective_ns_mean": 8e6, "exposed_collective_ns_mean": 2e6}}
    assert _reader("train_loop.py").read(train) == {"train.step_ms_max": pytest.approx(400.0),
                                                    "train.step_ms_p50": pytest.approx(200.0)}
    assert _reader("fsdp_collectives.py").read(train) == {"fsdp.exposed_collective_ms_per_step": pytest.approx(1.0)}
    assert _reader("fsdp_collectives.py").read({**train, "chips": 1}) is None
    assert _reader("flash_tile_share.py").read(train) is None
    serve = {"kind": "serve", "percentile": pct, "spans": [("prefill.chunk", 0.0, 0.001), ("decode.dispatch", 0.0, 0.002),
                                                           ("decode.host_post", 0.002, 0.001)],
             "samples": {"tpot_s": [0.01, 0.02, 0.03], "ttft_s": [0.1, 0.2, 0.6], "occupancy": [2, 4]},
             "counters": {"max_slots": 4, "prefilled_tokens": 30, "output_tokens": 10}}
    assert set(_reader("serve_model_step.py").read(serve)) == {"serve.tpot_ms_p50", "serve.tpot_ms_p90", "serve.ttft_ms_p50"}
    assert _reader("engine.py").read(serve) == {"engine.occupancy": pytest.approx(75.0),
                                                "engine.prefill_token_share": pytest.approx(75.0)}
    assert _reader("serve_model_step.py").read(train) is None and _reader("engine.py").read(train) is None


def test_the_family_kernels_reader_reports_nothing_for_a_configuration_without_a_layer_pattern():
    """It raised `KeyError: 'layer_pattern'` there (arithmetic_mimo_v2.layer_kinds) and run.py logged the
    traceback in every traced run of three cells; it asks nothing of the run before it has looked."""
    def load(fn):
        raise AssertionError(f"asked for {fn} with no layer_pattern to read by")

    assert _reader("serve_family_kernels.py").read({"kind": "serve", "model": {"n_layer": 4}, "load": load}) is None
