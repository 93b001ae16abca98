"""The window read in parts (reduce.window_parts, metrics/serve_window_parts.py)
on a made-up token log and request list. JAX-free:

    python -m pytest benchmarks/tests -q
"""

import importlib.util
import json
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*rel):
    path = os.path.join(HERE, *rel)
    spec = importlib.util.spec_from_file_location("t_" + rel[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


reduce = _load("reduce.py")
run_py = _load("run.py")
reader = _load("metrics", "serve_window_parts.py")

PARTS, W0, SECONDS = 8, 100.0, 40.0
CLIENTS, OUT, STEP, TTFT = 16, 41, 0.005, 0.030  # a request: 30 ms to its first token, 40 gaps of 5 ms


def traffic(stall_at=None, stall_s=0.0):
    """A closed loop of CLIENTS clients at even phases on the host's clock,
    through [W0 - 1, W0 + SECONDS + 1). A stall freezes every client for
    `stall_s` seconds at `stall_at`: whatever was due inside it comes at its end."""
    def shift(t):
        return t if stall_at is None or t < stall_at else max(t, stall_at + stall_s)

    tokens, requests = [], []
    for c in range(CLIENTS):
        t = W0 - 1.0 + c * 0.0137
        while t < W0 + SECONDS + 1.0:
            sub = t
            first = shift(sub + TTFT)
            stamps = [first]
            for _ in range(OUT - 1):
                stamps.append(shift(stamps[-1] + STEP))
            tokens.extend(stamps)
            requests.append((sub, first, stamps[-1], OUT))
            t = stamps[-1]
    tokens.sort()
    return {"w0": W0, "w1": W0 + SECONDS, "parts": PARTS,
            "token_times": [t for t in tokens if W0 <= t < W0 + SECONDS], "requests": requests}


def whole(window):
    """The end-to-end figures as serve_cell.py forms them: all the window."""
    w0, w1 = window["w0"], window["w1"]
    ttft = [f - s for s, f, _, _ in window["requests"] if w0 <= s and f < w1]
    tpot = [(l - f) / (n - 1) for _, f, l, n in window["requests"] if w0 <= l < w1]
    return (len(window["token_times"]) / (w1 - w0), 1e3 * statistics.fmean(ttft),
            1e3 * run_py.percentile(tpot, 90))


def medians(window):
    parts = reduce.window_parts(window, run_py.percentile)
    return tuple(statistics.median(parts[k]) for k in ("tokens_per_s", "ttft_ms_mean", "tpot_ms_p90"))


def test_a_stall_over_one_part_moves_no_median_and_moves_the_whole_window():
    calm, stalled = traffic(), traffic(stall_at=W0 + 17.2, stall_s=1.0)  # inside part 4 of 8
    for a, b in zip(medians(calm), medians(stalled)):
        assert abs(a - b) <= 2e-3 * a
    rate0, ttft0, _ = whole(calm)
    rate1, ttft1, _ = whole(stalled)
    assert rate1 < 0.98 * rate0  # a second is 2.5 % of the window
    assert ttft1 > 1.02 * ttft0  # and the requests that waited through it raise the mean
    parts = reduce.window_parts(stalled, run_py.percentile)
    assert min(parts["tokens_per_s"]) == parts["tokens_per_s"][3] < 0.85 * statistics.median(parts["tokens_per_s"])


def test_a_calm_window_reads_the_same_in_parts_and_whole():
    calm = traffic()
    for a, b in zip(medians(calm), whole(calm)):
        assert abs(a - b) <= 5e-3 * b


def test_a_part_with_no_finished_request_is_refused_not_skipped():
    window = traffic()
    a = W0 + 2 * SECONDS / PARTS
    window["requests"] = [r for r in window["requests"] if not a <= r[2] < a + SECONDS / PARTS]
    with pytest.raises(ValueError, match="part 3 of 8"):
        reduce.window_parts(window, run_py.percentile)


def test_the_reader_reports_the_three_medians_and_nothing_without_the_key():
    window = traffic()
    parts = reduce.window_parts(window, run_py.percentile)  # as serve_cell.py cuts them, once
    got = reader.read({"kind": "serve", "window_parts": parts})
    assert tuple(got[k] for k in ("serve.tokens_per_s_parts_p50", "serve.ttft_ms_mean_parts_p50",
                                  "serve.tpot_ms_p90_parts_p50")) == medians(window)
    assert reader.read({"kind": "serve", "window_parts": None}) is None  # serve_xl_chat: no `window_parts` in its file
    assert reader.read({"kind": "train"}) is None
    assert reader.read({"kind": "serve"}) is None  # the family cells hand no parts over


def test_the_traffic_files_and_benchmark_json_agree_on_who_reads_parts():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"] if m["name"].endswith("_parts_p50")}
    assert set(declared) == {"serve.tokens_per_s_parts_p50", "serve.ttft_ms_mean_parts_p50",
                             "serve.tpot_ms_p90_parts_p50"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in declared.values():
        for cell in m["workloads"]:
            (w,) = [w for w in bench["workloads"] if w["name"] == cell]
            with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
                spec = json.load(f)
            assert spec["kind"] == "serve" and spec.get("window_parts", 0) >= 2
            assert cell in e2e[m["moves"]].get("workloads", [cell])  # the cell reports what the metric moves


def test_the_cells_pool_is_sized_to_its_traffic_and_is_the_pool_tier1_pins():
    """tests/test_pages.py pins this cell's pool at 3,073 pages and may not be
    edited by a benchmark PR: 128 slots x 24 pages of 8 tokens + the sink page.
    The traffic fills at most 128 x 160 tokens of it: no page is reserved that
    the lengths could not reach but the 4 a slot between 160 and 192 tokens."""
    with open(os.path.join(HERE, "traffic", "sample_closed.json")) as f:
        spec = json.load(f)
    es = spec["engine"]
    pages_a_slot = -(-es["pool_tokens_per_slot"] // es["page_size"])
    assert es["max_slots"] * pages_a_slot + 1 == 3073
    assert spec["max_total"] <= es["pool_tokens_per_slot"]  # every slot at full length at once: no preemption
    live = spec["clients"] * -(-spec["max_total"] // es["page_size"])
    assert 0.8 < live / (es["max_slots"] * pages_a_slot) <= 1.0


def test_clients_start_together_where_the_traffic_file_says_so():
    loadgen = _load("loadgen.py")
    with open(os.path.join(HERE, "traffic", "sample_closed.json")) as f:
        spec = json.load(f)
    assert spec["stagger"] is False and spec["clients"] == spec["engine"]["max_slots"] == 128
    together = loadgen.Traffic(spec, 7, 50304).prime()
    assert [r.max_new_tokens for r in together] == [128] * 128  # one batch of samples, unscaled
    staggered = loadgen.Traffic({k: v for k, v in spec.items() if k != "stagger"}, 7, 50304).prime()
    assert [r.max_new_tokens for r in staggered] == list(range(1, 129))  # the default: phases one token apart
    # what follows the primers is the same sequence either way
    a, b = loadgen.Traffic(spec, 7, 50304), loadgen.Traffic({**spec, "stagger": True}, 7, 50304)
    a.prime(), b.prime()
    ra, rb = a.next(), b.next()
    assert (ra.index, ra.max_new_tokens, list(ra.prompt)) == (rb.index, rb.max_new_tokens, list(rb.prompt))
