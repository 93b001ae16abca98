"""The driver contract, executed: tools/bench_serve.py and friends must
emit exactly ONE schema-conformant JSON line on stdout. Runs the real
entry-point main()s in-process (tiny shapes, CPU mesh) and validates their
stdout through the shared checker in analysis/bench_contract.py — the one
place the contract is written down, so a silently renamed field or a stray
print fails here instead of in the driver. bench.py itself is a device
measurement: here it must refuse to run (no chip), not measure the CPU."""

import json
import os
import runpy
import sys

import pytest

from midgpt_tpu.analysis.bench_contract import (
    check_bench_stdout,
    check_graftcheck,
    check_serve_bench,
    check_serve_fleet_bench,
    check_serve_gqa_bench,
    check_serve_longctx_bench,
    check_serve_ops_bench,
    check_serve_prefix_bench,
    check_serve_slo_bench,
    check_serve_tp_bench,
    check_train_bench,
    check_train_chaos,
    parse_single_json_line,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_entry_point(path, argv, capsys):
    """Load a script module and run its main() with patched argv, returning
    captured stdout (run_name != '__main__' so nothing auto-executes)."""
    mod = runpy.run_path(path, run_name="bench_under_test")
    old_argv = sys.argv
    sys.argv = argv
    try:
        rc = mod["main"]()
    finally:
        sys.argv = old_argv
    assert rc == 0
    return capsys.readouterr().out


@pytest.mark.slow
def test_bench_serve_emits_conformant_json_line(capsys):
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--n-requests", "3",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve")
    assert not problems, problems
    assert rec["n_requests"] == 3
    assert rec["continuous_tok_s"] > 0 and rec["sequential_tok_s"] > 0
    # the counter hooks ride along: serving compiled a bounded program set
    assert rec["compile_counts"]["decode"] >= 1
    assert rec["compile_counts"]["prefill"] >= 1


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_bench_serve_spec_emits_conformant_json_line(capsys):
    """--spec mode: the serve_spec profile (speculative vs plain continuous
    engine) must hold the one-JSON-line contract too. Tiny shapes, 2 quick
    train steps — structure check, not a perf claim."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--spec",
            "--n-requests", "2",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
            "--spec-draft-layers", "1",
            "--spec-k", "4",
            "--train-steps", "2",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_spec")
    assert not problems, problems
    assert rec["draft_layers"] == 1 and rec["spec_k_max"] == 4
    assert rec["baseline_tok_s"] > 0 and rec["spec_tok_s"] > 0
    assert 0.0 <= rec["accept_rate"] <= 1.0
    assert rec["tokens_per_verify"] >= 1.0
    assert rec["compile_counts"]["spec_draft"] >= 1
    assert rec["compile_counts"]["spec_verify"] >= 1
    # prefix self-draft: speculation must not cost extra cache HBM
    assert rec["hbm_draft_cache_bytes"] == 0


def test_bench_serve_prefix_emits_conformant_json_line(capsys):
    """--shared-prefix-frac mode: the serve_prefix profile (prefix cache
    on vs off over a template-heavy workload) must hold the one-JSON-line
    contract, report exact greedy parity, and never prefill MORE with the
    cache on. Tiny shapes — structure check, not a perf claim."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--shared-prefix-frac", "0.8",
            "--n-requests", "6",
            "--template-tokens", "24",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_prefix")
    assert not problems, problems
    assert rec["greedy_match_frac"] == 1.0
    assert 0.0 < rec["prefix_hit_rate"] <= 1.0
    assert rec["prefix_prefill_tokens"] <= rec["baseline_prefill_tokens"]
    # checker drift behavior on the real record: inexact parity and a
    # prefill regression are contract violations, not numbers
    assert any(
        "greedy_match_frac" in p
        for p in check_serve_prefix_bench(dict(rec, greedy_match_frac=0.99))
    )
    assert any(
        "prefill" in p
        for p in check_serve_prefix_bench(
            dict(rec, prefix_prefill_tokens=rec["baseline_prefill_tokens"] + 1)
        )
    )


@pytest.mark.slow
def test_bench_serve_tp_emits_conformant_json_line(capsys):
    """--tp mode: the serve_tp profile (single-chip vs tensor-parallel
    engine per cache mode) must hold the one-JSON-line contract with every
    match_* EXACTLY 1.0 and the per-shard HBM arithmetic exact. Tiny
    shapes + few quick-train steps — structure check, not a perf claim.
    Default (17-page) pool geometry: disjoint from the 25/27/31-page
    geometries the recompile pins count from a pristine baseline."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--tp", "2",
            "--n-requests", "4",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
            "--train-steps", "8",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_tp")
    assert not problems, problems
    assert rec["match_f32"] == rec["match_int8"] == rec["match_spec"] == 1.0
    assert rec["mesh"] == {"data": 1, "tp": 2}
    assert rec["cache_hbm_bytes_per_shard"] * 2 == rec["cache_hbm_bytes"]
    # checker drift behavior on the real record: inexact parity and broken
    # shard arithmetic are contract violations, not numbers
    assert any(
        "match_int8" in p
        for p in check_serve_tp_bench(dict(rec, match_int8=0.998))
    )
    assert any(
        "per-shard" in p
        for p in check_serve_tp_bench(
            dict(rec, cache_hbm_bytes_per_shard=rec["cache_hbm_bytes"])
        )
    )


@pytest.mark.slow
def test_bench_serve_longctx_emits_conformant_json_line(capsys):
    """--long-ctx mode: the serve_longctx profile (split-K decode A/B at a
    long and a short context) must hold the one-JSON-line contract with
    EXACT greedy parity, the auto bucket rule engaged at t_long and
    resolving to the unsplit program at t_short. Small t_long=1024 point
    (the smallest the profile admits), tiny model, 2 quick-train steps —
    structure check, not a latency claim."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--long-ctx",
            "--t-long", "1024",
            "--t-short", "64",
            "--rounds", "2",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "32",
            "--decode-chunk", "4",
            "--train-steps", "2",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_longctx")
    assert not problems, problems
    assert rec["greedy_match_frac"] == 1.0
    assert rec["split_k_long"] == 2  # the 1024-token bucket
    assert rec["split_k_short"] == 1  # auto: short traffic stays unsplit
    assert rec["ms_round_long_split"] > 0 and rec["ms_round_long_unsplit"] > 0
    # checker drift behavior on the real record: inexact parity, a split
    # bucket leaking into short traffic, a vacuous (unsplit or short-T)
    # long arm, and a dead timing are contract violations, not numbers
    assert any(
        "greedy_match_frac" in p
        for p in check_serve_longctx_bench(dict(rec, greedy_match_frac=0.99))
    )
    assert any(
        "split_k_short" in p
        for p in check_serve_longctx_bench(dict(rec, split_k_short=2))
    )
    assert any(
        "split_k_long" in p
        for p in check_serve_longctx_bench(dict(rec, split_k_long=1))
    )
    assert any(
        "t_long" in p for p in check_serve_longctx_bench(dict(rec, t_long=512))
    )
    assert any(
        "ms_round_long_split" in p
        for p in check_serve_longctx_bench(dict(rec, ms_round_long_split=0.0))
    )


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_bench_serve_gqa_emits_conformant_json_line(capsys):
    """--gqa mode: the serve_gqa profile (GQA vs MHA KV-capacity A/B at a
    fixed pool byte budget, docs/SERVING.md 'Attention variants') must hold
    the one-JSON-line contract: G-fold page capacity from the same bytes,
    strictly fewer preemptions on an oversubscribed trace, and EXACT greedy
    parity on both arms. Tiny model — structure check, not a perf claim."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--gqa", "4",
            "--n-requests", "8",
            "--block-size", "128",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "4",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_gqa")
    assert not problems, problems
    assert rec["kv_groups"] == 4 and rec["n_kv_heads"] == 1
    # same bytes, 4x smaller pages -> ~4x pages (max(2,...) rounding aside)
    assert rec["gqa_page_bytes"] * 4 == rec["mha_page_bytes"]
    assert rec["pages_ratio"] >= 3.0
    assert rec["mha_preemptions"] > rec["gqa_preemptions"]
    assert rec["greedy_match_frac_mha"] == 1.0
    assert rec["greedy_match_frac_gqa"] == 1.0


def test_serve_gqa_checker_catches_drift():
    """The serve_gqa gates hold on a synthetic record without running the
    bench: the capacity conversion, the oversubscription requirement, and
    exact two-sided parity are contract, not numbers."""
    good = {
        "bench": "serve_gqa", "backend": "cpu", "n_requests": 8,
        "total_new_tokens": 96, "max_slots": 4, "page_size": 8,
        "kv_dtype": "bf16", "pool_hbm_bytes": 100000, "model": {},
        "kv_groups": 4, "n_kv_heads": 1, "sliding_window": 0,
        "attn_sinks": 0, "mha_page_bytes": 4096, "gqa_page_bytes": 1024,
        "mha_num_pages": 24, "gqa_num_pages": 96, "pages_ratio": 4.0,
        "mha_slots_capacity": 3, "gqa_slots_capacity": 12,
        "mha_preemptions": 16, "gqa_preemptions": 0,
        "mha_tok_s": 100.0, "gqa_tok_s": 220.0,
        "window_reclaimed_pages": 0,
        "greedy_match_frac_mha": 1.0, "greedy_match_frac_gqa": 1.0,
        "mha_cache_hbm_bytes": 98304, "gqa_cache_hbm_bytes": 98304,
        "compile_counts": {},
    }
    assert check_serve_gqa_bench(good) == []
    # an MHA-vs-MHA "A/B" is vacuous
    assert any("kv_groups" in p
               for p in check_serve_gqa_bench(dict(good, kv_groups=1)))
    # the byte budget must convert into KV-head-scaled page capacity
    assert any("pages_ratio" in p
               for p in check_serve_gqa_bench(dict(good, pages_ratio=2.0)))
    # a trace the MHA pool absorbs proves nothing about capacity
    assert any(
        "mha_preemptions" in p
        for p in check_serve_gqa_bench(
            dict(good, mha_preemptions=0, gqa_preemptions=0)
        )
    )
    # the extra pages must buy strictly fewer preemptions
    assert any("gqa_preemptions" in p
               for p in check_serve_gqa_bench(dict(good, gqa_preemptions=16)))
    # parity is exact on BOTH arms — 0.9999 is a kernel bug, not noise
    assert any(
        "greedy_match_frac_mha" in p
        for p in check_serve_gqa_bench(dict(good, greedy_match_frac_mha=0.9999))
    )
    assert any(
        "greedy_match_frac_gqa" in p
        for p in check_serve_gqa_bench(dict(good, greedy_match_frac_gqa=0.9999))
    )
    missing = dict(good)
    missing.pop("window_reclaimed_pages")
    assert any("window_reclaimed_pages" in p
               for p in check_serve_gqa_bench(missing))


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_bench_serve_ops_emits_conformant_json_line(capsys):
    """--hot-swap mode: the serve_ops profile (verified-checkpoint
    blue/green swap mid-trace + live pool grow) must hold the one-JSON-
    line contract with zero dropped streams, a zero swap-window jit-cache
    delta, both parity sides non-empty and summing to n_requests, and a
    non-vacuous migration. Tiny shapes — structure check; the full-size
    run is the driver's serve_ops gate (docs/ROBUSTNESS.md)."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--hot-swap",
            "--n-requests", "8",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_ops")
    assert not problems, problems
    assert rec["dropped"] == 0 and rec["swap_recompiles"] == 0
    assert rec["parity_old_side"] >= 1 and rec["parity_new_side"] >= 1
    assert rec["parity_old_side"] + rec["parity_new_side"] == 8
    assert rec["weights_version_after"].startswith(
        f"{rec['checkpoint_step']}:"
    )
    assert rec["pages_migrated"] >= 1 and rec["pages_conserved"] is True
    # checker drift behavior on the real record: a dropped stream, a swap
    # recompile, a vacuous parity side, and an unchanged version are each
    # contract violations, not numbers
    assert any("dropped" in p for p in check_serve_ops_bench(dict(rec, dropped=1)))
    assert any(
        "swap_recompiles" in p
        for p in check_serve_ops_bench(dict(rec, swap_recompiles=2))
    )
    assert any(
        "parity" in p
        for p in check_serve_ops_bench(
            dict(rec, parity_old_side=0,
                 parity_new_side=rec["n_requests"])
        )
    )
    assert any(
        "weights_version" in p
        for p in check_serve_ops_bench(
            dict(rec, weights_version_after=rec["weights_version_before"])
        )
    )
    assert any(
        "pages_migrated" in p
        for p in check_serve_ops_bench(dict(rec, pages_migrated=0))
    )


@pytest.mark.slow
def test_bench_serve_fleet_emits_conformant_json_line(capsys):
    """--fleet mode: the serve_fleet profile (single engine vs a crashed-
    replica fleet over the same template trace, with the shared mid-trace
    trie flush exercising the spill tier) must hold the one-JSON-line
    contract: a replica actually died, zero streams dropped, every stream
    bit-matched the single-engine pass, and affinity + spill re-adoption
    kept the fleet trie hit rate >= the single engine's. Tiny shapes —
    structure check; docs/ROBUSTNESS.md 'Fleet serving & failover'."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--fleet", "2",
            "--n-requests", "10",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_fleet")
    assert not problems, problems
    assert rec["fleet_size"] == 2 and rec["alive"] == 1
    assert rec["failovers"] >= 1 and rec["dropped"] == 0
    assert rec["greedy_match_frac"] == 1.0
    assert rec["parity_checked"] == 10
    assert rec["fleet_hit_rate"] >= rec["single_hit_rate"]
    assert rec["spill_readopted_pages"] >= 1  # the flush spilled, half 2 re-adopted
    assert rec["spill"]["total_spilled"] >= 1
    # checker drift behavior on the real record: an unfaulted fleet, a
    # dropped stream, inexact parity, and a diluted trie are each
    # contract violations, not numbers
    assert any("failovers" in p
               for p in check_serve_fleet_bench(dict(rec, failovers=0)))
    assert any("dropped" in p
               for p in check_serve_fleet_bench(dict(rec, dropped=1)))
    assert any(
        "greedy_match_frac" in p
        for p in check_serve_fleet_bench(dict(rec, greedy_match_frac=0.99))
    )
    assert any(
        "hit_rate" in p
        for p in check_serve_fleet_bench(
            dict(rec, fleet_hit_rate=rec["single_hit_rate"] / 2 - 0.01)
        )
    )


@pytest.mark.slow
def test_bench_serve_proc_fleet_emits_conformant_json_line(capsys):
    """--fleet --procs: the serve_fleet line from a cross-process fleet
    (worker processes behind the socket transport, a real kill -9
    mid-trace — docs/ROBUSTNESS.md 'Cross-process fleet') must conform,
    carry the transport claim, and hold zero-drop + exact parity across
    the process boundary. Tiny shapes — structure check."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "bench_serve.py"),
        [
            "bench_serve.py",
            "--fleet", "2",
            "--procs",
            "--n-requests", "10",
            "--block-size", "64",
            "--vocab-size", "96",
            "--n-layer", "2",
            "--n-head", "2",
            "--n-embd", "32",
            "--prefill-chunk", "16",
            "--decode-chunk", "4",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_fleet")
    assert not problems, problems
    assert rec["procs"] is True
    assert rec["fleet_size"] == 2 and rec["alive"] == 1
    assert rec["proc_failovers"] >= 1 and rec["failovers"] >= 1
    assert rec["dropped"] == 0
    assert rec["greedy_match_frac"] == 1.0
    assert rec["parity_checked"] == 10
    assert rec["wire_bytes"] >= 1
    assert rec["transport"]["rpc_count"] >= 1
    assert rec["router_compiles_delta"] == 0


@pytest.mark.slow
def test_loadgen_hot_swap_surfaces_version_transition(capsys):
    """tools/loadgen.py --hot-swap: the serve_slo line still conforms, a
    swap lands at every point, the headline carries the version
    transition, and the SLO acceptance (zero shed through the swap on an
    unbounded backlog) holds with no special-casing."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "loadgen.py"),
        [
            "loadgen.py",
            "--rates", "30,90",
            "--n-requests", "4",
            "--hot-swap",
            "--seed", "0",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_slo")
    assert not problems, problems
    assert rec["hot_swaps"] == 2  # one flip per point
    assert rec["weights_versions"][0] == "inline"
    assert rec["weights_versions"][1].startswith("3:")
    for p in rec["points"]:
        assert p["hot_swaps"] == 1
        assert p["weights_version"] == rec["weights_versions"][1]
        assert p["shed"] == 0 and p["completed"] == p["n_offered"]
    assert rec["slo_ok"] is True


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_loadgen_prefix_cache_emits_hit_rate(capsys):
    """tools/loadgen.py --prefix-cache: the serve_slo line still conforms
    and carries per-point + headline prefix_hit_rate fields."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "loadgen.py"),
        [
            "loadgen.py",
            "--rates", "30,90",
            "--n-requests", "4",
            "--template-frac", "0.75",
            "--prefix-cache",
            "--seed", "0",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_slo")
    assert not problems, problems
    assert rec["prefix_cache"] is True
    for p in rec["points"]:
        assert 0.0 <= p["prefix_hit_rate"] <= 1.0
    assert 0.0 <= rec["prefix_hit_rate"] <= 1.0


def test_loadgen_fleet_emits_fleet_headline(capsys):
    """tools/loadgen.py --fleet: the serve_slo line still conforms and
    every point plus the headline carries the fleet availability fields
    (fleet_size / failovers / spill_hits / fleet-wide prefix_hit_rate) —
    the serve_slo checker validates their types and ranges whenever
    fleet_size is present."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "loadgen.py"),
        [
            "loadgen.py",
            "--rates", "30,90",
            "--n-requests", "4",
            "--fleet", "2",
            "--template-frac", "0.75",
            "--seed", "0",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_slo")
    assert not problems, problems
    assert rec["prefix_cache"] is True  # --fleet implies the trie
    assert rec["fleet_size"] == 2
    assert rec["failovers"] >= 0 and rec["spill_hits"] >= 0
    for p in rec["points"]:
        assert p["fleet_size"] == 2
        assert p["failovers"] >= 0 and p["spill_hits"] >= 0
        assert 0.0 <= p["prefix_hit_rate"] <= 1.0
        assert p["shed"] == 0 and p["completed"] == p["n_offered"]
    # fleet-field drift is a contract violation once fleet_size appears
    bad = dict(rec, failovers="1")
    assert any("failovers" in p for p in check_serve_slo_bench(bad))


def test_loadgen_long_mixture_emits_conformant_serve_slo_line(capsys):
    """tools/loadgen.py --long-frac: the long-prompt/long-output mixture
    keeps the serve_slo line conformant and records the mixture knob. The
    pool default stays the auto rule's 27-page geometry below the
    long-context regime, so this composes with every other loadgen pin."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "loadgen.py"),
        [
            "loadgen.py",
            "--rates", "30,90",
            "--n-requests", "4",
            "--long-frac", "0.5",
            "--seed", "0",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_slo")
    assert not problems, problems
    assert rec["long_frac"] == 0.5
    assert rec["points"][0]["completed"] >= 1


def test_loadgen_emits_conformant_serve_slo_line(capsys):
    """tools/loadgen.py (SLO load harness) holds the one-JSON-line
    contract: a short seeded-arrival Poisson run against the CPU-mesh
    engine at TWO offered-load points, validated by the serve_slo profile.
    Structure check, not a latency claim — arrivals are deterministic
    (seeded), wall-clock percentiles are whatever the host gives."""
    out = _run_entry_point(
        os.path.join(REPO, "tools", "loadgen.py"),
        [
            "loadgen.py",
            "--rates", "30,90",
            "--n-requests", "4",
            "--seed", "0",
        ],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "serve_slo")
    assert not problems, problems
    assert rec["process"] == "poisson" and rec["scheduler"] == "fcfs"
    assert len(rec["points"]) == 2
    assert [p["offered_rps"] for p in rec["points"]] == [30.0, 90.0]
    for p in rec["points"]:
        assert p["n_offered"] == 4
        assert p["completed"] + p["shed"] + p["timeouts"] <= p["n_offered"]
        assert 0.0 <= p["shed_frac"] <= 1.0
    assert isinstance(rec["slo_ok"], bool)


def test_bench_train_refuses_to_measure_without_a_chip(capsys):
    """bench.py is a device measurement: on the CPU mesh it exits non-zero
    with an error naming the device and prints NO result line — a CPU
    number never appears under the MFU metric's name."""
    mod = runpy.run_path(
        os.path.join(REPO, "bench.py"), run_name="bench_under_test"
    )
    argv, sys.argv = sys.argv, ["bench.py", "--steps", "1", "--warmup", "1"]
    try:
        rc = mod["main"]()
    finally:
        sys.argv = argv
    assert rc != 0  # NOT the _run_entry_point helper: failure IS the pin
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "'cpu'" in captured.err and "found none" in captured.err


def test_unknown_device_kind_has_no_assumed_peak():
    """A device_kind missing from the peaks table is an error naming the
    device, never a default peak (bench.py and the train loop's MFU both
    read this table); the v5e the chip tool hands out is in it."""
    from types import SimpleNamespace

    from midgpt_tpu.training.metrics import device_peak_flops

    v5e = SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert device_peak_flops(v5e) == 197e12
    unknown = SimpleNamespace(device_kind="TPU v99x", platform="tpu")
    with pytest.raises(ValueError, match="TPU v99x"):
        device_peak_flops(unknown)


def test_graftcheck_cli_emits_conformant_json_line(capsys, tmp_path):
    """tools/graftcheck.py --json through the SAME in-process harness as
    the benches: its line must satisfy the graftcheck profile, including
    the pass-3/pass-4 stats fields and the jit-surface census count."""
    p = tmp_path / "clean.py"
    p.write_text("import jax\n\n@jax.jit\ndef f(x):\n    return x + 1\n")
    out = _run_entry_point(
        os.path.join(REPO, "tools", "graftcheck.py"),
        ["graftcheck.py", "--json", str(p)],
        capsys,
    )
    rec, problems = check_bench_stdout(out, "graftcheck")
    assert not problems, problems
    assert rec["tool"] == "graftcheck"
    assert rec["count"] == 0 and rec["files_scanned"] == 1
    assert rec["pass3_count"] == 0 and rec["pass3_wall_ms"] >= 0
    assert rec["pass4_count"] == 0 and rec["pass4_wall_ms"] >= 0
    assert rec["jit_surface_count"] == 1  # the @jax.jit wrapper above


# ----------------------------------------------------------------------
# checker unit behavior (no bench run needed)
# ----------------------------------------------------------------------


def test_checker_rejects_multiline_and_nonjson():
    rec, problems = parse_single_json_line('{"a": 1}\nextra line\n')
    assert any("exactly 1" in p for p in problems)
    rec, problems = parse_single_json_line("not json at all\n")
    assert rec is None and any("not valid JSON" in p for p in problems)


def test_checker_rejects_nan():
    """json.dumps happily emits bare NaN — which no strict consumer parses.
    The checker must treat it as a contract violation, not a number."""
    line = json.dumps({"metric": "m", "value": float("nan")}) + "\n"
    rec, problems = parse_single_json_line(line)
    assert rec is None and any("NaN" in p or "non-finite" in p for p in problems)


def test_graftcheck_checker_catches_pass4_field_drift():
    """The graftcheck profile holds on a synthetic record without running
    the CLI: dropping or mistyping any pass-4 / jit-surface stat field is
    a contract violation, not a number."""
    good = {
        "tool": "graftcheck", "count": 0, "suppressed": 0,
        "files_scanned": 1, "findings": [],
        "pass3_count": 0, "pass3_suppressed": 0, "pass3_wall_ms": 1.0,
        "pass4_count": 0, "pass4_suppressed": 0, "pass4_wall_ms": 1.0,
        "jit_surface_count": 3,
    }
    assert check_graftcheck(good) == []
    for field in (
        "pass4_count",
        "pass4_suppressed",
        "pass4_wall_ms",
        "jit_surface_count",
    ):
        missing = dict(good)
        missing.pop(field)
        assert any(field in p for p in check_graftcheck(missing)), field
    wrong_type = dict(good, pass4_count="0")
    assert any("pass4_count" in p for p in check_graftcheck(wrong_type))
    assert any(
        "jit_surface_count" in p
        for p in check_graftcheck(dict(good, jit_surface_count=2.5))
    )


def test_checker_catches_field_drift():
    good = {
        "metric": "train_mfu",
        "value": 48.5,
        "unit": "% MFU",
        "vs_baseline": 1.01,
        "detail": {"tokens_per_sec": 1.0, "step_ms": 2.0, "n_devices": 1},
    }
    assert check_train_bench(good) == []
    renamed = dict(good)
    renamed["vs_base"] = renamed.pop("vs_baseline")
    assert any("vs_baseline" in p for p in check_train_bench(renamed))
    wrong_type = dict(good, value="48.5")
    assert any("value" in p for p in check_train_bench(wrong_type))
    assert any(
        "bench" in p for p in check_serve_bench({"bench": "other"})
    )


def test_serve_fleet_checker_catches_drift():
    """The serve_fleet gates hold on a synthetic record without running
    the bench: structural availability claims (a replica died, zero
    drops, exact parity, undiluted trie) are contract, not numbers."""
    good = {
        "bench": "serve_fleet", "backend": "cpu", "n_requests": 12,
        "total_new_tokens": 120, "fleet_size": 2, "model": {},
        "kv_dtype": "bf16", "num_pages": 41, "n_templates": 2,
        "single_tok_s": 100.0, "fleet_tok_s": 90.0,
        "single_hit_rate": 0.2, "fleet_hit_rate": 0.6,
        "failovers": 1, "failed_over_streams": 2, "dropped": 0,
        "parity_checked": 12, "greedy_match_frac": 1.0,
        "spill_readopted_pages": 10, "spill": {}, "compile_counts": {},
        "pages_conserved": True,
    }
    assert check_serve_fleet_bench(good) == []
    assert any("fleet_size" in p
               for p in check_serve_fleet_bench(dict(good, fleet_size=1)))
    assert any("failovers" in p
               for p in check_serve_fleet_bench(dict(good, failovers=0)))
    assert any("dropped" in p
               for p in check_serve_fleet_bench(dict(good, dropped=1)))
    assert any(
        "greedy_match_frac" in p
        for p in check_serve_fleet_bench(dict(good, greedy_match_frac=0.9999))
    )
    assert any(
        "parity_checked" in p
        for p in check_serve_fleet_bench(dict(good, parity_checked=11))
    )
    assert any(
        "hit_rate" in p
        for p in check_serve_fleet_bench(dict(good, fleet_hit_rate=0.1))
    )
    assert any(
        "pages_conserved" in p
        for p in check_serve_fleet_bench(dict(good, pages_conserved="yes"))
    )
    # cross-process variant (bench_serve --fleet --procs): the hit-rate
    # ordering is waived — a SIGKILLed worker takes its host-RAM tier
    # with it, so the survivor honestly re-prefills — but the transport
    # claim becomes required (docs/ROBUSTNESS.md "Cross-process fleet")
    procs = dict(
        good, procs=True, fleet_hit_rate=0.1,
        proc_failovers=1, worker_pids=[11, 12], transport={},
        rpc_p50_ms=0.5, rpc_p95_ms=20.0, wire_bytes=4096,
    )
    assert check_serve_fleet_bench(procs) == []
    assert any(
        "proc_failovers" in p
        for p in check_serve_fleet_bench(dict(procs, proc_failovers=0))
    )
    assert any(
        "wire_bytes" in p
        for p in check_serve_fleet_bench(dict(procs, wire_bytes=0))
    )
    no_rpc = dict(procs)
    no_rpc.pop("rpc_p50_ms")
    assert any("rpc_p50_ms" in p for p in check_serve_fleet_bench(no_rpc))
    # the waiver is procs-only: the same diluted trie still fails in-proc
    assert any(
        "hit_rate" in p
        for p in check_serve_fleet_bench(dict(procs, procs=False))
    )


def test_serve_slo_checker_catches_drift():
    decomp = {"p50": 1.0, "p95": 2.0}
    point = {
        "offered_rps": 30.0, "n_offered": 4, "completed": 4, "shed": 0,
        "timeouts": 0, "shed_frac": 0.0, "timeout_frac": 0.0,
        "ttft_p50_ms": 5.0, "ttft_p95_ms": 9.0, "tpot_p50_ms": 1.0,
        "tpot_p95_ms": 2.0, "rounds": 8,
        "round_host_ms": dict(decomp), "round_device_ms": dict(decomp),
        "overlap_hidden_ms": dict(decomp), "overlap_mode": "off",
        "round_group": 1,
    }
    good = {
        "bench": "serve_slo", "backend": "cpu", "process": "poisson",
        "scheduler": "fcfs", "seed": 0, "n_requests": 4,
        "error_budget": 0.2, "model": {}, "slo_ok": True,
        "points": [point, dict(point, offered_rps=90.0)],
        "ttft_p50_ms": 5.0, "ttft_p95_ms": 9.0, "tpot_p50_ms": 1.0,
        "tpot_p95_ms": 2.0, "shed_frac": 0.0, "timeout_frac": 0.0,
        "round_host_ms": dict(decomp), "round_device_ms": dict(decomp),
        "overlap_hidden_ms": dict(decomp), "overlap_mode": "off",
        "round_group": 1,
    }
    assert check_serve_slo_bench(good) == []
    # round-overlap drift (docs/SERVING.md "Round-overlap dispatch"): a
    # bad mode name fails, and round_group != 1 demands mode == "group"
    assert any("overlap_mode" in p
               for p in check_serve_slo_bench(dict(good, overlap_mode="on")))
    assert any("round_group" in p
               for p in check_serve_slo_bench(dict(good, round_group=2)))
    # round-decomposition drift (docs/OBSERVABILITY.md): a missing or
    # malformed host/device object fails, as does a negative quantile
    no_decomp = dict(good, round_host_ms=None)
    assert any("round_host_ms" in p for p in check_serve_slo_bench(no_decomp))
    neg = dict(good, round_device_ms={"p50": -1.0, "p95": 2.0})
    assert any("round_device_ms.p50" in p for p in check_serve_slo_bench(neg))
    # one load point is a measurement, not the SLO curve the profile wants
    one_point = dict(good, points=[point])
    assert any(">= 2" in p for p in check_serve_slo_bench(one_point))
    # a renamed per-point percentile field fails with the point index
    bad_point = dict(point)
    bad_point["ttft95_ms"] = bad_point.pop("ttft_p95_ms")
    drifted = dict(good, points=[point, bad_point])
    assert any("points[1]" in p and "ttft_p95_ms" in p
               for p in check_serve_slo_bench(drifted))
    # shed_frac outside [0, 1] is a contract violation, not a number
    assert any("outside" in p
               for p in check_serve_slo_bench(dict(good, shed_frac=1.5)))
    # cross-process fleet (loadgen --fleet --procs): the transport
    # headline must be present and sane when procs is true
    procs = dict(
        good, procs=True, fleet_size=2, failovers=0, spill_hits=0,
        prefix_hit_rate=0.0, rpc_p50_ms=0.5, rpc_p95_ms=9.0,
        wire_bytes=1024,
    )
    assert check_serve_slo_bench(procs) == []
    assert any("wire_bytes" in p
               for p in check_serve_slo_bench(dict(procs, wire_bytes=0)))
    assert any("rpc_p95_ms" in p
               for p in check_serve_slo_bench(dict(procs, rpc_p95_ms=-1.0)))
    assert any("fleet_size" in p
               for p in check_serve_slo_bench(dict(procs, fleet_size=None)))


def test_train_chaos_checker_catches_drift():
    """The train_chaos gates hold on a synthetic record without running
    the chaos bench: the recovery claims (a fault FIRED, detection was
    timestamped, the recovered trajectory matches the unfaulted reference,
    the finishing mesh is named) are contract, not numbers."""
    good = {
        "tool": "chaos_run", "config": "shakespeare_char", "rundir": "/r",
        "status": "ok", "wall_s": 10.5,
        "faults_requested": ["resume_reshard@6"],
        "faults_fired": {"resume_reshard": 1},
        "supervisor": {"restarts": 0, "hung_steps": []},
        "loss_final": 4.5, "preempted": False, "bench": "train_chaos",
        "detected_at_ms": 5001.7, "restarts": 1,
        "final_mesh": {"n_devices": 4, "axes": {"data": 1, "fsdp": 4}},
        "n_devices_final": 4, "loss_ref": 4.5, "loss_parity": True,
    }
    assert check_train_chaos(good) == []
    assert any("loss_parity" in p
               for p in check_train_chaos(dict(good, loss_parity=False)))
    missing = dict(good)
    missing.pop("detected_at_ms")
    assert any("detected_at_ms" in p for p in check_train_chaos(missing))
    assert any("faults_fired" in p
               for p in check_train_chaos(dict(good, faults_fired={})))
    assert any("status" in p
               for p in check_train_chaos(dict(good, status="failed")))
    assert any("bench" in p
               for p in check_train_chaos(dict(good, bench="train")))
    assert any(
        "n_devices" in p
        for p in check_train_chaos(
            dict(good, final_mesh={"n_devices": 0, "axes": {"data": 1}})
        )
    )
    assert any(
        "axes" in p
        for p in check_train_chaos(
            dict(good, final_mesh={"n_devices": 4, "axes": {}})
        )
    )
    assert any("restarts" in p
               for p in check_train_chaos(dict(good, restarts=-1)))


@pytest.mark.slow
def test_chaos_run_train_cli_emits_conformant_train_chaos_line(
    capsys, tmp_path
):
    """`chaos_run.py --fault resume_reshard@6` (train mode) holds the
    one-JSON-line driver contract end to end: the fault ends attempt one
    like a preemption, the driver restarts on HALF the devices with
    on_resume_mesh='any', the run completes on the 4-device mesh, and the
    summary passes the train_chaos profile. Step logs and supervisor
    prints go to stderr — stdout is the summary line, full stop."""
    import numpy as np

    from midgpt_tpu.robustness import faults, preempt

    data = tmp_path / "data"
    data.mkdir()
    stream = (np.arange(20000) % 17).astype(np.uint16)
    stream.tofile(data / "train.bin")
    stream[:4000].tofile(data / "val.bin")

    mod = runpy.run_path(
        os.path.join(REPO, "tools", "chaos_run.py"), run_name="chaos_under_test"
    )
    argv, sys.argv = sys.argv, [
        "chaos_run.py", "--config=shakespeare_char",
        f"--rundir={tmp_path / 'run'}",
        "--fault", "resume_reshard@6",
        "--set", "max_steps=16", "--set", "eval_interval=8",
        "--set", "eval_steps=2", "--set", "batch_size=8",
        "--set", "log_interval=4",
        "--set", "model_config.n_layer=1", "--set", "model_config.n_head=2",
        "--set", "model_config.n_embd=32",
        "--set", "model_config.block_size=32",
        "--set", "model_config.vocab_size=96",
        f"--set", f"data_dir={data}",
        "--set", "mesh.data=2", "--set", "mesh.fsdp=4",
        "--set", "param_dtype=float32", "--set", "compute_dtype=float32",
        "--set", "restart_backoff_sec=0.0",
    ]
    try:
        rc = mod["main"]()
    finally:
        sys.argv = argv
        faults.clear()
        preempt.reset()
    assert rc == 0
    out = capsys.readouterr().out
    rec, problems = check_bench_stdout(out, "train_chaos")
    assert not problems, problems
    assert rec["faults_fired"] == {"resume_reshard": 1}
    # the topology actually changed hands: started on 8, finished on 4
    assert rec["final_mesh"]["n_devices"] == 4
    assert rec["restarts"] >= 1
    assert rec["loss_parity"] is True
    history = rec["supervisor"]["mesh_history"]
    assert [m["n_devices"] for m in history] == [8, 4]
    json.loads(out)  # strict JSON round-trip (no NaN etc.)
