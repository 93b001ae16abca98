"""The serving chaos gate: every serving fault kind, end to end through
robustness/chaos_serve.py (the exact scenario `chaos_run.py --serve`
drives). Each scenario runs a fault-free reference and a faulted pass of
the same seeded trace and asserts the degradation invariants internally —
engine alive, every page conserved, unaffected greedy streams bit-identical
— so these tests mostly assert on the returned summary. The CLI JSON line
is validated through the shared single-line parser at the end, and the
training chaos summary (`chaos_run.py` without --serve) through its
`train_chaos` profile."""

import json
import os
import runpy
import sys

import pytest

from midgpt_tpu.analysis.bench_contract import (
    check_bench_stdout,
    check_train_chaos,
    parse_single_json_line,
)
from midgpt_tpu.robustness import faults
from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.clear()
    yield
    faults.clear()


def test_chaos_kill_mid_decode_full_parity():
    """A killed decode round recompute-preempts every decode-ready slot;
    recovery is parity-preserving, so NO request may diverge."""
    s = run_serving_chaos("kill_mid_decode@6", seed=0)
    assert s["faults_fired"] == {"kill_mid_decode": 1}
    assert s["decode_kills"] == 1
    assert s["preemptions"] >= 1, "the kill must actually preempt someone"
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_checked"] == s["n_requests"]
    assert s["parity_ok"] == s["parity_checked"]
    assert s["pages_conserved"]


def test_chaos_kill_overlapped_round_recompute_parity():
    """Round-overlap twin of the kill_mid_decode gate (docs/SERVING.md
    "Round-overlap dispatch"): the engine runs double-buffered and the
    fault drops the IN-FLIGHT dispatched group un-settled mid host phase.
    Recovery is the same recompute preemption, so NO request may diverge —
    and because the reference pass runs un-overlapped, full parity here
    also re-proves overlap-on vs overlap-off bit-exactness under fault
    pressure. Pages conserved; zero silent drops."""
    s = run_serving_chaos("kill_overlapped_round@6", seed=0)
    assert s["faults_fired"] == {"kill_overlapped_round": 1}
    assert s["overlap_mode"] == "double"
    assert s["overlap_kills"] == 1
    assert s["preemptions"] >= 1, "the kill must actually preempt someone"
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_checked"] == s["n_requests"]
    assert s["parity_ok"] == s["parity_checked"]
    assert s["pages_conserved"]


def test_chaos_poisoned_page_isolates_the_victim():
    """HBM damage to one slot's page corrupts at most that slot: every
    other stream is bit-identical and the pool stays conserved."""
    s = run_serving_chaos("poisoned_page@3", seed=0)
    assert s["faults_fired"] == {"poisoned_page": 1}
    assert s["poisoned"] == 1
    assert s["parity_checked"] == s["n_requests"] - 1  # victim excluded
    assert s["parity_ok"] == s["parity_checked"]
    assert s["pages_conserved"]


def test_chaos_slow_client_shed_without_collateral():
    """A wedged streaming client is shed with status slow_client; the
    engine keeps serving and the other clients' DELIVERED streams match
    the reference."""
    s = run_serving_chaos("slow_client@1", seed=0)
    assert s["faults_fired"] == {"slow_client": 1}
    assert s["statuses"].get("slow_client") == 1
    assert s["cancelled"] == 1
    assert s["statuses"].get("ok") == s["n_requests"] - 1
    assert s["parity_ok"] == s["parity_checked"] == s["n_requests"] - 1
    assert s["pages_conserved"]


def test_chaos_submit_storm_sheds_and_survivors_finish():
    """A burst of duplicate submissions beyond the backpressure budget
    sheds (BackpressureError) instead of wedging the pool; whatever was
    admitted serves to completion with exact streams."""
    s = run_serving_chaos("submit_storm@2", seed=0)
    assert s["faults_fired"] == {"submit_storm": 1}
    assert s["shed"] >= 1, "the storm must overrun the backlog budget"
    assert s["parity_ok"] == s["parity_checked"] >= 1
    assert s["pages_conserved"]


def test_chaos_evict_shared_prefix_flush_never_corrupts_readers():
    """A forced flush of the prefix trie (pressure spike, LRU ignored)
    reclaims every unreferenced shared page mid-trace; referenced entries
    survive by construction, so every live stream stays bit-identical,
    later requests just re-prefill, and pages + refcounts are conserved
    through the flush. Both passes run cache-ON over template-shared
    traffic, so the reference pass doubles as a cache parity check."""
    s = run_serving_chaos("evict_shared_prefix@7", seed=0, n_requests=6)
    assert s["faults_fired"] == {"evict_shared_prefix": 1}
    assert s["prefix_cache"] is True
    assert s["prefix_reclaimed"] > 0, "the flush must reclaim trie pages"
    assert s["statuses"] == {"ok": 6}
    assert s["parity_ok"] == s["parity_checked"] == 6
    assert 0.0 < s["prefix_hit_rate"] < 1.0  # the flush cost later matches
    assert s["pages_conserved"]


@pytest.mark.slow  # heavy long-tail (~16 s): full suite only, per the
# tier-1 870 s gate budget (CLAUDE.md); the cheaper swap pins stay tier-1
def test_chaos_hot_swap_mid_decode_blue_green_parity():
    """The zero-downtime swap gate (docs/ROBUSTNESS.md 'Zero-downtime
    model ops'): a verified-checkpoint blue/green weight swap lands mid-
    trace with trickle arrivals. Zero streams drop; streams served before
    the flip are bit-identical to the fault-free OLD-weights pass, post-
    flip admissions to the NEW-weights pass; both sides non-empty; pages
    conserved through the flip."""
    s = run_serving_chaos("hot_swap_mid_decode@5", seed=0)
    assert s["faults_fired"] == {"hot_swap_mid_decode": 1}
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["dropped"] == 0
    # a REAL verified version: "<step>:<sha12>" from the manifest hash
    step = s["checkpoint_step"]
    assert s["weights_version"].startswith(f"{step}:")
    assert len(s["weights_version"].split(":")[1]) == 12
    assert s["swap"]["flip_round"] >= s["swap"]["staged_round"]
    assert s["parity_old_side"] >= 1 and s["parity_new_side"] >= 1
    assert s["parity_old_side"] + s["parity_new_side"] == s["n_requests"]
    assert s["pages_conserved"]


@pytest.mark.slow  # heavy long-tail (~25 s, the suite's priciest chaos
# gate): full suite only; the resize recompile pin stays tier-1
def test_chaos_pool_resize_grow_shrink_int8_parity():
    """The elastic-resize gate: grow then shrink mid-trace on an int8
    cache (scales must migrate with their pages or parity breaks). Every
    stream stays greedy-bit-exact vs the no-resize reference; page
    conservation holds at every boundary (asserted inside resize_pool on
    both sides of each migration)."""
    s = run_serving_chaos("pool_resize@4,pool_resize@8", seed=0)
    assert s["faults_fired"] == {"pool_resize": 2}
    assert s["cache_dtype"] == "int8"
    assert len(s["resizes"]) == 2
    grow, shrink = s["resizes"]
    assert grow["to_pages"] > grow["from_pages"]
    assert shrink["to_pages"] < shrink["from_pages"]
    assert s["final_num_pages"] == shrink["to_pages"]
    assert s["pages_migrated"] >= 1
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_ok"] == s["parity_checked"] == s["n_requests"]
    assert s["pages_conserved"]


def test_chaos_engine_crash_failover_zero_drops():
    """The fleet gate (docs/ROBUSTNESS.md 'Fleet serving & failover'): a
    replica killed mid-trace drops ZERO accepted streams — its in-flight
    work is resubmitted through the retryable path with the original
    prompt and full budget, and greedy batch-composition-independence
    makes the failover replays bit-identical to the fault-free single-
    engine reference. Page conservation holds on every survivor."""
    s = run_serving_chaos("engine_crash@6", seed=0)
    assert s["faults_fired"] == {"engine_crash": 1}
    assert s["fleet_size"] == 2 and s["alive"] == 1
    assert s["failovers"] == 1
    assert s["failed_over_streams"] >= 1, "the crash must orphan someone"
    assert s["dropped_streams"] == 0
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_ok"] == s["parity_checked"] == s["n_requests"]
    assert s["pages_conserved"]


def test_chaos_handoff_stall_falls_back_to_prefill():
    """A stalled spill-tier consult costs a re-prefill, never a wrong
    token: the router refuses the spilled run once (stall_fallbacks), the
    request recomputes its prefix, and every stream stays bit-identical
    with the cross-tier ledger closed."""
    s = run_serving_chaos("handoff_stall", seed=0)
    assert s["faults_fired"] == {"handoff_stall": 1}
    assert s["spill"]["stall_fallbacks"] >= 1
    assert s["dropped_streams"] == 0
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_ok"] == s["parity_checked"] == s["n_requests"]
    assert s["pages_conserved"]


def test_chaos_spill_corrupt_discards_never_poisons():
    """Host-RAM corruption of a spilled KV page is caught by the crc32
    verify at re-adoption and discarded — the page NEVER re-enters the
    device pool, so no stream can decode from damaged KV. The victim
    re-prefills; parity stays exact; the spill ledger accounts for the
    discard (total_spilled = resident + readopted + corrupt_discarded +
    capacity_dropped + stale_discarded)."""
    s = run_serving_chaos("spill_corrupt", seed=0)
    assert s["faults_fired"] == {"spill_corrupt": 1}
    assert s["spill"]["corrupt_discarded"] >= 1
    assert s["poisoned"] == 0
    assert s["dropped_streams"] == 0
    assert s["statuses"] == {"ok": s["n_requests"]}
    assert s["parity_ok"] == s["parity_checked"] == s["n_requests"]
    assert s["pages_conserved"]


def test_chaos_run_serve_cli_emits_one_json_line(capsys):
    """`chaos_run.py --serve` holds the one-JSON-line driver contract and
    carries the chaos verdict fields."""
    mod = runpy.run_path(
        os.path.join(REPO, "tools", "chaos_run.py"), run_name="chaos_under_test"
    )
    argv, sys.argv = sys.argv, [
        "chaos_run.py", "--serve", "--fault", "kill_mid_decode@5",
    ]
    try:
        rc = mod["main"]()
    finally:
        sys.argv = argv
    assert rc == 0
    out = capsys.readouterr().out
    rec, problems = parse_single_json_line(out)
    assert not problems, problems
    assert rec["tool"] == "chaos_run" and rec["mode"] == "serve"
    assert rec["status"] == "ok"
    assert rec["faults_fired"] == {"kill_mid_decode": 1}
    assert rec["pages_conserved"] is True
    # the record round-trips as strict JSON (no NaN etc.)
    json.loads(out)


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
        "--set", f"data_dir={data}",
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
