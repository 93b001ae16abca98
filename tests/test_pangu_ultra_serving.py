"""The serving engine over models/pangu_ultra.py's latent paged cache (one
kind, one pool array), the entry point, and the benchmark cell. CPU, toy
widths, float32 under "highest" (conftest). The model's own parity tests:
tests/test_pangu_ultra.py."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.pangu_ultra import PanguUltra
from midgpt_tpu.sampling.serve import ServeEngine
from test_pangu_ultra import ROOT, _load, _tokens, model, reference, toy  # noqa: F401 (model: the module-scoped fixture)
from rehearsal_tree import run_rehearsal

_APPLY = jax.jit(PanguUltra.apply, static_argnums=0)


def _greedy(c, params, prompt, n):
    """The full forward's argmax chain (causal: a padded buffer of one length, read at the last real position)."""
    seq = np.zeros((1, c.block_size), np.int32)
    seq[0, :len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        seq[0, i] = int(np.argmax(np.asarray(_APPLY(c, params, jnp.asarray(seq)))[0, i - 1]))
    return seq[0, :len(prompt) + n]


def _conserved(eng):
    assert len(eng.allocators) == 1 and eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)


@pytest.mark.parametrize("overlap", ["off", "group"])
def test_engine_serves_a_mixed_queue_like_the_model_path(model, overlap):
    """Short and long requests in one queue, more requests than slots, greedy:
    every stream is the full (expanded) forward's argmax chain, so every
    chunked prefill and every absorbed decode step's logits agreed."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4,
                      cache_dtype="float32", overlap=overlap, round_group=2)
    assert [k.name for k in eng.kinds] == ["latent"] and eng.prefill_width == 1
    assert [a.shape for a in eng.cache.pool_arrays()] == [(c.n_layer, 1, eng.allocator.num_pages, 4, c.latent_dim)]
    work = [(37, 9), (5, 12), (50, 20), (11, 7), (23, 30)]
    uids = {eng.submit(_tokens(p, seed=p), m): (p, m) for p, m in work}
    while not eng.idle:
        eng.step()
        _conserved(eng)
    for uid, (p, m) in uids.items():
        np.testing.assert_array_equal(eng.finished[uid].tokens, _greedy(c, params, _tokens(p, seed=p), m))
    counters = eng.serve_counters()
    # a kind without a window has no reclaim counter (what a family cell's check reads `correct` from)
    assert not [k for k in counters if k.endswith("_pages_reclaimed") or k.endswith("_tokens_per_slot_max")]
    assert counters["kv.latent_pages_live"] == 0 and counters["kv.latent_pages_live_max"] > 0
    assert counters["kv.latent_bytes_per_token"] == c.n_layer * c.latent_dim * 4
    assert counters["moe.dropped"] == 0 and counters["moe.decode_steps"] > 0 and counters["moe.experts_touched"] > 0
    assert eng.allocator.free_count == eng.allocator.num_pages - 1


def test_engine_hands_out_the_logits_its_rounds_sample_from(model):
    """Logits, not tokens, against the float32 REFERENCE in expanded form:
    with several slots live, sampled at a temperature, the prefill program's
    logits at each prompt's last position (`on_first_logits`) and the logits
    every later decode round starts from (`next_logits`: the round's own
    latent cache, table and lengths) are the reference's full forward's on the
    tokens the engine produced; probing changes no stream."""
    c, params = model
    work = [(37, 13), (50, 13), (11, 13)]

    def serve(probe):
        first, later = {}, {}
        eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4, temperature=0.8, seed=5,
                          cache_dtype="float32", on_first_logits=lambda uid, row: first.setdefault(uid, np.array(row)))
        uids = {eng.submit(_tokens(p, seed=p), m): p for p, m in work}
        live_max = 0
        while not eng.idle:
            if probe:
                fed = {s.request.uid: s.length for s in eng.slots if s is not None}
                for uid, row in eng.next_logits().items():
                    later.setdefault(uid, []).append((fed[uid], row))
            live_max = max(live_max, sum(s is not None for s in eng.slots))
            eng.step()
            _conserved(eng)
        return eng, uids, first, later, live_max

    eng, uids, first, later, live_max = serve(probe=True)
    plain = serve(probe=False)[0]
    assert live_max == 3
    for uid, p in uids.items():
        seq = eng.finished[uid].tokens
        np.testing.assert_array_equal(seq, plain.finished[uid].tokens)
        want = np.asarray(reference.logits(params, jnp.asarray(np.asarray(seq, np.int32)), dataclasses.asdict(c)))
        np.testing.assert_allclose(first[uid], want[p - 1], atol=2e-5)
        assert len(later[uid]) >= 2 and all(r >= p for r, _ in later[uid])
        for r, row in later[uid]:
            np.testing.assert_allclose(row, want[r], atol=2e-5)


def test_engine_conserves_the_pool_through_evict_and_cancel(model):
    """A pool too small for every slot at once: the youngest slot is preempted
    and re-queued, one request is cancelled mid-stream, and after every round
    free + live == pool; the streams that finish are still the model path's."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, num_pages=24, page_size=4, prefill_chunk=8, decode_chunk=4, cache_dtype="float32")
    work = [(30, 30), (28, 28), (26, 26)]
    uids = [eng.submit(_tokens(p, seed=p), m) for p, m in work]
    rounds = 0
    while not eng.idle:
        eng.step()
        _conserved(eng)
        rounds += 1
        if rounds == 6:
            assert eng.cancel(uids[2])
            _conserved(eng)
    assert eng.preemptions > 0 and eng.finished[uids[2]].status == "cancelled"
    for uid, (p, m) in list(zip(uids, work))[:2]:
        np.testing.assert_array_equal(eng.finished[uid].tokens, _greedy(c, params, _tokens(p, seed=p), m))


@pytest.mark.parametrize("what,kw", [
    ("int8", dict(cache_dtype="int8")),
    ("draft", dict(draft=True)),
    ("mesh", dict(mesh=True)),
])
def test_what_is_not_wired_over_a_latent_cache_is_refused(model, what, kw):
    """No int8 latent rows, no verify step (so no draft model), no serving mesh: each stops with an error naming it."""
    c, params = model
    kw = dict(kw)
    if kw.pop("draft", False):
        kw.update(draft_params=params, draft_config=c)
    if kw.pop("mesh", False):
        kw["mesh"] = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "tp"))
    with pytest.raises((NotImplementedError, ValueError, AttributeError)):
        eng = ServeEngine(c, params, max_slots=2, page_size=4, prefill_chunk=8, **{"cache_dtype": "float32", **kw})
        eng.submit(_tokens(9), 4)
        eng.run()


def test_kimi_linear_says_what_serving_it_still_lacks():
    """With a latent pool, the absorbed decode path and (since PR 59) a STATE
    kind with its two delta-rule steps in the tree, what the `kimi_linear` family
    still lacks is a NoPE latent row and its own serving members."""
    from midgpt_tpu.config import load_config

    with pytest.raises(NotImplementedError, match="STILL missing for this family: a NoPE variant of the latent row") as e:
        load_config("kimi_linear_48b_a3b").model_config.check_serving("sample.py")
    assert "models/pangu_ultra.py" in str(e.value) and "no absorbed-latent" not in str(e.value)
    assert "models/olmo_hybrid.py" in str(e.value) and "sampling/pages.py" in str(e.value)


# ---------------------------------------------------------------------------
# the benchmark cell
# ---------------------------------------------------------------------------

CELL = "serve_pangu_ultra_longctx"


def test_the_new_traffic_is_one_multiset_for_every_seed():
    loadgen = _load("benchmarks/loadgen.py")
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/longctx_mixed_closed.json")))
    a, b = loadgen.Traffic(spec, 1, 19200), loadgen.Traffic(spec, 2**31 + 12345, 19200)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 64 and a.clients == 16 == spec["engine"]["max_slots"]
    assert min(a.prompt_lens) == 512 and max(a.prompt_lens) == 32768 and all(o % 8 == 0 for o in a.output_lens)
    assert min(a.output_lens) == 64 and max(a.output_lens) == 1024
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 33792 == 2 * spec["engine"]["pool_tokens_per_slot"]
    assert 4000 < sorted(a.prompt_lens)[32] < 4300  # the median stratum
    assert [r.max_new_tokens for r in a.prime()] == [r.max_new_tokens for r in b.prime()]
    # the warm-up plans decode programs by power-of-two page buckets: the model's cap must be one
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/openpangu_ultra_moe_ep16.json")))
    pages = cfg["model"]["block_size"] // spec["engine"]["page_size"]
    assert pages & (pages - 1) == 0 and cfg["model"]["block_size"] >= spec["max_total"]


def test_benchmark_declares_the_cell_and_only_adds():
    """One configuration and one cell more; the metrics the cell lists are the
    family-neutral ones under the names the benchmark has, the latent ones new,
    and none of the window / global ones."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("openpangu_ultra_moe_ep16", "longctx_mixed_closed", 1)
    declared = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert {"serve.attn_latent_ms", "serve.moe_shared_ms", "serve.moe_route_ms", "serve.moe_experts_ms",
            "serve.model_unattributed_ms", "kv.latent_pool_fill", "kv.latent_bytes_per_token", "serve.moe_experts_touched",
            "serve.moe_load_max_over_mean", "latent_decode_attention_ms_per_token", "latent_decode_attention_roofline",
            "kv_write_ms_per_token", "kv_write_roofline", "engine.occupancy", "setup.programs", "window.compiles"} <= declared
    assert not {"serve.attn_global_ms", "serve.attn_window_ms", "global_decode_attention_roofline", "kv.global_pool_fill",
                "kv.window_tokens_per_slot_max", "paged_attention_roofline"} & declared
    mimo = {m["name"] for m in bench["per_layer"] if "serve_mimo_v2_5_mixed" in m.get("workloads", [])}
    assert not {n for n in mimo if "latent" in n or n == "serve.moe_shared_ms"}
    e2e = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"setup_s", "serve_tokens_per_s"}


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --workload serve_pangu_ultra_longctx --rehearse-cpu` exits 0, is
    `correct` (its only kind has no window: no `_pages_reclaimed` counter
    holds the check back) and names every metric declared for the cell that a
    CPU run can produce: all but those that read the TPU's Mosaic custom calls,
    its `XLA Modules` line or its memory counters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    proc = run_rehearsal(tmp_path, CELL, seconds="1")  # a tree of its own: tests/rehearsal_tree.py
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    cpu_cannot = {"latent_decode_attention_ms_per_token", "latent_decode_attention_roofline", "kv_write_ms_per_token",
                  "kv_write_roofline", "serve.prefill_device_share", "serve.peak_hbm_gb"}
    # PR 53: the serving engine's device-trace metrics join the trace's `XLA Modules` line, which a CPU trace lacks
    cpu_cannot |= {m["name"] for m in bench["per_layer"] if m["layer"] == "serving engine" and m["source"] == "device_trace"}
    assert declared - cpu_cannot <= set(last["would_report"]), sorted(declared - cpu_cannot - set(last["would_report"]))
    assert "correctness: ServeEngine" in proc.stdout and "-> ok" in proc.stdout
    assert "window pages reclaimed []" in proc.stdout
    assert "serve scopes (latent family)" in proc.stdout


def test_the_8_bit_control_is_refused_by_the_cells_own_limits(tmp_path):
    """The cell's control entry point: the reference with 8-bit matrices in
    the program's place, through the same rows, `judge` and limits, comes out
    NOT CORRECT while the program is correct (exit 0 says both)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "serve_latent_cell.py"), "--workload", CELL,
         "--seed", "3000000019", "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"program_correct": True, "control_correct": False}


def test_sample_py_serves_a_saved_checkpoint_of_the_family(tmp_path):
    """sample.py reaches the engine for this family through the same code as
    for the GPT: seeded parameters saved with the repo's checkpoint writer,
    restored through the family namespace, sampled greedily: the tokens are
    the full forward's argmax chain."""
    import pickle

    from midgpt_tpu.config import load_config, to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = toy(vocab_size=65, block_size=64)
    params = PanguUltra.init(c, jax.random.PRNGKey(7))
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("openpangu_ultra_moe").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
    (tmp_path / "config.json").write_text(to_json(exp))
    mngr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert mngr.save(3, {"params": params}, force=True)
    mngr.wait()
    mngr.close()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}", "--start=AB#", "--num_samples=2",
         "--max_new_tokens=6", "--temperature=0.0", "--engine=continuous"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "restored checkpoint step 3" in proc.stdout
    new = json.loads(next(l for l in proc.stdout.splitlines() if l.startswith("new_tokens: "))[len("new_tokens: "):])
    prompt = np.asarray([32, 33, 2], np.int32)  # "AB#" under the codec above
    with jax.default_matmul_precision("default"):  # as the entry point runs
        want = _greedy(c, params, prompt, 6)[3:].tolist()
    assert new == [want, want]
