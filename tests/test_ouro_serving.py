"""The serving engine over models/ouro.py's looped paged cache (one kind, K and
V pools of n_loop * n_layer layers), the entry point, and the benchmark cell.
CPU, toy widths, float32 under "highest" (conftest). The model's own parity
tests: tests/test_ouro.py. No family is named in sampling/: everything here
goes through `ServeEngine` and the family contract."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.ouro import Ouro
from midgpt_tpu.sampling.serve import ServeEngine
from test_ouro import ROOT, _load, _tokens, model, reference, seeded, toy  # noqa: F401 (model: the module-scoped fixture)
from rehearsal_tree import run_rehearsal

_APPLY = jax.jit(Ouro.apply, static_argnums=0)


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
    every stream is the full forward's argmax chain, so every batched chunked
    prefill and every looped decode step's logits agreed."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4,
                      cache_dtype="float32", overlap=overlap, round_group=2)
    assert [k.name for k in eng.kinds] == ["looped"] and eng.prefill_width == 3
    shape = (c.n_loop * c.n_layer, c.n_head, eng.allocator.num_pages, 4, c.head_dim)
    assert [a.shape for a in eng.cache.pool_arrays()] == [shape, shape]
    work = [(37, 9), (5, 12), (50, 20), (11, 7), (23, 30)]
    uids = {eng.submit(_tokens(p, seed=p), m): (p, m) for p, m in work}
    while not eng.idle:
        eng.step()
        _conserved(eng)
    for uid, (p, m) in uids.items():
        np.testing.assert_array_equal(eng.finished[uid].tokens, _greedy(c, params, _tokens(p, seed=p), m))
    counters = eng.serve_counters()
    assert not [k for k in counters if k.endswith("_pages_reclaimed") or k.endswith("_tokens_per_slot_max")]
    assert counters["kv.looped_pages_live"] == 0 and counters["kv.looped_pages_live_max"] > 0
    assert counters["kv.looped_bytes_per_token"] == 2 * c.n_loop * c.n_layer * c.n_head * c.head_dim * 4
    assert sum(a.nbytes for a in eng.cache.pool_arrays()) == counters["kv.looped_bytes_per_token"] * eng.allocator.num_pages * 4
    # every decoded token ran n_loop passes (a request's first token comes from the prefill program)
    assert counters["loop.passes_run"] == c.n_loop * sum(m - 1 for _, m in work)
    assert abs(sum(counters[f"loop.exit_mass_{r + 1}"] for r in range(c.n_loop)) - sum(m - 1 for _, m in work)) < 1e-2
    assert 1.0 < counters["loop.exit_pass_expected"] < c.n_loop
    assert eng.allocator.free_count == eng.allocator.num_pages - 1


@pytest.mark.parametrize("width", [3, 1], ids=["batched_prefill", "one_row_prefill"])
def test_engine_hands_out_the_logits_its_rounds_sample_from(model, width, monkeypatch):
    """Logits, not tokens, against the float32 REFERENCE: with slots of unequal
    length live together, sampled at a temperature, the prefill program's
    logits at each prompt's last position (`on_first_logits`) and the logits
    every later decode round starts from (`next_logits`: the round's own cache
    of n_loop * n_layer layers, table and lengths) are the reference's full
    forward's on the tokens the engine produced; probing changes no stream.
    `width` 1: a chunk past `PREFILL_ROWS` on its own, the family's one-row call."""
    c, params = model
    work = [(37, 13), (50, 13), (11, 13)]
    chunk = 10 if width == 3 else 20
    if width == 1:  # a chunk at the ridge on its own rides alone
        monkeypatch.setattr("midgpt_tpu.sampling.serve.PREFILL_ROWS", chunk)

    def serve_all(probe):
        first, later = {}, {}
        eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=chunk, decode_chunk=4, temperature=0.8, seed=5,
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

    eng, uids, first, later, live_max = serve_all(probe=True)
    plain = serve_all(probe=False)[0]
    assert live_max == 3 and eng.prefill_width == width
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
def test_what_is_not_wired_over_a_looped_cache_is_refused(model, what, kw):
    """No int8 pool, no verify step (so no draft model), no serving mesh: each stops with an error naming it."""
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


@pytest.mark.parametrize("program", ["decode4", "prefill"])
def test_serving_programs_hold_no_pool_sized_copy_in_any_loop(program):
    """The lowered decode chunk (steps x passes x layers: three nested loops)
    and prefill program (passes x layers) carry the pools through every loop
    with no pool-shaped copy beyond the CPU backend's per-scatter allowance
    (analysis/hlo_audit.loop_pool_copy_excess; the chip's own lowering is held
    to 0 relayouts and 0 copies in tests/test_chip_compile.py), and the
    program's text does not grow with `n_loop` or `n_layer`."""
    from midgpt_tpu.analysis.hlo_audit import loop_pool_copy_excess
    from midgpt_tpu.sampling import serve

    def lowered(c):
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        params = jax.tree.map(sds, jax.eval_shape(lambda k: Ouro.init(c, k), jax.random.PRNGKey(0)))
        cache = jax.tree.map(sds, jax.eval_shape(lambda: Ouro.init_cache(c, (13,), 4, jnp.float32)))
        arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
        B, T, key = 3, 8, arr((2,), jnp.uint32)
        if program == "decode4":
            low = serve._serve_decode_chunk.lower(c, params, arr((B,)), cache, arr((B, T)), arr((B,)), arr((B,), jnp.bool_), 4,
                                                  0.8, None, None, "gather", key)
        else:
            low = serve._serve_prefill_chunk.lower(c, params, arr((B, 8)), arr((B,)), arr((B,)), cache, arr((B, T)), None, "gather",
                                                   0.8, None, None, key)
        return low, "f32[%s]" % ",".join(map(str, cache.pools[0][0].shape))

    c = toy()
    low, pool = lowered(c)
    census = loop_pool_copy_excess(low.compile().as_text(), pool)
    assert len(census) >= (3 if program == "decode4" else 2), census  # the nested loops are rolled
    assert not {b: n for b, n in census.items() if n}, census
    sizes = {len(lowered(toy(n_layer=L, n_loop=R))[0].as_text()) for L, R in [(3, 4), (6, 4), (3, 2)]}
    assert max(sizes) - min(sizes) < 200, sizes  # only the digits of the shapes differ


# ---------------------------------------------------------------------------
# the benchmark cell
# ---------------------------------------------------------------------------

CELL = "serve_ouro_reason"


def test_the_new_traffic_is_one_multiset_for_every_seed():
    loadgen = _load("benchmarks/loadgen.py")
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/reason_closed.json")))
    a, b = loadgen.Traffic(spec, 1, 49152), loadgen.Traffic(spec, 2**31 + 12345, 49152)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 48 and a.clients == 12 == spec["engine"]["max_slots"]
    assert min(a.prompt_lens) == 32 and max(a.prompt_lens) == 256 and all(o % 8 == 0 for o in a.output_lens)
    assert min(a.output_lens) == 64 and max(a.output_lens) == 512
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 768
    assert 60 < sorted(a.prompt_lens)[24] < 70 and sorted(a.output_lens)[24] == 192  # the median strata
    assert sum(a.output_lens) / (sum(a.output_lens) + sum(a.prompt_lens)) > 0.7  # decode-heavy
    assert [r.max_new_tokens for r in a.prime()] == [r.max_new_tokens for r in b.prime()]
    es = spec["engine"]
    assert es["max_slots"] * -(-es["pool_tokens_per_slot"] // es["page_size"]) + 1 == 157
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/ouro_2p6b.json")))
    pages = cfg["model"]["block_size"] // es["page_size"]
    assert pages & (pages - 1) == 0 and cfg["model"]["block_size"] >= spec["max_total"]


def test_the_configuration_file_is_the_catalog_row_and_the_preset():
    """Every published key at its published value, nothing under `reduced`,
    and `model` is the repo preset as it stands."""
    from midgpt_tpu.config import load_config

    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/ouro_2p6b.json")))
    published = dict(head_dim=128, hidden_act="silu", hidden_size=2048, intermediate_size=5632, max_position_embeddings=65536,
                     model_type="ouro", num_attention_heads=16, num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-6,
                     rope_theta=1000000, tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1, vocab_size=49152)
    assert {k: cfg[k] for k in published} == published and cfg["layer_types"] == ["full_attention"] * 48
    assert cfg["reduced"] == [] and cfg["overrides"] == {} and cfg["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert cfg["model"] == dataclasses.asdict(load_config(cfg["repo_config"]).model_config)
    assert set(cfg["metrics"]["scopes"]) == {"attn", "mlp", "exit_gate", "lm_head"}


def test_benchmark_declares_the_cell_and_only_adds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro_2p6b", "reason_closed", 1)
    # the eighth cell and the sixth configuration: later PRs append after them, and edit neither
    assert bench["workloads"][7] is cell and bench["configs"][5]["name"] == "ouro_2p6b" and bench["configs"][5]["reduced"] == []
    declared = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    own = {"serve.loop_attn_ms", "serve.loop_mlp_ms", "serve.exit_gate_ms", "serve.lm_head_ms", "serve.weight_read_share",
           "loop.exit_pass_expected", "kv.looped_pool_fill", "kv.looped_bytes_per_token",
           "looped_decode_attention_ms_per_token", "looped_decode_attention_roofline"}
    assert own | {"serve.model_unattributed_ms", "kv_write_ms_per_token", "kv_write_roofline", "engine.occupancy",
                  "setup.programs", "setup.trace_lower_s", "window.compiles", "serve.device_idle_share"} <= declared
    assert not {n for n in declared if "moe" in n or "latent" in n or "global" in n or "window_" in n or n.startswith("paged_attention")}
    later = {"serve.lm_head_ms", "serve.weight_read_share"}  # PR 46's cell reports under these names too, appended after this one
    for m in bench["per_layer"]:
        if m["name"] in own:
            assert m["workloads"][0] == CELL and m["moves"] == "serve_tokens_per_s"
            assert m["workloads"] == [CELL] or m["name"] in later
    e2e = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"setup_s", "serve_tokens_per_s"}


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --workload serve_ouro_reason --rehearse-cpu --trace 1` exits 0,
    is `correct`, and names every metric declared for the cell that a CPU run
    can produce: all but those that read the TPU's Mosaic custom calls, its
    `XLA Modules` line, its peaks or its memory counters."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    proc = run_rehearsal(tmp_path, CELL, seconds="1", timeout=600)  # a tree of its own: tests/rehearsal_tree.py
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    cpu_cannot = {"looped_decode_attention_ms_per_token", "looped_decode_attention_roofline", "kv_write_ms_per_token",
                  "kv_write_roofline", "serve.prefill_device_share", "serve.peak_hbm_gb", "serve.weight_read_share"}
    # PR 53: the serving engine's device-trace metrics join the trace's `XLA Modules` line, which a CPU trace lacks
    cpu_cannot |= {m["name"] for m in bench["per_layer"] if m["layer"] == "serving engine" and m["source"] == "device_trace"}
    assert declared - cpu_cannot <= set(last["would_report"]), sorted(declared - cpu_cannot - set(last["would_report"]))
    assert "correctness: ServeEngine" in proc.stdout and "-> ok" in proc.stdout
    assert "serve scopes (from the configuration's list)" in proc.stdout
    assert "'kv.looped_bytes_per_token': 6144.0" in proc.stdout  # 2 x (4 x 3) layers x 4 heads x 16 channels x 4 B


def test_the_8_bit_control_is_refused_by_the_cells_limits(tmp_path):
    """The cell's control entry point: the reference with 8-bit matrices in
    the program's place, through the same rows, `judge` and limits, comes out
    NOT CORRECT while the program is correct (exit 0 says both)."""
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/reason_closed.json")))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", spec["kind"] + "_cell.py"), "--workload", CELL,
         "--seed", "3000000019", "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
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
    params = seeded(c, seed=7)
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("ouro_2p6b").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
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
