"""The serving engine over models/mimo_v2.py's two kinds of paged cache, the
entry points, and the benchmark cell. CPU, toy widths, float32 under "highest"
(conftest). The model's own parity tests: tests/test_mimo_v2.py."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.models.mimo_v2 import GLOBAL, WINDOW, MimoV2
from midgpt_tpu.sampling.serve import ServeEngine
from test_mimo_v2 import ROOT, _load, _tokens, model, toy  # noqa: F401 (model: the module-scoped fixture)
from rehearsal_tree import run_rehearsal


# ---------------------------------------------------------------------------
# the engine over two kinds of cache
# ---------------------------------------------------------------------------


_APPLY = jax.jit(MimoV2.apply, static_argnums=0)


def _greedy(c, params, prompt, n):
    """The full forward's argmax chain (causal: a padded buffer of one length, read at the last real position)."""
    seq = np.zeros((1, c.block_size), np.int32)
    seq[0, :len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        seq[0, i] = int(np.argmax(np.asarray(_APPLY(c, params, jnp.asarray(seq)))[0, i - 1]))
    return seq[0, :len(prompt) + n]


def _conserved(eng):
    assert len(eng.allocators) == 2 and eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)


@pytest.mark.parametrize("overlap", ["off", "group"])
def test_engine_serves_a_mixed_queue_like_the_model_path(model, overlap):
    """(e) short and long requests in one queue, more requests than slots,
    greedy: every stream is the full forward's argmax chain, so every step's
    logits agreed; window pages a slot stay bounded while contexts pass 60."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4,
                      cache_dtype="float32", overlap=overlap, round_group=2)
    work = [(37, 9), (5, 12), (50, 20), (11, 7), (23, 30)]
    uids = {eng.submit(_tokens(p, seed=p), m): (p, m) for p, m in work}
    while not eng.idle:
        eng.step()
        _conserved(eng)
    for uid, (p, m) in uids.items():
        np.testing.assert_array_equal(eng.finished[uid].tokens, _greedy(c, params, _tokens(p, seed=p), m))
    assert eng.next_logits() == {}  # nothing left to decode
    counters = eng.serve_counters()
    burst = max(eng.prefill_chunk, eng.decode_chunk * eng.round_group)
    assert 0 < counters["kv.window_tokens_per_slot_max"] <= c.sliding_window + burst + eng.page_size
    assert "kv.global_pages_reclaimed" not in counters and counters["kv.window_pages_reclaimed"] > 0
    assert counters["kv.global_pages_live"] == counters["kv.window_pages_live"] == 0
    assert counters["moe.dropped"] == 0 and counters["moe.decode_steps"] > 0
    assert [a.free_count for a in eng.allocators] == [a.num_pages - 1 for a in eng.allocators]


def test_engine_hands_out_the_logits_its_rounds_sample_from(model):
    """(e) logits, not tokens: with several slots live, sampled at a
    temperature, the prefill program's logits at each prompt's last position
    (`on_first_logits`) and the logits every later decode round starts from
    (`next_logits`: the round's own cache, tables and lengths, after window
    pages were reclaimed and earlier rounds' K/V written) are the full
    forward's on the tokens the engine produced; probing changes no stream."""
    c, params = model
    work = [(37, 13), (50, 13), (11, 13)]  # a prefill row and, from the second of three decode rounds on, a probe a round

    def serve(probe):
        first, later = {}, {}
        eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4, temperature=0.8, seed=5,
                          cache_dtype="float32", on_first_logits=lambda uid, row: first.setdefault(uid, np.array(row)))
        uids = {eng.submit(_tokens(p, seed=p), m): p for p, m in work}
        while not eng.idle:
            if probe:
                fed = {s.request.uid: s.length for s in eng.slots if s is not None}
                for uid, row in eng.next_logits().items():
                    later.setdefault(uid, []).append((fed[uid], row))
            eng.step()
            _conserved(eng)
        return eng, uids, first, later

    eng, uids, first, later = serve(probe=True)
    plain = serve(probe=False)[0]
    assert eng.serve_counters()["kv.window_pages_reclaimed"] > 0
    for uid, p in uids.items():
        seq = eng.finished[uid].tokens
        np.testing.assert_array_equal(seq, plain.finished[uid].tokens)
        buf = np.zeros((1, c.block_size), np.int32)
        buf[0, :len(seq)] = seq
        want = np.asarray(_APPLY(c, params, jnp.asarray(buf)))[0]
        np.testing.assert_allclose(first[uid], want[p - 1], atol=2e-5)
        assert len(later[uid]) >= 2 and all(r >= p for r, _ in later[uid])
        for r, row in later[uid]:
            np.testing.assert_allclose(row, want[r], atol=2e-5)


# ---------------------------------------------------------------------------
# the prefill program samples each row's first token itself
# ---------------------------------------------------------------------------

_GPT = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)
_PAGE = 4


@pytest.fixture(scope="module")
def gpt_params():
    return GPT.init(_GPT, jax.random.PRNGKey(0))


def _prefill_case(shape, model, gpt_params):
    """(config, params, tokens, start, n_valid, a fresh cache, table, rows that hold a chunk) of one of the three
    shapes a family hands logits out in (models/__init__.py `prefill_paged_chunk`)."""
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    if shape == "batch":  # (W, V): four slots' chunks, one mid-prompt, one place empty
        start, n_valid = [0, 8, 0, 0], [8, 3, 0, 5]
        tokens = np.stack([_tokens(8, seed=r, vocab=_GPT.vocab_size) for r in range(4)])
        table = 1 + np.arange(16, dtype=np.int32).reshape(4, 4)
        table[2] = 0  # the empty place: the sink page
        return (_GPT, gpt_params, i32(tokens), i32(start), i32(n_valid), lambda: GPT.init_cache(_GPT, (17,), _PAGE, jnp.float32),
                i32(table), [0, 1, 3])
    tokens, table = i32(_tokens(8, seed=9)[None] % 96), i32(np.arange(1, 3)[None])
    if shape == "one_row_all":  # the GPT's one-row call: (1, T, V), n_valid < T
        return (_GPT, gpt_params, tokens, i32(0), i32(5), lambda: GPT.init_cache(_GPT, (3,), _PAGE, jnp.float32), table, [0])
    c, params = model  # MimoV2's: (1, 1, V), the last valid row's alone
    return c, params, tokens, i32(0), i32(5), lambda: MimoV2.init_cache(c, (3, 3), _PAGE, jnp.float32), (table, table), [0]


@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.8, None), (0.8, 5)], ids=["greedy", "t0.8", "t0.8_top5"])
@pytest.mark.parametrize("shape,family_gives", [("batch", (4, 96)), ("one_row_all", (1, 8, 96)), ("one_row_last", (1, 1, 97))])
def test_prefill_program_samples_its_first_tokens_from_the_rows_it_hands_back(model, gpt_params, shape, family_gives,
                                                                               temperature, top_k):
    """`_serve_prefill_chunk` brings what the family hands out to one row a
    slot, the last valid position's, and its tokens are `sample_logits` of
    those rows under the key it splits off the engine's, which it was given
    (the f32 argmax at temperature 0)."""
    from midgpt_tpu.sampling.engine import sample_logits
    from midgpt_tpu.sampling.serve import _serve_prefill_chunk

    config, params, tokens, start, n_valid, cache, table, live = _prefill_case(shape, model, gpt_params)
    family = jax.jit(lambda p, ca: config.model().prefill_paged_chunk(config, p, tokens, start, n_valid, ca, table))
    want = np.asarray(family(params, cache())[0])
    assert want.shape == family_gives
    if want.ndim == 3:
        want = want[:, min(int(n_valid), want.shape[1]) - 1]
    key = None if temperature == 0.0 else jax.random.PRNGKey(11)
    first, rows, _, next_key = _serve_prefill_chunk(config, params, tokens, start, n_valid, cache(), table, None, "gather",
                                                    temperature, top_k, None, key)
    if key is None:
        assert next_key is None
    else:  # the program's first operation is the split the host used to make: it keeps one half and hands back the other
        kept, key = jax.random.split(key)
        np.testing.assert_array_equal(np.asarray(next_key), np.asarray(kept))
    assert (first.shape, first.dtype, rows.shape) == ((tokens.shape[0],), jnp.int32, want.shape)
    np.testing.assert_allclose(np.asarray(rows)[live], want[live], atol=1e-6)
    if temperature == 0.0:
        expect = np.argmax(np.asarray(rows, np.float32), axis=-1)
    else:
        expect = np.asarray(sample_logits(rows, key, temperature, top_k, None))
        if top_k is not None:  # every token is one of its row's top five
            assert (np.asarray(rows)[np.arange(len(expect)), expect] >= np.sort(np.asarray(rows), -1)[:, -top_k]).all()
    np.testing.assert_array_equal(np.asarray(first), expect)


@pytest.mark.parametrize("width", [16, 1])
def test_on_first_logits_gets_the_row_the_first_token_was_sampled_from(model, gpt_params, width):
    """The hook is what brings a call's logits to the host, once a call that
    ends a prompt; greedy, each row it is handed argmaxes to the token the
    engine appended. Width 16: the GPT, prompts ending together in one call;
    width 1: MimoV2's one-row call."""
    got = {}
    hook = lambda uid, row: got.setdefault(uid, np.array(row))
    if width == 16:
        config, params = _GPT, gpt_params
        eng = ServeEngine(config, params, max_slots=16, page_size=4, prefill_chunk=16, decode_chunk=4,
                          cache_dtype="float32", on_first_logits=hook)
        work = [(5, 4), (16, 3), (21, 5), (9, 2), (40, 4), (33, 1)]
    else:
        config, params = model
        eng = ServeEngine(config, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4,
                          cache_dtype="float32", on_first_logits=hook)
        work = [(37, 4), (5, 1), (23, 3)]
    assert eng.prefill_width == width and eng.temperature == 0.0
    uids = {eng.submit(_tokens(p, seed=p) % config.vocab_size, m): p for p, m in work}
    done = eng.run()
    assert set(got) == set(uids) == set(done)
    for uid, p in uids.items():
        assert got[uid].shape == (config.vocab_size,)
        assert int(np.argmax(got[uid].astype(np.float32))) == done[uid].tokens[p]
    assert eng.first_tokens == len(work)
    # calls that ended a prompt: the width-16 engine's end three in its first call, one in its second, two in its third
    assert eng.first_logit_pulls == (3 if width == 16 else len(work))


def test_engine_conserves_both_pools_through_evict_and_cancel(model):
    """(d) a global pool too small for every slot at once: the youngest slot is
    preempted and re-queued, one request is cancelled mid-stream, and after
    every round free + live == pool for BOTH kinds; the streams that finish are
    still the model path's."""
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
    assert "kv.global_pages_reclaimed" not in eng.serve_counters()  # a kind without a window has no reclaim counter


def test_window_pool_is_sized_from_window_chunk_and_page(model):
    c, params = model
    eng = ServeEngine(c, params, max_slots=5, page_size=4, prefill_chunk=10, decode_chunk=4, cache_dtype="float32")
    assert [k.name for k in eng.kinds] == [GLOBAL, WINDOW]
    assert eng.allocators[1].num_pages == 1 + 5 * (-(-(8 + 10) // 4) + 1)
    assert eng.cache.pools[1][0].shape[2] == eng.allocators[1].num_pages and eng.cache.pools[0][0].shape[2] == eng.allocator.num_pages
    assert eng.cache.pools[0][0].shape[:2] == (2, 1) and eng.cache.pools[1][0].shape[:2] == (5, 2)
    assert eng.cache.pools[0][0].shape[-1] == 24 and eng.cache.pools[0][1].shape[-1] == 16


@pytest.mark.parametrize("what,kw", [
    ("prefix cache", dict(prefix_cache=True)),
    ("speculative decoding", dict(draft=True)),
    ("int8 pools", dict(cache_dtype="int8")),
    ("serving mesh", dict(mesh=True)),
    ("byte-budgeted pool", dict(pool_hbm_bytes=1 << 20)),
    ("hot-swap", dict(call="hot_swap")),
    ("pool resize", dict(call="resize")),
    ("spill tier", dict(call="attach_spill")),
    ("disaggregated prefill", dict(call="disagg")),
])
def test_what_is_not_wired_for_two_kinds_of_cache_is_refused_by_name(model, what, kw):
    """(f) each mechanism stops with NotImplementedError naming it."""
    c, params = model
    kw = dict(kw)
    call = kw.pop("call", None)
    if kw.pop("draft", False):
        kw.update(draft_params=params, draft_config=c)
    if kw.pop("mesh", False):
        kw["mesh"] = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "tp"))
    with pytest.raises(NotImplementedError, match=what.split()[0]):
        if call == "disagg":
            from midgpt_tpu.sampling.disagg import DisaggServe

            DisaggServe(c, params, max_slots=2)
        eng = ServeEngine(c, params, max_slots=2, page_size=4, prefill_chunk=8, **{"cache_dtype": "float32", **kw})
        if call == "hot_swap":
            eng.hot_swap(params)
        elif call == "resize":
            eng.resize(64)
        elif call == "attach_spill":
            eng.attach_spill(object())


def test_training_is_refused_by_name_and_the_config_round_trips():
    from midgpt_tpu.config import from_json, load_config, to_json

    exp = load_config("mimo_v2_5")
    mc = exp.model_config
    assert mc.layers_of(GLOBAL) == (0, 5, 11, 17, 23, 29, 35, 41, 47) and mc.moe_layers == tuple(range(1, 48))
    assert from_json(to_json(exp)).model_config == mc
    with pytest.raises(NotImplementedError, match="cannot train"):
        mc.check_training("launch.py")
    assert mc.check_serving("sample.py") is None


def test_the_benchmark_share_counts_what_the_issue_reckoned():
    """The cut the configuration file makes, under eval_shape: 5.42 B parameters."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/mimo_v2_5_ep16.json")))
    from midgpt_tpu.config import load_config

    mc = dataclasses.replace(load_config(cfg["repo_config"]).model_config, **cfg["overrides"]["model_config"])
    ran = dataclasses.asdict(mc)
    assert all(ran[k] == v for k, v in cfg["model"].items())
    shapes = jax.eval_shape(lambda k: MimoV2.init(mc, k), jax.random.PRNGKey(0))
    n = MimoV2.count_params(shapes)
    assert abs(n / 1e9 - 5.42) < 0.02, n
    cat = [json.loads(l) for l in open("/opt/skills/guides/model-configs/architectures.jsonl")] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in cat:
        if row["name"] == "MiMo-V2.5":
            for k, v in row["config"].items():
                if k not in cfg["reduced"]:
                    assert cfg[k] == v, k


def test_kimi_linear_says_what_serving_it_still_lacks():
    from midgpt_tpu.config import load_config

    with pytest.raises(NotImplementedError, match="STATE kind of cache.*STILL missing for this family"):
        load_config("kimi_linear_48b_a3b").model_config.check_serving("sample.py")


# ---------------------------------------------------------------------------
# (h) the benchmark cell
# ---------------------------------------------------------------------------


def test_the_new_traffic_is_one_multiset_for_every_seed_and_selfcheck_passes():
    loadgen = _load("benchmarks/loadgen.py")
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/agent_mixed_closed.json")))
    a, b = loadgen.Traffic(spec, 1, 19072), loadgen.Traffic(spec, 2**31 + 12345, 19072)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 64
    assert min(a.prompt_lens) == 128 and max(a.prompt_lens) == 16384 and all(o % 8 == 0 for o in a.output_lens)
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 17408
    assert [r.max_new_tokens for r in a.prime()] == [r.max_new_tokens for r in b.prime()]
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--selfcheck"],
                          cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --workload serve_mimo_v2_5_mixed --rehearse-cpu` exits 0 and
    names every metric declared for the cell that a CPU run can produce: all but
    those that read the TPU's Mosaic custom calls (`global_decode_attention_*`,
    `kv_write_*`), its `XLA Modules` line (`serve.prefill_device_share`) or its
    memory counters (`serve.peak_hbm_gb`)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "serve_mimo_v2_5_mixed"
    declared = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [])}
    assert {"serve.attn_global_ms", "serve.attn_window_ms", "serve.moe_route_ms", "serve.moe_experts_ms",
            "serve.model_unattributed_ms", "kv.global_pool_fill", "kv.window_tokens_per_slot_max",
            "serve.moe_experts_touched", "serve.moe_load_max_over_mean",
            "global_decode_attention_roofline", "kv_write_roofline", "engine.occupancy", "setup.programs"} <= declared
    assert not {"paged_attention_ms_per_token", "paged_attention_roofline"} & declared
    e2e = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    assert e2e == {"setup_s", "serve_tokens_per_s"}
    proc = run_rehearsal(tmp_path, cell, seconds="2")  # a tree of its own: tests/rehearsal_tree.py
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    cpu_cannot = {"global_decode_attention_ms_per_token", "global_decode_attention_roofline", "kv_write_ms_per_token",
                  "kv_write_roofline", "serve.prefill_device_share", "serve.peak_hbm_gb"}
    # PR 53: the serving engine's device-trace metrics join the trace's `XLA Modules` line, which a CPU trace lacks
    cpu_cannot |= {m["name"] for m in bench["per_layer"] if m["layer"] == "serving engine" and m["source"] == "device_trace"}
    assert declared - cpu_cannot <= set(last["would_report"]), sorted(declared - cpu_cannot - set(last["would_report"]))
    assert "correctness: ServeEngine" in proc.stdout and "-> ok" in proc.stdout
    assert "serve.moe_overflowed" not in declared  # the serving dispatch has no buffer to overflow


def test_the_8_bit_control_is_refused_by_the_cells_own_limits(tmp_path):
    """The cell's control entry point: the reference with 8-bit matrices in
    the program's place, through the same rows, `judge` and limits, comes out
    NOT CORRECT while the program is correct (exit 0 says both)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "serve_family_cell.py"), "--workload", "serve_mimo_v2_5_mixed",
         "--seed", "3000000019", "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"program_correct": True, "control_correct": False}
    control = next(l for l in proc.stdout.splitlines() if l.startswith("[bench] control:"))
    assert "float8_e4m3fn" in control and control.endswith("NOT CORRECT")


def test_sample_py_serves_a_saved_checkpoint_of_the_family(tmp_path):
    """sample.py reaches the engine for this family through the same code as
    for the GPT: seeded parameters saved with the repo's checkpoint writer,
    restored through the family namespace, sampled greedily: the tokens are
    the full forward's argmax chain."""
    import pickle

    from midgpt_tpu.config import load_config, to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = toy(vocab_size=65, block_size=64)
    params = MimoV2.init(c, jax.random.PRNGKey(7))
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("mimo_v2_5").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
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
    # the batch engine is the GPT's dense cache: refused by name before any restore, never switched to another
    batch = subprocess.run([sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}", "--start=AB#"],
                           cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=600)
    assert batch.returncode != 0 and "--engine=continuous" in batch.stderr and "restored checkpoint" not in batch.stdout
