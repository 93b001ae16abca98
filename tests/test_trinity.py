"""models/trinity.py (AFMoE: rotary window layers beside position-free global
layers over two kinds of paged cache, QK-norm, an output gate, sandwich norms, a
muP embedding, sigmoid-routed experts with a selection bias beside a shared
expert) against the plain float32 reference that lies beside its benchmark
configuration, and the serving engine over it. CPU, toy widths, float32 under
"highest" (conftest): every tolerance below is float32 rounding through five
layers at logits of unit scale (readings 1e-6 to 3e-6), with a decade of room."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import ServeCache
from midgpt_tpu.models.mimo_v2 import GLOBAL, WINDOW
from midgpt_tpu.models.trinity import FULL, SLIDING, Trinity, TrinityConfig
from midgpt_tpu.ops.moe import route
from midgpt_tpu.sampling.serve import ServeEngine
from test_mimo_v2 import ROOT, _load, _tokens

reference = _load("benchmarks/configs/trinity_mini_pp_reference.py")
ATOL = 3e-5


def toy(**kw):
    base = dict(
        block_size=128, vocab_size=97, n_layer=5, n_head=4, n_embd=64, layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 2,
        n_dense_layers=1, head_dim=16, n_kv_heads=2, sliding_window=8, dense_width=96, n_experts=16, n_experts_held=16,
        moe_top_k=4, expert_width=24,
    )
    return TrinityConfig(**{**base, **kw})


def seeded(c, seed=0):
    """Seeded parameters with what `init` leaves at a constant made random: the
    selection bias (nonzero, so a test sees it select) and every gain."""
    params = Trinity.init(c, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    gain = lambda g: g * (1.0 + 0.2 * jax.random.normal(next(keys), g.shape))

    def layer(p):
        mlp = p.mlp
        if hasattr(mlp, "expert_bias"):
            mlp = dataclasses.replace(mlp, expert_bias=0.05 * jax.random.normal(next(keys), mlp.expert_bias.shape))
        attn = dataclasses.replace(p.attn, q_norm=gain(p.attn.q_norm), k_norm=gain(p.attn.k_norm))
        return dataclasses.replace(p, attn=attn, mlp=mlp, norm_in=gain(p.norm_in), norm_post_attn=gain(p.norm_post_attn),
                                   norm_pre_mlp=gain(p.norm_pre_mlp), norm_post_mlp=gain(p.norm_post_mlp))

    return dataclasses.replace(params, layers=tuple(layer(p) for p in params.layers), final_norm=gain(params.final_norm))


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, seeded(c)


def _ref(c, params, seq, **kw):
    return np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c), **kw))


def test_full_forward_matches_the_reference(model):
    """45 tokens through all five layers (three window layers whose band of 8
    cuts the context, the global layer, the dense and the expert FFNs)."""
    c, params = model
    seq = _tokens(45)
    np.testing.assert_allclose(np.asarray(Trinity.apply(c, params, jnp.asarray(seq[None])))[0], _ref(c, params, seq), atol=ATOL)
    assert [c.attn_kind(i) for i in range(5)] == [WINDOW, WINDOW, WINDOW, GLOBAL, WINDOW] and c.moe_layers == (1, 2, 3, 4)


def _one_layer_reference(c, params, seq, sliding, gate=True):
    """The reference's pieces put together by hand for a ONE-layer model, with the layer's kind and the gate as asked."""
    cfg, p, eps = dataclasses.asdict(c), params.layers[0], c.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params.wte, jnp.asarray(seq), axis=0) * np.sqrt(c.n_embd)
        o = reference.attention_layer(p.attn, reference._rms(x, p.norm_in, eps), cfg, sliding, gate=gate)
        x = reference.mlp_half(p, x + reference._rms(o, p.norm_post_attn, eps), cfg, 0)
        return np.asarray(reference._rms(x, params.final_norm, eps) @ params.lm_head.T)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_each_attention_kind_rotates_or_does_not_and_is_gated(kind):
    """One layer of one kind over 8 tokens (= the window, so both kinds see the
    same keys and only the rotation tells them apart): the program agrees with
    the reference of ITS kind, and is far from the reference that rotates where
    it should not (or does not where it should), and from the one without the
    output gate: a full layer that rotated, a window layer that did not, or a
    missing gate fails here."""
    c = toy(n_layer=1, layer_types=(kind,))
    params, seq = seeded(c, seed=3), _tokens(8, seed=4)
    got = np.asarray(Trinity.apply(c, params, jnp.asarray(seq[None])))[0]
    np.testing.assert_allclose(got, _one_layer_reference(c, params, seq, kind == SLIDING), atol=ATOL)
    np.testing.assert_allclose(got, _ref(c, params, seq), atol=ATOL)  # the reference's own assembly says the same
    for wrong in (_one_layer_reference(c, params, seq, kind != SLIDING), _one_layer_reference(c, params, seq, kind == SLIDING, gate=False)):
        assert np.max(np.abs(got - wrong)) > 1e-2


def test_router_keeps_routes_contract_with_a_nonzero_bias(model):
    """`ops/moe.py` `route` IS this family's router: the bias moves the
    SELECTION (some token picks another expert than without it), the weights
    are the selected scores WITHOUT it over their sum, times route_scale; and
    the reference's expert layer, which spells the rule out by itself, agrees
    with the program's through `route`."""
    c, params = model
    p = params.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(5), (64, c.n_embd))
    idx, w = route(x, p.router, p.expert_bias, top_k=c.moe_top_k, scale=c.route_scale, renormalize=c.route_norm)
    s = np.asarray(jax.nn.sigmoid(x @ p.router.T))
    want_idx = np.argsort(-(s + np.asarray(p.expert_bias)), axis=-1)[:, :c.moe_top_k]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(w), c.route_scale * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    idx0, _ = route(x, p.router, jnp.zeros_like(p.expert_bias), top_k=c.moe_top_k, scale=c.route_scale, renormalize=True)
    assert not np.array_equal(np.sort(np.asarray(idx), -1), np.sort(np.asarray(idx0), -1))  # the bias selects
    y, _, stats = Trinity._moe(c, p, x)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(y), np.asarray(reference.moe_layer(p, x, dataclasses.asdict(c))), atol=ATOL)
    assert int(stats["dropped"]) == 0


def test_eight_shares_of_sixteen_experts_add_up_to_the_uncut_layer():
    """The guide's share test at the published counts: 128 experts, top-8, as 8
    shares of 16 (`n_experts_held` / `expert_offset`). Each share routes over
    all 128 and computes its own experts' part; the parts, with the shared
    expert (which every chip computes alike) counted ONCE, add up to what the
    uncut reference gives for the whole layer; so do the program's shares."""
    c = toy(n_layer=2, n_embd=32, n_head=2, n_experts=128, n_experts_held=128, moe_top_k=8, expert_width=8)
    p = seeded(c, seed=6).layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(7), (40, c.n_embd))
    cfg = dataclasses.asdict(c)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.moe_layer(p, x, cfg))
        shared = np.asarray(reference._swiglu(x, p.shared, reference._f32))
        ref_parts, own_parts = [], []
        for s in range(8):
            lo = 16 * s
            share = dataclasses.replace(p, w_gate=p.w_gate[lo:lo + 16], w_up=p.w_up[lo:lo + 16], w_down=p.w_down[lo:lo + 16])
            ref_parts.append(np.asarray(reference.moe_layer(share, x, {**cfg, "expert_offset": lo}, include_shared=False)))
            cs = dataclasses.replace(c, n_experts_held=16, expert_offset=lo)
            own_parts.append(np.asarray(Trinity._moe(cs, share, x)[0]) - shared)  # the program's share, less the shared expert
    np.testing.assert_allclose(shared + sum(ref_parts), whole, atol=ATOL)
    np.testing.assert_allclose(shared + sum(own_parts), whole, atol=ATOL)
    assert sum(float(np.abs(q).max()) > 1e-3 for q in ref_parts) == 8  # every share adds something


def test_engine_prefill_then_decode_match_the_reference_past_the_window(model):
    """Chunked prefill, then paged decode through `ServeEngine`, three requests
    live at once and sampled at a temperature, one context over 2 x window +
    chunk (so window pages are reclaimed while it is served): the prefill
    program's logits at each prompt's last position (`on_first_logits`) and the
    logits every later decode round starts from (`next_logits`) are the
    REFERENCE's full forward's on the tokens the engine produced."""
    c, params = model
    first, later = {}, {}
    eng = ServeEngine(c, params, max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4, temperature=0.8, seed=5,
                      cache_dtype="float32", on_first_logits=lambda uid, row: first.setdefault(uid, np.array(row)))
    assert isinstance(eng.cache, ServeCache) and [k.name for k in eng.kinds] == [GLOBAL, WINDOW]
    assert eng.cache.pools[0][0].shape[:2] == (1, 2) and eng.cache.pools[1][0].shape[:2] == (4, 2)
    assert eng.prefill_width == 3 == eng.max_slots  # the toy's 16 experts at top-4 ask for 512 token rows: every slot rides
    uids = {eng.submit(_tokens(p, seed=p), 13): p for p in (37, 50, 11)}
    assert 50 > 2 * c.sliding_window + eng.prefill_chunk
    while not eng.idle:
        fed = {s.request.uid: s.length for s in eng.slots if s is not None}
        for uid, row in eng.next_logits().items():
            later.setdefault(uid, []).append((fed[uid], row))
        eng.step()
        assert eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)
    counters = eng.serve_counters()
    assert counters["kv.window_pages_reclaimed"] > 0 and counters["moe.dropped"] == 0
    assert 0 < counters["kv.window_tokens_per_slot_max"] <= c.sliding_window + eng.prefill_chunk + eng.page_size
    for uid, p in uids.items():
        seq = np.asarray(eng.finished[uid].tokens, np.int32)
        want = _ref(c, params, seq)
        np.testing.assert_allclose(first[uid], want[p - 1], atol=ATOL)
        assert len(later[uid]) >= 2 and all(r >= p for r, _ in later[uid])
        for r, row in later[uid]:
            np.testing.assert_allclose(row, want[r], atol=ATOL)


def test_the_batched_engine_serves_the_width_one_engines_tokens(model, monkeypatch):
    """Five prompts on four slots, greedy, two of them past 2 x window + chunk
    (window pages reclaimed while their chunks ride a call with others'): the
    engine whose prefill calls carry the round's slots as rows produces, token
    for token, what the engine of width 1 does (the family's one-row call, a
    slot at a time: what `prefill_batched = False` gave before), in fewer calls."""
    c, params = model
    prompts = [(_tokens(p, seed=p), m) for p, m in ((37, 9), (50, 6), (11, 12), (29, 5), (64, 7))]

    def run():
        eng = ServeEngine(c, params, max_slots=4, page_size=4, prefill_chunk=10, decode_chunk=4, temperature=0.0,
                          cache_dtype="float32")
        uids = [eng.submit(p, m) for p, m in prompts]
        done = eng.run()
        counters = eng.serve_counters()
        assert counters["moe.dropped"] == 0 and counters["kv.window_pages_reclaimed"] > 0
        return eng, [np.asarray(done[u].tokens) for u in uids]

    batched, got = run()
    monkeypatch.setattr(Trinity, "prefill_batched", False)
    one_row, want = run()
    assert (batched.prefill_width, one_row.prefill_width) == (4, 1)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert batched.prefill_chunks == one_row.prefill_chunks == one_row.prefill_calls > 1.5 * batched.prefill_calls


def test_window_decode_through_the_kernel_never_reads_behind_the_window(model, monkeypatch):
    """The decode step's kernel lowering (interpret mode), blocks cut to two
    pages: both kinds' attention is the gather lowering's, on a table whose
    entries behind the window are POISONED (a reclaimed page is never read)."""
    import midgpt_tpu.kernels.attention_template as at

    c, params = model
    monkeypatch.setattr(at, "block_pages", lambda *a: 2)
    ps, MP, lengths = 4, 16, jnp.asarray([39, 36, 5])
    active = jnp.asarray([True, True, False])
    pools = jax.random.normal(jax.random.PRNGKey(8), (4, 4, 2, 3 * MP + 1, ps, c.head_dim))
    cache = dataclasses.replace(Trinity.init_cache(c, (3 * MP + 1, 3 * MP + 1), ps, jnp.float32),
                                pools=((pools[0, :1], pools[1, :1]), (pools[2], pools[3])))
    table = 1 + np.arange(3 * MP, dtype=np.int32).reshape(3, MP)
    poisoned = table.copy()
    for b, n in enumerate((39, 36)):
        poisoned[b, : (n + 1 - c.sliding_window) // ps] = 10 ** 6  # behind every future window: never dereferenced
    tok = jnp.asarray(_tokens(3, seed=9))
    want, _ = Trinity.decode_step_paged(c, params, tok, cache, (table, table), lengths, active, attn_impl="gather")
    got, _ = Trinity.decode_step_paged(c, params, tok, cache, (table, poisoned), lengths, active, attn_impl="kernel")
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2], atol=ATOL)


def test_the_family_is_registered_served_and_not_trained():
    from midgpt_tpu.config import from_json, load_config, to_json

    exp = load_config("trinity_mini")
    mc = exp.model_config
    assert mc.family == "afmoe" and mc.layers_of(GLOBAL) == tuple(range(3, 32, 4)) and mc.moe_layers == tuple(range(2, 32))
    assert from_json(to_json(exp)).model_config == mc
    with pytest.raises(NotImplementedError, match="cannot train an? afmoe model: no backward"):
        mc.check_training("launch.py")
    assert mc.check_serving("sample.py") is None
    with pytest.raises(NotImplementedError, match="int8"):
        Trinity.init_cache(toy(), (3, 3), 4, jnp.int8)


def test_the_benchmark_configuration_counts_what_the_issue_reckoned():
    """The cut the configuration file makes, under eval_shape: 4,241,534,720
    parameters, every published key value for value but the two in `reduced`."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/trinity_mini_pp.json")))
    from midgpt_tpu.config import load_config

    mc = dataclasses.replace(load_config(cfg["repo_config"]).model_config, **cfg["overrides"]["model_config"])
    ran = dataclasses.asdict(mc)
    assert all(ran[k] == v for k, v in cfg["model"].items())
    assert Trinity.count_params(jax.eval_shape(lambda k: Trinity.init(mc, k), jax.random.PRNGKey(0))) == 4_241_534_720
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers"] and cfg["num_experts"] == mc.n_experts_held == 128
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    for row in ([json.loads(l) for l in open(catalog)] if os.path.exists(catalog) else []):
        if row["name"] == "Trinity-Mini":
            assert cfg["source"] == row["source_url"]
            assert all(cfg[k] == v for k, v in row["config"].items() if k not in cfg["reduced"])
    arith = _load("benchmarks/arithmetic_trinity.py")
    assert arith.expert_bytes(ran) == 12_582_912 and arith.kv_write_token(ran) == (0.0, 10_240.0)
    assert arith.decode_attention_token(ran, "window", 5000) == (4 * 16384.0 * 2048, 4 * (2048 * 2048 + 16384.0))
    assert arith.decode_attention_token(ran, "global", 5000)[0] == 16384.0 * 5000
    assert round(arith.decode_step_weight_bytes(ran, 2, experts_touched=126) / 1e9, 2) == 7.56


def test_the_traffic_is_one_multiset_for_every_seed():
    loadgen = _load("benchmarks/loadgen.py")
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/reason_agent_closed.json")))
    a, b = loadgen.Traffic(spec, 1, 200192), loadgen.Traffic(spec, 2**31 + 12345, 200192)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 128
    assert min(a.prompt_lens) == 512 and max(a.prompt_lens) == 8192 and all(o % 8 == 0 for o in a.output_lens)
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 10240
    assert sum(p + o > 2048 for p, o in a.multiset()) >= 0.6 * 128  # most contexts pass the window
    check = spec["check"]["prompts"]
    assert max(check) > 2 * 2048 + spec["engine"]["prefill_chunk"] and min(check) < spec["engine"]["prefill_chunk"]


def test_sample_py_serves_a_saved_checkpoint_of_the_family(tmp_path):
    """sample.py reaches the engine for this family through the same code as
    for the GPT (nothing names it): seeded parameters saved with the repo's
    checkpoint writer under the `trinity_mini` preset at a toy size, restored
    through the family namespace, sampled greedily: the tokens are the full
    forward's argmax chain."""
    import pickle
    import subprocess
    import sys

    from midgpt_tpu.config import load_config, to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = toy(vocab_size=65, block_size=64)
    params = seeded(c, seed=7)
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("trinity_mini").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
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
    seq = np.zeros((1, c.block_size), np.int32)
    seq[0, :3] = [32, 33, 2]  # "AB#" under the codec above
    with jax.default_matmul_precision("default"):  # as the entry point runs
        for i in range(3, 9):
            seq[0, i] = int(np.argmax(np.asarray(Trinity.apply(c, params, jnp.asarray(seq)))[0, i - 1]))
    assert new == [seq[0, 3:9].tolist()] * 2


# ---------------------------------------------------------------------------
# the cell's judgment: every row, under the nearest choice of experts
# (benchmarks/serve_routed_cell.py; the reference's "one token again")
# ---------------------------------------------------------------------------

routed = _load("benchmarks/serve_routed_cell.py")


def _own_path(c, params, streams, t, swap_at=None, tie=1.0):
    """The token at `t` run again through the routed layers: the reference's own
    experts, or at layer `swap_at` the cheapest exchange -> (logits (V,), deficit)."""
    cfg, x, deficit = dataclasses.asdict(c), streams[c.n_dense_layers][t], 0.0
    for i in range(c.n_dense_layers, c.n_layer):
        p = params.layers[i]
        h = reference.token_attention(p, streams[i], x, t, cfg, i)
        options = routed.choices(np.asarray(reference.token_scores(p, h, cfg)), c.moe_top_k, tie)
        cost, experts, _ = options[1 if i == swap_at else 0]
        deficit += cost
        x = reference.token_experts(p, h, experts, cfg)
    return np.asarray(reference.token_logits(params, x, cfg)), deficit


@pytest.mark.parametrize("case", ["the_same_experts", "a_tie_decided_the_other_way", "a_fault_in_one_row"])
def test_every_row_is_judged_under_its_nearest_choice_of_experts(model, case, monkeypatch):
    """`nearest_choice` on one sequence's rows. A token run again under the
    reference's own experts IS the full forward's row (float32 rounding). A
    "program" that took the 5th expert for the 4th at one layer of one row is
    far from the full forward there (the old judgment's "flipped" row), is found
    under exactly that exchange, and is right; one whose row is off by a tenth of
    the logits' spread under EVERY choice is not, though 7 rows of 8 are exact
    (the review round's lower-quartile judgment passed it)."""
    c, params = model
    cfg, seq = dataclasses.asdict(c), _tokens(40, seed=21)
    streams = []
    want = np.asarray(reference.logits(params, jnp.asarray(seq), cfg, keep=streams))
    sd, rows = float(np.std(want)), [5, 11, 17, 23, 29, 33, 36, 39]
    got = want[rows].copy()
    if case == "a_tie_decided_the_other_way":
        got[3], deficit = _own_path(c, params, streams, rows[3], swap_at=2)
        monkeypatch.setattr(routed, "TIE", 1.5 * deficit)
        assert np.sqrt(np.mean((got[3] - want[rows[3]]) ** 2)) / sd > routed.ROW_RMS_TOLERANCE  # a whole expert's weight
    elif case == "a_fault_in_one_row":
        got[3] = got[3] + 0.1 * sd * np.sign(np.sin(np.arange(c.vocab_size)))
    found = [routed.nearest_choice(reference, params, cfg, streams, t, row, sd) for t, row in zip(rows, got)]
    for f in found[:3] + found[4:]:
        assert f["rms"] < 1e-5 and f["own_rms"] < 1e-5 and not f["swaps"] and f["nodes"] == c.n_layer - c.n_dense_layers + 1
    if case == "the_same_experts":
        assert routed.judge(found) and found[3]["rms"] < 1e-5 and 0 < found[3]["gap"] < 1
    elif case == "a_tie_decided_the_other_way":
        (layer, gone, come, cost), = found[3]["swaps"]
        assert routed.judge(found) and found[3]["rms"] < 1e-5 < routed.ROW_RMS_TOLERANCE < found[3]["own_rms"]
        assert layer == 2 and len(gone) == len(come) == 1 and abs(cost - deficit) < 1e-4
    else:
        assert not routed.judge(found) and found[3]["rms"] > routed.ROW_RMS_TOLERANCE and found[3]["nodes"] <= routed.NODES
