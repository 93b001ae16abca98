"""models/olmo_hybrid.py (Gated-DeltaNet layers, three to every full-attention
layer) behind the family seam, and the serving stack's STATE kind of cache
(sampling/pages.py "State kinds"): one row a slot, reset by a prompt's first
chunk, carried from prefill chunk to prefill chunk, updated in place by a decode step and left
bit for bit where a slot sits a round out. CPU, toy widths, float32 under
"highest" (conftest), against the plain float32 reference beside the
configuration file (benchmarks/configs/olmo_hybrid_7b_pp2_reference.py), which
imports nothing from the program. No family is named in sampling/: everything
goes through `ServeEngine` and the family contract."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import load_config
from midgpt_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from midgpt_tpu.ops.kda import kda_chunked, kda_recurrent, kda_step
from midgpt_tpu.sampling.serve import ServeEngine
from rehearsal_tree import run_rehearsal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "serve_olmo_hybrid_docchat", "olmo_hybrid_7b_pp2"


def _load(rel):
    import sys

    spec = importlib.util.spec_from_file_location("olmo_" + os.path.basename(rel)[:-3], os.path.join(ROOT, rel))
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


reference = _load(f"benchmarks/configs/{CONFIG}_reference.py")
with open(os.path.join(ROOT, f"benchmarks/configs/{CONFIG}.json")) as f:
    FILE = json.load(f)


def toy() -> OlmoHybridConfig:
    """The configuration file's rehearsal: three heads of 12 x 24 (unequal key
    and value widths, no multiple of the kernel's four heads a step), two periods."""
    return dataclasses.replace(load_config(FILE["repo_config"]).model_config, **FILE["rehearsal"]["overrides"]["model_config"])


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, OlmoHybrid.init(c, jax.random.PRNGKey(0))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, toy().vocab_size, n, dtype=np.int32)


def _ref_logits(c, params, seq):
    return np.asarray(reference.logits(params, jnp.asarray(np.asarray(seq, np.int32)), dataclasses.asdict(c)))


def _serve(c, params, work, *, slots, chunk, probe=True, **kw):
    """Serve `work` ((prompt length, new tokens), ...) through a ServeEngine;
    (engine, uid -> prompt length, uid -> [(row, logits)]) with the prefill
    program's logits at the prompt's last row and every later round's first step's."""
    got = {}
    eng = ServeEngine(c, params, max_slots=slots, page_size=4, prefill_chunk=chunk, decode_chunk=4, cache_dtype="float32",
                      on_first_logits=lambda uid, row: got[uid].append((uids[uid] - 1, np.array(row))), **kw)
    uids = {eng.submit(_tokens(p, seed=p), m): p for p, m in work}
    got.update({uid: [] for uid in uids})
    live_max = 0
    while not eng.idle:
        if probe:
            fed = {s.request.uid: s.length for s in eng.slots if s is not None}
            for uid, row in eng.next_logits().items():
                got[uid].append((fed[uid], row))
        live_max = max(live_max, sum(s is not None for s in eng.slots))
        eng.step()
        assert eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)
    return eng, uids, got, live_max


def _worst(c, params, eng, uids, got):
    """The largest |engine logit - reference logit| over every compared row."""
    worst = 0.0
    for uid in uids:
        want = _ref_logits(c, params, eng.finished[uid].tokens)
        worst = max([worst] + [float(np.abs(row - want[r]).max()) for r, row in got[uid]])
    return worst


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


def test_full_forward_matches_the_reference(model):
    c, params = model
    seq = _tokens(70, seed=1)
    np.testing.assert_allclose(np.asarray(OlmoHybrid.apply(c, params, jnp.asarray(seq)[None])[0]), _ref_logits(c, params, seq), atol=1e-4)


@pytest.mark.parametrize("change", ["beta_not_doubled", "gate_per_token_sign", "no_qk_norm_scale", "norm_on_the_input"])
def test_a_departure_from_the_equations_fails_the_comparison(model, change, monkeypatch):
    """The comparison has teeth: each of four plausible misreadings of the
    layer moves the logits by far more than the tolerance the tests hold."""
    import midgpt_tpu.models.olmo_hybrid as mod

    c, params = model
    seq = _tokens(40, seed=2)
    want = _ref_logits(c, params, seq)
    if change == "beta_not_doubled":
        c = dataclasses.replace(c, allow_neg_eigval=False)
    elif change == "gate_per_token_sign":
        monkeypatch.setattr(mod, "_gates", lambda c_, p, a, g=mod._gates: (lambda gb: (-gb[0], gb[1]))(g(c_, p, a)))
    elif change == "no_qk_norm_scale":
        monkeypatch.setattr(mod, "_l2", lambda x: x.astype(jnp.float32))
    else:
        monkeypatch.setattr(mod, "_mlp", lambda c_, p, x: x + mod.swiglu(mod._norm(c_, x, p.norm_mlp), p.w_gate, p.w_up, p.w_down))
    got = np.asarray(OlmoHybrid.apply(c, params, jnp.asarray(seq)[None])[0])
    assert float(np.abs(got - want).max()) > 1e-2


def test_compute_copy_keeps_the_gate_parameters_and_norms_in_float32(model):
    c, params = model
    lo = OlmoHybrid.cast_params(params, jnp.bfloat16)
    for name in ("a_log", "dt_bias", "o_norm", "norm_attn", "norm_mlp"):
        assert getattr(lo.linear, name).dtype == jnp.float32, name
    assert lo.full.q_norm.dtype == lo.final_norm.dtype == jnp.float32
    assert lo.linear.wq.dtype == lo.linear.conv.dtype == lo.full.wo.dtype == lo.lm_head.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# the delta rule as a serving op: one oracle for the training op and this one
# ---------------------------------------------------------------------------


def _gdn_inputs(seed, T, H=3, dk=12, dv=24):
    """Gated-DeltaNet-like inputs: unit keys, a SCALAR gate a head, beta up to 2, d_k != d_v, a non-zero state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = unit(jax.random.normal(ks[0], (2, T, H, dk))) * dk**-0.5, unit(jax.random.normal(ks[1], (2, T, H, dk)))
    v = jax.random.normal(ks[2], (2, T, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (2, T, H))) * 0.3
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (2, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (2, H, dk, dv))


def _T(s):
    """The oracle's state, the equations' (d_k, d_v), as a carried state, (d_v, d_k), and back."""
    return jnp.swapaxes(s, 2, 3)


def _kernel_body(q, k, v, g, beta, s0):
    """`kda_chunked` as the TPU runs it: kernels/kda.py, here in Pallas interpret mode."""
    from midgpt_tpu.kernels.kda import kda_scan
    from midgpt_tpu.ops.kda import CHUNK, SUB, _per_channel

    return kda_scan(q, k, v, _per_channel(g, k), beta, s0, chunk=CHUNK, sub=SUB)


@pytest.mark.parametrize("body", ["jnp", "kernel"])
@pytest.mark.parametrize("T", [150, 64, 5], ids=["three_chunks", "one_chunk", "under_a_sub_block"])
def test_chunked_delta_rule_from_a_carried_state_matches_the_recurrence(body, T):
    """From a NON-zero state, a scalar gate a head, beta up to 2 (1.97 at 150
    tokens), 12 x 24 heads, three of them, 150 tokens (no multiple of the
    chunk), one whole chunk and less than a sub-block: values and final state
    are `kda_recurrent`'s with the gate broadcast, in both bodies."""
    q, k, v, g, beta, s0 = _gdn_inputs(3, T)
    assert T < 150 or float(beta.max()) > 1.9
    o_r, s_r = kda_recurrent(q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, s0)
    chunked = kda_chunked if body == "jnp" else _kernel_body
    o, s = chunked(q, k, v, g, beta, _T(s0))
    np.testing.assert_allclose(o, o_r, atol=5e-6)
    np.testing.assert_allclose(_T(s), s_r, atol=5e-6)


def test_masked_rows_leave_the_state_bit_for_bit():
    """g = 0, beta = 0 (how a prefill chunk masks its rows past `n_valid`): the state out is the state in, exactly."""
    q, k, v, g, beta, s0 = _gdn_inputs(4, 70)
    _, s = kda_chunked(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), _T(s0))
    np.testing.assert_array_equal(s, _T(s0))


def test_one_token_step_is_the_recurrence():
    q, k, v, g, beta, s0 = _gdn_inputs(5, 1)
    o_r, s_r = kda_recurrent(q, k, v, g, beta, s0)
    o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], _T(s0))
    np.testing.assert_allclose(o, o_r[:, 0], atol=2e-6)
    np.testing.assert_allclose(_T(s), s_r, atol=2e-6)


# ---------------------------------------------------------------------------
# the engine: chunk carry, several slots, slot reuse, the rows' books
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [3, 1], ids=["batched_prefill", "one_row_prefill"])
def test_engine_logits_match_the_reference_through_chunks_and_rounds(model, width, monkeypatch):
    """Prompts of 37, 50 and 11 tokens in chunks of 10 or 20 (no multiple, up
    to four chunk boundaries), all three slots live, at a temperature: the
    prefill program's logits at each prompt's last row and the first step's of
    every later decode round are the reference's full forward's on the tokens
    the engine produced, and probing (`next_logits`) changes no stream: it
    hands the state rows back as they came in."""
    c, params = model
    chunk = 10 if width == 3 else 20
    if width == 1:  # a chunk at the ridge on its own rides alone: the family's one-row call
        monkeypatch.setattr("midgpt_tpu.sampling.serve.PREFILL_ROWS", chunk)
    work = [(37, 13), (50, 13), (11, 13)]
    eng, uids, got, live_max = _serve(c, params, work, slots=3, chunk=chunk, temperature=0.8, seed=5)
    plain = _serve(c, params, work, slots=3, chunk=chunk, probe=False, temperature=0.8, seed=5)[0]
    assert live_max == 3 and eng.prefill_width == width
    assert [k.name for k in eng.kinds] == ["global"] and [k.name for k in eng.state_kinds] == ["gdn_state"]
    for uid in uids:
        np.testing.assert_array_equal(eng.finished[uid].tokens, plain.finished[uid].tokens)
        assert len(got[uid]) >= 3
    assert _worst(c, params, eng, uids, got) < 1e-4
    counters = eng.serve_counters()
    assert counters["gdn.prefill_tokens"] == 37 + 50 + 11 and counters["gdn.prefill_chunks"] == sum(-(-p // chunk) for p, _ in work)
    assert counters["state.rows"] == 3 == counters["state.rows_live_max"] and counters["state.rows_live"] == 0
    per_slot = c.n_linear * (c.linear_heads * c.linear_key_dim * c.linear_value_dim * 4 + (c.conv_kernel - 1) * c.conv_channels * 4)
    assert counters["state.bytes_per_slot"] == per_slot
    assert eng.pool.hbm_bytes() >= sum(a.nbytes for a in eng.cache.pool_arrays()) + 4 * per_slot  # the sink row too


def test_slots_are_reused_and_every_request_starts_from_a_reset_row(model):
    """Two slots serve five requests: each is the reference's (a row is reset
    for the request admitted to it, by its first chunk)."""
    c, params = model
    work = [(5, 9), (37, 9), (23, 9), (50, 9), (9, 9)]
    eng, uids, got, _ = _serve(c, params, work, slots=2, chunk=16)
    assert _worst(c, params, eng, uids, got) < 1e-4
    assert eng.serve_counters()["state.resets"] == 5


def test_a_step_applied_twice_is_seen(model, monkeypatch):
    """`next_logits` runs a decode step and commits nothing of the state
    (`pages.keep_state`); were it to commit, the round after it would apply the
    step a second time, and the comparison sees it."""
    c, params = model
    monkeypatch.setattr("midgpt_tpu.sampling.serve.keep_state", lambda new, old: new)
    from midgpt_tpu.sampling import serve

    raw = serve._serve_decode_logits.__wrapped__

    def _serve_decode_logits(config, params, token, cache, page_table, lengths, active, attn_impl, mesh=None, split_k=1):
        return raw(config, params, token, cache, page_table, lengths, active, attn_impl, mesh, split_k)  # a function of its own: traced anew

    monkeypatch.setattr(serve, "_serve_decode_logits", serve._PoolProgram(jax.jit(
        _serve_decode_logits, static_argnums=(0, 7, 8, 9), donate_argnums=(3,))))
    eng, uids, got, _ = _serve(c, params, [(21, 13)], slots=2, chunk=16)
    assert _worst(c, params, eng, uids, got) > 1e-2


def _cache(c, rows=3, pages_=9, seed=0):
    """A cache with every state row filled with noise (and zero pools)."""
    cache = OlmoHybrid.init_cache(c, (pages_, rows + 1), page_size=4, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(cache.state))
    return dataclasses.replace(cache, state=tuple(jax.random.normal(k, a.shape, a.dtype) for k, a in zip(ks, cache.state)))


def test_rows_past_n_valid_change_neither_state_nor_history(model):
    """A chunk of 16 of which 7 are real: the state and the convolution's
    history it leaves are the same bit for bit whatever the padding holds (that
    they are the 7 tokens' is the engine tests': their last chunks are padded),
    and every OTHER row is bit for bit as it was (an empty place of a batched
    call names the sink row: the batched engine test's calls carry some)."""
    c, params = model
    cache = _cache(c)
    table = (np.array([[1, 2, 3, 4]], np.int32), np.array([1], np.int32))
    run = lambda toks, n: OlmoHybrid.prefill_paged_chunk(c, params, jnp.asarray(toks)[None], jnp.int32(0), jnp.int32(n), cache, table)[1]
    real = _tokens(7, seed=3)
    a = run(np.concatenate([real, np.zeros(9, np.int32)]), 7)
    b = run(np.concatenate([real, _tokens(9, seed=4)]), 7)
    for x, y, before in zip(a.state, b.state, cache.state):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x[:, 1], before[:, 1])
        np.testing.assert_array_equal(np.delete(np.asarray(x), 1, axis=1), np.delete(np.asarray(before), 1, axis=1))


@pytest.mark.parametrize("batched", [True, False], ids=["batched", "one_row"])
def test_a_prompts_first_chunk_starts_from_zeros_and_a_later_one_from_its_row(model, batched):
    """The row's reset is the prefill program's (the seam's contract): a chunk
    at start 0 leaves the state, the history and the logits a ZEROED row gives,
    bit for bit, whatever the slot's last request left in the row; a chunk at
    start 16 carries on from the row, so dirt there IS seen (the engine tests
    would see a reset that was lost)."""
    c, params = model
    dirty, clean = _cache(c, seed=2), OlmoHybrid.init_cache(c, (9, 4), page_size=4, dtype=jnp.float32)
    toks = jnp.asarray(_tokens(16, seed=6))[None]
    table = (np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32), np.array([2], np.int32))

    def run(cache, start):
        lift = (lambda x: jnp.asarray([x], jnp.int32)) if batched else jnp.int32
        logits, after = OlmoHybrid.prefill_paged_chunk(c, params, toks, lift(start), lift(16), cache, table)
        return np.asarray(logits), [np.asarray(a[:, 2]) for a in after.state]

    (l_dirty, s_dirty), (l_clean, s_clean) = run(dirty, 0), run(clean, 0)
    np.testing.assert_array_equal(l_dirty, l_clean)
    for a, b in zip(s_dirty, s_clean):
        np.testing.assert_array_equal(a, b)
    (l_dirty, s_dirty), (l_clean, s_clean) = run(dirty, 16), run(clean, 16)
    assert float(np.abs(l_dirty - l_clean).max()) > 1e-2 and not np.array_equal(s_dirty[0], s_clean[0])


def test_a_decode_rounds_state_rows_are_the_slots_in_order(model):
    """`decode_step_paged` updates rows [0, slots) where they lie and does not
    read the row vector; the pool owner holds every decode round's table to
    that (slot i's row is row i) and hands a prefill call the rows it names,
    the sink row for an empty place."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, num_pages=24, page_size=4, prefill_chunk=10, decode_chunk=4, cache_dtype="float32")
    slot = lambda: type("S", (), {"pages": [[1, 2]], "state_row": -1, "reclaimed_to": [0]})()
    a, b = slot(), slot()
    eng.pool.claim_state(a, 0)
    eng.pool.claim_state(b, 2)
    np.testing.assert_array_equal(eng.pool.tables([a, None, b], 2)[-1], [0, 3, 2])  # 3: the sink row
    np.testing.assert_array_equal(eng.pool.tables([a, None, b], 2, rows=[2])[-1][:1], [2])
    with pytest.raises(RuntimeError, match="slot 1 holds state row 2"):
        eng.pool.tables([a, b, None], 2)


def test_a_decode_round_leaves_an_inactive_slots_row_bit_for_bit(model):
    """Three slots of which the middle one sits the round out (it is in the
    middle of its chunked prefill): its delta-rule state and its convolution
    history are bit for bit what they were; the active slots' moved."""
    c, params = model
    cache = _cache(c, seed=1)
    table = (np.array([[1, 2], [3, 4], [5, 6]], np.int32), np.arange(3, dtype=np.int32))
    active = np.array([True, False, True])
    _, after = OlmoHybrid.decode_step_paged(c, params, jnp.asarray([5, 6, 7]), cache, table, jnp.asarray([3, 2, 5]), jnp.asarray(active))
    for x, before in zip(after.state, cache.state):
        np.testing.assert_array_equal(x[:, 1], before[:, 1])
        np.testing.assert_array_equal(x[:, 3], before[:, 3])  # the sink row: no decode step touches it
        assert not np.array_equal(x[:, 0], before[:, 0]) and not np.array_equal(x[:, 2], before[:, 2])
    assert int(after.counters[0][0]) == 2


def test_the_books_hold_the_state_kind_through_evict_and_cancel(model):
    """A pool too small for every slot at once: the youngest slot is preempted
    (its row given back, zeroed again when it is admitted anew), one request is
    cancelled, and after every round rows free + rows live == the slots beside
    pages free + pages live == the pool; what finishes is the reference's."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, num_pages=24, page_size=4, prefill_chunk=10, decode_chunk=4, cache_dtype="float32")
    work = [(30, 30), (28, 28), (26, 26)]
    uids = [eng.submit(_tokens(p, seed=p), m) for p, m in work]
    rounds = 0
    while not eng.idle:
        eng.step()
        rounds += 1
        if rounds == 6:
            assert eng.cancel(uids[2])
        terms = {t["kind"]: t for t in eng.pool.ledger(eng.slots)}
        assert eng.pool.conserved(eng.slots) and set(terms) == {"global", "gdn_state"}, terms
        assert terms["gdn_state"]["live_only"] == sum(s is not None for s in eng.slots) and terms["gdn_state"]["allocatable"] == 3
    # one row zeroed an admission: the three first ones, and every preempted request that came back before it was cancelled
    assert eng.stats()["preemptions"] > 0 and 3 < eng.serve_counters()["state.resets"] == eng._admitted
    for uid, (p, m) in list(zip(uids, work))[:2]:
        seq = eng.finished[uid].tokens
        want = np.argmax(_ref_logits(c, params, seq)[p - 1:-1], axis=-1)
        np.testing.assert_array_equal(seq[p:], want)
    # a leak is seen: a slot that vanishes without `release` breaks the law
    slot = type("S", (), {"pages": [[]], "state_row": -1})()
    eng.pool.claim_state(slot, 0)
    assert not eng.pool.conserved([None, None, None]) and eng.pool.conserved([slot, None, None])


@pytest.mark.parametrize("what,kw,says", [
    ("prefix cache", dict(prefix_cache=True), "the prefix cache"),
    ("int8", dict(cache_dtype="int8"), "int8 pools"),
    ("pool_hbm_bytes", dict(pool_hbm_bytes=1 << 20), "byte-budgeted"),
    ("speculation", "draft", "speculative decoding"),
    ("mesh", "mesh", "a serving mesh"),
])
def test_what_moves_pages_is_refused_by_name_beside_a_state_kind(model, what, kw, says):
    c, params = model
    if kw == "draft":
        from midgpt_tpu.models.gpt import GPTConfig

        kw = dict(draft_params=params, draft_config=GPTConfig(block_size=c.block_size, vocab_size=c.vocab_size, n_layer=1, n_head=2, n_embd=16))
    elif kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "tp")))
    with pytest.raises(NotImplementedError, match="STATE kind of cache") as e:
        ServeEngine(c, params, max_slots=2, page_size=4, prefill_chunk=8, decode_chunk=4, **kw)
    assert says in str(e.value) and "gdn_state" in str(e.value) and "olmo_hybrid" in str(e.value)


def test_engine_operations_on_pages_are_refused_by_name(model):
    c, params = model
    eng = ServeEngine(c, params, max_slots=2, page_size=4, prefill_chunk=8, decode_chunk=4, cache_dtype="float32")
    for call, says in ((lambda: eng.hot_swap(params), "weight swap"), (lambda: eng.resize(64), "pool resize"),
                       (lambda: eng.attach_spill(object()), "spill tier")):
        with pytest.raises(NotImplementedError, match="STATE kind of cache") as e:
            call()
        assert says in str(e.value)
    from midgpt_tpu.sampling.disagg import DisaggServe

    with pytest.raises(NotImplementedError, match="gdn_state"):
        DisaggServe(c, params)
    assert OlmoHybrid.verify_step_paged is None
    with pytest.raises(NotImplementedError, match="cannot train a olmo_hybrid model"):
        c.check_training("launch.py")
    with pytest.raises(NotImplementedError, match="int8"):
        OlmoHybrid.init_cache(c, (9, 3), 4, jnp.int8)


@pytest.mark.parametrize("axis", ["fsdp", "sp", "tp", "pp", "ep"])
def test_a_mesh_axis_other_than_data_is_refused_by_name(axis):
    config = load_config("olmo_hybrid_7b")
    with pytest.raises(ValueError, match="no mesh axis but data"):
        config = config.replace(mesh=dataclasses.replace(config.mesh, **{axis: 2}))
        config.model_config.check_experiment(config)


# ---------------------------------------------------------------------------
# the configuration file, the preset, the arithmetic
# ---------------------------------------------------------------------------


def test_configuration_file_matches_the_catalog_row_key_for_key():
    """Every published key value for value except `num_hidden_layers` (the one
    key in `reduced`); the preset the file names is the published model; the
    resolved cut is what `model` states."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    assert FILE["source"] == row["source_url"] and FILE["reduced"] == ["num_hidden_layers"]
    differs = {k for k, v in row["config"].items() if FILE.get(k) != v}
    assert differs == {"num_hidden_layers"} and FILE["num_hidden_layers"] == 16 and FILE["published"] == {"num_hidden_layers": 32}
    pub = row["config"]
    mc = load_config(FILE["repo_config"]).model_config
    assert (mc.n_layer, mc.n_embd, mc.n_head, mc.vocab_size, mc.dense_width, mc.block_size) == (
        pub["num_hidden_layers"], pub["hidden_size"], pub["num_attention_heads"], pub["vocab_size"], pub["intermediate_size"],
        pub["max_position_embeddings"])
    assert (mc.linear_heads, mc.linear_key_dim, mc.linear_value_dim, mc.conv_kernel, mc.allow_neg_eigval) == (
        pub["linear_num_key_heads"], pub["linear_key_head_dim"], pub["linear_value_head_dim"], pub["linear_conv_kernel_dim"],
        pub["linear_allow_neg_eigval"])
    assert list(mc.layer_types) == pub["layer_types"] == FILE["layer_types"] and mc.rms_norm_eps == pub["rms_norm_eps"]
    assert pub["num_key_value_heads"] == pub["num_attention_heads"] and pub["rope_parameters"] == {"rope_theta": None}
    cut = dataclasses.replace(mc, **FILE["overrides"]["model_config"])
    assert {k: v for k, v in dataclasses.asdict(cut).items() if k in FILE["model"]} == FILE["model"]
    assert set(FILE["assumed"]) >= {"block_arrangement", "linear_layer", "output_gate", "gate_parameters", "full_layer", "block_size", "weights"}


def test_parameter_counts_are_the_issues():
    """4,100,788,944 parameters at the cut (215,570,172 a linear layer,
    185,809,920 a full one), 7,430,870,808 as published; one slot's state
    27,371,520 B as published; the arithmetic module's counts by hand."""
    mc = load_config(FILE["repo_config"]).model_config
    count = lambda c: OlmoHybrid.count_params(jax.eval_shape(lambda k: OlmoHybrid.init(c, k), jax.random.PRNGKey(0)))
    cut = dataclasses.replace(mc, **FILE["overrides"]["model_config"])
    assert count(cut) == 4 * (3 * 215_570_172 + 185_809_920) + 770_703_360 + 3_840 == 4_100_788_944
    assert count(mc) == 24 * 215_570_172 + 8 * 185_809_920 + 770_703_360 + 3_840
    assert "4,100,788,944" in FILE["what"] and FILE["state"]["per_slot_published_bytes"] == 27_371_520
    arith = _load("benchmarks/arithmetic_olmo_hybrid.py")
    m = dataclasses.asdict(cut)
    assert arith.state_bytes_per_slot(m) == 27_371_520 == sum(
        int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in cut.state_shapes(jnp.bfloat16))
    # one token's update, 12 layers: state read + written, q k v (bf16), o (f32), g and beta
    assert arith.state_update_token(m)[1] == 12 * (2 * 4 * 30 * 96 * 192 + 2 * 30 * 384 + 4 * 30 * 192 + 8 * 30) == 53_640_000
    assert arith.state_update_token(m)[0] == 12 * 30 * 7 * 96 * 192
    assert arith.prefill_scan_token(m)[0] == 12 * 30 * (64 * (3 * 96 + 2 * 192) + 6 * 96 * 192) == 55_296_000
    assert arith.kv_write_token(m) == (0.0, 61_440.0) == (0.0, float(FILE["state"]["kv_per_token_bytes"]))
    assert arith.decode_attention_token(m, "global", 1000) == (4.0 * 1000 * 3840 * 4, float((2 * 1000 * 3840 * 2 + 4 * 3840) * 4))
    assert arith.decode_step_weight_bytes(m) == 2.0 * (4_100_788_944 - 100_352 * 3_840 - 3_840 - 12 * (60 + 192 + 7_680) - 4 * 15_360)
    assert OlmoHybrid.flops_per_token(cut, 1) > 2 * (4_100_788_944 - 100_352 * 3_840 - 2e6)


def test_config_json_round_trip_keeps_the_family():
    from midgpt_tpu.config import from_json, to_json

    config = load_config("olmo_hybrid_7b").replace(model_config=toy())
    back = from_json(to_json(config))
    assert back.model_config == toy() and isinstance(back.model_config.layer_types, tuple)


# ---------------------------------------------------------------------------
# the benchmark's cell
# ---------------------------------------------------------------------------


def test_the_cells_traffic_and_entries_are_the_issues():
    loadgen = _load("benchmarks/loadgen.py")
    with open(os.path.join(ROOT, "benchmarks/traffic/docchat_state_closed.json")) as f:
        spec = json.load(f)
    a, b = loadgen.Traffic(spec, 1, 100352), loadgen.Traffic(spec, 2**31 + 12345, 100352)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 96 and spec["kind"] == "serve_state"
    assert 2240 < np.mean(a.prompt_lens) < 2255 and 450 < np.mean(a.output_lens) < 460 and all(o % 8 == 0 for o in a.output_lens)
    e = spec["engine"]
    assert sum(p > e["pool_tokens_per_slot"] for p in a.prompt_lens) == 17 and sum(p > 8192 for p in a.prompt_lens) == 3
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 13824 and (spec["clients"], spec["cycle"]) == (24, 96)
    assert (e["max_slots"], e["page_size"], e["prefill_chunk"], e["decode_chunk"], e["pool_tokens_per_slot"]) == (24, 32, 512, 8, 3584)
    assert sorted(spec["check"]["prompts"]) == [200, 1300, 2600, 4800] and spec["check"]["decode_rounds"] == 8
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "docchat_state_closed", 1)
    entry = next(cfg for cfg in bench["configs"] if cfg["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])} == {"setup_s", "serve_tokens_per_s"}
    # the entries PR 59 declared for this cell: its name stands FIRST in their lists (PR 63's state-space cell joined them)
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads", [None])[0] == CELL]
    assert mine == ["serve.attn_linear_ms", "serve.dense_ffn_ms", "linear_state_update_ms_per_token", "linear_state_update_roofline",
                    "linear_prefill_scan_ms_per_token", "linear_prefill_scan_roofline", "state.pool_fill"]
    joined = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert {"serve.attn_global_ms", "global_decode_attention_roofline", "prefill_attention_ms_per_token", "kv.global_pool_fill", "kv_write_roofline", "serve.lm_head_ms",
            "serve.weight_read_share", "serve.model_unattributed_ms", "engine.occupancy", "setup.programs"} <= joined


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --workload serve_olmo_hybrid_docchat --rehearse-cpu --trace 1`
    (from a tree of its own) exits 0, is `correct` through dirty state rows,
    returns what it compared, and names every metric declared for the cell that
    a CPU run can produce."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    proc = run_rehearsal(tmp_path, CELL, seconds="1")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    cpu_cannot = {"global_decode_attention_ms_per_token", "global_decode_attention_roofline", "prefill_attention_ms_per_token", "kv_write_ms_per_token",
                  "kv_write_roofline", "serve.prefill_device_share", "serve.peak_hbm_gb", "serve.weight_read_share",
                  "linear_state_update_ms_per_token", "linear_state_update_roofline", "linear_prefill_scan_ms_per_token",
                  "linear_prefill_scan_roofline"}  # the last four and the share need the chip's peaks
    cpu_cannot |= {m["name"] for m in bench["per_layer"] if m["layer"] == "serving engine" and m["source"] == "device_trace"}
    assert declared - cpu_cannot <= set(last["would_report"]), sorted(declared - cpu_cannot - set(last["would_report"]))
    assert "every state row dirty" in proc.stdout and "-> ok" in proc.stdout and "state kind: 3 rows" in proc.stdout
    # the compared requests land on the high rows beside a neighbour that holds slot 0 and decodes on
    assert "1 of them held their slots and decoded on (1 still live" in proc.stdout and "admitted to slots [1, 2]" in proc.stdout
    assert "rows reset, one an admission: 5 (want 5)" in proc.stdout
    assert "serve scopes by kind (from the configuration's list)" in proc.stdout


def test_the_8_bit_control_is_refused_by_the_cells_own_limits(tmp_path):
    proc = run_rehearsal(tmp_path, CELL, script="serve_state_cell.py")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"program_correct": True, "control_correct": False}


def test_toy_checkpoint_serves_through_sample_py(tmp_path):
    """sample.py reaches the engine for this family through the same code as
    for the GPT: seeded parameters saved with the repo's checkpoint writer,
    restored through the family namespace, sampled greedily: the tokens are
    the full forward's argmax chain."""
    import pickle
    import subprocess
    import sys

    from midgpt_tpu.config import to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = dataclasses.replace(toy(), vocab_size=65, block_size=64)
    params = OlmoHybrid.init(c, jax.random.PRNGKey(7))
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("olmo_hybrid_7b").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
    (tmp_path / "config.json").write_text(to_json(exp))
    mngr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert mngr.save(3, {"params": params}, force=True)
    mngr.wait()
    mngr.close()
    args = [sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}", "--start=AB#", "--num_samples=2",
            "--max_new_tokens=6", "--temperature=0.0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(args + ["--engine=continuous"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "restored checkpoint step 3" in proc.stdout
    new = json.loads(next(l for l in proc.stdout.splitlines() if l.startswith("new_tokens: "))[len("new_tokens: "):])
    seq = [32, 33, 2]  # "AB#" under the codec above
    with jax.default_matmul_precision("default"):  # as the entry point runs
        for _ in range(6):
            seq.append(int(np.argmax(np.asarray(OlmoHybrid.apply(c, params, jnp.asarray(seq)[None]))[0, -1])))
    assert new == [seq[3:], seq[3:]]
