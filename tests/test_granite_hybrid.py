"""models/granite_hybrid.py (Mamba-2 state-space layers, nine to every
position-free grouped-query attention layer, under the family's four
multipliers) behind the family seam, ops/ssd.py, and the serving stack's STATE
kind of cache at this family's shapes (sampling/pages.py "State kinds"). CPU,
toy widths, float32 under "highest" (conftest), against the plain float32
reference beside the configuration file
(benchmarks/configs/granite_4_0_h_micro_reference.py), which runs the
recurrence token by token and imports nothing from the program. No family is
named in sampling/: everything goes through `ServeEngine` and the family
contract. The letters are ISSUE 63's."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import load_config
from midgpt_tpu.models.granite_hybrid import GraniteHybrid, GraniteHybridConfig
from midgpt_tpu.ops.ssd import ssd_chunked, ssd_recurrent, ssd_step
from midgpt_tpu.sampling.serve import ServeEngine
from rehearsal_tree import run_rehearsal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, CONFIG = "serve_granite4_h_sessions", "granite_4_0_h_micro"


def _load(rel):
    import sys

    spec = importlib.util.spec_from_file_location("granite_" + os.path.basename(rel)[:-3], os.path.join(ROOT, rel))
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)  # a dataclass looks its module up there
    spec.loader.exec_module(mod)
    return mod


reference = _load(f"benchmarks/configs/{CONFIG}_reference.py")
with open(os.path.join(ROOT, f"benchmarks/configs/{CONFIG}.json")) as f:
    FILE = json.load(f)


def toy() -> GraniteHybridConfig:
    """The configuration file's rehearsal: four query heads on two K/V heads,
    one group, three mamba heads of 8 x 16, SSD chunks of 8, two periods of
    (mamba, mamba, attention, mamba): the attention layer INSIDE a period."""
    return dataclasses.replace(load_config(FILE["repo_config"]).model_config, **FILE["rehearsal"]["overrides"]["model_config"])


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, GraniteHybrid.init(c, jax.random.PRNGKey(0))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, toy().vocab_size, n, dtype=np.int32)


def _ref_logits(c, params, seq):
    return np.asarray(reference.logits(params, jnp.asarray(np.asarray(seq, np.int32)), dataclasses.asdict(c)))


def _serve(c, params, work, *, slots, chunk, **kw):
    """Serve `work` ((prompt length, new tokens), ...) through a ServeEngine;
    (engine, uid -> prompt length, uid -> [(row, logits)]) with the prefill
    program's logits at the prompt's last row and every later round's first step's."""
    got = {}
    eng = ServeEngine(c, params, max_slots=slots, page_size=4, prefill_chunk=chunk, decode_chunk=4, cache_dtype="float32",
                      on_first_logits=lambda uid, row: got[uid].append((uids[uid] - 1, np.array(row))), **kw)
    uids = {eng.submit(_tokens(p, seed=p), m): p for p, m in work}
    got.update({uid: [] for uid in uids})
    while not eng.idle:
        fed = {s.request.uid: s.length for s in eng.slots if s is not None}
        for uid, row in eng.next_logits().items():
            got[uid].append((fed[uid], row))
        eng.step()
        assert eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)
    return eng, uids, got


def _worst(c, params, eng, uids, got):
    """The largest |engine logit - reference logit| over every compared row, over the reference logits' scale."""
    worst = 0.0
    for uid in uids:
        want = _ref_logits(c, params, eng.finished[uid].tokens)
        worst = max([worst] + [float(np.abs(row - want[r]).max() / want.std()) for r, row in got[uid]])
    return worst


# ---------------------------------------------------------------------------
# (a), (e): the model against the reference
# ---------------------------------------------------------------------------


def test_full_forward_matches_the_token_by_token_reference(model):
    """(a) 70 tokens: nine SSD chunks of 8 (the last one short) against the reference's recurrence, to 1e-5 of the logits' scale."""
    c, params = model
    seq = _tokens(70, seed=1)
    got, want = np.asarray(GraniteHybrid.apply(c, params, jnp.asarray(seq)[None])[0]), _ref_logits(c, params, seq)
    assert float(np.abs(got - want).max()) < 1e-5 * float(want.std())


@pytest.mark.parametrize("field", ["embedding_multiplier", "attention_multiplier", "residual_multiplier", "logits_scaling"])
def test_no_multiplier_can_be_dropped_unseen(model, field):
    """(e) each of the four multipliers set to 1 (the attention's to head_dim^-1/2,
    what a template left to its default scores at) moves the logits far past (a)'s tolerance."""
    c, params = model
    seq = _tokens(40, seed=2)
    want = _ref_logits(c, params, seq)
    changed = dataclasses.replace(c, **{field: c.head_dim**-0.5 if field == "attention_multiplier" else 1.0})
    got = np.asarray(GraniteHybrid.apply(changed, params, jnp.asarray(seq)[None])[0])
    assert float(np.abs(got - want).max()) > 1e-2 * float(want.std())


def test_compute_copy_keeps_the_recurrence_parameters_and_norms_in_float32(model):
    c, params = model
    lo = GraniteHybrid.cast_params(params, jnp.bfloat16)
    for name in ("a_log", "d_skip", "dt_bias", "gate_norm", "norm_in", "norm_mlp"):
        assert getattr(lo.mamba, name).dtype == jnp.float32, name
    assert lo.attn.norm_in.dtype == lo.final_norm.dtype == jnp.float32
    assert lo.mamba.w_z.dtype == lo.mamba.conv.dtype == lo.mamba.conv_bias.dtype == lo.attn.wo.dtype == lo.wte.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# (b), (c), (f): the recurrence as a serving op, against one oracle
# ---------------------------------------------------------------------------


def _ssd_inputs(seed, T, H=3, P=8, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x, dt = jax.random.normal(ks[0], (2, T, H, P)), jax.nn.softplus(jax.random.normal(ks[1], (2, T, H)) - 1.0)
    A, D = -jnp.exp(jax.random.normal(ks[2], (H,))), jax.random.normal(ks[3], (H,))
    return x, dt, A, jax.random.normal(ks[4], (2, T, N)), jax.random.normal(ks[5], (2, T, N)), D, jax.random.normal(ks[6], (2, H, P, N))


@pytest.mark.parametrize("carried", [False, True], ids=["from_zeros", "from_a_state"])
@pytest.mark.parametrize("T", [37, 16, 5], ids=["five_chunks_the_last_short", "two_whole_chunks", "under_a_chunk"])
def test_chunked_ssd_matches_the_recurrence(T, carried):
    """(b) lengths that are no multiple of the chunk, with and without `initial_state`: values and final state are `ssd_recurrent`'s."""
    x, dt, A, B, C, D, h0 = _ssd_inputs(3, T)
    init = h0 if carried else None
    y_r, h_r = ssd_recurrent(x, dt, A, B, C, D, init)
    y, h = ssd_chunked(x, dt, A, B, C, D, init, chunk=8)
    np.testing.assert_allclose(y, y_r, atol=2e-5)
    np.testing.assert_allclose(h, h_r, atol=2e-5)


def test_two_chunked_calls_are_one_and_masked_rows_change_nothing():
    """(b) a prefill in two calls (21 + 16 tokens, the state carried) is the one call of 37; tokens whose dt is 0
    (how a prefill chunk masks its rows past `n_valid`) leave the state bit for bit."""
    x, dt, A, B, C, D, h0 = _ssd_inputs(4, 37)
    y_1, h_1 = ssd_chunked(x, dt, A, B, C, D, h0, chunk=8)
    cut = lambda a, lo, hi: a[:, lo:hi]
    y_a, h_a = ssd_chunked(cut(x, 0, 21), cut(dt, 0, 21), A, cut(B, 0, 21), cut(C, 0, 21), D, h0, chunk=8)
    y_b, h_b = ssd_chunked(cut(x, 21, 37), cut(dt, 21, 37), A, cut(B, 21, 37), cut(C, 21, 37), D, h_a, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], axis=1), y_1, atol=2e-5)
    np.testing.assert_allclose(h_b, h_1, atol=2e-5)
    np.testing.assert_array_equal(ssd_chunked(x, jnp.zeros_like(dt), A, B, C, D, h0, chunk=8)[1], h0)


def test_one_token_steps_after_a_chunked_prefill_are_the_recurrence():
    """(c) `ssd_chunked` over 21 tokens, then `ssd_step` over the next 5, one by one: the recurrence over all 26."""
    x, dt, A, B, C, D, h0 = _ssd_inputs(5, 26)
    y_r, h_r = ssd_recurrent(x, dt, A, B, C, D, h0)
    _, h = ssd_chunked(x[:, :21], dt[:, :21], A, B[:, :21], C[:, :21], D, h0, chunk=8)
    for t in range(21, 26):
        y, h = ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, h)
        np.testing.assert_allclose(y, y_r[:, t], atol=2e-5)
    np.testing.assert_allclose(h, h_r, atol=2e-5)


def test_b_and_c_are_shared_by_the_heads():
    """(f) ONE B for all heads: a perturbation of B at one token moves EVERY head's output from that token on, and no earlier one."""
    x, dt, A, B, C, D, h0 = _ssd_inputs(6, 20)
    y0 = ssd_chunked(x, dt, A, B, C, D, h0, chunk=8)[0]
    y1 = ssd_chunked(x, dt, A, B.at[:, 9].add(1.0), C, D, h0, chunk=8)[0]
    moved = np.abs(np.asarray(y1 - y0)).max(axis=(0, 3))  # (T, H)
    assert (moved[:9] == 0).all() and (moved[9:] > 1e-4).all()


# ---------------------------------------------------------------------------
# (d), (g): the engine: chunk carry, the convolution's history, slot reuse, the rows' books
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [3, 1], ids=["batched_prefill", "one_row_prefill"])
def test_engine_logits_match_the_reference_through_chunks_and_rounds(model, width, monkeypatch):
    """(d), (g) prompts of 37, 50, 11 and 3 tokens in chunks of 10 or 20 (no
    multiple of the SSD chunk of 8, so SSD chunks, prefill chunks and the
    convolution's history all cross; the prompt of 3 is shorter than the
    convolution's 4 taps), three slots serving four requests at a temperature
    (a slot is re-admitted on a row its last request left dirty): the prefill
    program's logits at each prompt's last row and the first step's of every
    later decode round are the reference's full forward's on the tokens the
    engine produced."""
    c, params = model
    chunk = 10 if width == 3 else 20
    if width == 1:  # a chunk at the ridge on its own rides alone: the family's one-row call
        monkeypatch.setattr("midgpt_tpu.sampling.serve.PREFILL_ROWS", chunk)
    work = [(37, 13), (50, 13), (11, 13), (3, 9)]
    eng, uids, got = _serve(c, params, work, slots=3, chunk=chunk, temperature=0.8, seed=5)
    assert eng.prefill_width == width and all(len(got[uid]) >= 2 for uid in uids)
    assert [k.name for k in eng.kinds] == ["global"] and [k.name for k in eng.state_kinds] == ["ssm_state"]
    assert _worst(c, params, eng, uids, got) < 1e-4
    counters = eng.serve_counters()
    assert counters["ssm.prefill_tokens"] == 37 + 50 + 11 + 3 and counters["ssm.prefill_chunks"] == sum(-(-p // chunk) for p, _ in work)
    assert counters["ssm.decode_tokens"] >= sum(m - 1 for _, m in work)  # a round runs whole chunks of 4 steps
    assert counters["state.rows"] == 3 == counters["state.rows_live_max"] and counters["state.resets"] == 4 and counters["state.rows_live"] == 0
    per_slot = c.n_mamba * (c.mamba_heads * c.mamba_head_dim * c.mamba_state * 4 + (c.mamba_conv - 1) * c.conv_channels * 4)
    assert counters["state.bytes_per_slot"] == per_slot


def _cache(c, rows=3, pages_=9, seed=0):
    """A cache with every state row filled with noise (and zero pools)."""
    cache = GraniteHybrid.init_cache(c, (pages_, rows + 1), page_size=4, dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(cache.state))
    return dataclasses.replace(cache, state=tuple(jax.random.normal(k, a.shape, a.dtype) for k, a in zip(ks, cache.state)))


@pytest.mark.parametrize("batched", [True, False], ids=["batched", "one_row"])
def test_a_prompts_first_chunk_starts_from_zeros_and_a_later_one_from_its_row(model, batched):
    """(d) the row's reset is the prefill program's: a chunk at start 0 leaves
    the state, the history and the logits a ZEROED row gives, bit for bit,
    whatever the slot's last request left; a chunk at start 16 carries on from
    the row, so dirt there IS seen (a lost reset or a lost carry would show)."""
    c, params = model
    dirty, clean = _cache(c, seed=2), GraniteHybrid.init_cache(c, (9, 4), page_size=4, dtype=jnp.float32)
    toks = jnp.asarray(_tokens(16, seed=6))[None]
    table = (np.array([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32), np.array([2], np.int32))

    def run(cache, start):
        lift = (lambda x: jnp.asarray([x], jnp.int32)) if batched else jnp.int32
        logits, after = GraniteHybrid.prefill_paged_chunk(c, params, toks, lift(start), lift(16), cache, table)
        return np.asarray(logits), [np.asarray(a[:, 2]) for a in after.state]

    (l_dirty, s_dirty), (l_clean, s_clean) = run(dirty, 0), run(clean, 0)
    np.testing.assert_array_equal(l_dirty, l_clean)
    for a, b in zip(s_dirty, s_clean):
        np.testing.assert_array_equal(a, b)
    (l_dirty, s_dirty), (l_clean, s_clean) = run(dirty, 16), run(clean, 16)
    assert float(np.abs(l_dirty - l_clean).max()) > 1e-2 * float(l_clean.std()) and not np.array_equal(s_dirty[0], s_clean[0])


def test_rows_past_n_valid_change_neither_state_nor_history(model):
    """(g) a chunk of 16 of which 7 are real: the state and the history it leaves are the same bit for bit whatever the
    padding holds, and every OTHER row is bit for bit as it was."""
    c, params = model
    cache = _cache(c)
    table = (np.array([[1, 2, 3, 4]], np.int32), np.array([1], np.int32))
    run = lambda toks, n: GraniteHybrid.prefill_paged_chunk(c, params, jnp.asarray(toks)[None], jnp.int32(0), jnp.int32(n), cache, table)[1]
    real = _tokens(7, seed=3)
    a, b = run(np.concatenate([real, np.zeros(9, np.int32)]), 7), run(np.concatenate([real, _tokens(9, seed=4)]), 7)
    for x, y, before in zip(a.state, b.state, cache.state):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x[:, 1], before[:, 1])
        np.testing.assert_array_equal(np.delete(np.asarray(x), 1, axis=1), np.delete(np.asarray(before), 1, axis=1))


def test_a_decode_round_leaves_an_inactive_slots_row_bit_for_bit(model):
    """(d) three slots of which the middle one sits the round out (it is in the middle of its chunked prefill): its SSM
    state and its convolution history are bit for bit what they were, and so is the sink row; the active slots' moved."""
    c, params = model
    cache = _cache(c, seed=1)
    table = (np.array([[1, 2], [3, 4], [5, 6]], np.int32), np.arange(3, dtype=np.int32))
    _, after = GraniteHybrid.decode_step_paged(c, params, jnp.asarray([5, 6, 7]), cache, table, jnp.asarray([3, 2, 5]),
                                               jnp.asarray([True, False, True]))
    for x, before in zip(after.state, cache.state):
        np.testing.assert_array_equal(x[:, 1], before[:, 1])
        np.testing.assert_array_equal(x[:, 3], before[:, 3])
        assert not np.array_equal(x[:, 0], before[:, 0]) and not np.array_equal(x[:, 2], before[:, 2])
    assert int(after.counters[0][0]) == 2


def test_a_preempted_request_is_recomputed_and_the_books_hold(model):
    """(d) a pool too small for every slot at once: the youngest slot is preempted (its row given back, reset again by the
    first chunk of its recompute), and after every round rows free + rows live == the slots; what finishes is the reference's."""
    c, params = model
    eng = ServeEngine(c, params, max_slots=3, num_pages=24, page_size=4, prefill_chunk=10, decode_chunk=4, cache_dtype="float32")
    work = [(30, 30), (28, 28), (26, 26)]
    uids = [eng.submit(_tokens(p, seed=p), m) for p, m in work]
    while not eng.idle:
        eng.step()
        terms = {t["kind"]: t for t in eng.pool.ledger(eng.slots)}
        assert eng.pool.conserved(eng.slots) and set(terms) == {"global", "ssm_state"}, terms
    assert eng.stats()["preemptions"] > 0 and 3 < eng.serve_counters()["state.resets"] == eng._admitted
    for uid, (p, m) in zip(uids, work):
        seq = eng.finished[uid].tokens
        np.testing.assert_array_equal(seq[p:], np.argmax(_ref_logits(c, params, seq)[p - 1:-1], axis=-1))


# ---------------------------------------------------------------------------
# (h): the refusals, by name
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("what,kw,says", [
    ("prefix cache", dict(prefix_cache=True), "the prefix cache"),
    ("int8", dict(cache_dtype="int8"), "int8 pools"),
    ("speculation", "draft", "speculative decoding"),
    ("mesh", "mesh", "a serving mesh"),
])
def test_what_moves_pages_is_refused_by_name_beside_the_state_kind(model, what, kw, says):
    c, params = model
    if kw == "draft":
        from midgpt_tpu.models.gpt import GPTConfig

        kw = dict(draft_params=params, draft_config=GPTConfig(block_size=c.block_size, vocab_size=c.vocab_size, n_layer=1, n_head=2, n_embd=16))
    elif kw == "mesh":
        kw = dict(mesh=jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "tp")))
    with pytest.raises(NotImplementedError, match="STATE kind of cache") as e:
        ServeEngine(c, params, max_slots=2, page_size=4, prefill_chunk=8, decode_chunk=4, **kw)
    assert says in str(e.value) and "ssm_state" in str(e.value) and "granite_hybrid" in str(e.value)


def test_training_a_mesh_axis_and_an_int8_pool_are_refused_by_name(model):
    c, _ = model
    with pytest.raises(NotImplementedError, match="cannot train a granite_hybrid model"):
        c.check_training("launch.py")
    with pytest.raises(NotImplementedError, match="int8"):
        GraniteHybrid.init_cache(c, (9, 3), 4, jnp.int8)
    assert GraniteHybrid.verify_step_paged is None
    config = load_config(FILE["repo_config"])
    for axis in ("fsdp", "sp", "tp", "pp", "ep"):
        with pytest.raises(ValueError, match="no mesh axis but data"):
            bad = config.replace(mesh=dataclasses.replace(config.mesh, **{axis: 2}))
            bad.model_config.check_experiment(bad)
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(c, layer_types=("mamba", "attention", "mamba", "mamba", "mamba", "mamba", "attention", "mamba"))
    with pytest.raises(ValueError, match="ONE B and ONE C"):
        dataclasses.replace(c, mamba_groups=2)


# ---------------------------------------------------------------------------
# (i): the configuration file, the preset, the arithmetic
# ---------------------------------------------------------------------------


def test_configuration_file_matches_the_catalog_row_key_for_key():
    """Every published key value for value, nothing in `reduced`; the preset the file names is the published model; the
    resolved configuration is what `model` states."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    pub = row["config"]
    assert FILE["source"] == row["source_url"] and FILE["reduced"] == [] and not {k for k, v in pub.items() if FILE.get(k) != v}
    mc = load_config(FILE["repo_config"]).model_config
    assert (mc.n_layer, mc.n_embd, mc.n_head, mc.n_kv_head, mc.vocab_size, mc.dense_width, mc.block_size) == (
        pub["num_hidden_layers"], pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"], pub["vocab_size"],
        pub["shared_intermediate_size"], pub["max_position_embeddings"])
    assert (mc.mamba_heads, mc.mamba_head_dim, mc.mamba_state, mc.mamba_groups, mc.mamba_conv, mc.mamba_chunk) == (
        pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"], pub["mamba_n_groups"], pub["mamba_d_conv"], pub["mamba_chunk_size"])
    assert mc.mamba_inner == pub["mamba_expand"] * pub["hidden_size"] and list(mc.layer_types) == pub["layer_types"]
    assert (mc.embedding_multiplier, mc.attention_multiplier, mc.residual_multiplier, mc.logits_scaling, mc.rms_norm_eps) == (
        pub["embedding_multiplier"], pub["attention_multiplier"], pub["residual_multiplier"], pub["logits_scaling"], pub["rms_norm_eps"])
    assert (mc.period, mc.attn_at, mc.n_periods, mc.n_mamba) == (10, 5, 4, 36) and pub["tie_word_embeddings"] and pub["num_local_experts"] == 0
    ran = dataclasses.replace(mc, **FILE["overrides"]["model_config"])
    assert {k: v for k, v in dataclasses.asdict(ran).items() if k in FILE["model"]} == FILE["model"]
    assert set(FILE["assumed"]) >= {"block_arrangement", "mamba_layer", "state_dtype", "attention_layer", "initialisers", "weights", "block_size"}


def test_parameter_and_byte_counts_are_the_issues():
    """3,191,396,096 parameters (76,182,976 a mamba layer, 60,821,504 an attention layer); one slot's state 76,437,504 B;
    a token's keys and values 8,192 B; the arithmetic module's counts at the published shapes by hand."""
    mc = dataclasses.replace(load_config(FILE["repo_config"]).model_config, **FILE["overrides"]["model_config"])
    shapes = jax.eval_shape(lambda k: GraniteHybrid.init(mc, k), jax.random.PRNGKey(0))
    assert GraniteHybrid.count_params(shapes) == 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2_048 == 3_191_396_096
    a_layer = lambda group: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(group))
    assert a_layer(shapes.mamba) == 76_182_976 and a_layer(shapes.attn) == 60_821_504
    assert "3,191,396,096" in FILE["what"] and FILE["state"]["per_slot_published_bytes"] == 76_437_504 and FILE["state"]["kv_per_token_bytes"] == 8_192
    arith = _load("benchmarks/arithmetic_granite_hybrid.py")
    m = dataclasses.asdict(mc)
    assert arith.state_bytes_per_slot(m) == 76_437_504 == 36 * (64 * 64 * 128 * 4 + 3 * 4_352 * 2) == sum(
        int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in mc.state_shapes(jnp.bfloat16))
    # one token's update, 36 layers: the state read and written (2 x 2,097,152 B), x and z (2 x 4,096 bf16), B and C (2 x 128 bf16), dt, y
    assert arith.state_update_token(m) == (36.0 * 5 * 64 * 64 * 128, 36.0 * (4_194_304 + 2 * 8_448 + 256 + 16_384)) == (94_371_840.0, 152_202_240.0)
    # a prompt token: Q N + H (Q P + 4 P N) = 32,768 + 64 x 49,152 FLOPs a layer; the state's 4 MB shared by 512 tokens
    assert arith.prefill_scan_token(m) == (36.0 * 3_178_496, 36.0 * (16_896 + 256 + 16_384 + 4_194_304 / 512)) == (114_425_856.0, 1_502_208.0)
    assert arith.kv_write_token(m) == (0.0, 8_192.0)
    assert arith.decode_attention_token(m, "global", 1000) == (4.0 * 1000 * 2_048 * 4, float((2 * 1000 * 512 * 2 + 4 * 2_048) * 4))
    # every matrix, taps and bias once, the tied embedding once as the head: all but the norm gains and A_log, D, dt_bias
    assert arith.decode_step_weight_bytes(m) == 2.0 * (3_191_396_096 - 2_048 - 36 * (2 * 2_048 + 4_096 + 3 * 64) - 4 * 2 * 2_048)
    assert GraniteHybrid.flops_per_token(mc, 1) > 2 * 3_191_000_000


def test_config_json_round_trip_keeps_the_family():
    from midgpt_tpu.config import from_json, to_json

    back = from_json(to_json(load_config(FILE["repo_config"]).replace(model_config=toy())))
    assert back.model_config == toy() and isinstance(back.model_config.layer_types, tuple)


def test_the_cells_traffic_and_entries_are_the_issues():
    loadgen = _load("benchmarks/loadgen.py")
    with open(os.path.join(ROOT, "benchmarks/traffic/sessions_state_closed.json")) as f:
        spec = json.load(f)
    a, b = loadgen.Traffic(spec, 1, 100352), loadgen.Traffic(spec, 2**31 + 12345, 100352)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 128 and spec["kind"] == "serve_state_ssm"
    assert 1480 < np.mean(a.prompt_lens) < 1540 and 600 < np.mean(a.output_lens) < 620 and all(o % 8 == 0 for o in a.output_lens)
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 10240 and (spec["clients"], spec["cycle"]) == (64, 128)
    e = spec["engine"]
    assert (e["max_slots"], e["page_size"], e["prefill_chunk"], e["decode_chunk"], e["pool_tokens_per_slot"], e["prefix_cache"]) == (64, 32, 512, 8, 4096, False)
    assert spec["check"] == {"prompts": [300, 1300, 2600, 5000], "decode_rounds": 8}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["workloads"][-1] == dict(bench["workloads"][-1], name=CELL, config=CONFIG, traffic="sessions_state_closed", chips=1)
    entry = bench["configs"][-1]
    assert (entry["name"], entry["reduced"], entry["file"], entry["source"]) == (CONFIG, [], f"benchmarks/configs/{CONFIG}.json", FILE["source"])
    assert {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])} == {"setup_s", "serve_tokens_per_s"}
    joined = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert joined == {m["name"] for m in bench["per_layer"] if "serve_olmo_hybrid_docchat" in m.get("workloads", [])}
    assert {"serve.attn_linear_ms", "serve.dense_ffn_ms", "linear_state_update_roofline", "linear_prefill_scan_roofline", "state.pool_fill",
            "serve.attn_global_ms", "global_decode_attention_roofline", "kv.global_pool_fill", "kv_write_roofline",
            "prefill_attention_ms_per_token", "serve.lm_head_ms", "serve.weight_read_share", "serve.model_unattributed_ms",
            "decode.live_block_share"} <= joined
    assert not [m["name"] for m in bench["per_layer"] if m.get("workloads") == [CELL]] and len(bench["per_layer"]) <= 128


# ---------------------------------------------------------------------------
# (j): the benchmark's cell, and the entry point
# ---------------------------------------------------------------------------


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """(j) `run.py --workload serve_granite4_h_sessions --rehearse-cpu --trace 1` (from a tree of its own) exits 0, is
    `correct` through dirty state rows, and names every metric declared for the cell that a CPU run can produce."""
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
    assert {"serve.attn_linear_ms", "serve.attn_global_ms", "serve.dense_ffn_ms", "serve.lm_head_ms", "state.pool_fill", "kv.global_pool_fill"} <= set(last["would_report"])
    assert "every state row dirty" in proc.stdout and "-> ok" in proc.stdout and "state kind: 3 rows" in proc.stdout
    assert "admitted to slots [1, 2]" in proc.stdout and "rows reset, one an admission: 5 (want 5)" in proc.stdout


def test_the_8_bit_control_is_refused_by_the_cells_own_limits(tmp_path):
    proc = run_rehearsal(tmp_path, CELL, script="serve_state_ssm_cell.py")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"program_correct": True, "control_correct": False}


def test_toy_checkpoint_serves_through_sample_py(tmp_path):
    """sample.py reaches the engine for this family through the same code as for the GPT: seeded parameters saved with the
    repo's checkpoint writer, restored through the family namespace, sampled greedily: the full forward's argmax chain."""
    import pickle
    import subprocess
    import sys

    from midgpt_tpu.config import to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = dataclasses.replace(toy(), vocab_size=65, block_size=64)
    params = GraniteHybrid.init(c, jax.random.PRNGKey(7))
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config(FILE["repo_config"]).replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
    (tmp_path / "config.json").write_text(to_json(exp))
    mngr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert mngr.save(3, {"params": params}, force=True)
    mngr.wait()
    mngr.close()
    args = [sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}", "--start=AB#", "--num_samples=2",
            "--max_new_tokens=6", "--temperature=0.0", "--engine=continuous"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(args, cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    new = json.loads(next(l for l in proc.stdout.splitlines() if l.startswith("new_tokens: "))[len("new_tokens: "):])
    seq = [32, 33, 2]  # "AB#" under the codec above
    with jax.default_matmul_precision("default"):  # as the entry point runs
        for _ in range(6):
            seq.append(int(np.argmax(np.asarray(GraniteHybrid.apply(c, params, jnp.asarray(seq)[None]))[0, -1])))
    assert new == [seq[3:], seq[3:]]
