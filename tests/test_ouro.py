"""models/ouro.py (a stack of layers applied `n_loop` times with the same
weights, the final norm and an exit gate after every pass, a paged cache of
`n_loop * n_layer` layers) against the plain float32 reference that lies beside
its benchmark configuration. CPU, toy widths, float32 under "highest"
(conftest). The engine over it: tests/test_ouro_serving.py."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.ouro import LOOPED, Ouro, OuroConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel):
    spec = importlib.util.spec_from_file_location("bench_" + os.path.basename(rel)[:-3], os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


reference = _load("benchmarks/configs/ouro_2p6b_reference.py")
arithmetic = _load("benchmarks/arithmetic_ouro.py")


def toy(**kw):
    base = dict(block_size=128, vocab_size=97, n_layer=3, n_head=4, n_embd=64, n_loop=4, head_dim=16, dense_width=96)
    return OuroConfig(**{**base, **kw})


def seeded(c, seed=0):
    """Seeded parameters with the norm gains away from 1 and the gate's bias
    away from 0, so that a norm or the bias left out or misplaced shows."""
    params = Ouro.init(c, jax.random.PRNGKey(seed))
    bump = lambda path, a: a * (1.0 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=a.dtype)).reshape(a.shape)) if "norm" in str(path[-1]) else a
    return dataclasses.replace(jax.tree_util.tree_map_with_path(bump, params), exit_b=jnp.asarray(0.3))


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, seeded(c)


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_full_forward_matches_the_reference(model):
    c, params = model
    seq = _tokens(45)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    got = np.asarray(Ouro.apply(c, params, jnp.asarray(seq[None])))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_exit_distribution_sums_to_one_and_is_the_references(model):
    c, params = model
    seq = _tokens(33, seed=2)
    _, want = reference.forward(params, jnp.asarray(seq), dataclasses.asdict(c))
    _, p = Ouro.forward(c, params, jnp.asarray(seq[None]))
    assert p.shape == (1, 33, c.n_loop)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(p)[0], np.asarray(want), atol=1e-5)
    assert np.asarray(p).min() > 1e-3  # every pass holds mass: the gate is neither stuck open nor shut


@pytest.mark.parametrize("n_loop", [1, 2, 4])
def test_the_loop_is_real(model, n_loop):
    """With `n_loop` passes the model is the reference run for that many
    passes over the SAME weights, and differs from the 1-pass reference as
    soon as it runs more than one."""
    c, params = model
    c_n, cfg = dataclasses.replace(c, n_loop=n_loop), dataclasses.asdict(c)
    seq = jnp.asarray(_tokens(21, seed=3))
    got = np.asarray(Ouro.apply(c_n, params, seq[None]))[0]
    np.testing.assert_allclose(got, np.asarray(reference.forward(params, seq, cfg, n_loop=n_loop)[0]), atol=2e-5)
    one_pass = np.asarray(reference.forward(params, seq, cfg, n_loop=1)[0])
    assert (np.abs(got - one_pass).max() < 2e-5) == (n_loop == 1)


def test_the_stack_visits_cache_rows_pass_major():
    """`_run` hands `attend` row r * n_layer + l, in order: pass r of layer l
    has a cache layer of its own, the passes of one layer n_layer rows apart."""
    c = toy(n_layer=3, n_loop=4)
    params = Ouro.init(c, jax.random.PRNGKey(1))
    x = jnp.zeros((1, 2, c.n_embd))

    def attend(state, row, q, k, v):
        n, seen = state
        return v, (n + 1, seen.at[n].set(row))

    _, _, (n, seen) = Ouro._run(c, params, x, jnp.arange(2), (jnp.zeros((), jnp.int32), jnp.full((12,), -1, jnp.int32)), attend)
    assert int(n) == 12 and seen.tolist() == list(range(12))


def test_published_preset_counts_its_parameters_and_its_cache():
    from midgpt_tpu.config import load_config

    mc = load_config("ouro_2p6b").model_config
    shapes = jax.eval_shape(lambda k: Ouro.init(mc, k), jax.random.PRNGKey(0))
    assert Ouro.count_params(shapes) == 2_667_974_657
    assert {a.shape[0] for a in jax.tree.leaves(shapes.layers)} == {48}  # every layer leaf stacked
    cache = jax.eval_shape(lambda: Ouro.init_cache(mc, (157,), 32, jnp.bfloat16, kernel_layout=True))
    assert [a.shape for a in cache.pool_arrays()] == [(192, 16, 157, 32, 128)] * 2
    assert 2 * cache.pools[0][0].size * 2 == 157 * 32 * 1_572_864  # bytes: 7.90 GB, 1,572,864 B a token
    # forward FLOPs a token at one key: 2 x (4 x (the layers' matrices + a key's scores and values + the gate) + the head)
    assert Ouro.flops_per_token(mc, 1) == 2.0 * (4 * (2_466_250_752 + 48 * 2048 + 2048) + 49152 * 2048)
    model = dataclasses.asdict(mc)
    assert arithmetic.cache_layers(model) == 192
    assert arithmetic.kv_write_token(model) == (0.0, 1_572_864.0)
    assert arithmetic.decode_attention_token(model, 100) == (4.0 * 100 * 2048 * 192, 100 * 1_572_864.0)
    assert arithmetic.decode_step_weight_bytes(model) == 2.0 * (4 * 2_466_250_752 + 49152 * 2048)  # 19.93 GB


def test_cast_params_keeps_norms_and_gate_in_float32(model):
    c, params = model
    cast = Ouro.cast_params(params, jnp.bfloat16)
    f32 = {"norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp", "final_norm", "exit_w", "exit_b"}
    for path, a in jax.tree_util.tree_leaves_with_path(cast):
        name = str(getattr(path[-1], "name", path[-1]))
        assert a.dtype == (jnp.float32 if name in f32 else jnp.bfloat16), name


def test_training_is_refused_by_name_and_the_preset_round_trips():
    from midgpt_tpu.config import from_json, load_config, to_json

    exp = load_config("ouro_2p6b")
    with pytest.raises(NotImplementedError, match="exit-weighted"):
        exp.model_config.check_training("launch.py")
    assert exp.model_config.check_serving("sample.py") is None
    back = from_json(to_json(exp))
    assert back.model_config == exp.model_config and type(back.model_config) is OuroConfig
    assert json.loads(to_json(exp))["model_config"]["family"] == "ouro"
    with pytest.raises(ValueError, match="early_exit_threshold"):
        dataclasses.replace(exp.model_config, early_exit_threshold=0.9)


def test_the_references_8_bit_rounding_moves_the_logits(model):
    c, params = model
    seq, cfg = jnp.asarray(_tokens(30)), dataclasses.asdict(c)
    rows = np.arange(21, 30)
    want = np.asarray(reference.logits(params, seq, cfg, rows=rows))
    got = np.asarray(reference.logits(params, seq, cfg, rows=rows, round_to=jnp.float8_e4m3fn))
    assert want.shape == got.shape == (9, c.vocab_size)
    assert np.sqrt(np.mean((got - want) ** 2)) / np.std(want) > 5e-2


# ---------------------------------------------------------------------------
# the paged path, the model's own functions (the engine: test_ouro_serving.py)
# ---------------------------------------------------------------------------

PS = 4


def _prefilled(c, params, seq, chunk, one_row, pages=None):
    """`seq` prefilled in chunks of `chunk` into pages 1.. of a fresh cache: (last logits, cache, table (1, pages))."""
    cache = Ouro.init_cache(c, (-(-c.block_size // PS) + 1,), PS, jnp.float32)
    table = jnp.arange(1, (pages or -(-c.block_size // PS)) + 1, dtype=jnp.int32)[None]
    logits = None
    for s in range(0, len(seq), chunk):
        toks = np.zeros((1, chunk), np.int32)
        n = min(chunk, len(seq) - s)
        toks[0, :n] = seq[s:s + n]
        start, n_valid = (jnp.asarray(s), jnp.asarray(n)) if one_row else (jnp.asarray([s]), jnp.asarray([n]))
        logits, cache = Ouro.prefill_paged_chunk(c, params, jnp.asarray(toks), start, n_valid, cache, table, attn_impl="gather")
    return logits, cache, table


@pytest.mark.parametrize("one_row", [False, True], ids=["batched", "one_row"])
def test_chunked_prefill_then_paged_decode_is_the_full_forward(model, one_row):
    c, params = model
    seq = _tokens(29, seed=5)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    logits, cache, table = _prefilled(c, params, seq[:22], 8, one_row)
    assert logits.shape == ((1, 1, c.vocab_size) if one_row else (1, c.vocab_size))
    np.testing.assert_allclose(np.asarray(logits).reshape(-1), want[21], atol=2e-5)
    for t in range(22, 29):
        logits, cache = Ouro.decode_step_paged(c, params, jnp.asarray(seq[t:t + 1]), cache, table, jnp.asarray([t]),
                                               jnp.asarray([True]), attn_impl="gather")
        np.testing.assert_allclose(np.asarray(logits)[0], want[t], atol=2e-5)
    counters = Ouro.serve_counters(c, cache)
    assert counters["loop.decode_steps"] == 7 and counters["loop.passes_run"] == 7 * c.n_loop
    _, p = reference.forward(params, jnp.asarray(seq), dataclasses.asdict(c), rows=np.arange(22, 29))
    np.testing.assert_allclose([counters[f"loop.exit_mass_{r + 1}"] for r in range(c.n_loop)], np.asarray(p).sum(0), atol=1e-4)
    assert abs(counters["loop.exit_pass_expected"] - float((np.asarray(p) * np.arange(1, 5)).sum() / 7)) < 1e-4


def test_a_pass_reads_its_own_cache_layers_and_no_other(model):
    """The cache has n_loop * n_layer layers. Run with ONE pass over a cache
    that four passes filled, the model reads rows [0, n_layer) alone: NaNs in
    every other pass's rows change nothing, and the logits are the 1-pass
    reference's. NaNs in any ONE pass's rows reach the 4-pass logits (each is
    read), NaNs in pages the slot's table does not name do not."""
    c, params = model
    L = c.n_layer
    seq = _tokens(18, seed=7)
    _, cache, table = _prefilled(c, params, seq[:17], 8, False, pages=5)  # 18 tokens: pages 1..5
    assert cache.pools[0][0].shape[0] == c.n_loop * L == 12
    step = lambda cfg, cache: np.asarray(Ouro.decode_step_paged(
        cfg, params, jnp.asarray(seq[17:18]), cache, table, jnp.asarray([17]), jnp.asarray([True]), attn_impl="gather")[0])[0]
    poison = lambda rows: dataclasses.replace(cache, pools=(tuple(a.at[rows].set(jnp.nan) for a in cache.pools[0]),))
    clean = step(c, cache)
    c1 = dataclasses.replace(c, n_loop=1)
    one = step(c1, poison(slice(L, None)))
    np.testing.assert_allclose(one, np.asarray(reference.forward(params, jnp.asarray(seq), dataclasses.asdict(c), n_loop=1)[0])[17], atol=2e-5)
    for r in range(c.n_loop):
        assert np.isnan(step(c, poison(slice(r * L, (r + 1) * L)))).all(), r
    unnamed = dataclasses.replace(cache, pools=(tuple(a.at[:, :, 6:].set(jnp.nan) for a in cache.pools[0]),))
    np.testing.assert_array_equal(step(c, unnamed), clean)


def test_inactive_slots_and_empty_rows_write_nothing(model):
    c, params = model
    cache = Ouro.init_cache(c, (9,), PS, jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, after = Ouro.decode_step_paged(c, params, jnp.asarray([3, 4]), cache, table, jnp.asarray([0, 0]),
                                       jnp.asarray([False, False]), attn_impl="gather")
    assert not np.asarray(after.pools[0][0]).any() and Ouro.serve_counters(c, after)["loop.passes_run"] == 0
    _, after = Ouro.prefill_paged_chunk(c, params, jnp.zeros((2, 8), jnp.int32), jnp.asarray([0, 0]), jnp.asarray([0, 5]),
                                        cache, table, attn_impl="gather")
    k = np.asarray(after.pools[0][0])
    assert not k[:, :, 1:5].any() and k[:, :, 5:7].any() and not k[:, :, 7:].any() and not k[:, :, 0].any()


def test_cache_kind_and_contract_members():
    c = toy()
    (kind,) = Ouro.cache_kinds(c)
    assert (kind.name, kind.window, kind.sinks) == (LOOPED, 0, 0)
    assert Ouro.prefill_batched and Ouro.verify_step_paged is None
    cache = Ouro.init_cache(c, (5,), PS, jnp.float32)
    assert Ouro.kernel_sweep(c, cache) == ((12, 4, 5, PS, 16), 1, 0, 0)
    assert Ouro.serve_counters(c, cache)[f"kv.{LOOPED}_bytes_per_token"] == 2 * c.n_loop * c.n_layer * c.n_head * c.head_dim * 4
    with pytest.raises(NotImplementedError, match="int8"):
        Ouro.init_cache(c, (5,), PS, jnp.int8)
