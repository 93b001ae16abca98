"""Kimi-Linear (models/kimi_linear.py, ops/kda.py, ops/moe.py) against its plain
float32 reference (benchmarks/configs/kimi_linear_48b_a3b_ep32_reference.py),
at a tiny size with all five layer kinds: KDA + dense MLP, KDA + MoE, MLA + MoE.
float32 compute on the CPU under `highest` matmul precision (conftest), so the
tolerances are float32 reassociation: 2e-4 on logits of O(1), 1e-3 relative on
gradients (the chunked recurrence sums in another order than token by token).
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ExperimentConfig, from_json, load_config, to_json
from midgpt_tpu.models.kimi_linear import KimiLinear, KimiLinearConfig
from midgpt_tpu.ops.kda import kda_chunked, kda_recurrent
from midgpt_tpu.ops.moe import moe_experts, route, swiglu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "configs", "kimi_linear_48b_a3b_ep32_reference.py")
    spec = importlib.util.spec_from_file_location("kimi_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def tiny(**kw) -> KimiLinearConfig:
    base = dict(
        block_size=40, vocab_size=64, n_layer=5, n_head=2, n_embd=32,
        kda_layers=(1, 2, 3, 5, 6, 7), full_attn_layers=(4, 8),
        kda_head_dim=16, kda_gate_rank=8, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, dense_width=64,
        n_experts=16, n_experts_held=4, expert_offset=4, moe_top_k=4, expert_width=24,
        attn_impl="naive",
    )
    base.update(kw)
    return KimiLinearConfig(**base)


def tiny_experiment(**kw) -> ExperimentConfig:
    return load_config("kimi_linear_48b_a3b").replace(model_config=tiny(), **kw)


@pytest.fixture(scope="module")
def setup():
    c = tiny()
    params = KimiLinear.init(c, jax.random.PRNGKey(0))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, c.block_size), 0, c.vocab_size)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, c.block_size), 0, c.vocab_size)
    return c, params, x, y


def _system_losses(c, params, x, y):
    h = KimiLinear.hidden(c, params, x)
    lg = jnp.einsum("btd,vd->btv", h, params.lm_head)
    return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0], lg


def test_layer_kinds_are_the_five(setup):
    c = setup[0]
    kinds = [(c.mixer_kind(i), c.mlp_kind(i)) for i in range(c.n_layer)]
    assert kinds == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe")]


def test_logits_and_losses_match_the_reference(setup):
    c, params, x, y = setup
    cfg = dataclasses.asdict(c)
    losses, lg = _system_losses(c, params, x, y)
    np.testing.assert_allclose(lg, REF.logits(params, x, cfg), atol=2e-4, rtol=0)
    np.testing.assert_allclose(losses, REF.token_losses(params, x, y, cfg), atol=2e-4, rtol=0)


def test_gradients_match_the_reference(setup):
    c, params, x, y = setup
    cfg = dataclasses.asdict(c)
    g_sys = jax.grad(lambda p: jnp.mean(_system_losses(c, p, x, y)[0]))(params)
    g_ref = jax.grad(lambda p: jnp.mean(REF.token_losses(p, x, y, cfg)))(params)
    flat_s, flat_r = jax.tree_util.tree_leaves_with_path(g_sys), jax.tree.leaves(g_ref)
    assert len(flat_s) == len(flat_r)
    for (path, a), b in zip(flat_s, flat_r):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 1e-3 * scale + 1e-7, jax.tree_util.keystr(path)
    assert float(jnp.abs(g_sys.layers[1].mlp.router_bias).max()) == 0.0  # selection only


@pytest.mark.parametrize("change", [
    {"moe_renormalize": False}, {"routed_scaling_factor": 1.0}, {"expert_offset": 8},
])
def test_a_departure_from_the_equations_fails_the_comparison(setup, change):
    """The comparison has teeth: a missing renormalisation, a missing scale or
    another chip's experts moves the logits by far more than the tolerance of
    the test above."""
    c, params, x, y = setup
    _, lg = _system_losses(dataclasses.replace(c, **change), params, x, y)
    err = float(jnp.abs(lg - REF.logits(params, x, dataclasses.asdict(c))).max())
    assert err > 50 * 2e-4, err


# ---------------------------------------------------------------------------
# ops/kda.py: chunked against token by token
# ---------------------------------------------------------------------------


def _kda_inputs(seed, T, decay):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, H, d = 2, 2, 8
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k = unit(jax.random.normal(ks[0], (B, T, H, d))), unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H, d))) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _kda_kernel(*args, **kw):
    """`kda_chunked` as the TPU runs it: kernels/kda.py, here in Pallas interpret mode."""
    from midgpt_tpu.kernels.kda import kda_scan
    from midgpt_tpu.ops.kda import CHUNK, SUB

    return kda_scan(*args, chunk=CHUNK, sub=SUB, **kw)


_KDA_BODIES = {"jnp": kda_chunked, "kernel": _kda_kernel}


@pytest.mark.parametrize("body", sorted(_KDA_BODIES))
@pytest.mark.parametrize("T,decay", [(192, 0.3), (150, 0.3), (150, 2.0), (7, 1.0)])
def test_chunked_kda_matches_the_recurrence(T, decay, body):
    """Values, final state and gradients over three chunks of 64; T = 150 and
    7 are no multiple of the chunk; decay 2.0 a token is e^-128 a chunk, which
    a factored e^{G_r} e^{-G_i} would overflow on. Both bodies: the jnp one
    (what `kda_chunked` runs off the TPU) and the Pallas kernels."""
    args = _kda_inputs(T, T, decay)
    chunked = _KDA_BODIES[body]
    o_r, s_r = kda_recurrent(*args)
    o_c, s_c = chunked(*args)
    np.testing.assert_allclose(o_c, o_r, atol=2e-6)
    np.testing.assert_allclose(s_c, jnp.swapaxes(s_r, 2, 3), atol=2e-6)  # a carried state is (d_v, d_k)
    scalar = lambda fn: lambda *a: jnp.sum(fn(*a)[0] ** 2) + jnp.sum(fn(*a)[1])
    g_r = jax.grad(scalar(kda_recurrent), argnums=(0, 1, 2, 3, 4))(*args)
    g_c = jax.grad(scalar(chunked), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_c, g_r):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max())


@pytest.mark.parametrize("body", sorted(_KDA_BODIES))
def test_chunked_kda_carries_its_state_in_float32(body):
    """bf16 inputs: the products run in bf16, the state does not. A state
    rounded to bf16 at every chunk (what this guards against) is 10x further
    from the float32 recurrence than the op is."""
    args = _kda_inputs(3, 256, 0.05)
    o_ref, _ = kda_recurrent(*args)
    lo = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    o_ref_lo, _ = kda_recurrent(*(a.astype(jnp.float32) for a in lo))
    o, s = _KDA_BODIES[body](*lo)
    assert s.dtype == jnp.float32
    err = float(jnp.sqrt(jnp.mean((o.astype(jnp.float32) - o_ref_lo) ** 2)))
    assert err < 2e-2 * float(jnp.sqrt(jnp.mean(o_ref**2))), err


def test_the_kda_kernel_starts_from_a_state_carried_in():
    """The kernels take the state IN (a chunked-prefill step will start from a
    slot's state): the last 100 tokens from the state the first 90 left are
    the last 100 of all 190, values, final state and the gradients of the
    tokens and of the state carried in. The oracle's state is the equations'
    (d_k, d_v); carried, it is (d_v, d_k)."""
    args = _kda_inputs(11, 190, 0.3)
    head, tail = (a[:, :90] for a in args), tuple(a[:, 90:] for a in args)
    T_ = lambda s: jnp.swapaxes(s, 2, 3)
    o_all, s_all = kda_recurrent(*args)
    _, s_head = kda_recurrent(*head)
    o, s = _kda_kernel(*tail, initial_state=T_(s_head))
    np.testing.assert_allclose(o, o_all[:, 90:], atol=2e-6)
    np.testing.assert_allclose(s, T_(s_all), atol=2e-6)

    scalar = lambda fn: lambda s0, *a: jnp.sum(fn(*a, initial_state=s0)[0] ** 2) + jnp.sum(fn(*a, initial_state=s0)[1])
    g_r = jax.grad(scalar(kda_recurrent), argnums=tuple(range(6)))(s_head, *tail)
    g_k = jax.grad(scalar(_kda_kernel), argnums=tuple(range(6)))(T_(s_head), *tail)
    for a, b in zip(g_k, (T_(g_r[0]),) + g_r[1:]):
        assert float(jnp.abs(a - b).max()) <= 2e-5 * float(jnp.abs(b).max())


# ---------------------------------------------------------------------------
# ops/moe.py: dispatch against a masked-dense float32 sum
# ---------------------------------------------------------------------------


def _moe_case(bias):
    N, D, F, E = 96, 16, 24, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x, wr = jax.random.normal(ks[0], (N, D)), jax.random.normal(ks[1], (E, D))
    wg, wu, wd = (jax.random.normal(k, s) / 4 for k, s in zip(ks[2:5], [(E, F, D), (E, F, D), (E, D, F)]))
    return x, wr, bias, wg, wu, wd


def _dense(x, wr, b, wg, wu, wd, off, n_held):
    idx, w = route(x, wr, b, top_k=4, scale=2.446)
    y = 0.0
    for e in range(off, off + n_held):
        y = y + swiglu(x, wg[e], wu[e], wd[e]) * jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)[:, None]
    return y


def _dispatched(x, wr, b, wg, wu, wd, off, n_held, n_tiles):
    idx, w = route(x, wr, b, top_k=4, scale=2.446)
    sl = slice(off, off + n_held)
    return moe_experts(x, idx, w, wg[sl], wu[sl], wd[sl], offset=off, n_tiles=n_tiles, tile=8)


_ALL_TO_5 = jnp.full((16,), -10.0).at[5].set(10.0).at[0:3].set(5.0)  # expert 5: every token; 4, 6, 7: none


@pytest.mark.parametrize("name,bias,off,n_held,n_tiles,overflow", [
    ("balanced_all_held", jnp.zeros((16,)), 0, 16, 64, False),
    ("balanced_share", jnp.zeros((16,)), 4, 4, 16, False),
    ("over_capacity", jnp.zeros((16,)), 4, 4, 4, True),
    ("all_tokens_to_one_expert", _ALL_TO_5, 4, 4, 12, False),  # 96 pairs = 12 tiles of 8: any skew fits
    ("all_tokens_to_one_expert_over_capacity", _ALL_TO_5, 4, 4, 11, True),
])
def test_moe_dispatch_matches_masked_dense_and_drops_nothing(name, bias, off, n_held, n_tiles, overflow):
    case = _moe_case(bias)
    y, stats = _dispatched(*case, off, n_held, n_tiles)
    np.testing.assert_allclose(y, _dense(*case, off, n_held), atol=2e-6)
    assert int(stats["dropped"]) == 0 and bool(stats["overflowed"]) == overflow
    idx, _ = route(case[0], case[1], bias, top_k=4, scale=1.0)
    want = [int(jnp.sum(idx == e)) for e in range(off, off + n_held)]
    assert stats["counts"].tolist() == want
    if name.startswith("all_tokens"):
        assert want == [0, 96, 0, 0]  # one expert has them all, three have none
    args = (0, 1, 3, 4, 5)
    g_d = jax.grad(lambda *a: jnp.sum(_dispatched(*a, off, n_held, n_tiles)[0] ** 2), argnums=args)(*case)
    g_m = jax.grad(lambda *a: jnp.sum(_dense(*a, off, n_held) ** 2), argnums=args)(*case)
    for a, b in zip(g_d, g_m):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(jnp.abs(b).max()) + 1e-9


def test_moe_capacity_is_the_mean_load_in_tiles():
    from midgpt_tpu.ops.moe import moe_capacity

    assert moe_capacity(8192, 8, 256, 8, 2.0) == (24, 256)  # the cell: 2 x 2,048 pairs in 16 tiles + 8 of padding
    assert moe_capacity(16384, 8, 256, 8, 2.0) == (40, 256)
    assert moe_capacity(80, 4, 16, 4, 2.0) == (14, 16)


def test_router_adds_the_bias_to_the_selection_and_not_to_the_weights():
    x, wr = jax.random.normal(jax.random.PRNGKey(0), (8, 16)), jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    bias = jnp.zeros((16,)).at[3].set(100.0)
    idx, w = route(x, wr, bias, top_k=4, scale=2.446)
    assert bool(jnp.all(jnp.any(idx == 3, axis=-1)))  # the bias selects expert 3 everywhere
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.446, rtol=1e-6)  # renormalised, then scaled
    s = jax.nn.sigmoid(x @ wr.T)
    np.testing.assert_allclose(w, 2.446 * jnp.take_along_axis(s, idx, -1) / jnp.sum(jnp.take_along_axis(s, idx, -1), -1, keepdims=True), rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts that all the shares
    give, with the shared expert counted once, are the uncut reference layer."""
    whole = tiny(n_experts_held=16, expert_offset=0)
    p = KimiLinear.init(whole, jax.random.PRNGKey(3)).layers[1].mlp
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 40, whole.n_embd))
    uncut = REF.moe_layer(p, h[0], dataclasses.asdict(whole))
    shared = swiglu(h, p.shared.w_gate, p.shared.w_up, p.shared.w_down)[0]
    total, pairs = shared, 0
    for r in range(4):
        share_cfg = tiny(n_experts_held=4, expert_offset=4 * r)
        sl = slice(4 * r, 4 * r + 4)
        p_r = dataclasses.replace(p, w_gate=p.w_gate[sl], w_up=p.w_up[sl], w_down=p.w_down[sl])
        y_r, stats = KimiLinear._moe(share_cfg, p_r, h)
        total = total + (y_r[0] - shared)  # every chip computes the shared expert alike: once
        pairs += int(jnp.sum(stats["counts"]))
        np.testing.assert_allclose(
            y_r[0] - shared, REF.moe_layer(p_r, h[0], dataclasses.asdict(share_cfg), include_shared=False), atol=2e-6)
    np.testing.assert_allclose(total, uncut, atol=5e-6)
    assert pairs == 40 * whole.moe_top_k  # every pair was somebody's


# ---------------------------------------------------------------------------
# the preset, the config round trip, the runtime
# ---------------------------------------------------------------------------


def test_published_preset_counts_49b_parameters():
    mc = load_config("kimi_linear_48b_a3b").model_config
    assert (mc.n_layer, mc.n_experts, mc.n_experts_held, mc.vocab_size, mc.n_embd) == (27, 256, 256, 163840, 2304)
    assert sum(mc.mixer_kind(i) == "kda" for i in range(27)) == 20
    shapes = jax.eval_shape(lambda k: KimiLinear.init(mc, k), jax.random.PRNGKey(0))
    n = KimiLinear.count_params(shapes)
    assert abs(n - 49.1e9) < 0.01 * 49.1e9, n
    cut = dataclasses.replace(mc, n_layer=5, n_experts_held=8, vocab_size=20480)
    n_cut = KimiLinear.count_params(jax.eval_shape(lambda k: KimiLinear.init(cut, k), jax.random.PRNGKey(0)))
    assert abs(n_cut - 602e6) < 1e6, n_cut  # the cell's 9.64 GB at 16 B a parameter


def test_config_json_round_trip_keeps_the_family():
    for name in ("kimi_linear_48b_a3b", "shakespeare_char"):
        config = load_config(name)
        assert from_json(to_json(config)) == config
    raw = json.loads(to_json(load_config("kimi_linear_48b_a3b")))
    assert raw["model_config"]["family"] == "kimi_linear"
    raw["model_config"]["family"] = "nope"
    with pytest.raises(ValueError, match="model family"):
        from_json(json.dumps(raw))


@pytest.mark.parametrize("axis", ["fsdp", "sp", "tp", "pp", "ep"])
def test_a_mesh_axis_other_than_data_is_refused_by_name(axis):
    config = tiny_experiment()
    with pytest.raises(ValueError, match="only the data-parallel mesh is wired"):
        config.replace(mesh=dataclasses.replace(config.mesh, **{axis: 2}))


@pytest.mark.parametrize("impl", ["blockwise", "ring", "ulysses"])
def test_an_attention_the_mla_path_cannot_take_is_refused(impl):
    with pytest.raises(ValueError, match="the MLA layers take"):
        tiny(attn_impl=impl)


def test_weight_decay_skips_the_leaves_that_are_no_matrix(setup):
    from midgpt_tpu.training.optim import make_optimizer

    c, params, _, _ = setup
    skipped = {
        jax.tree_util.keystr(path).split(".")[-1]
        for path, decays in jax.tree_util.tree_leaves_with_path(KimiLinear.weight_decay_mask(params)) if not decays
    }
    assert skipped == {"norm1", "norm2", "final_norm", "kv_norm", "o_norm", "A_log", "dt_bias", "router_bias", "conv"}
    optimizer, _ = make_optimizer(tiny_experiment(warmup_steps=0, weight_decay=0.1))
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, _ = optimizer.update(zero, optimizer.init(params), params)  # zero gradient: decay alone
    assert float(jnp.abs(updates.layers[0].mixer.A_log).max()) == 0.0
    assert float(jnp.abs(updates.layers[0].norm1).max()) == 0.0
    assert float(jnp.abs(updates.layers[1].mlp.router_bias).max()) == 0.0
    assert float(jnp.abs(updates.layers[0].mixer.wo).max()) > 0.0
    assert float(jnp.abs(updates.lm_head).max()) > 0.0


def test_compute_copy_keeps_decay_rates_and_the_router_in_float32(setup):
    pc = KimiLinear.cast_params(setup[1], jnp.bfloat16)
    assert pc.layers[0].mixer.w_qkv.dtype == jnp.bfloat16 and pc.lm_head.dtype == jnp.bfloat16
    for leaf in (pc.layers[0].mixer.A_log, pc.layers[0].mixer.dt_bias, pc.layers[1].mlp.router,
                 pc.layers[1].mlp.router_bias, pc.layers[0].norm1, pc.final_norm):
        assert leaf.dtype == jnp.float32


@pytest.mark.parametrize("bias,overflowed", [(0.0, False), (50.0, True)])
def test_route_stats_say_when_the_dispatch_buffer_overflowed(setup, bias, overflowed):
    """A bias of 50 on the four experts held here sends every token's four
    pairs to them: 4x the mean load, over the buffer's 2x, in each of the
    four routed layers. The exact path runs, nothing is dropped, and the
    counter says in how many layers."""
    c, params, x, _ = setup
    held = jnp.zeros((c.n_experts,)).at[c.expert_offset : c.expert_offset + c.n_experts_held].set(bias)
    layers = tuple(
        dataclasses.replace(l, mlp=dataclasses.replace(l.mlp, router_bias=held)) if c.mlp_kind(i) == "moe" else l
        for i, l in enumerate(params.layers)
    )
    stats = KimiLinear.route_stats(c, dataclasses.replace(params, layers=layers), x)
    n_moe = sum(c.mlp_kind(i) == "moe" for i in range(c.n_layer))
    assert int(stats["moe.tokens"]) == x.size and int(stats["moe.dropped"]) == 0
    assert int(stats["moe.overflowed"]) == (n_moe if overflowed else 0)
    if overflowed:
        assert int(stats["moe.assignments_here"]) == n_moe * x.size * c.moe_top_k


def test_the_benchmarks_flop_count_is_the_programs(setup):
    """benchmarks/arithmetic_kimi_linear.py is the yardstick's own copy of
    KimiLinear.flops_per_token: at the tiny size, the cell's and the
    published one, balanced and with counted pairs, they give one number."""
    spec = importlib.util.spec_from_file_location(
        "arithmetic_kimi_linear", os.path.join(ROOT, "benchmarks", "arithmetic_kimi_linear.py"))
    arith = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arith)
    published = load_config("kimi_linear_48b_a3b").model_config
    for mc in (setup[0], published, dataclasses.replace(published, n_layer=5, n_experts_held=8, vocab_size=20480)):
        model = dataclasses.asdict(mc)
        assert arith.flops_per_token(model) == KimiLinear.flops_per_token(mc)
        stats = {"moe.assignments_here": 1234.0, "moe.tokens": 1000.0}
        assert arith.flops_per_token(model, 1.234) == pytest.approx(KimiLinear.flops_per_token(mc, stats=stats), rel=1e-12)


@pytest.mark.parametrize("family", sorted(__import__("midgpt_tpu.config", fromlist=["x"]).MODEL_FAMILIES))
def test_every_family_has_what_the_runtime_calls(family):
    """models/__init__.py lists it; training/train.py, optim.py, metrics.py,
    sample.py and ServeEngine call it without asking."""
    from midgpt_tpu.config import MODEL_FAMILIES, _model_config_class

    cls = _model_config_class({"family": family})
    assert MODEL_FAMILIES[family].endswith(":" + cls.__name__)
    for name in ("model", "check_experiment", "check_serving"):
        assert callable(getattr(cls, name)), name
    mc = tiny() if family == "kimi_linear" else load_config("shakespeare_char").model_config
    model = mc.model()
    for name in ("init", "hidden", "count_params", "cast_params", "param_specs", "flops_per_token"):
        assert callable(getattr(model, name)), name
    for name in ("weight_decay_mask", "route_stats"):
        assert getattr(model, name) is None or callable(getattr(model, name)), name
    assert model.flops_per_token(mc) > 0
