"""Mixture-of-experts MLP (models/gpt.py MoEParams) + expert parallelism.

The only §2.3 parallelism strategy absent from BOTH trees until r5
(VERDICT r4 #9): dense -> top-k routed MLP over a mesh 'ep' axis."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPT, GPTConfig, MLPParams, MoEParams
from midgpt_tpu.ops.loss import cross_entropy_loss
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh

CFG = GPTConfig(block_size=32, vocab_size=96, n_layer=2, n_head=2, n_embd=32)
MOE1 = dataclasses.replace(CFG, n_experts=1, moe_top_k=1)
MOE4 = dataclasses.replace(CFG, n_experts=4, moe_top_k=2)


def _dense_to_moe1(params):
    """Map dense params onto the E=1 routed tree (same weights)."""

    def convert(mlp: MLPParams) -> MoEParams:
        L = mlp.w_up.shape[0]
        return MoEParams(
            router=jnp.zeros((L, 1, CFG.n_embd), mlp.w_up.dtype),
            experts_up=mlp.w_up[:, None],
            experts_down=mlp.w_down[:, None],
        )

    return dataclasses.replace(
        params, blocks=dataclasses.replace(params.blocks, mlp=convert(params.blocks.mlp))
    )


def test_moe_e1_matches_dense_forward_and_grads():
    """At E=1/top_k=1 the routed MLP is EXACTLY the dense MLP (gate softmax
    over one expert is 1.0): logits and the shared leaves' grads match; the
    router grad is exactly zero (constant gate)."""
    dense = GPT.init(CFG, jax.random.PRNGKey(0))
    moe = _dense_to_moe1(dense)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab_size)
    labels = (tokens + 1) % CFG.vocab_size

    l_dense = GPT.apply(CFG, dense, tokens, inference=True)
    l_moe = GPT.apply(MOE1, moe, tokens, inference=True)
    np.testing.assert_allclose(np.asarray(l_moe), np.asarray(l_dense), atol=1e-6)

    def loss(cfg, p):
        return cross_entropy_loss(GPT.apply(cfg, p, tokens, inference=True), labels)

    g_dense = jax.grad(lambda p: loss(CFG, p))(dense)
    g_moe = jax.grad(lambda p: loss(MOE1, p))(moe)
    np.testing.assert_allclose(
        np.asarray(g_moe.blocks.mlp.experts_up[:, 0]),
        np.asarray(g_dense.blocks.mlp.w_up),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(g_moe.blocks.mlp.experts_down[:, 0]),
        np.asarray(g_dense.blocks.mlp.w_down),
        atol=1e-6,
    )
    np.testing.assert_array_equal(np.asarray(g_moe.blocks.mlp.router), 0.0)
    np.testing.assert_allclose(
        np.asarray(g_moe.wte), np.asarray(g_dense.wte), atol=1e-6
    )


def test_moe_top_k_routing_properties():
    """E=4/top_k=2: gates are a distribution with at most k nonzeros per
    token, the forward is finite, and gradients flow to every expert (the
    batch is big enough that each expert wins somewhere)."""
    params = GPT.init(MOE4, jax.random.PRNGKey(2))
    mlp = jax.tree.map(lambda x: x[0], params.blocks.mlp)  # layer 0 slice
    h = jax.random.normal(jax.random.PRNGKey(3), (4, 32, CFG.n_embd)) * 0.5
    logits = jnp.einsum("btd,ed->bte", h, mlp.router)
    kth = jax.lax.top_k(logits, 2)[0][..., -1:]
    gates = jax.nn.softmax(
        jnp.where(logits >= kth, logits, -jnp.inf), axis=-1
    )
    nnz = jnp.sum(gates > 0, axis=-1)
    assert int(nnz.max()) <= 2
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)

    out = GPT._moe_mlp(MOE4, mlp, h)
    assert out.shape == h.shape and bool(jnp.isfinite(out).all())

    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, CFG.vocab_size)
    labels = (tokens + 1) % CFG.vocab_size
    g = jax.grad(
        lambda p: cross_entropy_loss(
            GPT.apply(MOE4, p, tokens, inference=True), labels
        )
    )(params)
    for leaf in (g.blocks.mlp.router, g.blocks.mlp.experts_up, g.blocks.mlp.experts_down):
        assert float(jnp.abs(leaf).max()) > 0


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_moe_train_step_ep2_matches_ep1():
    """Expert parallelism: one full train step with the experts sharded over
    a real 'ep' axis reproduces the unsharded (ep=1) loss — same math,
    different placement (the combine einsum's E contraction becomes the EP
    all-reduce)."""
    from midgpt_tpu.training.train import init_state, make_train_step

    base = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=2,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        eval_interval=5,
        beta2=0.95,
        weight_decay=1e-4,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        mesh=MeshConfig(data=2, fsdp=2, sp=1, ep=2),
        model_config=MOE4,
    )
    rng = np.random.default_rng(0)
    x = rng.integers(0, CFG.vocab_size, (1, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    for name, cfg in {
        "ep2": base,
        "ep1": base.replace(mesh=MeshConfig(data=2, fsdp=4, sp=1)),
    }.items():
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        if name == "ep2":  # the experts really shard over 'ep'
            assert "ep" in str(specs.blocks.mlp.experts_up)
        step, *_ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["ep2"], losses["ep1"], rtol=1e-5)


def test_moe_top_k_exact_on_ties():
    """Regression (ADVICE r5): `logits >= kth` threshold masking admits
    MORE than k experts on exact ties — a zero/collapsed router (all-equal
    logits) silently turned routing dense. Selection now goes through
    jax.lax.top_k INDICES: exactly k experts per token, ties broken by
    lowest expert index, even in the fully degenerate state."""
    params = GPT.init(MOE4, jax.random.PRNGKey(5))
    mlp = jax.tree.map(lambda x: x[0], params.blocks.mlp)
    mlp = dataclasses.replace(mlp, router=jnp.zeros_like(mlp.router))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 8, CFG.n_embd))
    gates, aux = GPT._moe_gates(MOE4, mlp, h)
    nnz = jnp.sum(gates > 0, axis=-1)
    np.testing.assert_array_equal(np.asarray(nnz), 2)  # exactly k, not E
    # the k survivors split the mass evenly (equal logits)
    np.testing.assert_allclose(np.asarray(gates.max(-1)), 0.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)
    # tie-break is deterministic: lowest expert indices win
    np.testing.assert_array_equal(np.asarray(gates[..., :2] > 0), True)


def test_moe_aux_loss_value_and_balance():
    """The Switch-style load-balance term: exactly 1.0 under perfectly
    balanced routing (uniform router), > 1 when the router collapses onto
    one expert, and differentiable through the router."""
    params = GPT.init(MOE4, jax.random.PRNGKey(7))
    mlp = jax.tree.map(lambda x: x[0], params.blocks.mlp)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 16, CFG.n_embd))

    uniform = dataclasses.replace(mlp, router=jnp.zeros_like(mlp.router))
    _, aux_uniform = GPT._moe_gates(MOE4, uniform, h)
    np.testing.assert_allclose(float(aux_uniform), 1.0, rtol=1e-6)

    # Collapse deterministically: h = all-ones and router row 0 = ones
    # makes expert 0's logit D and the rest 0 for EVERY token, so P ~ e_0
    # and assignment is always {0, 1} (tie-break): aux = E * (1 * 1/2) = 2.
    collapsed = dataclasses.replace(
        mlp, router=jnp.zeros_like(mlp.router).at[0].set(1.0)
    )
    ones = jnp.ones_like(h)
    _, aux_collapsed = GPT._moe_gates(MOE4, collapsed, ones)
    np.testing.assert_allclose(float(aux_collapsed), 2.0, rtol=1e-5)

    g = jax.grad(
        lambda r: GPT._moe_gates(MOE4, dataclasses.replace(mlp, router=r), h)[1]
    )(mlp.router)
    assert float(jnp.abs(g).max()) > 0  # pressure flows through P_e


@pytest.mark.slow
def test_moe_aux_coef_zero_impact_when_disabled():
    """ISSUE satellite pin: with moe_aux_coef=0.0 (default) the train-step
    loss is EXACTLY the CE loss (the aux term is never requested, so it
    cannot perturb the graph); with a nonzero coef the reported loss shifts
    by coef * aux."""
    from midgpt_tpu.training.train import init_state, make_train_step

    base = ExperimentConfig(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
        warmup_steps=2, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
        eval_interval=5, beta2=0.95, weight_decay=0.0,
        param_dtype="float32", compute_dtype="float32", g_accum_iters=1,
        shard_model=True, fsdp_min_size=0,
        # the pin is on the COMPILER's schedule: the aux term exists only in
        # the implicit-GSPMD loss, and 'auto' would hand the aux-free side to
        # the authored schedule on this mesh (two different programs)
        fsdp_mode="gspmd",
        mesh=MeshConfig(data=2, fsdp=4, sp=1), model_config=MOE4,
    )
    rng = np.random.default_rng(3)
    x = rng.integers(0, CFG.vocab_size, (1, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)

    losses = {}
    for name, cfg in {
        "off": base,
        "on": base.replace(moe_aux_coef=0.01),
    }.items():
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, eval_loss, _ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
        if name == "off":
            # dropout=0, so the dropout-free eval CE on the same batch IS
            # the pre-knob training loss — byte-for-byte zero impact.
            params2, *_ = init_state(cfg, make_mesh(cfg.mesh))
            ce = float(eval_loss(params2, xg[0], yg[0]))
            np.testing.assert_allclose(losses["off"], ce, rtol=1e-6)
    # aux >= 1 always (Cauchy-Schwarz equality at perfect balance), so a
    # nonzero coef must move the loss by at least coef * 1.
    assert losses["on"] > losses["off"] + 0.009


def test_moe_aux_coef_config_validation():
    kw = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=1,
        min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.99, weight_decay=0.0,
        eval_interval=5, param_dtype="float32", compute_dtype="float32",
        g_accum_iters=1, shard_model=True,
    )
    with pytest.raises(ValueError, match="routed MLP"):
        ExperimentConfig(moe_aux_coef=0.01, model_config=CFG, **kw)
    with pytest.raises(ValueError, match="gspmd"):
        ExperimentConfig(
            moe_aux_coef=0.01, fsdp_mode="shard_map", model_config=MOE4, **kw
        )


def test_moe_config_validation():
    kw = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=1,
        min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.99, weight_decay=0.0,
        eval_interval=5, param_dtype="float32", compute_dtype="float32",
        g_accum_iters=1, shard_model=True,
    )
    with pytest.raises(ValueError, match="n_experts"):
        ExperimentConfig(mesh=MeshConfig(ep=2), model_config=CFG, **kw)
    with pytest.raises(ValueError, match="divisible"):
        ExperimentConfig(
            mesh=MeshConfig(ep=2),
            model_config=dataclasses.replace(CFG, n_experts=3),
            **kw,
        )
    with pytest.raises(ValueError, match="pp"):
        ExperimentConfig(mesh=MeshConfig(fsdp=1, pp=2), model_config=MOE4, **kw)
