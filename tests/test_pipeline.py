"""GPipe pipeline parallelism (parallel/pipeline.py) on the virtual mesh:
spec placement, loss/grad parity vs the dense model, and full-train-step
trajectory parity vs the FSDP oracle. Beyond the reference's capability set
(its only model sharding is FSDP, reference model.py:167-178)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.ops.loss import fused_linear_cross_entropy
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.fsdp import constrain
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
from midgpt_tpu.parallel.pipeline import make_pipeline_loss, pipeline_param_specs
from midgpt_tpu.training.train import init_state, make_train_step

CFG = GPTConfig(block_size=32, vocab_size=128, n_layer=4, n_head=2, n_embd=64)


def _dense_loss(params, x, y):
    h = GPT.hidden(CFG, params, x, inference=True)
    return fused_linear_cross_entropy(h, params.lm_head, y, 8192)


def test_pipeline_param_specs():
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params)
    assert specs.blocks.attn.wqkv == P("pp", None, None, None)
    assert specs.blocks.mlp.w_up == P("pp", None, None)
    assert specs.blocks.attn.q_scale == P("pp", None)
    assert specs.wte == P()
    assert specs.lm_head == P()
    opt_like = {"mu": params, "count": jnp.zeros(())}
    opt_specs = pipeline_param_specs(opt_like)
    assert opt_specs["mu"].blocks.attn.wqkv == P("pp", None, None, None)
    assert opt_specs["count"] == P()


@pytest.mark.parametrize("pp,microbatches", [(2, 2), (4, 4), (4, 8)])
def test_pipeline_loss_matches_dense(pp, microbatches):
    mesh = make_mesh(MeshConfig(data=8 // pp, fsdp=1, sp=1, tp=1, pp=pp))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    rng = np.random.default_rng(0)
    # per-data-shard batch must divide into M microbatches
    B = (8 // pp) * microbatches
    x = rng.integers(0, CFG.vocab_size, (B, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))

    pipe_loss = make_pipeline_loss(CFG, mesh, specs, 8192, microbatches=microbatches)
    got = jax.jit(lambda p, a, b: pipe_loss(p, a, b, None))(sharded, xg, yg)
    want = _dense_loss(params, jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_pipeline_gradients_match_dense():
    """Reverse AD through the tick scan + ppermute (the GPipe backward
    schedule) must equal dense-model gradients — including the replicated
    wte/lm_head grads that shard_map's transpose psums across stages."""
    pp = 4
    mesh = make_mesh(MeshConfig(data=8 // pp, fsdp=1, sp=1, tp=1, pp=pp))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    rng = np.random.default_rng(1)
    x = rng.integers(0, CFG.vocab_size, (8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))

    pipe_loss = make_pipeline_loss(CFG, mesh, specs, 8192)
    g_pipe = jax.jit(jax.grad(lambda p, a, b: pipe_loss(p, a, b, None)))(sharded, xg, yg)
    g_dense = jax.grad(_dense_loss)(params, jnp.asarray(x), jnp.asarray(y))
    for gp, gd in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gd), atol=3e-5, rtol=3e-5
        )


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_pipeline_train_step_matches_fsdp_only():
    """One full training step on a (data=2, pp=4) mesh reproduces the
    FSDP-only oracle's loss on the same batch and seed."""
    base = dict(
        rundir="",
        data_dir="",
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=50,
        max_steps=50,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=25,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=2,
        shard_model=True,
        fsdp_min_size=0,
        eval_steps=2,
        model_config=CFG,
    )
    oracle_cfg = ExperimentConfig(mesh=MeshConfig(data=2, fsdp=4, sp=1), **base)
    pp_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=1, sp=1, tp=1, pp=4), **base
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, CFG.vocab_size, (2, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    evals = {}
    for name, cfg in (("oracle", oracle_cfg), ("pp", pp_cfg)):
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, eval_loss, _ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        params, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
        evals[name] = float(eval_loss(params, xg[0], yg[0]))
    np.testing.assert_allclose(losses["pp"], losses["oracle"], rtol=1e-5)
    np.testing.assert_allclose(evals["pp"], evals["oracle"], rtol=1e-5)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_pipeline_fsdp_composition_train_step_matches_oracle():
    """v2: stage weights shard over 'fsdp' (per-layer gathers inside the
    stage scan, ZeRO-3 style) — one full train step + eval on a
    (fsdp=2, pp=4) mesh reproduces the FSDP-only oracle."""
    base = dict(
        rundir="",
        data_dir="",
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=50,
        max_steps=50,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=25,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=2,
        shard_model=True,
        fsdp_min_size=0,
        eval_steps=2,
        model_config=CFG,
    )
    oracle_cfg = ExperimentConfig(mesh=MeshConfig(data=2, fsdp=4, sp=1), **base)
    pp_cfg = ExperimentConfig(
        mesh=MeshConfig(data=1, fsdp=2, sp=1, tp=1, pp=4), **base
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, CFG.vocab_size, (2, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses, evals = {}, {}
    for name, cfg in (("oracle", oracle_cfg), ("pp_fsdp", pp_cfg)):
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, eval_loss, _ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        params, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
        evals[name] = float(eval_loss(params, xg[0], yg[0]))
    np.testing.assert_allclose(losses["pp_fsdp"], losses["oracle"], rtol=1e-5)
    np.testing.assert_allclose(evals["pp_fsdp"], evals["oracle"], rtol=1e-5)


def test_pipeline_tp_composition_train_step_matches_oracle():
    """r5 composition: Megatron 'tp' rides a GSPMD auto axis INSIDE the
    pipeline shard_map (manual axes: data/fsdp/sp/pp only) — the stage
    weights shard their Megatron axes over 'tp' (pipeline_param_specs), the
    tick body stays written in pp/fsdp collectives, and GSPMD inserts the
    tp psums at the block joins. One full train step + eval on a
    (fsdp=2, tp=2, pp=2) mesh reproduces the FSDP-only oracle."""
    base = dict(
        rundir="",
        data_dir="",
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=50,
        max_steps=50,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=25,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=2,
        shard_model=True,
        fsdp_min_size=0,
        eval_steps=2,
        model_config=CFG,
    )
    oracle_cfg = ExperimentConfig(mesh=MeshConfig(data=2, fsdp=4, sp=1), **base)
    pp_tp_cfg = ExperimentConfig(
        mesh=MeshConfig(data=1, fsdp=2, sp=1, tp=2, pp=2), **base
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, CFG.vocab_size, (2, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses, evals = {}, {}
    for name, cfg in (("oracle", oracle_cfg), ("pp_tp", pp_tp_cfg)):
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, eval_loss, _ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        params, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
        evals[name] = float(eval_loss(params, xg[0], yg[0]))
    np.testing.assert_allclose(losses["pp_tp"], losses["oracle"], rtol=1e-5)
    np.testing.assert_allclose(evals["pp_tp"], evals["oracle"], rtol=1e-5)
    # and the stage weights really are tp-sharded (not silently replicated)
    mesh = make_mesh(pp_tp_cfg.mesh)
    params, _, specs, _ = init_state(pp_tp_cfg, mesh)
    assert specs.blocks.attn.wqkv == P("pp", None, "tp", "fsdp")
    assert specs.blocks.mlp.w_up == P("pp", "tp", "fsdp")
    assert specs.blocks.mlp.w_down == P("pp", "fsdp", "tp")


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_1f1b_loss_and_grads_match_gpipe():
    """The hand-written 1F1B backward (make_pipeline_loss_and_grad) computes
    the SAME loss and gradients as reverse AD of the GPipe schedule — and
    both match the dense oracle."""
    from midgpt_tpu.parallel.pipeline import make_pipeline_loss_and_grad

    pp, M = 4, 8
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sp=1, tp=1, pp=pp))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params, mesh)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    rng = np.random.default_rng(2)
    B = 2 * M * pp  # per-data-shard batch M*pp: microbatches divide by pp
    x = rng.integers(0, CFG.vocab_size, (B, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))

    pipe_loss = make_pipeline_loss(CFG, mesh, specs, 8192, microbatches=M)
    l_g, g_g = jax.jit(
        jax.value_and_grad(lambda p, a, b: pipe_loss(p, a, b, None))
    )(sharded, xg, yg)

    lag = make_pipeline_loss_and_grad(CFG, mesh, specs, 8192, microbatches=M)
    l_f, g_f = jax.jit(lambda p, a, b: lag(p, a, b, None))(sharded, xg, yg)

    np.testing.assert_allclose(float(l_f), float(l_g), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_g), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5, rtol=3e-5
        )
    # and against the dense oracle
    want = _dense_loss(params, jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(float(l_f), float(want), rtol=1e-5)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_1f1b_grads_match_gpipe_with_fsdp_replicated_leaves():
    """Regression (r5 review): with mesh.fsdp>1 and block leaves that are
    fsdp-REPLICATED (here: default fsdp_min_size leaves q/k scales and, with
    shard_model=False, everything replicated), each fsdp rank's grads must
    still be summed over 'fsdp' — GPipe's shard_map AD inserts that psum;
    the hand-written 1F1B backward must too. Loss alone cannot catch this
    (it matched while grads were ~31% off)."""
    from midgpt_tpu.parallel.pipeline import make_pipeline_loss_and_grad

    pp, M = 2, 2
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, sp=1, tp=1, pp=pp))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params, mesh, shard_model=False)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    rng = np.random.default_rng(4)
    B = 2 * 2 * M * pp
    x = rng.integers(0, CFG.vocab_size, (B, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))

    pipe_loss = make_pipeline_loss(CFG, mesh, specs, 8192, microbatches=M)
    l_g, g_g = jax.jit(
        jax.value_and_grad(lambda p, a, b: pipe_loss(p, a, b, None))
    )(sharded, xg, yg)
    lag = make_pipeline_loss_and_grad(CFG, mesh, specs, 8192, microbatches=M)
    l_f, g_f = jax.jit(lambda p, a, b: lag(p, a, b, None))(sharded, xg, yg)
    np.testing.assert_allclose(float(l_f), float(l_g), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_g), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5, rtol=3e-5
        )


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_1f1b_activation_stash_is_m_independent():
    """THE point of 1F1B (VERDICT r4 #5): growing the microbatch count must
    not grow the backward's activation memory. Compare compiled temp memory
    at M=4 vs M=16 for both schedules: GPipe's stash grows ~4x (reverse AD
    saves every tick's stage input), 1F1B's 2*pp-slot ring buffer does not.
    Asserted as a ratio so absolute allocator noise can't flake it."""
    from midgpt_tpu.parallel.pipeline import make_pipeline_loss_and_grad

    pp = 4
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sp=1, tp=1, pp=pp))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params, mesh)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)

    def temp_bytes(schedule, M):
        B = 2 * M * pp
        xg = jax.device_put(
            jnp.zeros((B, 32), jnp.int32),
            jax.sharding.NamedSharding(mesh, batch_spec(with_accum=False)),
        )
        if schedule == "gpipe":
            pipe = make_pipeline_loss(CFG, mesh, specs, 8192, microbatches=M)
            fn = jax.jit(jax.value_and_grad(lambda p, a, b: pipe(p, a, b, None)))
        else:
            lag = make_pipeline_loss_and_grad(CFG, mesh, specs, 8192, microbatches=M)
            fn = jax.jit(lambda p, a, b: lag(p, a, b, None))
        mem = fn.lower(sharded, xg, xg).compile().memory_analysis()
        assert mem is not None, "backend reports no memory analysis"
        return mem.temp_size_in_bytes

    gpipe_growth = temp_bytes("gpipe", 16) / max(temp_bytes("gpipe", 4), 1)
    f1b_growth = temp_bytes("1f1b", 16) / max(temp_bytes("1f1b", 4), 1)
    # GPipe stash scales with M (16/4 -> ~4x); 1F1B must stay ~flat.
    assert gpipe_growth > 2.0, f"premise broken: gpipe growth {gpipe_growth}"
    assert f1b_growth < 1.5, (
        f"1F1B temp memory grew {f1b_growth:.2f}x with 4x microbatches — "
        "the activation stash is no longer M-independent"
    )


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_1f1b_train_step_matches_gpipe_step():
    """One full training step with pipeline_schedule='1f1b' reproduces the
    GPipe step's loss (same params/batch/seed) through make_train_step."""
    base = dict(
        rundir="",
        data_dir="",
        learning_rate=1e-2,
        batch_size=32,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=50,
        max_steps=50,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=25,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        eval_steps=2,
        model_config=CFG,
        # per-data-shard batch 16, M=4 -> microbatch 4, divisible by pp=4
        # (the 1F1B scattered CE's extra constraint)
        pipeline_microbatches=4,
    )
    rng = np.random.default_rng(3)
    x = rng.integers(0, CFG.vocab_size, (1, 32, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    for name, sched in (("gpipe", "gpipe"), ("1f1b", "1f1b")):
        cfg = ExperimentConfig(
            mesh=MeshConfig(data=2, fsdp=1, sp=1, tp=1, pp=4),
            pipeline_schedule=sched,
            **base,
        )
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, *_ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["1f1b"], losses["gpipe"], rtol=1e-5)


def test_pipeline_ce_volume_sharded_over_pp():
    """FLOP-level proof the lm_head/CE volume is 1x, not pp x: with a
    CE-dominated shape (V >> L·D), the compiled per-device program must cost
    ~F_dense/(data·pp) flops. The v1 schedule (every stage computing the
    full-batch CE on its collected outputs) costs ~F_dense/data per device —
    4x the asserted bound on this mesh."""
    cfg = dataclasses.replace(CFG, vocab_size=4096)
    data, pp = 2, 4
    mesh = make_mesh(MeshConfig(data=data, fsdp=1, sp=1, tp=1, pp=pp))
    params = GPT.init(cfg, jax.random.PRNGKey(0))
    specs = pipeline_param_specs(params, mesh)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    rng = np.random.default_rng(0)
    B = 16
    x = rng.integers(0, cfg.vocab_size, (B, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))

    pipe_loss = make_pipeline_loss(cfg, mesh, specs, 8192)
    comp_pp = (
        jax.jit(lambda p, a, b: pipe_loss(p, a, b, None))
        .lower(sharded, xg, yg)
        .compile()
    )

    def dense_loss(p, a, b):
        h = GPT.hidden(cfg, p, a, inference=True)
        return fused_linear_cross_entropy(h, p.lm_head, b, 8192)

    comp_dense = (
        jax.jit(dense_loss).lower(params, jnp.asarray(x), jnp.asarray(y)).compile()
    )

    def flops(comp):
        ca = comp.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca["flops"])

    # margin covers the bubble-inflated backbone + replicated embedding;
    # a pp x CE (v1) would exceed this bound ~4x.
    assert flops(comp_pp) < flops(comp_dense) / (data * pp) * 1.6, (
        flops(comp_pp), flops(comp_dense)
    )


def test_pipeline_config_validation():
    kw = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=1,
        min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.99, weight_decay=0.0,
        eval_interval=5, param_dtype="float32", compute_dtype="float32",
        g_accum_iters=1, shard_model=True,
    )
    with pytest.raises(ValueError, match="n_layer"):
        ExperimentConfig(
            mesh=MeshConfig(fsdp=1, pp=3),
            model_config=CFG,  # n_layer=4 % 3 != 0
            **kw,
        )
    with pytest.raises(ValueError, match="dropout"):
        ExperimentConfig(
            mesh=MeshConfig(fsdp=1, pp=2),
            model_config=dataclasses.replace(CFG, dropout=0.1),
            **kw,
        )
    # v2: fsdp composes with pp; r5: tp does too; sp still does not
    ExperimentConfig(mesh=MeshConfig(fsdp=2, pp=2), model_config=CFG, **kw)
    ExperimentConfig(mesh=MeshConfig(fsdp=1, tp=2, pp=2), model_config=CFG, **kw)
    with pytest.raises(ValueError, match="sp"):
        ExperimentConfig(mesh=MeshConfig(fsdp=1, sp=2, pp=2), model_config=CFG, **kw)
