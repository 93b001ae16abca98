"""Tensor parallelism (Megatron column/row over the mesh 'tp' axis) on the
8-device virtual CPU mesh: spec placement, numerical parity of the sharded
forward, and train-step trajectory parity vs the FSDP-only schedule.

Beyond the reference's capability set (its only model sharding is FSDP,
reference model.py:167-178) — see parallel/tp.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.data.dataset import TokenDataset
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.fsdp import constrain
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
from midgpt_tpu.parallel.tp import tp_param_specs
from midgpt_tpu.training.train import init_state, make_train_step

import dataclasses

CFG = GPTConfig(block_size=32, vocab_size=256, n_layer=2, n_head=4, n_embd=64)
# What make_train_step selects under tp > 1: the batched per-third QKV
# lowering that keeps each of q/k/v independently column-sharded.
CFG3 = dataclasses.replace(CFG, qkv_proj="split3")


def test_tp_spec_placement():
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, sp=1, tp=4))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = tp_param_specs(params, mesh, shard_model=True, min_size=0)
    # column-parallel: 'tp' on output features, 'fsdp' composed on input
    assert specs.blocks.attn.wqkv == P(None, None, "tp", "fsdp")
    assert specs.blocks.mlp.w_up == P(None, "tp", "fsdp")
    # row-parallel: 'tp' on input features
    assert specs.blocks.attn.wo == P(None, "fsdp", "tp")
    assert specs.blocks.mlp.w_down == P(None, "fsdp", "tp")
    # vocab-parallel (default): wte/lm_head shard the vocab axis over 'tp'
    assert specs.wte == P("tp", "fsdp")
    assert specs.lm_head == P("tp", "fsdp")
    # with vocab_parallel off they fall back to the FSDP rule
    specs_nv = tp_param_specs(params, mesh, True, 0, vocab_parallel=False)
    assert specs_nv.wte == P(None, "fsdp")
    assert specs_nv.lm_head == P(None, "fsdp")
    assert specs_nv.blocks.attn.wqkv == P(None, None, "tp", "fsdp")
    # optimizer-state-shaped trees (params nested deeper) get the same rule
    opt_like = {"mu": params, "nu": params, "count": jnp.zeros(())}
    opt_specs = tp_param_specs(opt_like, mesh, shard_model=True, min_size=0)
    assert opt_specs["mu"].blocks.attn.wqkv == P(None, None, "tp", "fsdp")
    assert opt_specs["count"] == P()


def test_tp_specs_reduce_to_fsdp_at_tp1():
    from midgpt_tpu.parallel.fsdp import fsdp_param_specs

    mesh = make_mesh(MeshConfig(data=2, fsdp=4, sp=1, tp=1))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    assert tp_param_specs(params, mesh, True, 0) == fsdp_param_specs(params, mesh, True, 0)


def test_tp_sharded_forward_matches_single_device():
    """tp x fsdp sharded forward == unsharded forward (GSPMD is semantics-
    preserving; this pins the spec rule to a correct placement)."""
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, sp=1, tp=4))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, CFG.vocab_size)
    base = GPT.apply(CFG, params, tokens, inference=True)

    specs = tp_param_specs(params, mesh, shard_model=True, min_size=0)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    xg = make_global_batch(np.asarray(tokens), mesh, batch_spec(with_accum=False))
    out = jax.jit(lambda p, t: GPT.apply(CFG, p, t, inference=True))(sharded, xg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=2e-5, rtol=2e-5)


def test_tp_forward_is_collective_minimal():
    """The Megatron property, asserted on compiled HLO: with pure tp sharding
    the forward needs ONLY the two all-reduces per block body (after the
    row-parallel wo and w_down) — no all-gather / all-to-all / resharding of
    activations. This is what the (3, D, D) wqkv layout + split3 lowering buy
    (models/gpt.py AttentionParams): sharding a flat stacked [q;k;v] axis
    straddles the q/k/v boundaries and forces GSPMD to reshard every block."""
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sp=1, tp=4))
    params = GPT.init(CFG3, jax.random.PRNGKey(0))
    # vocab_parallel off: full logits out of GPT.apply would legitimately
    # need a vocab gather; the property under test is the BLOCK schedule.
    specs = tp_param_specs(params, mesh, shard_model=True, min_size=0, vocab_parallel=False)
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    xg = make_global_batch(np.zeros((8, 32), np.int32), mesh, batch_spec(with_accum=False))
    hlo = (
        jax.jit(lambda p, t: GPT.apply(CFG3, p, t, inference=True))
        .lower(sharded, xg)
        .compile()
        .as_text()
    )
    for banned in ("all-gather", "all-to-all", "collective-permute"):
        assert banned not in hlo, f"unexpected {banned} in tp forward"


def test_tp_vocab_parallel_loss_schedule():
    """Pin the vocab-parallel collective schedule (parallel/tp.py docstring):
    the fused CE over a tp-sharded lm_head must lower to small per-chunk
    psums — never an all-gather (which would rematerialize the V-sized
    buffers the sharding exists to split)."""
    from midgpt_tpu.ops.loss import fused_linear_cross_entropy

    mesh = make_mesh(MeshConfig(data=2, fsdp=1, sp=1, tp=4))
    params = GPT.init(CFG3, jax.random.PRNGKey(0))
    specs = tp_param_specs(params, mesh, shard_model=True, min_size=0)
    assert specs.lm_head == P("tp", None)  # fsdp=1 here: tp on vocab only
    sharded = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    x = make_global_batch(np.zeros((8, 32), np.int32), mesh, batch_spec(with_accum=False))
    y = make_global_batch(np.ones((8, 32), np.int32), mesh, batch_spec(with_accum=False))

    def loss_fn(p, xx, yy):
        h = GPT.hidden(CFG3, p, xx, inference=True)
        return fused_linear_cross_entropy(h, p.lm_head, yy, 8192)

    hlo = (
        jax.jit(jax.value_and_grad(loss_fn)).lower(sharded, x, y).compile().as_text()
    )
    for banned in ("all-gather", "all-to-all", "collective-permute"):
        assert banned not in hlo, f"unexpected {banned} in vocab-parallel loss"


def _run_steps(cfg: ExperimentConfig, data_dir: str, n: int = 5):
    mesh = make_mesh(cfg.mesh)
    ds = TokenDataset(data_dir, seed=cfg.data_seed)
    params, opt_state, specs, optimizer = init_state(cfg, mesh)
    step, *_ = make_train_step(cfg, optimizer, mesh, specs)
    spec = batch_spec(with_accum=True)
    losses = []
    for itr in range(n):
        x, y = ds.batch("train", itr, cfg.model_config.block_size, cfg.batch_size,
                        cfg.g_accum_iters)
        xg = make_global_batch(x, mesh, spec)
        yg = make_global_batch(y, mesh, spec)
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), itr)
        params, opt_state, loss = step(params, opt_state, xg, yg, key)
        losses.append(float(loss))
    return losses


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("tp_data")
    stream = (np.arange(20000) % 23).astype(np.uint16)
    stream.tofile(d / "train.bin")
    stream[:4000].tofile(d / "val.bin")
    return str(d)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_tp_train_step_matches_fsdp_only(data_dir):
    """5-step loss trajectory: (data=2, fsdp=2, tp=2) == (data=2, fsdp=4).

    Same seeds, same data, two different parallelization schedules — the
    tp schedule must compute the same math as the FSDP oracle."""
    base = dict(
        rundir="",
        data_dir=data_dir,
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=60,
        max_steps=60,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=30,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=2,
        shard_model=True,
        eval_steps=2,
        fsdp_min_size=0,
        fsdp_mode="gspmd",  # the compiler's Megatron schedule is what this file tests (authored x tp: test_shard_map_fsdp.py)
        model_config=CFG,
    )
    ref = ExperimentConfig(mesh=MeshConfig(data=2, fsdp=4, sp=1), **base)
    tp = ExperimentConfig(mesh=MeshConfig(data=2, fsdp=2, sp=1, tp=2), **base)
    losses_ref = _run_steps(ref, data_dir)
    losses_tp = _run_steps(tp, data_dir)
    np.testing.assert_allclose(losses_tp, losses_ref, rtol=2e-5, atol=2e-5)
    assert losses_ref[-1] < losses_ref[0]  # and it actually learns


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_tp_ring_sp_composition_matches_fsdp_only(data_dir):
    """All four parallelism kinds at once: a (data=1, fsdp=2, sp=2, tp=2)
    mesh — real FSDP param sharding, ring attention over 'sp', and
    Megatron-sharded (head-sharded, via ring's head_axis) projections over
    'tp' — must reproduce the FSDP-only oracle's loss trajectory."""
    import dataclasses

    base = dict(
        rundir="",
        data_dir=data_dir,
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=60,
        max_steps=60,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=30,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        eval_steps=2,
        fsdp_min_size=0,
        fsdp_mode="gspmd",  # as above: the compiler's schedule on both legs
    )
    ref = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=4, sp=1), model_config=CFG, **base
    )
    ring_cfg = dataclasses.replace(CFG, attn_impl="ring")
    tpsp = ExperimentConfig(
        mesh=MeshConfig(data=1, fsdp=2, sp=2, tp=2), model_config=ring_cfg, **base
    )
    losses_ref = _run_steps(ref, data_dir, n=4)
    losses_tpsp = _run_steps(tpsp, data_dir, n=4)
    np.testing.assert_allclose(losses_tpsp, losses_ref, rtol=2e-5, atol=2e-5)


def test_tp_config_validation():
    mc = GPTConfig(block_size=32, vocab_size=64, n_layer=1, n_head=3, n_embd=48)
    kw = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=1,
        min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.99, weight_decay=0.0,
        eval_interval=5, param_dtype="float32", compute_dtype="float32",
        g_accum_iters=1, shard_model=True,
    )
    with pytest.raises(ValueError, match="n_head"):
        ExperimentConfig(mesh=MeshConfig(tp=2), model_config=mc, **kw)
    with pytest.raises(ValueError, match="vocab_size"):
        ExperimentConfig(
            mesh=MeshConfig(tp=2),
            model_config=GPTConfig(block_size=32, vocab_size=65, n_layer=1,
                                   n_head=2, n_embd=64),
            **kw,
        )
    # ... but indivisible vocab is fine with tp_vocab off
    ExperimentConfig(
        mesh=MeshConfig(tp=2), tp_vocab=False,
        model_config=GPTConfig(block_size=32, vocab_size=65, n_layer=1,
                               n_head=2, n_embd=64),
        **kw,
    )
    # r5: shard_map composes with tp (auto axis) — but not together with
    # its sequence-parallel schedules yet
    ExperimentConfig(
        mesh=MeshConfig(tp=2), fsdp_mode="shard_map",
        model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                               n_head=2, n_embd=64),
        **kw,
    )
    with pytest.raises(ValueError, match="sequence parallelism"):
        ExperimentConfig(
            mesh=MeshConfig(tp=2, sp=2), fsdp_mode="shard_map",
            model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                                   n_head=2, n_embd=64, attn_impl="ring"),
            **kw,
        )
