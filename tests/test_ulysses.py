"""Ulysses (all-to-all) sequence parallelism parity vs the oracle.

Second context-parallel schedule next to the ring (parallel/ulysses.py):
the sequence sharding is traded for a head sharding by one all-to-all and
attention runs dense per head group. Same parity bar as tests/test_ring.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from midgpt_tpu.ops.attention import naive_causal_attention
from midgpt_tpu.parallel.ulysses import ulysses_attention_sharded


def _mesh(sp: int) -> Mesh:
    devs = np.array(jax.devices()[: 2 * sp]).reshape(2, 1, sp)
    return Mesh(devs, ("data", "fsdp", "sp"))


def _qkv(B=4, H=4, T=128, C=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (B, H, T, C), dtype) for k in ks)


@pytest.mark.parametrize("sp", [2, 4])
def test_ulysses_matches_naive_forward(sp):
    q, k, v = _qkv()
    mesh = _mesh(sp)
    out = ulysses_attention_sharded(q, k, v, mesh)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_gradients_match(sp=2):
    """AD through the all-to-alls (self-transposing) equals oracle AD."""
    q, k, v = _qkv(B=2, H=2, T=64, C=8)
    mesh = _mesh(sp)

    def loss_uly(q, k, v):
        return jnp.sum(jnp.sin(ulysses_attention_sharded(q, k, v, mesh)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gu, gf in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gf), atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_ulysses_train_step_matches_naive_sp1():
    """One full training step on a (data=2, fsdp=2, sp=2) mesh with
    attn_impl='ulysses' reproduces the naive sp=1 oracle's loss."""
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.train import init_state, make_train_step

    mc = GPTConfig(block_size=64, vocab_size=128, n_layer=2, n_head=4, n_embd=64)
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
    )
    oracle_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=4, sp=1), model_config=mc,
        fsdp_mode="gspmd", **base,
    )
    uly_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=2, sp=2),
        model_config=dataclasses.replace(mc, attn_impl="ulysses"),
        **base,
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, mc.vocab_size, (2, 8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    for name, cfg in (("oracle", oracle_cfg), ("ulysses", uly_cfg)):
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, _, _ = make_train_step(cfg, optimizer, mesh, specs)
        shard_seq = cfg.model_config.attn_impl == "ulysses"
        xg = make_global_batch(x, mesh, batch_spec(shard_seq=shard_seq))
        yg = make_global_batch(y, mesh, batch_spec(shard_seq=shard_seq))
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["ulysses"], losses["oracle"], rtol=1e-5)


def test_ulysses_bf16_forward_parity():
    """bf16 inputs (the real training dtype) against the f32 oracle at bf16
    tolerance — same bar as the ring's T=4096 bf16 check."""
    q, k, v = _qkv(B=2, H=4, T=256, C=16, dtype=jnp.bfloat16)
    mesh = _mesh(4)
    out = ulysses_attention_sharded(q, k, v, mesh)
    ref = naive_causal_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


@pytest.mark.parametrize("sp", [2, 4])
def test_ulysses_kernel_path_forward_parity(sp, monkeypatch):
    """The Pallas flash kernel serves the inner dense attention (what a real
    TPU slice runs): interpret mode on CPU, forced via the kernel module's
    off-TPU switch. All-to-alls wrap the kernel; parity must hold."""
    import importlib

    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")

    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    q, k, v = _qkv(B=2, H=4, T=128, C=32)
    mesh = _mesh(sp)
    out = ulysses_attention_sharded(q, k, v, mesh)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_kernel_path_gradients(monkeypatch, sp=2):
    """Backward through all_to_all (self-transposing) + the flash kernel's
    custom VJP equals oracle AD — the exact program a TPU training step
    differentiates."""
    import importlib

    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")

    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    q, k, v = _qkv(B=2, H=2, T=128, C=32)
    mesh = _mesh(sp)

    def loss_uly(q, k, v):
        return jnp.sum(jnp.sin(ulysses_attention_sharded(q, k, v, mesh)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gu, gf, name in zip(g_uly, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gu), np.asarray(gf), atol=3e-5, rtol=3e-5, err_msg=f"d{name}"
        )


def test_ulysses_kernel_jnp_paths_agree(monkeypatch):
    """Kernel-served inner attention (interpret mode) vs the blockwise jnp
    inner attention: the all-to-all schedule is identical, so the two inner
    impls must agree."""
    import importlib

    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")

    q, k, v = _qkv(B=2, H=4, T=256, C=16)
    mesh = _mesh(4)
    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    out_k = ulysses_attention_sharded(q, k, v, mesh, impl="flash")
    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", False)
    out_j = ulysses_attention_sharded(q, k, v, mesh, impl="blockwise")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ulysses_shard_map_fsdp_train_step_matches_gspmd():
    """Ulysses composes with the explicit shard_map ZeRO-3 schedule the same
    way the ring does (parallel/shard_map_fsdp.py): one body, weight gathers
    on 'fsdp', head<->sequence all_to_alls on 'sp'. Same loss as the GSPMD
    Ulysses step, the naive sp=1 oracle, AND the tp x sp composition
    (heads sharded over tp, then sp)."""
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.train import init_state, make_train_step

    mc = GPTConfig(block_size=64, vocab_size=128, n_layer=2, n_head=4, n_embd=64)
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
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        eval_steps=2,
    )
    oracle_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=4, sp=1), model_config=mc, **base
    )
    uly = dataclasses.replace(mc, attn_impl="ulysses")
    gspmd_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=2, sp=2), model_config=uly,
        fsdp_mode="gspmd", **base,
    )
    sm_cfg = ExperimentConfig(
        mesh=MeshConfig(data=2, fsdp=2, sp=2), model_config=uly,
        fsdp_mode="shard_map", **base,
    )
    # Megatron-TP composition (train.py passes head_axis='tp': heads shard
    # over tp x sp, all-to-alls ride 'sp' within each head group)
    tp_cfg = ExperimentConfig(
        mesh=MeshConfig(data=1, fsdp=2, sp=2, tp=2), model_config=uly, **base
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, mc.vocab_size, (1, 8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    for name, cfg in (
        ("oracle", oracle_cfg), ("gspmd", gspmd_cfg), ("shard_map", sm_cfg),
        ("tp_sp", tp_cfg),
    ):
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, _, _ = make_train_step(cfg, optimizer, mesh, specs)
        shard_seq = cfg.model_config.attn_impl == "ulysses"
        xg = make_global_batch(x, mesh, batch_spec(shard_seq=shard_seq))
        yg = make_global_batch(y, mesh, batch_spec(shard_seq=shard_seq))
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["gspmd"], losses["oracle"], rtol=1e-5)
    np.testing.assert_allclose(losses["shard_map"], losses["oracle"], rtol=1e-5)
    np.testing.assert_allclose(losses["tp_sp"], losses["oracle"], rtol=1e-5)


def test_ulysses_rejects_indivisible_heads_directly():
    """Direct ulysses_attention callers (bypassing config validation) get a
    ValueError, not an all_to_all shape error — and not an `assert` that
    python -O strips."""
    q, k, v = _qkv(B=2, H=3, T=64, C=8)  # 3 heads over sp=2
    mesh = _mesh(2)
    with pytest.raises(ValueError, match="n_head"):
        ulysses_attention_sharded(q, k, v, mesh)


def test_ulysses_config_validation():
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig

    kw = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8, warmup_steps=1,
        min_lr=1e-4, lr_decay_steps=10, max_steps=10, beta2=0.99, weight_decay=0.0,
        eval_interval=5, param_dtype="float32", compute_dtype="float32",
        g_accum_iters=1, shard_model=True,
    )
    # n_head=2 over sp=4: no whole head per device -> rejected up front
    with pytest.raises(ValueError, match="n_head"):
        ExperimentConfig(
            mesh=MeshConfig(data=2, fsdp=1, sp=4),
            model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=1,
                                   n_head=2, n_embd=64, attn_impl="ulysses"),
            **kw,
        )
