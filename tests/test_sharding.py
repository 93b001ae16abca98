"""Mesh/FSDP tests on the 8-device virtual CPU mesh (test infra the
reference never had — SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from midgpt_tpu.config import MeshConfig
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.fsdp import constrain, fsdp_param_specs
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh

CFG = GPTConfig(block_size=32, vocab_size=256, n_layer=2, n_head=2, n_embd=64)


def test_devices_available():
    assert jax.device_count() == 8


def test_make_mesh_shapes():
    mesh = make_mesh(MeshConfig(data=-1, fsdp=4, sp=1))
    assert dict(mesh.shape) == {"data": 2, "fsdp": 4, "sp": 1, "tp": 1, "pp": 1, "ep": 1}
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, sp=2))
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "sp": 2, "tp": 1, "pp": 1, "ep": 1}
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, sp=1, tp=2))
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "sp": 1, "tp": 2, "pp": 1, "ep": 1}


def test_make_mesh_refuses_an_fsdp_that_does_not_fit():
    """8 devices, fsdp=16 configured: make_mesh raises (a silently clamped
    axis is how "everything on the first chip" hides); only the explicit
    elastic-resume re-derivation, fit_mesh_config, resizes it."""
    import pytest

    from midgpt_tpu.parallel.mesh import fit_mesh_config

    cfg = MeshConfig(data=-1, fsdp=16, sp=1)
    with pytest.raises(ValueError, match="fsdp=16"):
        make_mesh(cfg)
    fitted = fit_mesh_config(cfg, 8)
    assert (fitted.data, fitted.fsdp) == (-1, 8)
    assert dict(make_mesh(fitted).shape)["fsdp"] == 8
    # 6 devices' worth of room for fsdp=4: largest divisor <= 4 is 3
    assert fit_mesh_config(MeshConfig(fsdp=4), 6).fsdp == 3


def test_fsdp_specs_shard_large_replicate_small():
    mesh = make_mesh(MeshConfig(data=2, fsdp=4, sp=1))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    specs = fsdp_param_specs(params, mesh, shard_model=True, min_size=0)
    # Big 2D+ leaves sharded over 'fsdp' on exactly one axis:
    assert specs.wte == P(None, "fsdp")
    assert specs.lm_head == P(None, "fsdp")
    assert specs.blocks.attn.wqkv == P(None, None, None, "fsdp")
    assert specs.blocks.mlp.w_up == P(None, None, "fsdp")
    # per-head norm scales: (L, C) with C=32 not divisible by 4 on last axis?
    # C=32 divisible; but skip_leading keeps axis 1: either sharded or replicated is legal.
    # With min_size=big, everything replicated:
    specs2 = fsdp_param_specs(params, mesh, shard_model=True, min_size=2**30)
    assert all(s == P() for s in jax.tree.leaves(specs2))
    specs3 = fsdp_param_specs(params, mesh, shard_model=False)
    assert all(s == P() for s in jax.tree.leaves(specs3))


def test_fsdp_indivisible_falls_back_replicated():
    mesh = make_mesh(MeshConfig(data=1, fsdp=8, sp=1))
    x = jnp.zeros((3, 5, 7))
    specs = fsdp_param_specs({"w": x}, mesh, shard_model=True, min_size=0)
    assert specs["w"] == P()


def test_sharded_forward_matches_single_device():
    """FSDP-sharded forward must be numerically identical to unsharded."""
    mesh = make_mesh(MeshConfig(data=2, fsdp=4, sp=1))
    params = GPT.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, CFG.vocab_size)

    base = GPT.apply(CFG, params, tokens, inference=True)

    specs = fsdp_param_specs(params, mesh, shard_model=True, min_size=0)
    sharded_params = jax.jit(lambda p: constrain(p, specs, mesh))(params)
    xg = make_global_batch(np.asarray(tokens), mesh, batch_spec(with_accum=False))
    out = jax.jit(
        lambda p, t: GPT.apply(CFG, p, t, inference=True)
    )(sharded_params, xg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(base), atol=2e-5, rtol=2e-5
    )


def test_make_global_batch_sharding():
    mesh = make_mesh(MeshConfig(data=2, fsdp=4, sp=1))
    x = np.arange(16 * 8, dtype=np.int32).reshape(16, 8)
    g = make_global_batch(x, mesh, batch_spec(with_accum=False))
    assert g.shape == (16, 8)
    np.testing.assert_array_equal(np.asarray(g), x)
    # batch axis sharded over data*fsdp = 8 ways
    assert len(g.sharding.device_set) == 8
