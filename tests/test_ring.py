"""Ring attention (sequence-parallel) parity vs the single-device oracle.

The `sp` mesh axis stops being plumbing here: these tests shard the sequence
over 2 and 4 virtual CPU devices and assert the ring produces the same
outputs AND the same gradients as unsharded causal attention, including the
long-context shape (T=4096) the reference cannot represent at all (its
materialized T x T scores, reference model.py:71-73).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from midgpt_tpu.ops.attention import naive_causal_attention
from midgpt_tpu.parallel.ring_attention import ring_attention_sharded


def _mesh(sp: int) -> Mesh:
    devs = np.array(jax.devices()[: 2 * sp]).reshape(2, 1, sp)
    return Mesh(devs, ("data", "fsdp", "sp"))


def _qkv(B=4, H=2, T=128, C=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (B, H, T, C), dtype) for k in ks)


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_matches_naive_forward(sp):
    q, k, v = _qkv()
    mesh = _mesh(sp)
    out = ring_attention_sharded(q, k, v, mesh)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_gradients_match(sp=2):
    """AD through the ring (scan + ppermute) equals AD through the oracle."""
    q, k, v = _qkv(B=2, H=2, T=64, C=8)
    mesh = _mesh(sp)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring_attention_sharded(q, k, v, mesh)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_ring_long_context_t4096():
    """T=4096 across sp=4: per-device score blocks are (1024, 1024) — the
    full T x T matrix is never materialized on any device."""
    q, k, v = _qkv(B=2, H=1, T=4096, C=8, dtype=jnp.bfloat16)
    mesh = _mesh(4)
    out = ring_attention_sharded(q, k, v, mesh)
    assert out.shape == (2, 1, 4096, 8)
    # oracle on a slice: the final 16 positions attend across every shard
    ref = naive_causal_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out[..., -16:, :], dtype=np.float32),
        np.asarray(ref[..., -16:, :]),
        atol=3e-2,
        rtol=3e-2,
    )


def test_ring_respects_sharding_layout():
    """Inputs placed with the T axis actually sharded over sp stay sharded:
    the ring only ever moves K/V shards (neighbor ppermute), never gathers."""
    q, k, v = _qkv(T=128)
    mesh = _mesh(2)
    sh = NamedSharding(mesh, P(("data", "fsdp"), None, "sp", None))
    q, k, v = (jax.device_put(a, sh) for a in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention_sharded(q, k, v, mesh))(q, k, v)
    assert out.sharding.spec == P(("data", "fsdp"), None, "sp", None)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_train_step_matches_naive_sp1():
    """One full training step (FSDP x SP mesh, ring attention, T sharded over
    'sp') produces the same loss as the naive-attention sp=1 step on the same
    batch and seed — sequence parallelism changes the schedule, not the math."""
    import dataclasses

    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.train import init_state, make_train_step

    base = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=10,
        min_lr=1e-4,
        lr_decay_steps=100,
        max_steps=100,
        beta2=0.95,
        weight_decay=1e-4,
        eval_interval=50,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        # the GSPMD-bound ring (ring_attention_sharded); the authored
        # schedule x ring is tests/test_shard_map_fsdp.py's
        fsdp_mode="gspmd",
        mesh=MeshConfig(data=2, fsdp=2, sp=2),
        model_config=GPTConfig(
            block_size=64, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
            attn_impl="ring",
        ),
    )

    rng = np.random.default_rng(0)
    x = rng.integers(0, 128, (1, 8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)

    losses = {}
    for name, cfg in {
        "ring_sp2": base,
        "naive_sp1": base.replace(
            mesh=MeshConfig(data=2, fsdp=4, sp=1),
            model_config=dataclasses.replace(base.model_config, attn_impl="naive"),
        ),
    }.items():
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, _, _ = make_train_step(cfg, optimizer, mesh, specs)
        sp = batch_spec(shard_seq=cfg.mesh.sp > 1)
        xg = make_global_batch(x, mesh, sp)
        yg = make_global_batch(y, mesh, sp)
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)

    assert np.isfinite(losses["ring_sp2"])
    np.testing.assert_allclose(losses["ring_sp2"], losses["naive_sp1"], rtol=1e-5)


@pytest.mark.parametrize("block_size", [16, 32, 48, 64])
def test_ring_kv_subblocking_parity(block_size):
    """Sub-blocking the visiting K/V shard (bounded scores memory) is exact:
    same outputs for any block size, including non-dividing relationships."""
    q, k, v = _qkv(B=2, H=2, T=128, C=16)
    mesh = _mesh(2)
    out = ring_attention_sharded(q, k, v, mesh, block_size=block_size)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_kernel_path_forward_parity(sp):
    """The Pallas-kernel per-pair path (the one a real TPU slice runs):
    interpret mode on CPU, forced with use_kernel=True. The diagonal pair
    uses the causal kernel, off-diagonal pairs the non-causal kernel."""
    q, k, v = _qkv(T=128, C=32)
    mesh = _mesh(sp)
    out = ring_attention_sharded(q, k, v, mesh, use_kernel=True)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_kernel_path_gradients(sp):
    """Backward through the authored ring backward pass (custom VJP, flash
    backward kernels per pair, dK/dV riding the ring) equals oracle AD."""
    q, k, v = _qkv(B=2, H=2, T=128, C=32)
    mesh = _mesh(sp)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring_attention_sharded(q, k, v, mesh, use_kernel=True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gf), atol=3e-5, rtol=3e-5, err_msg=f"d{name}"
        )


def test_ring_kernel_jnp_paths_agree():
    """Both per-pair implementations of the same ring schedule produce the
    same result (kernel path in interpret mode vs blockwise jnp)."""
    q, k, v = _qkv(B=2, H=2, T=256, C=16)
    mesh = _mesh(4)
    out_k = ring_attention_sharded(q, k, v, mesh, use_kernel=True)
    out_j = ring_attention_sharded(q, k, v, mesh, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j), atol=2e-5, rtol=2e-5)


def test_ring_kernel_auto_falls_back_on_unservable_shard():
    """Shard lengths with no kernel-servable block (Tl=160 at block 64: no
    8-aligned divisor of 160 in [128, 64] exists) fall back to the jnp pair
    path rather than erroring — parity holds, and the perf cliff announces
    itself with a one-time RuntimeWarning naming the shapes."""
    from midgpt_tpu.parallel import ring_attention as ring_mod

    q, k, v = _qkv(B=2, H=1, T=320, C=16)  # Tl=160 over sp=2; 160 % 64 != 0
    mesh = _mesh(2)
    ring_mod._WARNED.clear()
    with pytest.warns(RuntimeWarning, match="shard length 160"):
        out = ring_attention_sharded(q, k, v, mesh, block_size=64, use_kernel=True)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_ring_kernel_block_auto_adjusts_to_divisor():
    """Tl=1280 at the default block 1024 does NOT fall back: the plan
    auto-adjusts to the largest 8-aligned divisor in [128, 1024] (640) and
    stays on the kernel path — no warning, kernel parity."""
    import warnings

    from midgpt_tpu.parallel import ring_attention as ring_mod

    assert ring_mod._resolve_pair_plan(1280, 1024, True) == (True, 640)
    # already servable (the dispatcher clamps the block to Tl): unchanged
    assert ring_mod._resolve_pair_plan(160, 1024, True) == (True, 1024)
    # fallback cases return use_kernel=False unchanged
    ring_mod._WARNED.clear()
    with pytest.warns(RuntimeWarning):
        assert ring_mod._resolve_pair_plan(120, 64, True) == (False, 64)

    q, k, v = _qkv(B=2, H=2, T=2560, C=32, dtype=jnp.float32)  # Tl=1280 over sp=2
    mesh = _mesh(2)
    ring_mod._WARNED.clear()
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no fallback warning
        out = ring_attention_sharded(q, k, v, mesh, block_size=1024, use_kernel=True)
    ref = naive_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)
