"""Pallas flash-attention parity vs the naive fp32-softmax oracle — forward
and backward — in interpret mode on CPU (compiled on real TPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.kernels.flash_attention import flash_attention
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.ops.attention import naive_causal_attention
from midgpt_tpu.ops.loss import cross_entropy_loss


def make_qkv(key, B, H, T, C, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, T, C), dtype)
    k = jax.random.normal(kk, (B, H, T, C), dtype)
    v = jax.random.normal(kv, (B, H, T, C), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "T,blk_q,blk_k",
    [(128, 128, 128), (128, 64, 64), (256, 64, 128), (128, 32, 64)],
)
def test_forward_parity_f32(T, blk_q, blk_k):
    q, k, v = make_qkv(jax.random.PRNGKey(0), 2, 2, T, 64)
    ref = naive_causal_attention(q, k, v)
    out = flash_attention(q, k, v, blk_q, blk_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_parity_bf16():
    q, k, v = make_qkv(jax.random.PRNGKey(1), 1, 2, 128, 64, jnp.bfloat16)
    ref = naive_causal_attention(q, k, v)
    out = flash_attention(q, k, v, 64, 64)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
    )


def test_backward_parity_f32():
    q, k, v = make_qkv(jax.random.PRNGKey(2), 1, 2, 128, 32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, 64, 64)))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5, err_msg=f"d{name}"
        )


@pytest.fixture
def force_flash_interpret(monkeypatch):
    """Route the model's 'flash' dispatch to the real kernel (interpret mode)
    instead of the off-TPU blockwise fallback."""
    import importlib

    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_model_end_to_end_flash_matches_naive(force_flash_interpret):
    """Full GPT fwd+bwd with attn_impl='flash' vs 'naive'."""
    cfg = GPTConfig(
        block_size=64, vocab_size=64, n_layer=2, n_head=2, n_embd=64,
        attn_impl="naive",
    )
    cfg_flash = dataclasses.replace(cfg, attn_impl="flash", attn_block_size=32)
    params = GPT.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, 64)

    def loss(p, c):
        return cross_entropy_loss(GPT.apply(c, p, tokens, inference=True), labels)

    l1, g1 = jax.value_and_grad(loss)(params, cfg)
    l2, g2 = jax.value_and_grad(loss)(params, cfg_flash)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_indivisible_blocks_adjust_not_raise():
    """Explicit block sizes that don't tile T adjust to ones that do (the
    KV block widens to T, the Q block follows) instead of raising."""
    q, k, v = make_qkv(jax.random.PRNGKey(3), 1, 1, 96, 32)
    ref = naive_causal_attention(q, k, v)
    out = flash_attention(q, k, v, 64, 64)  # 96 % 64 != 0 -> blocks become (96, 96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_dispatch_never_downgrades_a_configured_flash():
    """multihead_attention(impl='flash') on a length no block tiles is an
    error, not a quiet blockwise run; arbitrary-T callers (KV-cache
    prefill) pick their blockwise route BY NAME through
    flash_or_blockwise, and that route matches the naive oracle."""
    import pytest

    from midgpt_tpu.ops.attention import flash_or_blockwise, multihead_attention

    q, k, v = make_qkv(jax.random.PRNGKey(4), 1, 2, 90, 32)
    with pytest.raises(ValueError, match="attn_impl='flash' cannot serve T=90"):
        multihead_attention(q, k, v, impl="flash", inference=True, block_size=64)
    assert flash_or_blockwise("flash", 90, 64) == "blockwise"
    assert flash_or_blockwise("naive", 90, 64) == "naive"
    ref = naive_causal_attention(q, k, v)
    out = multihead_attention(
        q, k, v, impl=flash_or_blockwise("flash", 90, 64), inference=True,
        block_size=64,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_backward_parity_fused_single_step():
    """blk_k == T <= 1024 routes backward through the fully-fused dQ/dK/dV
    kernel (one probability reconstruction) — the hot path at T=1024."""
    q, k, v = make_qkv(jax.random.PRNGKey(5), 1, 2, 128, 32)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, 64, 128)))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5, err_msg=f"d{name}"
        )


def test_backward_parity_single_kv_long_seq():
    """blk_k == T > 1024 skips the fused kernel: stateless dq-single +
    tiled dk/dv kernels (the long-context backward split)."""
    q, k, v = make_qkv(jax.random.PRNGKey(6), 1, 1, 2048, 8)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, 512, 2048)))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.sin(naive_causal_attention(q, k, v)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


def test_default_blocks_fallback_non_divisible_T():
    """Direct flash_attention(q, k, v) calls with the default block sizes
    must serve sequence lengths the defaults don't divide (e.g. T=96): the
    KV block widens to T and the Q block follows, instead of raising."""
    q, k, v = make_qkv(jax.random.PRNGKey(9), 1, 2, 96, 32)
    ref = naive_causal_attention(q, k, v)
    out = flash_attention(q, k, v)  # defaults (512, 1024) do not divide 96
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    gf = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(q, k, v))), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(naive_causal_attention(q, k, v))), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5, err_msg=f"d{name}"
        )
