"""Pallas flash-attention parity vs the naive fp32-softmax oracle — forward
and backward — in interpret mode on CPU (compiled on real TPU)."""

import dataclasses
import importlib

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
    """blk_k == T <= 1024 routes backward through the fully-fused tiled
    dQ/dK/dV kernel (one probability reconstruction a tile; here two tiles
    a side) — the hot path at T=1024."""
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
    """blk_k == T > 1024 is past the tiled kernels (a head's operands whole
    in VMEM): the multi-block grid kernels serve it with one KV step (dq +
    dk/dv, the long-context backward split)."""
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


# ----------------------------------------------------------------------
# the tiled kernels (one KV block holds the sequence, T <= 1024): square
# score tiles on and under the diagonal only
# ----------------------------------------------------------------------

fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")


def _unmasked_attention(q, k, v):
    s = jnp.einsum("bhqc,bhkc->bhqk", q, k).astype(jnp.float32) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bhkc->bhqc", jax.nn.softmax(s, axis=-1).astype(q.dtype), v)


def _derived_tile(T):
    from midgpt_tpu.ops.attention import flash_block_sizes

    return flash_block_sizes(T, 1024)[0]


# (T, C, tile): the tile the policy derives at every benchmark shape, and
# small private tiles (n = 2 and n = 4 tiles a side) at small T
_TILED_CASES = [(T, C, None) for T in (256, 512, 1024) for C in (64, 128)] + [
    (128, 64, 64), (128, 64, 32), (256, 128, 128), (256, 128, 64),
]


@pytest.mark.parametrize("T,C,tile", _TILED_CASES)
def test_tiled_forward_and_gradient_parity_f32(T, C, tile):
    tile = tile or _derived_tile(T)
    assert fa._tiled(T, T) and T % tile == 0
    q, k, v = make_qkv(jax.random.PRNGKey(T + C), 1, 1, T, C)

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    flash = lambda q, k, v: flash_attention(q, k, v, tile, T)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(naive_causal_attention(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss(naive_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("T,tile", [(128, 128), (128, 64), (256, 64)])
def test_tiled_not_causal_parity_through_the_rings_entry(T, tile):
    """causal=False (ring attention's off-diagonal pairs, through the
    private _flash_forward / _flash_backward) walks all n^2 tiles and masks
    none: parity against unmasked attention, outputs and gradients."""
    q, k, v = make_qkv(jax.random.PRNGKey(7), 1, 2, T, 32)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, q.dtype)
    out, lse = fa._flash_forward(q, k, v, tile, T, causal=False)
    ref, vjp = jax.vjp(_unmasked_attention, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    s = jnp.einsum("bhqc,bhkc->bhqk", q, k) / np.sqrt(q.shape[-1])
    np.testing.assert_allclose(
        np.asarray(lse[..., 0]), np.asarray(jax.nn.logsumexp(s, axis=-1)), atol=2e-5, rtol=2e-5
    )
    got = fa._flash_backward(tile, T, (q, k, v, out, lse), g, causal=False)
    for a, b, name in zip(got, vjp(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("T,C,tile", [(256, 64, 64), (512, 128, 256)])
def test_tiled_parity_bf16(T, C, tile):
    q, k, v = make_qkv(jax.random.PRNGKey(11), 1, 2, T, C, jnp.bfloat16)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(
        f32(flash_attention(q, k, v, tile, T)), f32(naive_causal_attention(q, k, v)),
        atol=2e-2, rtol=2e-2,
    )
    loss = lambda attn: lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)
    gf = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, tile, T)), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss(naive_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "qkv"):
        np.testing.assert_allclose(f32(a), f32(b), atol=6e-2, rtol=6e-2, err_msg=f"d{name}")


def _kernel_dots(fn, *args):
    """dot_generals in the bodies of the pallas_calls `fn` makes (the traced
    kernel functions, sub-jaxprs included)."""

    def count(jaxpr, inside):
        n = 0
        for eqn in jaxpr.eqns:
            kernel = inside or eqn.primitive.name == "pallas_call"
            n += kernel and eqn.primitive.name == "dot_general"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub, kernel)
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr, False)


@pytest.mark.parametrize("tile,share", [(256, 10 / 16), (512, 3 / 4), (1024, 1.0)])
def test_tiled_kernels_form_only_the_tiles_under_the_diagonal(tile, share):
    """Fails if the skipping is lost: the counter, and the products in the
    traced kernel bodies at T = 1024 (2 a formed tile forward, 5 backward)."""
    T, n = 1024, 1024 // tile
    assert fa.score_tile_share(T, tile, T, causal=True) == share
    assert fa.score_tile_share(T, tile, T, causal=False) == 1.0
    x = jnp.zeros((1, 1, T, 64), jnp.bfloat16)
    lse = jnp.zeros((1, 1, T, fa._STATS_LANES), jnp.float32)
    for causal, tiles in ((True, n * (n + 1) // 2), (False, n * n)):
        assert tiles == round(fa.score_tile_share(T, tile, T, causal) * n * n)
        fwd = lambda q, k, v: fa._flash_forward(q, k, v, tile, T, causal=causal)
        bwd = lambda q, k, v, o, g: fa._flash_backward(tile, T, (q, k, v, o, lse), g, causal=causal)
        assert _kernel_dots(fwd, x, x, x) == 2 * tiles
        assert _kernel_dots(bwd, x, x, x, x, x) == 5 * tiles


def test_score_tile_share_of_the_multi_block_grid_and_the_policy():
    """Over several KV blocks the share is the blocks the grid's pl.when
    lets through; the dispatcher's policy gives the tiled kernels tile 256
    wherever one KV block holds the sequence, and leaves the multi-block
    blocks (train_kimi_linear_t8k: T=8192, attn_block_size 512) alone."""
    from midgpt_tpu.ops.attention import flash_block_sizes

    assert flash_block_sizes(1024, 1024) == (256, 1024)
    assert flash_block_sizes(512, 1024) == (256, 512)
    assert flash_block_sizes(128, 1024) == (128, 128)
    assert flash_block_sizes(384, 1024) == (384, 384)
    assert flash_block_sizes(8192, 512) == (512, 512)
    assert flash_block_sizes(2048, 1024) == (512, 1024)
    assert fa.score_tile_share(1024, *flash_block_sizes(1024, 1024)) == 10 / 16
    assert fa.score_tile_share(8192, 512, 512) == (16 * 17 // 2) / 16**2
    assert fa.score_tile_share(2048, 512, 1024) == 6 / 8
    # one KV block past the tiled kernels' reach: the multi-block grid forms all of it
    assert not fa._tiled(2048, 2048) and fa.score_tile_share(2048, 512, 2048) == 1.0
