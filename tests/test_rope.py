"""RoPE properties. The shift-equivariance test promotes the reference's
manual eyeball script (reference scripts/test_rotary.py:11-32) into a real
assertion: rolling Q and K by s positions must roll the attention scores."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.ops import rope
from midgpt_tpu.ops.rope import apply_rope, rope_table, rotate_interleaved, rotate_interleaved_strided

ROTARY_CASES = [(C, dtype) for C in (64, 128) for dtype in ("float32", "bfloat16")]
rotary_cases = pytest.mark.parametrize(
    "C,dtype", ROTARY_CASES, ids=[f"c{C}-{d}" for C, d in ROTARY_CASES]
)


@pytest.mark.parametrize("rotate", [rotate_interleaved, rotate_interleaved_strided])
def test_rotate_interleaved_pattern(rotate):
    x = jnp.array([[1.0, 2.0, 3.0, 4.0]])
    np.testing.assert_allclose(np.asarray(rotate(x)), [[-2.0, 1.0, -4.0, 3.0]])


@rotary_cases
def test_rolled_rotation_is_the_strided_spelling_to_the_bit(C, dtype):
    """[1 2 3 4 ...] -> [-2 1 -4 3 ...] at the two head widths, and any values
    as the stride-2 spelling (the training entry points', the parent of PR
    57's everywhere) rotates them: a permutation and a sign."""
    ramp = jnp.arange(1, C + 1, dtype=dtype)[None]
    want = np.stack((-np.arange(2, C + 1, 2), np.arange(1, C, 2)), -1).reshape(1, C)
    np.testing.assert_array_equal(np.asarray(rotate_interleaved(ramp), np.float32), want)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 2, C), dtype)
    got = rotate_interleaved(x)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(rotate_interleaved_strided(x)))


@rotary_cases
def test_interleaved_entry_points_agree_to_the_bit(C, dtype):
    """At equal positions the serving entry point (`apply_rope_positions`:
    rolls, the tables widened before their rows are taken) is the training
    entry points' (`apply_rope`, `apply_rope_bthc`: stride-2 slices, rows of
    the half-width tables duplicated after) values to the bit."""
    B, T, H = 2, 8, 3
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, H, C), dtype)
    sin, cos = rope_table(C, 32)
    want = np.asarray(rope.apply_rope_bthc(x, sin, cos))
    np.testing.assert_array_equal(
        np.asarray(rope.apply_rope_bthc(x, sin, cos, positions=jnp.arange(T))), want
    )
    bhtc = apply_rope(x.transpose(0, 2, 1, 3), sin, cos)
    np.testing.assert_array_equal(np.asarray(bhtc.transpose(0, 2, 1, 3)), want)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    np.testing.assert_array_equal(
        np.asarray(rope.apply_rope_positions(x, sin, cos, positions)), want
    )


@rotary_cases
def test_rolled_gradient_is_the_strided_spelling_s_to_the_bit(C, dtype):
    """`jax.grad` through the rotation of `apply_rope_positions` against
    `jax.grad` through `apply_rope_bthc`: the transpose of two rolls and a
    select gives what the transpose of the stride-2 slices (a pad / scatter)
    gives, so an entry point may change its spelling without a new golden."""
    B, T = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 3, C), dtype)
    w = jax.random.normal(jax.random.PRNGKey(8), x.shape, dtype)
    sin, cos = rope_table(C, T)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    loss = lambda f: lambda x: jnp.sum((f(x) * w).astype(jnp.float32))
    got = jax.grad(loss(lambda x: rope.apply_rope_positions(x, sin, cos, positions)))(x)
    want = jax.grad(loss(lambda x: rope.apply_rope_bthc(x, sin, cos)))(x)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_rope_preserves_norm():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 4, 32, 16))
    sin, cos = rope_table(16, 32)
    out = apply_rope(x, sin, cos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )


def test_rope_shift_equivariance():
    """scores(rope(q), rope(k)) shifted == scores(rope(roll(q)), rope(roll(k)))."""
    key = jax.random.PRNGKey(1)
    kq, kk = jax.random.split(key)
    H, T, C, s = 2, 64, 16, 5
    q = jax.random.normal(kq, (H, T, C))
    k = jax.random.normal(kk, (H, T, C))
    sin, cos = rope_table(C, T)

    def scores(q, k):
        qr = apply_rope(q, sin, cos)
        kr = apply_rope(k, sin, cos)
        return jnp.einsum("hqc,hkc->hqk", qr, kr)

    base = scores(q, k)
    rolled = scores(jnp.roll(q, s, axis=1), jnp.roll(k, s, axis=1))
    # Valid region: both query and key indices >= s after the roll.
    np.testing.assert_allclose(
        np.asarray(rolled[:, s:, s:]), np.asarray(base[:, :-s, :-s]), atol=1e-4
    )


def test_split_style_is_permutation_conjugate():
    """The split lowering computes the SAME rotation as the reference's
    interleaved form after the C axis is permuted by `split_permutation` —
    the op-level exactness behind rope_style='split' (models/gpt.py applies
    the permutation to the q/k projection rows, so QK^T is unchanged)."""
    from midgpt_tpu.ops.rope import apply_rope_bthc, split_permutation

    key = jax.random.PRNGKey(3)
    B, T, H, C = 2, 16, 3, 32
    x = jax.random.normal(key, (B, T, H, C))
    sin, cos = rope_table(C, T)
    perm = split_permutation(C)
    ref = apply_rope_bthc(x, sin, cos, style="interleaved")
    got = apply_rope_bthc(x[..., perm], sin, cos, style="split")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref[..., perm]), atol=1e-6
    )
    # and scores are invariant: q.k == q[perm].k[perm]
    q, k = x, jnp.roll(x, 1, axis=0)
    s_ref = jnp.einsum(
        "bthc,bshc->bhts",
        apply_rope_bthc(q, sin, cos),
        apply_rope_bthc(k, sin, cos),
    )
    s_split = jnp.einsum(
        "bthc,bshc->bhts",
        apply_rope_bthc(q[..., perm], sin, cos, style="split"),
        apply_rope_bthc(k[..., perm], sin, cos, style="split"),
    )
    np.testing.assert_allclose(np.asarray(s_split), np.asarray(s_ref), atol=1e-5)


def test_rope_per_slot_positions():
    """apply_rope_positions ((B, T) per-token absolute positions — the
    continuous-batching decode path, where B slots sit at B different
    write positions) must be bit-identical to apply_rope_bthc run per-row
    at that row's position, in both rotation styles."""
    from midgpt_tpu.ops.rope import apply_rope_bthc, apply_rope_positions

    key = jax.random.PRNGKey(4)
    B, T, H, C = 3, 2, 2, 16
    x = jax.random.normal(key, (B, T, H, C))
    sin, cos = rope_table(C, 64)
    positions = jnp.asarray([[0, 1], [17, 18], [40, 41]])
    for style in ("interleaved", "split"):
        got = apply_rope_positions(x, sin, cos, positions, style=style)
        for b in range(B):
            want = apply_rope_bthc(
                x[b : b + 1], sin, cos, positions=positions[b], style=style
            )
            np.testing.assert_array_equal(np.asarray(got[b]), np.asarray(want[0]))


def test_rope_positions_gather():
    """Explicit positions must equal the contiguous-prefix default."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (1, 8, 16))
    sin, cos = rope_table(16, 32)
    out_default = apply_rope(x, sin, cos)
    out_positions = apply_rope(x, sin, cos, positions=jnp.arange(8))
    np.testing.assert_allclose(np.asarray(out_default), np.asarray(out_positions), atol=1e-6)
    # A single token at absolute position p == slicing it out of a longer pass.
    p = 5
    single = apply_rope(x[:, p : p + 1], sin, cos, positions=jnp.array([p]))
    np.testing.assert_allclose(np.asarray(single), np.asarray(out_default[:, p : p + 1]), atol=1e-6)
