"""Rotary position embeddings, GPT-J interleaved style.

Matches reference layers.py:79-99: pairs are interleaved ([a b c d] rotates to
[-b a -d c]), the sin/cos tables use base 10000 over even channel indices, and
the table is duplicated across each pair so rotation is applied at full head
dim. The table is computed in float32 with jnp (constant-folded by XLA under
jit for static T — the reference computes it in host numpy, reference
layers.py:79-82, which is the same thing after tracing) and cast to the
activation dtype at the point of use.

`positions` is explicit so the KV-cache decode path can rotate a single new
token at its absolute position.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp

Array = jax.Array


def rope_table(head_dim: int, length: int, base: float = 10000.0) -> tp.Tuple[Array, Array]:
    """(sin, cos) tables of shape (length, head_dim // 2), float32."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def rotate_interleaved(x: Array) -> Array:
    """[a b c d] -> [-b a -d c] over the trailing axis, with nothing for XLA
    to gather: two static lane rolls (each a `concatenate(slice, slice)` on
    the minor axis, what `rotate_half` is) and a select on the channel's
    parity. The lanes a roll wraps around are the ones the select drops. The
    same values to the bit as `rotate_interleaved_strided` (a permutation and
    a sign; pinned by tests/test_rope.py, forward and `jax.grad`)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(lane % 2 == 0, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))


def rotate_interleaved_strided(x: Array) -> Array:
    """`rotate_interleaved` as stride-2 slices, a stack and a reshape: on the
    TPU a gather over the channel axis, forward, and a pad / scatter,
    backward. The TRAINING entry points (`apply_rope`, `apply_rope_bthc`)
    keep it and the serving one (`apply_rope_positions`) rolls, each by a
    chip reading (PERF.md section 6 PR 57, v5e): rolling took 3.8 ms off the
    XL's 12.7 ms (16, 16) prefill call; in the XL's four-chip training step
    it took 7 ms off the layers' own time and the authored FSDP schedule
    then left 10 ms more of its collectives exposed, 357.3 -> 360.8 ms a
    step."""
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    return jnp.stack((-x2, x1), axis=-1).reshape(x.shape)


def _duplicate_pairs(t: Array) -> Array:
    """(..., C/2) -> (..., C) by repeating each element twice (interleaved)."""
    return jnp.stack((t, t), axis=-1).reshape(t.shape[:-1] + (t.shape[-1] * 2,))


def apply_rope(
    x: Array,
    sin: Array,
    cos: Array,
    positions: tp.Optional[Array] = None,
    style: str = "interleaved",
) -> Array:
    """Rotate `x` (..., T, head_dim) by the (sin, cos) tables.

    If `positions` (shape (T,)) is given, rows of the tables are gathered at
    those absolute positions; otherwise the first T rows are used.
    `style` as in `apply_rope_bthc`."""
    if positions is not None:
        sin = jnp.take(sin, positions, axis=0)
        cos = jnp.take(cos, positions, axis=0)
    else:
        sin = sin[: x.shape[-2]]
        cos = cos[: x.shape[-2]]
    if style == "split":
        sin = _tile_halves(sin).astype(x.dtype)
        cos = _tile_halves(cos).astype(x.dtype)
        return x * cos + rotate_half(x) * sin
    sin = _duplicate_pairs(sin).astype(x.dtype)
    cos = _duplicate_pairs(cos).astype(x.dtype)
    return x * cos + rotate_interleaved_strided(x) * sin


def apply_rope_positions(
    x: Array,
    sin: Array,
    cos: Array,
    positions: Array,
    style: str = "interleaved",
) -> Array:
    """Rotate `x` (B, T, H, C) with PER-TOKEN absolute positions (B, T).

    The continuous-batching decode path runs B independent requests at B
    different write positions in one step; `apply_rope_bthc` broadcasts one
    (T,) position vector over the batch, this gathers a (B, T) table slice
    instead. Same elementwise rotation, so for equal positions it is
    bit-identical to `apply_rope_bthc` (pinned by tests/test_rope.py)."""
    if style == "split":
        sin = jnp.take(sin, positions, axis=0)  # (B, T, C/2)
        cos = jnp.take(cos, positions, axis=0)
        sin = _tile_halves(sin).astype(x.dtype)[:, :, None, :]  # (B, T, 1, C)
        cos = _tile_halves(cos).astype(x.dtype)[:, :, None, :]
        return x * cos + rotate_half(x) * sin
    # The (block_size, C/2) tables are formed from iotas inside the program:
    # widened ONCE, before the rows are taken, a step takes full-width rows
    # of a constant and interleaves nothing of its own.
    sin = jnp.take(jnp.repeat(sin, 2, axis=-1), positions, axis=0)  # (B, T, C)
    cos = jnp.take(jnp.repeat(cos, 2, axis=-1), positions, axis=0)
    sin = sin.astype(x.dtype)[:, :, None, :]
    cos = cos.astype(x.dtype)[:, :, None, :]
    return x * cos + rotate_interleaved(x) * sin


def rotate_half(x: Array) -> Array:
    """[a b | c d] -> [-c -d | a b] over the trailing axis (contiguous
    halves — the TPU-friendly form: two static slices instead of the
    stride-2 gathers of the interleaved rotation)."""
    h1, h2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((-h2, h1), axis=-1)


def _tile_halves(t: Array) -> Array:
    """(..., C/2) -> (..., C) by concatenating the table with itself."""
    return jnp.concatenate((t, t), axis=-1)


def split_permutation(head_dim: int):
    """Index array p with p[i]=2i, p[i+C/2]=2i+1: gathering a head's C axis
    by p moves interleaved pair (2i, 2i+1) to positions (i, i+C/2), turning
    the reference's interleaved rotation into `rotate_half` with the SAME
    angles (rope_table's frequency order is already the even-channel order).
    Exactness of the conjugation is pinned by tests/test_rope.py."""
    import numpy as np

    p = np.empty((head_dim,), np.int32)
    half = head_dim // 2
    p[:half] = np.arange(half) * 2
    p[half:] = np.arange(half) * 2 + 1
    return p


def apply_rope_bthc(
    x: Array,
    sin: Array,
    cos: Array,
    positions: tp.Optional[Array] = None,
    style: str = "interleaved",
) -> Array:
    """Rotate `x` of shape (B, T, H, C) — sequence at axis 1, heads at axis 2.

    Same math as `apply_rope`, with the tables broadcast over the head axis
    instead of the sequence axis sitting next to head_dim. This is the layout
    the fused QKV projection produces; using it end-to-end (projection → RoPE
    → flash kernel → merge heads) eliminates all head transposes.

    style='interleaved' is the reference rotation (layers.py:79-99), here in
    its stride-2 spelling (`rotate_interleaved_strided` says why).
    style='split' expects the C axis pre-permuted by `split_permutation`
    (models/gpt.py permutes the q/k projection rows in-graph) and applies
    the mathematically-identical rotate-half form, introduced against the
    interleaved form's stride-2 pair gathers, which cost real copy passes in
    forward AND backward. What those cost where they were last read
    (PERF.md section 6 PR 57, v5e, the XL): 4.3 ms of `step.attn_ms` 90.8
    and 2.9 of `step.mlp_ms` 126.1 in a 357 ms four-chip training step."""
    if positions is not None:
        sin = jnp.take(sin, positions, axis=0)
        cos = jnp.take(cos, positions, axis=0)
    else:
        sin = sin[: x.shape[1]]
        cos = cos[: x.shape[1]]
    if style == "split":
        sin = _tile_halves(sin).astype(x.dtype)[:, None, :]  # (T, 1, C)
        cos = _tile_halves(cos).astype(x.dtype)[:, None, :]
        return x * cos + rotate_half(x) * sin
    sin = _duplicate_pairs(sin).astype(x.dtype)[:, None, :]  # (T, 1, C)
    cos = _duplicate_pairs(cos).astype(x.dtype)[:, None, :]
    return x * cos + rotate_interleaved_strided(x) * sin


def apply_rope_leading(x: Array, sin: Array, cos: Array, positions: Array) -> Array:
    """Partial rotary: rotate-half on the LEADING `2 * sin.shape[-1]` channels
    of `x` (B, T, H, C), the rest pass through. `positions` is (T,) (one
    vector for the batch) or (B, T) (per-token, the paged decode step).
    The tables are `rope_table(rotary_dim, length, base)`: a model with two
    bases (window and global layers) holds two tables."""
    rot = 2 * sin.shape[-1]
    s = _tile_halves(jnp.take(sin, positions, axis=0)).astype(x.dtype)[..., None, :]
    c = _tile_halves(jnp.take(cos, positions, axis=0)).astype(x.dtype)[..., None, :]
    xr = x[..., :rot]
    return jnp.concatenate((xr * c + rotate_half(xr) * s, x[..., rot:]), axis=-1)
