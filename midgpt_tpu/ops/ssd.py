"""The Mamba-2 state-space recurrence (SSD: a scalar decay a head and token).

Per head, with a float32 state h of (P, N) (P channels of the head, N =
`d_state`), a step dt_t > 0, ONE decay rate A < 0 a head, and B_t, C_t of (N,)
SHARED by every head (`n_groups` 1):

    a_t = dt_t * A                                   # one scalar a head and token, <= 0
    h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T          # (P, N)
    y_t = h_t C_t + D x_t                            # (P,)

No key is subtracted from the state before the write (ops/kda.py's delta rule
is `S' + beta k (v - S'^T k)^T`: no setting of beta gives the line above), no
triangular system is solved, and the decay is a scalar: the chunked form is
three matrix products a chunk.

`ssd_recurrent` is the recurrence token by token (`lax.scan` over T): the test
oracle, which nothing on the serving path calls. `ssd_step` is ONE token of it,
the decode step over every slot's state (`ssd_step_terms`: what it reads and
its output; `ssd_step_write`: the state's update from those terms). `ssd_chunked` computes the same in
chunks of Q tokens from `initial_state` (zeros if None) to the final state it
returns: a chunked prefill passes a slot's state in and keeps what comes back.
A state has ONE layout everywhere, (B, H, P, N) float32.

Inside a chunk, with s_t = sum_{j<=t} a_j (inclusive, so every decay between
two positions of the chunk is exp(s_t - s_j) <= 1: nothing is factored as
e^{s_t} e^{-s_j}, which overflows float32 once a head has decayed by e^-88
inside a chunk) and h_0 the state at the chunk's start:

    Y   = (L o (C B^T)) (dt x) + exp(s) (C h_0^T) + D x,   L_tj = exp(s_t - s_j) for j <= t, else 0
    h_Q = exp(s_Q) h_0 + sum_j exp(s_Q - s_j) dt_j x_j B_j^T

C B^T is ONE (Q, Q) tile a chunk for all heads; L is a (Q, Q) tile A HEAD that
the vector unit forms (exponentials and products, no matrix product). A token
whose dt is 0 changes nothing (a = 0: no decay; dt x B^T = 0: no write), which
is how a caller masks the rows past a chunk's valid tokens and how T is padded
to whole chunks here.

Precisions: a, s, L, the state and every accumulation float32; the matrix
products multiply in x's dtype (bfloat16 when served, float32 in the tests)
and accumulate in float32.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp

Array = jax.Array
_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_step_terms(x: Array, dt: Array, A: Array, B: Array, C: Array, D: Array,
                   state: Array) -> tp.Tuple[Array, Array, Array]:
    """What ONE token of the recurrence READS of B_ slots' states, and the terms
    of its write. x (B_, H, P); dt (B_, H) > 0 (after softplus); A, D (H,); B,
    C (B_, N), shared by the heads; `state` (B_, H, P, N) float32, as it came.
    y = h_t C = e^a (h_{t-1} C) + (B . C) dt x + D x: the sum over N is taken
    of the state AS IT CAME, so nothing here needs the written state. Float32,
    elementwise products and one sum over N (no matmul). Returns (y (B_, H, P)
    float32, the decay e^a (B_, H), the written rows dt x (B_, H, P))."""
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    decay = jnp.exp(dt * A.astype(f32))
    wrote = dt[..., None] * x
    seen = jnp.sum(state.astype(f32) * C[:, None, None, :], axis=-1)  # (B_, H, P)
    y = decay[..., None] * seen + jnp.sum(B * C, axis=-1)[:, None, None] * wrote + D.astype(f32)[:, None] * x
    return y, decay, wrote


def ssd_step_write(state: Array, decay: Array, wrote: Array, B: Array) -> Array:
    """h_t = e^a h_{t-1} + dt x B^T from `ssd_step_terms`' terms: the state
    (B_, H, P, N) read and written once, 2 x 4 x P x N bytes a head."""
    return decay[..., None, None] * state.astype(jnp.float32) + wrote[..., None] * B.astype(jnp.float32)[:, None, None, :]


def ssd_step(x: Array, dt: Array, A: Array, B: Array, C: Array, D: Array, state: Array) -> tp.Tuple[Array, Array]:
    """ONE token of the recurrence for B_ slots: `ssd_step_terms`, then
    `ssd_step_write`. A serving decode step calls the two apart, the write in
    a loop of its own after the layers' (models/granite_hybrid.py), so that a
    program that commits no state reads the rows and writes nothing. Returns (y
    (B_, H, P) float32, the next state (B_, H, P, N) float32)."""
    y, decay, wrote = ssd_step_terms(x, dt, A, B, C, D, state)
    return y, ssd_step_write(state, decay, wrote, B)


def ssd_recurrent(x: Array, dt: Array, A: Array, B: Array, C: Array, D: Array,
                  initial_state: tp.Optional[Array] = None) -> tp.Tuple[Array, Array]:
    """Token-by-token oracle. x (B_, T, H, P); dt (B_, T, H); A, D (H,); B, C
    (B_, T, N); `initial_state` (B_, H, P, N), zeros if None. Returns (y (B_,
    T, H, P) float32, final state (B_, H, P, N) float32)."""
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    Bn, T, H, P = x.shape
    h0 = jnp.zeros((Bn, H, P, B.shape[-1]), f32) if initial_state is None else initial_state.astype(f32)

    def step(h, t):
        x_t, dt_t, B_t, C_t = t
        h = jnp.exp(dt_t * A.astype(f32))[..., None, None] * h + jnp.einsum(
            "bhp,bn->bhpn", dt_t[..., None] * x_t, B_t, precision=_HIGHEST)
        return h, jnp.einsum("bhpn,bn->bhp", h, C_t, precision=_HIGHEST) + D.astype(f32)[:, None] * x_t

    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), h


def ssd_chunked(x: Array, dt: Array, A: Array, B: Array, C: Array, D: Array,
                initial_state: tp.Optional[Array] = None, chunk: int = 256) -> tp.Tuple[Array, Array]:
    """The recurrence in chunks of `chunk` tokens (module docstring); shapes as
    `ssd_recurrent`'s. T need not be a multiple of `chunk` (padded with dt = 0,
    which changes nothing). Returns (y (B_, T, H, P) float32, final state)."""
    f32 = jnp.float32
    Bn, T, H, P = x.shape
    N, Q = B.shape[-1], chunk
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C))
    nc = (T + pad) // Q
    mm = x.dtype  # what the matrix products multiply in
    prec = _HIGHEST if mm == f32 else None
    dt = dt.astype(f32)
    a = dt * A.astype(f32)  # (B_, T, H)
    by_chunk = lambda v: jnp.moveaxis(v.reshape(Bn, nc, Q, *v.shape[2:]), 1, 0)
    keep = jnp.tril(jnp.ones((Q, Q), bool))
    h0 = jnp.zeros((Bn, H, P, N), f32) if initial_state is None else initial_state.astype(f32)

    def one_chunk(h, c):
        x_c, dt_c, a_c, B_c, C_c = c  # (B_, Q, H, P), (B_, Q, H), (B_, Q, H), (B_, Q, N), (B_, Q, N)
        s = jnp.cumsum(a_c, axis=1)  # (B_, Q, H), inclusive
        s_h = jnp.moveaxis(s, 1, 2)  # (B_, H, Q)
        L = jnp.exp(jnp.where(keep, s_h[..., :, None] - s_h[..., None, :], -jnp.inf))  # (B_, H, Q, Q)
        cb = jnp.einsum("btn,bjn->btj", C_c, B_c, preferred_element_type=f32, precision=prec)  # ONE tile for all heads
        dtx = (dt_c[..., None] * x_c.astype(f32)).astype(mm)  # (B_, Q, H, P)
        y = jnp.einsum("bhtj,bjhp->bthp", (L * cb[:, None]).astype(mm), dtx, preferred_element_type=f32, precision=prec)
        seen = jnp.einsum("btn,bhpn->bthp", C_c.astype(f32), h, precision=_HIGHEST)  # C h_0^T, float32 like the state
        y = y + jnp.exp(s)[..., None] * seen
        to_end = jnp.exp(s[:, -1:, :] - s)  # (B_, Q, H): exp(s_Q - s_j) <= 1
        wrote = jnp.einsum("bjhp,bjn->bhpn", (to_end[..., None] * dtx.astype(f32)).astype(mm), B_c,
                           preferred_element_type=f32, precision=prec)
        return jnp.exp(s[:, -1, :])[..., None, None] * h + wrote, y

    h, y = jax.lax.scan(one_chunk, h0, tuple(by_chunk(v) for v in (x, dt, a, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(Bn, nc * Q, H, P)[:, :T]
    return y + D.astype(f32)[:, None] * x[:, :T].astype(f32), h
