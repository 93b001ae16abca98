"""Kimi Delta Attention (KDA): the gated delta-rule recurrence, chunked.

Per head, with a float32 state S of (d_k, d_v), a per-CHANNEL log decay
g_t <= 0 of (d_k,) and a write strength beta_t (in (0, 1) as Kimi-Linear
publishes it; Gated DeltaNet with `allow_neg_eigval` doubles it to (0, 2), and
nothing below asks for less than 2: the transition I - beta k k^T of a unit key
then has eigenvalues in (-1, 1)):

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

`kda_recurrent` is that, token by token (`lax.scan` over T): the test oracle,
and nothing on the training path calls it. `kda_step` is ONE token of it, the
serving path's decode step over every slot's state. `kda_chunked` computes the
same in chunks of C tokens, from `initial_state` (zeros if None) to the final
state it returns: training passes none and drops the result, a chunked prefill
passes a slot's state in and keeps what comes back, one function and one oracle.
A state that is CARRIED (into and out of `kda_step`, `kda_chunked` and the
kernels) has one layout, S^T: (B, H, d_v, d_k), the one the kernels hold it in,
so a serving pool stores its rows so and nothing transposes a slot's state at
either end of a chunk (stored (d_k, d_v) the chip's compiler relaid the WHOLE
pool of rows out around the prefill program: PERF.md section 6 PR 59). Only the
oracle keeps the equations' (d_k, d_v); its tests transpose once.
A gate that is ONE scalar a head and token (Gated DeltaNet) is the per-channel
gate broadcast: g of (B, T, H) is taken as such (`_per_channel`). d_k and d_v
need not be equal (96 x 192 there, 128 x 128 in Kimi-Linear).

Inside a chunk, with G_r = sum_{j<=r} g_j (so every decay
between two positions of the chunk is exp(G_r - G_i) <= 1) and S_0 the state
at the chunk's start, each token writes a rank-one term k_r u_r^T:

    u_r = beta_r (v_r - S_0^T (k_r * e^{G_r}) - sum_{i<r} A_ri u_i),
    A_ri = sum_c k_r[c] k_i[c] e^{G_r[c] - G_i[c]}
    => U = W_v - W_k S_0,   [W_v | W_k] = (I + Diag(beta) tril(A,-1))^{-1} Diag(beta) [V | K * e^G]
    O   = (Q * e^G) S_0 + tril(B) U,   B_ri = sum_c q_r[c] k_i[c] e^{G_r[c] - G_i[c]}
    S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U

Everything but the three products with S_0 is independent of the state, so it
is computed for all chunks at once, as matmuls; the `lax.scan` over chunks
carries only S, in float32, and does three matmuls a chunk.

Decay without overflow: A and B are NOT factored as (k_r e^{G_r}) . (k_i
e^{-G_i}): e^{-G_i} overflows float32 once a channel has decayed by e^-88
inside a chunk, which the published initial range of `A_log` and `dt_bias`
reaches in 64 tokens. A chunk is cut into sub-blocks of `sub` tokens. Between
two sub-blocks the decay is split at the later block's start R_a,
e^{G_r - R_a} * e^{R_a - G_i}, both factors <= 1, and the product over the
channels is a matmul. Inside one sub-block the (sub, sub, d_k) tensor of
e^{G_r - G_i} is formed directly.

What runs where: on the TPU `kda_chunked` is `kernels/kda.py`, one Pallas call
forward and one backward in which a chunk's terms and the state stay in VMEM;
on any other backend it is the jnp body below, the readable statement of the
algorithm between `kda_recurrent` and the kernels (same equations, same
`CHUNK` and `SUB`, same precisions; tests/test_kimi_linear.py holds both to
the recurrence). Which one is the backend's to say: there is no option.

Backward of the jnp body: plain reverse-mode AD of the above. The
state-independent part of each chunk and the scan's body are
`jax.checkpoint`ed, so the backward pass keeps one state per CHUNK (T / C of
them), never one per token, and recomputes a chunk's (sub, sub, d_k) tensors
when it reaches the chunk. The kernels keep the same residual.
"""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp

Array = jax.Array
_HIGHEST = jax.lax.Precision.HIGHEST
# Tokens a chunk and tokens a sub-block, shared by the kernels and the jnp body;
# and, for the jnp body alone, chunks whose state-independent terms are
# prepared together (a `lax.map` batch: it bounds what its backward pass
# recomputes at once). With the jnp body on the v5e (B=1, T=8,192, 32 heads of
# 128, forward + backward 72.2 ms a layer) no other setting moved the time by
# more than 6 %, most for the worse (PERF.md §6, PR 26): constants, not options.
CHUNK, SUB, CHUNKS_PER_BATCH = 64, 16, 8
assert CHUNK % SUB == 0


def causal_depthwise_conv(x: Array, taps: Array) -> Array:
    """y_t[c] = sum_j taps[c, j] * x_{t-(K-1)+j}[c]: a causal convolution over
    time of K taps on each channel alone (x (B, T, C), taps (C, K); the LAST
    tap multiplies the current token, as in a `Conv1d` with left padding)."""
    K = taps.shape[-1]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j : j + T] * taps[:, j] for j in range(K))


def _per_channel(g: Array, k: Array) -> Array:
    """The log decay as (..., d_k) float32: a gate of k's shape as it is, one
    scalar a head (k's shape less its last dim) broadcast over the channels."""
    g = g.astype(jnp.float32)
    return g if g.ndim == k.ndim else jnp.broadcast_to(g[..., None], k.shape)


def kda_step(q: Array, k: Array, v: Array, g: Array, beta: Array, state: Array) -> tp.Tuple[Array, Array]:
    """ONE token of the recurrence, the module docstring's three lines. q, k
    (B, H, d_k); v (B, H, d_v); g (B, H, d_k) or one scalar a head (B, H); beta
    (B, H); `state` (B, H, d_v, d_k) float32, a carried state's one layout
    (module docstring). Everything in float32, elementwise products and sums
    over d_k (no matmul: a step is bound by the state it reads and writes, 2 x
    4 x d_k x d_v bytes a head). Returns (o (B, H, d_v) float32, the next
    state (B, H, d_v, d_k))."""
    f32 = jnp.float32
    q, k, v, beta = (a.astype(f32) for a in (q, k, v, beta))
    of_k = lambda a: a[..., None, :]  # a (.., d_k) vector against the state's rows
    S, decay = state.astype(f32), jnp.exp(_per_channel(g, k))
    # Both sums over d_k are taken of the state AS IT CAME (one sweep reads it for both), the decay folded into the
    # vectors: S'^T k = S^T (e^g k), and o = S_t^T q = S^T (e^g q) + beta (k . q) (v - S'^T k). With the write below
    # the state is read twice and written once a step; summed after the write it would be read a third time.
    pred = jnp.sum(S * of_k(decay * k), axis=-1)
    seen = jnp.sum(S * of_k(decay * q), axis=-1)
    wrote = v - pred
    S = of_k(decay) * S + of_k(beta[..., None] * k) * wrote[..., None]
    return seen + (beta * jnp.sum(k * q, axis=-1))[..., None] * wrote, S


def kda_recurrent(
    q: Array, k: Array, v: Array, g: Array, beta: Array, initial_state: tp.Optional[Array] = None
) -> tp.Tuple[Array, Array]:
    """Token-by-token oracle. q, k (B, T, H, d_k); g the same or (B, T, H); v
    (B, T, H, d_v); beta (B, T, H); `initial_state` (B, H, d_k, d_v), zeros if
    None. Returns (o (B, T, H, d_v) float32, final state (B, H, d_k, d_v))."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, _per_channel(g, k), beta))
    B, T, H, dk = k.shape
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32) if initial_state is None else initial_state.astype(f32)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x  # (B, H, d), beta (B, H)
        S = jnp.exp(g_t)[..., None] * S
        pred = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HIGHEST)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - pred, precision=_HIGHEST)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


def _unit_lower_solve(L: Array, rhs: Array, sub: int) -> Array:
    """X with (I + L) X = rhs, L (..., C, C) STRICTLY lower triangular, rhs
    (..., C, n), by blocks of `sub` rows, as matmuls: each diagonal block's
    inverse is the finite Neumann product (I - N)(I + N^2)(I + N^4)... (N is
    nilpotent of order `sub`), then forward substitution over the C / sub block
    rows. Exact in exact arithmetic, float32 at `highest` here. (XLA's
    triangular solve on these (64, 64) systems took 22 % of the v5e's step,
    PERF.md §6 PR 26. The Neumann product over the WHOLE chunk would be one
    step shorter and is not used: its powers grow as C(C, j) |L|^j before they
    vanish, which float32 does not survive at C = 64 once the keys correlate.)"""
    C = L.shape[-1]
    ns = C // sub
    lead = L.shape[:-2]
    Lb = L.reshape(*lead, ns, sub, ns, sub)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    N = jnp.stack([Lb[..., a, :, a, :] for a in range(ns)], axis=-3)  # (..., ns, sub, sub) diagonal blocks
    eye = jnp.eye(sub, dtype=L.dtype)
    inv, power, order = eye - N, N, 2
    while order < sub:
        power = mm(power, power)
        inv = mm(inv, eye + power)
        order *= 2
    R = rhs.reshape(*lead, ns, sub, rhs.shape[-1])
    X = []
    for a in range(ns):
        r = R[..., a, :, :]
        for b in range(a):
            r = r - mm(Lb[..., a, :, b, :], X[b])
        X.append(mm(inv[..., a, :, :], r))
    return jnp.concatenate(X, axis=-2)


def _chunk_terms(q: Array, k: Array, v: Array, g: Array, beta: Array, *, sub: int, out_dtype):
    """The state-independent part of ONE chunk. q, k, g (..., C, d_k), v
    (..., C, d_v), beta (..., C), all float32. Returns W_v, W_k, Q e^G,
    tril(B), K e^{G_C - G}, e^{G_C}: the last four and W_k in `out_dtype`
    (they only feed matmuls), W_v in float32."""
    C, dk = k.shape[-2:]
    ns = C // sub
    lead = k.shape[:-2]
    G = jnp.cumsum(g, axis=-2)  # (..., C, dk), decreasing along C
    Gb = G.reshape(*lead, ns, sub, dk)
    # R_a: G just before sub-block a (0 for the first)
    R = jnp.concatenate([jnp.zeros_like(Gb[..., :1, 0, :]), Gb[..., :-1, -1, :]], axis=-2)  # (..., ns, dk)
    rows = jnp.stack([k, q], axis=-3).reshape(*lead, 2, ns, sub, dk)  # k-rows give A, q-rows give B
    # -- between sub-blocks: (row_r e^{G_r - R_a}) . (k_i e^{R_a - G_i}), i before block a
    row_f = rows * jnp.exp(Gb - R[..., None, :])[..., None, :, :, :]
    col_f = k[..., None, :, :] * jnp.exp(jnp.minimum(R[..., None, :] - G[..., None, :, :], 0.0))  # (..., ns, C, dk)
    # in `out_dtype` with float32 accumulation, like every product against the state
    cross = jnp.einsum(
        "...xard,...aid->...xari", row_f.astype(out_dtype), col_f.astype(out_dtype),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )  # (..., 2, ns, sub, C)
    blk = jnp.arange(C) // sub
    cross = jnp.where(blk[None, None, :] < jnp.arange(ns)[:, None, None], cross, 0.0)
    # -- inside a sub-block: the (sub, sub, dk) decay tensor, directly
    kb = k.reshape(*lead, ns, sub, dk)
    decay = jnp.exp(jnp.minimum(Gb[..., :, None, :] - Gb[..., None, :, :], 0.0))  # (..., ns, sub, sub, dk)
    diag = jnp.einsum("...xard,...aid,...arid->...xari", rows, kb, decay, precision=_HIGHEST)
    eye = jnp.eye(ns, dtype=diag.dtype)
    full = cross.reshape(*lead, 2, ns, sub, ns, sub) + diag[..., :, :, None, :] * eye[:, None, :, None]
    full = full.reshape(*lead, 2, C, C)
    r_i = jnp.arange(C)
    A = jnp.where(r_i[:, None] > r_i[None, :], full[..., 0, :, :], 0.0)
    Bm = jnp.where(r_i[:, None] >= r_i[None, :], full[..., 1, :, :], 0.0)
    # -- [W_v | W_k] = (I + Diag(beta) A)^{-1} Diag(beta) [V | K e^G]
    eG = jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([v, k * eG], axis=-1)
    W = _unit_lower_solve(beta[..., None] * A, rhs, sub)
    dv = v.shape[-1]
    G_last = G[..., -1:, :]
    return (
        W[..., :dv],
        W[..., dv:].astype(out_dtype),
        (q * eG).astype(out_dtype),
        Bm.astype(out_dtype),
        (k * jnp.exp(G_last - G)).astype(out_dtype),
        jnp.exp(G_last[..., 0, :]),
    )


def kda_chunked(
    q: Array, k: Array, v: Array, g: Array, beta: Array, initial_state: tp.Optional[Array] = None
) -> tp.Tuple[Array, Array]:
    """Chunked KDA. q, k (B, T, H, d_k); g the same or one scalar a head (B, T,
    H); v (B, T, H, d_v); beta (B, T, H); `initial_state` (B, H, d_v, d_k),
    zeros if None. Returns (o (B, T, H, d_v) in v's dtype, final state (B, H,
    d_v, d_k) f32). A row with g = 0, beta = 0 leaves the state as it is (no
    decay, no write): how a prefill chunk's rows past `n_valid` are masked.

    The matmuls against the state, and the decayed q-k and k-k products
    between sub-blocks, run in q's dtype with float32 accumulation (bf16 on the
    training path: the MXU's native product; float32 in a float32 forward); the
    state itself, the decays, the products inside a sub-block, the triangular
    solve and U are float32 always. On the TPU the Pallas kernels compute it
    (module docstring), elsewhere `kda_chunked_jnp`."""
    if jax.default_backend() == "tpu":
        from midgpt_tpu.kernels.kda import kda_scan

        return kda_scan(q, k, v, _per_channel(g, k), beta, initial_state, chunk=CHUNK, sub=SUB)
    return kda_chunked_jnp(q, k, v, g, beta, initial_state)


def kda_chunked_jnp(
    q: Array, k: Array, v: Array, g: Array, beta: Array, initial_state: tp.Optional[Array] = None
) -> tp.Tuple[Array, Array]:
    """`kda_chunked` as plain jnp: the terms of `CHUNKS_PER_BATCH` chunks at a
    time under `lax.map`, then a `lax.scan` over the chunks (which carries the
    state as the docstring's equations write it, (d_k, d_v): the carried
    layout is taken and given at the two ends)."""
    chunk, sub = CHUNK, SUB
    g = _per_channel(g, k)
    f32, mm = jnp.float32, q.dtype
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    N = -(-T // chunk)
    pad = N * chunk - T

    def to_chunks(a):  # (B, T, H, ...) -> (N, B, H, C, ...); zero rows change nothing
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, N, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 2, 3), 1, 0)

    xs = tuple(to_chunks(a) for a in (q, k, v, g.astype(f32), beta.astype(f32)[..., None]))
    terms = jax.checkpoint(  # q, k, v travel in their own dtype and become float32 a batch of chunks at a time
        lambda q_, k_, v_, g_, b_: _chunk_terms(
            q_.astype(f32), k_.astype(f32), v_.astype(f32), g_, b_[..., 0], sub=sub, out_dtype=mm)
    )
    Wv, Wk, Qg, Bm, Kd, gC = jax.lax.map(
        lambda x: terms(*x), xs, batch_size=min(CHUNKS_PER_BATCH, N)
    )

    dot = functools.partial(jnp.einsum, preferred_element_type=f32)

    @jax.checkpoint
    def step(S, x):
        wv, wk, qg, bm, kd, gc = x
        Sm = S.astype(mm)
        U = wv - dot("bhck,bhkv->bhcv", wk, Sm)
        Um = U.astype(mm)
        o = dot("bhck,bhkv->bhcv", qg, Sm) + dot("bhci,bhiv->bhcv", bm, Um)
        S = gc[..., None] * S + dot("bhck,bhcv->bhkv", kd, Um)
        return S, o.astype(v.dtype)

    S0 = jnp.zeros((B, H, dk, dv), f32) if initial_state is None else jnp.swapaxes(initial_state, 2, 3).astype(f32)
    S, o = jax.lax.scan(step, S0, (Wv, Wk, Qg, Bm, Kd, gC))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, N * chunk, H, dv)
    return o[:, :T], jnp.swapaxes(S, 2, 3)
