"""Pallas TPU kernels for chunked Kimi Delta Attention: one call forward, one
backward (a `jax.custom_vjp`), the mathematics of `ops/kda.py`'s docstring
with the same constants and the same precisions as its jnp body.

A grid step is one chunk of `HEADS_PER_STEP` heads of one sequence; the chunk
axis is the sequential ("arbitrary") one. A chunk's q, k, v, g, beta, the
running sum G of g down the chunk, every intermediate of the state-independent
terms (decay tiles, A, B, the block solve, W_v, W_k) and the float32 state live
in VMEM; HBM sees the inputs, `o`, the state at every chunk's start (the
residual of the backward pass: T / C states a head, what the jnp body's
checkpointed scan keeps), the final state and the cotangents. The state comes
IN as an operand (zeros where `ops/kda.py` `kda_chunked` is given none: the
training path), so a chunked-prefill step starts from a slot's state and hands
the next chunk's back (models/olmo_hybrid.py). d_k and d_v are the operands'
own (128 x 128 in Kimi-Linear, 96 x 192 in Olmo-Hybrid: blocks as wide as the
arrays, nothing padded), and a head count that four does not divide takes two
or one a step (`_dims`).

Layout: q, k, v, g travel head-major, (B, H, T, d): a chunk of a head is one
contiguous block, and the two transposes around the call are the compiler's to
fold into the layouts of what feeds and reads them. beta and dbeta are
(B, H, N, 1, C) rows. The state is held TRANSPOSED, (d_v, d_k): its decay
Diag(e^{G_C}) S is then a row broadcast, and its three products are the
contractions the MXU takes directly (NT, NT, TN).

Every value in a kernel body has the step's heads as its leading axis and every
operation takes them all at once. That is what the time is made of: a chunk's
solve is a chain of 13 small float32 products, each waiting ~0.1 us for the one
before, and a head alone leaves the units idle between them (v5e, B=1,
T=8,192, 32 heads of 128: forward 11.2 ms a layer with one head a step, 7.7
with two, 6.1 with four, 5.8 with sixteen, which needs a raised VMEM limit; four
fit the default one: PERF.md section 6, PR 42).

Forward, a chunk: G by shifted adds (`_running_sum`). The products between
sub-blocks are one matmul a sub-block row in q's dtype, the decay split at the
later block's start so that no factor exceeds 1; inside a sub-block the 16
columns are formed one at a time, each a (16, d_k) tile e^{min(G_r - G_i, 0)}
k_i times the k- and q-rows and a lane reduce, in float32 on the VPU: no
factor e^{-G_i} anywhere. The solve is the jnp body's: the finite Neumann
product of the diagonal blocks (the four of them side by side against their
block-diagonal (C, C) form, exact zeros elsewhere), then forward substitution
over the block rows, float32 at `highest`.

Backward, a chunk (chunks walked in reverse, dS carried in VMEM): the terms are
recomputed from the inputs and the saved chunk-start state, then transposed by
hand: the products with the state, the solve (block BACK substitution with the
same diagonal inverses, transposed), the rows and columns of A and B, and the
running sum (dg is the sum of dG UP the chunk). One term of the plain
reverse-mode program is not formed: the cotangent of the split point R_a,
whose two halves cancel exactly (the product e^{G_r - R_a} e^{R_a - G_i} does
not depend on R_a). A cotangent that meets an operand of q's dtype in a matmul
is rounded to that dtype first, which is what the TPU's default precision does
to the float32 cotangent in the jnp body's backward.

Off the TPU the kernels run in Pallas interpret mode (tests/test_kimi_linear.py);
`ops/kda.py` calls them only on the TPU.
"""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from midgpt_tpu.utils.stack_chunk import call_on_own_chunk

Array = jax.Array
_F32 = jnp.float32
_COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a: Array, b: Array, contract: tp.Tuple[int, int]) -> Array:
    """a . b over (a's dim, b's dim) of the last two, batched over the leading
    (heads) axis, float32 out; `highest` for float32 operands, the MXU's native
    product for bf16 (said per call: a process-wide default of `highest` must
    not reach a bf16 product)."""
    precision = jax.lax.Precision.HIGHEST if a.dtype == _F32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        a, b, (((1 + contract[0],), (1 + contract[1],)), ((0,), (0,))), precision=precision,
        preferred_element_type=_F32)


_nn = functools.partial(_dot, contract=(1, 0))  # (h, m, k) (h, k, n)
_nt = functools.partial(_dot, contract=(1, 1))  # (h, m, k) (h, n, k)
_tn = functools.partial(_dot, contract=(0, 0))  # (h, k, m) (h, k, n)


def _iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _eye(n: int) -> Array:
    return _iota2((n, n), 0) == _iota2((n, n), 1)


def _row_to_col(row: Array) -> Array:
    """(h, 1, n) -> (h, n, 1) without a transpose: a masked lane reduce."""
    return jnp.sum(jnp.where(_eye(row.shape[-1]), row, 0.0), axis=-1, keepdims=True)


def _col_to_row(col: Array) -> Array:
    return jnp.sum(jnp.where(_eye(col.shape[-2]), col, 0.0), axis=-2, keepdims=True)


def _rows(x: Array, a: int, sub: int, n: int = 1) -> Array:
    """Rows of sub-blocks a .. a + n - 1."""
    return x[:, a * sub : (a + n) * sub]


def _stack(blocks: tp.Sequence[Array]) -> Array:
    return jnp.concatenate(blocks, axis=1)


def _shifted_sums(x: Array, reverse: bool = False) -> Array:
    """Inclusive sums down the rows (up them if `reverse`): log2(C) shifted adds."""
    C = x.shape[1]
    r = _iota2(x.shape, 1)
    s = 1
    while s < C:
        moved = pltpu.roll(x, C - s if reverse else s, axis=1)  # row r receives row r + s if reverse, else r - s
        x = x + jnp.where(r < C - s if reverse else r >= s, moved, 0.0)
        s *= 2
    return x


def _running_sum(g: Array) -> Array:
    """G_r = sum_{j <= r} g_j down the chunk, by shifted adds, refined once: a
    tree of adds leaves G_r - G_{r-1} off g_r by a few ulps of G, and the decay
    between NEAR tokens is made of exactly such differences, so what the first
    pass's differences miss of g is summed the same way and taken off. As good
    as the sum taken row by row, in 12 vector steps and not 63 row steps."""
    G = _shifted_sums(g)
    before = jnp.where(_iota2(g.shape, 1) >= 1, pltpu.roll(G, 1, axis=1), 0.0)
    return G - _shifted_sums((G - before) - g)


class _Terms(tp.NamedTuple):
    """A chunk's state-independent terms, all float32 (cast where they meet a
    matmul), every one with the grid step's heads as its leading axis."""
    A: Array  # (C, C) strictly lower
    Bm: Array  # (C, C) lower
    L: Array  # beta A
    inv: Array  # (sub, C): the inverses of I + L's diagonal blocks, side by side
    R0: Array  # [V | K e^G]
    W: Array  # [W_v | W_k]
    eG: Array
    Qg: Array
    eD: Array  # e^{G_C - G}
    Kd: Array
    gC: Array  # (1, d_k)


def _between(q, k, G, a, sub):
    """Sub-block row `a` >= 1 against every earlier column: the row factors
    (k- then q-rows, decayed from the block's start), the column factors
    (decayed up to it) and e^{G_r - R_a}."""
    R = G[:, a * sub - 1 : a * sub]
    e_row = jnp.exp(_rows(G, a, sub) - R)
    rowf = _stack([_rows(k, a, sub) * e_row, _rows(q, a, sub) * e_row])
    e_col = jnp.exp(jnp.minimum(R - G, 0.0))
    return rowf, k * e_col, e_row, e_col


def _chunk_terms(q, k, v, G, beta, k_row, G_row, *, sub: int, mm) -> _Terms:
    """q, k, G (h, C, d_k), v (h, C, d_v), beta (h, C, 1), float32 values of
    the step's h heads; k_row(i) and G_row(i) give row i as (h, 1, d_k). Every
    operation takes all the heads at once, so that one head's chain of
    dependent products (the solve: 13 of them) is interleaved with the others'."""
    C = k.shape[1]
    ns = C // sub
    row, col = _iota2((C, C), 0), _iota2((C, C), 1)
    lane = _iota2((sub, C), 1)
    lane2 = _iota2((2 * sub, C), 1)
    A_rows, B_rows = [], []
    for a in range(ns):
        Ga, ka, qa = (_rows(x, a, sub) for x in (G, k, q))
        if a == 0:
            accA = accB = jnp.zeros((k.shape[0], sub, C), _F32)
        else:
            rowf, colf, _, _ = _between(q, k, G, a, sub)
            cross = jnp.where(lane2 < a * sub, _nt(rowf.astype(mm), colf.astype(mm)), 0.0)
            accA, accB = cross[:, :sub], cross[:, sub:]
        for j in range(sub):  # the diagonal tile, a column at a time
            i = a * sub + j
            t = jnp.exp(jnp.minimum(Ga - G_row(i), 0.0)) * k_row(i)
            accA = jnp.where(lane == i, jnp.sum(ka * t, axis=-1, keepdims=True), accA)
            accB = jnp.where(lane == i, jnp.sum(qa * t, axis=-1, keepdims=True), accB)
        A_rows.append(accA)
        B_rows.append(accB)
    A = jnp.where(row > col, _stack(A_rows), 0.0)
    Bm = jnp.where(row >= col, _stack(B_rows), 0.0)
    # [W_v | W_k] = (I + Diag(beta) A)^{-1} Diag(beta) [V | K e^G]
    L = beta * A
    same = (row // sub) == (col // sub)
    # the ns diagonal blocks side by side, (sub, C), and back to block-diagonal (C, C)
    side = lambda bd: functools.reduce(jnp.add, [_rows(bd, b, sub) for b in range(ns)])
    blockdiag = lambda sd: jnp.where(same, _stack([sd] * ns), 0.0)
    eye = jnp.where(row == col, 1.0, 0.0).astype(_F32)
    power, order = side(jnp.where(same, L, 0.0)), 2
    inv = jnp.where(lane % sub == _iota2((sub, C), 0), 1.0, 0.0).astype(_F32) - power
    while order < sub:
        power = _nn(power, blockdiag(power))
        inv = _nn(inv, eye + blockdiag(power))
        order *= 2
    eG = jnp.exp(G)
    R0 = jnp.concatenate([v, k * eG], axis=2)
    rhs = beta * R0
    X: tp.List[Array] = []
    for a in range(ns):
        t = _rows(rhs, a, sub)
        if a:
            t = t - _nn(_rows(L, a, sub)[:, :, : a * sub], _stack(X))
        X.append(_nn(inv[:, :, a * sub : (a + 1) * sub], t))
    W = _stack(X)
    G_last = G_row(C - 1)
    eD = jnp.exp(G_last - G)
    return _Terms(A, Bm, L, inv, R0, W, eG, q * eG, eD, k * eD, jnp.exp(G_last))


def _written(t: _Terms, Sm: Array, dv: int) -> Array:
    """U = W_v - W_k S_0, what the chunk's tokens write, in the dtype of `Sm`
    (the chunk-start states, transposed, (h, d_v, d_k), in the matmuls' dtype)."""
    return (t.W[:, :, :dv] - _nt(t.W[:, :, dv:].astype(Sm.dtype), Sm)).astype(Sm.dtype)


def _load(q_ref, k_ref, v_ref, g_ref, b_ref, k32, G32):
    """The step's operands as float32 values, G = the running sum of g down
    the chunk, and row readers for k and G (from VMEM scratch: a row load is
    cheaper than a row cut out of a value)."""
    k32[...] = k_ref[...].astype(_F32)
    G32[...] = _running_sum(g_ref[...])
    return (q_ref[...].astype(_F32), k32[...], v_ref[...].astype(_F32), G32[...], _row_to_col(b_ref[...]),
            lambda i: k32[:, i : i + 1, :], lambda i: G32[:, i : i + 1, :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, st_ref, sf_ref, S, k32, G32, *, sub):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        S[...] = s0_ref[...]

    mm = q_ref.dtype
    ST = S[...]
    st_ref[...] = ST
    q, k, v, G, beta, k_row, G_row = _load(q_ref, k_ref, v_ref, g_ref, b_ref, k32, G32)
    t = _chunk_terms(q, k, v, G, beta, k_row, G_row, sub=sub, mm=mm)
    Sm = ST.astype(mm)
    Um = _written(t, Sm, v.shape[2])
    o_ref[...] = (_nt(t.Qg.astype(mm), Sm) + _nn(t.Bm.astype(mm), Um)).astype(o_ref.dtype)
    ST1 = t.gC * ST + _tn(Um, t.Kd.astype(mm))
    S[...] = ST1

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        sf_ref[...] = ST1


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref, dsf_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds0_ref, dS, k32, G32, *, sub):
    c = pl.program_id(2)  # the chunks arrive last first (the index maps reverse them)

    @pl.when(c == 0)
    def _():
        dS[...] = dsf_ref[...]

    mm = q_ref.dtype
    q, k, v, G, beta, k_row, G_row = _load(q_ref, k_ref, v_ref, g_ref, b_ref, k32, G32)
    _, C, dvw = v.shape
    ns = C // sub
    t = _chunk_terms(q, k, v, G, beta, k_row, G_row, sub=sub, mm=mm)
    ST = st_ref[...]
    Sm = ST.astype(mm)
    Um = _written(t, Sm, dvw)
    row, col = _iota2((C, C), 0), _iota2((C, C), 1)

    # -- the products with the state
    dST1 = dS[...]
    dS1m, dom = dST1.astype(mm), do_ref[...].astype(mm)
    Wkm, Qgm, Bmm, Kdm = (x.astype(mm) for x in (t.W[:, :, dvw:], t.Qg, t.Bm, t.Kd))
    dgC = jnp.sum(dST1 * ST, axis=1, keepdims=True)
    dKd = _nn(Um, dS1m)
    dU = _nt(Kdm, dS1m) + _tn(Bmm, dom)
    dQg = _nn(dom, Sm)
    dBm = jnp.where(row >= col, _nt(dom, Um), 0.0)
    dUm = dU.astype(mm)
    dWk = -_nn(dUm, Sm)
    dS[...] = t.gC * dST1 + _tn(dom, Qgm) - _tn(dUm, Wkm)

    # -- the solve: Y = (I + L)^{-T} dW by block back substitution
    dW = jnp.concatenate([dU, dWk], axis=2)
    Y: tp.List[Array] = []  # block rows ns - 1 down to a + 1, last first
    for a in reversed(range(ns)):
        cols = slice(a * sub, (a + 1) * sub)
        y = _rows(dW, a, sub)
        if Y:
            y = y - _tn(_rows(t.L, a + 1, sub, ns - 1 - a)[:, :, cols], _stack(Y[::-1]))
        Y.append(_tn(t.inv[:, :, cols], y))
    Yf = _stack(Y[::-1])
    dL = -jnp.where(row > col, _nt(Yf, t.W), 0.0)
    dbeta = jnp.sum(Yf * t.R0, axis=2, keepdims=True) + jnp.sum(dL * t.A, axis=2, keepdims=True)
    db_ref[...] = _col_to_row(dbeta)
    dA = beta * dL
    dR0 = beta * Yf
    dv_ref[...] = dR0[:, :, :dvw].astype(dv_ref.dtype)
    dkeG = dR0[:, :, dvw:]

    # -- the elementwise terms: K e^G, Q e^G, K e^{G_C - G}, e^{G_C}
    tmp = dKd * t.Kd
    dGl = jnp.sum(tmp, axis=1, keepdims=True) + dgC * t.gC
    dk = dkeG * t.eG + dKd * t.eD
    dq = dQg * t.eG
    dG = dkeG * (k * t.eG) + dQg * t.Qg - tmp + jnp.where(_iota2(G.shape, 1) == C - 1, dGl, 0.0)

    # -- A and B: between sub-blocks (matmuls), then the diagonal tiles (columns)
    lane2 = _iota2((2 * sub, C), 1)
    subl = _iota2((sub, k.shape[2]), 0)
    dq_rows, dk_rows, dG_rows = [], [], []
    for a in range(ns):
        Ga, ka, qa, dAa, dBa = (_rows(x, a, sub) for x in (G, k, q, dA, dBm))
        if a == 0:
            dka = dqa = dGa = jnp.zeros_like(ka)
        else:
            rowf, colf, e_row, e_col = _between(q, k, G, a, sub)
            dcross = jnp.where(lane2 < a * sub, _stack([dAa, dBa]), 0.0).astype(mm)
            d_rowf = _nn(dcross, colf.astype(mm))
            d_colf = _tn(dcross, rowf.astype(mm))
            dka, dqa = d_rowf[:, :sub] * e_row, d_rowf[:, sub:] * e_row
            dGa = d_rowf[:, :sub] * rowf[:, :sub] + d_rowf[:, sub:] * rowf[:, sub:]
            dk = dk + d_colf * e_col
            dG = dG - d_colf * colf
        rk = rg = jnp.zeros_like(ka)  # rows i of the block: what column i's k_i and G_i receive
        for j in range(sub):
            i = a * sub + j
            D = jnp.exp(jnp.minimum(Ga - G_row(i), 0.0))
            td = D * k_row(i)
            a_col, b_col = dAa[:, :, i : i + 1], dBa[:, :, i : i + 1]
            w = a_col * ka + b_col * qa
            dka = dka + a_col * td
            dqa = dqa + b_col * td
            wt = w * td
            dGa = dGa + wt
            rk = jnp.where(subl == j, jnp.sum(w * D, axis=1, keepdims=True), rk)
            rg = jnp.where(subl == j, jnp.sum(wt, axis=1, keepdims=True), rg)
        dq_rows.append(dqa)
        dk_rows.append(dka + rk)
        dG_rows.append(dGa - rg)
    dq_ref[...] = (dq + _stack(dq_rows)).astype(dq_ref.dtype)
    dk_ref[...] = (dk + _stack(dk_rows)).astype(dk_ref.dtype)
    # G is the running sum of g: g_i reaches every G_r, r >= i
    dg_ref[...] = _shifted_sums(dG + _stack(dG_rows), reverse=True)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        ds0_ref[...] = dS[...]


# Heads a grid step (2 or 1 where H does not divide): module docstring.
HEADS_PER_STEP = 4


def _specs(hb: int, N: int, chunk: int, dk: int, dv: int, reverse: bool):
    """Block specs of a (b, h, c) grid step of `hb` heads: their (C, d) blocks
    of a (B, H, T, d) array, their beta rows, their per-chunk states, their
    per-head states."""
    at = (lambda c: N - 1 - c) if reverse else (lambda c: c)
    tok = lambda d: pl.BlockSpec((None, hb, chunk, d), lambda b, h, c: (b, h, at(c), 0))
    beta = pl.BlockSpec((None, hb, None, 1, chunk), lambda b, h, c: (b, h, at(c), 0, 0))
    per_chunk = pl.BlockSpec((None, hb, None, dv, dk), lambda b, h, c: (b, h, at(c), 0, 0))
    per_head = pl.BlockSpec((None, hb, dv, dk), lambda b, h, c: (b, h, 0, 0))
    return tok, beta, per_chunk, per_head


def _dims(q, v, b5):
    B, H, Tp, dk = q.shape
    return B, Tp, H, max(n for n in (1, 2, HEADS_PER_STEP) if H % n == 0), b5.shape[2], b5.shape[4], dk, v.shape[3]


def _as_kda_scan(call):
    """`call` (a `pallas_call`, whose kernel body is traced when it is applied)
    under the scope models/kimi_linear.py opens around the op: the compiler
    names a Mosaic custom call after the innermost scope or jit on its path,
    which would be `_forward` / `_backward` below, and the trace's name
    `kda_scan.<n>` is what benchmarks/metrics/kda_kernel.py finds the kernels
    by. And on a stack chunk of its own: a body is a few thousand jnp calls
    from one depth, which on the benchmark's machines traced in 4.7 and 6.9 s
    from wherever the step program's tracing had left the frame stack, and
    in a fraction of a second here (utils/stack_chunk.py; PERF.md section 6,
    PR 42)."""
    def scoped(*args):
        with jax.named_scope("kda_scan"):
            return call_on_own_chunk(call, *args)

    return scoped


# Jitted, so that every layer of a step program, and the programs beside it,
# share ONE trace of each kernel body (a few seconds of unrolled Python each).
@functools.partial(jax.jit, static_argnames=("sub",))
def _forward(q, k, v, g, b5, s0T, sub):
    B, Tp, H, hb, N, chunk, dk, dv = _dims(q, v, b5)
    tok, beta, per_chunk, per_head = _specs(hb, N, chunk, dk, dv, reverse=False)
    return _as_kda_scan(pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub),
        grid=(B, H // hb, N),
        in_specs=[tok(dk), tok(dk), tok(dv), tok(dk), beta, per_head],
        out_specs=[tok(dv), per_chunk, per_head],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, N, dv, dk), _F32),
            jax.ShapeDtypeStruct((B, H, dv, dk), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32), pltpu.VMEM((hb, chunk, dk), _F32), pltpu.VMEM((hb, chunk, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    ))(q, k, v, g, b5, s0T)


@functools.partial(jax.jit, static_argnames=("sub",))
def _backward(q, k, v, g, b5, states, do, dsfT, sub):
    B, Tp, H, hb, N, chunk, dk, dv = _dims(q, v, b5)
    tok, beta, per_chunk, per_head = _specs(hb, N, chunk, dk, dv, reverse=True)
    return _as_kda_scan(pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub),
        grid=(B, H // hb, N),
        in_specs=[tok(dk), tok(dk), tok(dv), tok(dk), beta, per_chunk, tok(dv), per_head],
        out_specs=[tok(dk), tok(dk), tok(dv), tok(dk), beta, per_head],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct(b5.shape, _F32),
            jax.ShapeDtypeStruct((B, H, dv, dk), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32), pltpu.VMEM((hb, chunk, dk), _F32), pltpu.VMEM((hb, chunk, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    ))(q, k, v, g, b5, states, do, dsfT)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda(q, k, v, g, b5, s0T, sub):
    o, _, sfT = _forward(q, k, v, g, b5, s0T, sub)
    return o, sfT


def _kda_fwd(q, k, v, g, b5, s0T, sub):
    o, states, sfT = _forward(q, k, v, g, b5, s0T, sub)
    return (o, sfT), (q, k, v, g, b5, states)


def _kda_bwd(sub, res, cts):
    do, dsfT = cts
    return tuple(_backward(*res, do, dsfT, sub))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_scan(
    q: Array, k: Array, v: Array, g: Array, beta: Array, initial_state: tp.Optional[Array] = None,
    *, chunk: int, sub: int,
) -> tp.Tuple[Array, Array]:
    """`ops/kda.py`'s `kda_chunked` through the kernels. q, k, g (B, T, H,
    d_k); v (B, T, H, d_v); beta (B, T, H); `initial_state` (B, H, d_v, d_k)
    float32, zeros if None. Returns (o (B, T, H, d_v) in v's dtype, final
    state (B, H, d_v, d_k) float32): the state as the kernels hold it, in and
    out, and no transpose is made."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    N = -(-T // chunk)
    pad = N * chunk - T  # zero rows (no decay, no write) change nothing

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) if pad else a

    # head-major: a chunk of one head is then one contiguous block, and the
    # transposes are the compiler's to fold into the layouts of what feeds them
    q, k, v, g = (jnp.swapaxes(padded(a), 1, 2) for a in (q, k, v, g.astype(_F32)))
    b5 = jnp.swapaxes(padded(beta.astype(_F32)), 1, 2).reshape(B, H, N, 1, chunk)
    s0T = jnp.zeros((B, H, dv, dk), _F32) if initial_state is None else initial_state.astype(_F32)
    o, sfT = _kda(q, k, v, g, b5, s0T, sub)
    return jnp.swapaxes(o, 1, 2)[:, :T], sfT
