"""Pallas TPU flash attention (causal, FlashAttention-2 style) with custom VJP.

Replaces the reference's materialized T×T attention (reference model.py:71-77)
— the O(T²) memory wall that caps its context at 1024. Two sets of kernels,
chosen by `_tiled(T, block_k)`:

  * One KV block holds the sequence and T <= 1024 (every GPT training
    config; `train_124m`, `train_xl_fsdp4`): the TILED kernels. One grid
    step a head, q/k/v whole in VMEM, and a Python-unrolled walk over the
    square (block_q x block_q) score tiles on and under the causal diagonal;
    a tile wholly above it is never formed, only the diagonal tiles are
    masked. Forward: a direct softmax a row block (no running statistics).
    Backward: ONE fused kernel, dQ, dK and dV from one score/probability
    reconstruction a tile (five products, one exp). `score_tile_share` says
    how much of the matrix that forms: 10/16 at T=1024, tile 256. On the
    v5e, (16, 12, 1024, 64) bf16: forward 0.50 ms and backward 1.10 ms a
    call against 0.76 and 1.43 for the whole matrix (PERF.md, PR 40).
  * Otherwise (T > block_k: `train_kimi_linear_t8k`, T=8192 in blocks of
    512): the MULTI-BLOCK grid kernels. Forward: grid (B*H, n_q, n_k), KV
    innermost; TPU grid steps execute sequentially over the minor
    dimension, so the (m, l, acc) running statistics live in VMEM scratch
    across the KV sweep of each Q tile. Blocks strictly above the causal
    diagonal are predicated off with pl.when; every block that runs is
    masked elementwise. Backward: two kernels — dQ (grid over KV for each
    Q tile) and dK/dV (grid over Q for each KV tile) — each recomputing
    p = exp(s - lse) from the saved log-sum-exp rather than storing T×T
    probabilities (seven products and two exps a block where the fused
    kernel has five and one).

In both, delta = rowsum(dO ⊙ O), the softmax-jacobian correction, is
computed in-kernel from the O / dO tiles already in VMEM (no separate delta
pass, no broadcast side buffers), and lse is stored 8 lanes wide (f32), not
broadcast to a 128-lane buffer — 16x less statistics traffic than a
full-tile store.

Numerics match the reference semantics: QK^T and PV matmuls run on the MXU
in the input dtype (bf16) with float32 accumulation (preferred_element_type),
the softmax/statistics are float32, and the 1/sqrt(C) scale is applied to the
f32 scores exactly as reference model.py:76 does. Masking uses large-negative
finite values (not -inf): the running max starts at M_INIT > MASK, so
exp(MASK - m) underflows to exactly 0 and no NaN-scrubbing selects are needed
in the hot loop.

On non-TPU backends the kernels run in Pallas interpret mode (tests);
numerical parity against the naive path is asserted in tests/test_flash.py.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# Finite stand-ins for -inf (see module docstring), re-exported from the
# canonical home of the shared online-softmax math. Kept as module names
# because the kernel-template/decode/ring modules import them from here
# historically and the kernels below use them directly.
from midgpt_tpu.ops.online_softmax import (  # noqa: E402
    M_INIT,
    MASK,
    finalize,
    online_block,
)
# lane width of the statistics outputs/scratch (min useful; padded to a
# 128-lane tile in VMEM but only these lanes are stored in HBM)
_STATS_LANES = 8

# Grid semantics: batch*heads and Q tiles are independent ("parallel");
# the KV/Q sweep of the reduction is the sequential dimension ("arbitrary").
# Lets Mosaic pipeline/parallelize grid steps instead of running them serially.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

# Run the kernels in interpret mode off-TPU (tests set this; the normal
# dispatcher in ops/attention.py falls back to blockwise instead, because
# interpret mode is orders of magnitude slower than compiled jnp).
RUN_INTERPRET_OFF_TPU = False


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _out_struct(shape, dtype, like: Array) -> jax.ShapeDtypeStruct:
    """A kernel output's type, varying over the same manual mesh axes as
    the input it is computed from: inside a shard_map that checks varying
    axes (the explicit ZeRO-3 body, parallel/shard_map_fsdp.py) pallas_call
    refuses an output that does not say; outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _block_sizes(T: int, block_q: int, block_k: int) -> tp.Tuple[int, int]:
    """Clamp requested block sizes to ones that tile T exactly.

    Requested blocks are honored when they divide T; otherwise the KV block
    widens to the full sequence and the Q block falls back to the KV block
    (the dispatcher-side policy, ops.attention.flash_block_sizes, is only
    reached when its blocks divide T). Deterministic in (T, block_q,
    block_k), so the forward and backward passes of the custom VJP always
    agree. Widened blocks are bounded by the f32 score-tile budget of the
    multi-block kernels (bq*bk <= 1M elements = 4 MB beside their operand
    tiles in the ~16 MB scoped VMEM): past that, an explicit error beats a
    Mosaic compile failure — long indivisible sequences belong on the
    blockwise path."""
    bq = min(block_q, T)
    bk = min(block_k, T)
    if T % bk:
        bk = T
    if T % bq:
        bq = bk
    if bq * bk > 1024 * 1024:
        raise ValueError(
            f"blocks ({bq}, {bk}) for seq len {T} need a {bq}x{bk} f32 "
            "score tile that cannot fit VMEM; pass block sizes that divide "
            "T (or use the blockwise path)"
        )
    return bq, bk


def _tiled(T: int, bk: int) -> bool:
    """One KV block holds the sequence and a head's operands fit VMEM whole:
    the tiled kernels serve it (one grid step a head, square score tiles of
    the Q block's size). Anything else is the multi-block grid path."""
    return bk == T and T <= 1024


def score_tile_share(T: int, block_q: int, block_k: int, causal: bool = True) -> float:
    """Score elements the kernels FORM over T^2, for one call (the forward
    and every backward kernel walk the same tiles). 1.0 = the whole matrix;
    0.5 is the causal limit. The tiled kernels form the square tiles on and
    under the diagonal; the multi-block grid the (bq, bk) blocks its
    `pl.when` lets through."""
    bq, bk = _block_sizes(T, block_q, block_k)
    if not causal:
        return 1.0
    if _tiled(T, bk):
        n = T // bq
        return (n + 1) / (2 * n)
    formed = sum(
        ik * bk <= iq * bq + (bq - 1)
        for iq in range(T // bq) for ik in range(T // bk)
    )
    return formed * bq * bk / (T * T)


def _masked(s: Array, iq, ik, block_q: int, block_k: int) -> Array:
    """Apply the causal mask elementwise, as a straight-line select. The
    multi-block kernels mask every block they run; the tiled kernels know at
    trace time which tiles the diagonal crosses and mask only those."""
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, MASK)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _rows(i: int, tile: int) -> slice:
    """Rows of block i of a tiled kernel's (1, T, C) operand."""
    return slice(i * tile, (i + 1) * tile)


def _score_tile(q_ref, k_ref, i: int, j: int, tile: int, scale: float, causal: bool) -> Array:
    """One (tile, tile) f32 score tile: row block i's queries against key
    block j, both read from their refs here (see `_fwd_kernel_tiled`). Only
    a tile the diagonal crosses pays for the iota / compare / select; a tile
    under it is wholly visible."""
    s = jax.lax.dot_general(
        q_ref[0, _rows(i, tile), :], k_ref[0, _rows(j, tile), :],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale
    return _masked(s, 0, 0, tile, tile) if causal and i == j else s


def _fwd_kernel_tiled(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, tile, causal):
    """T <= 1024 in one KV block: ONE grid step a head, and a Python-unrolled
    walk over the square score tiles on and under the diagonal (all n^2 when
    not causal); a tile wholly above it is never formed. A row block's keys
    are all in VMEM, so its softmax is direct over its own static width: no
    running statistics, no rescale, no scratch.

    Operand blocks are read from their refs where a product uses them, not
    once a row or column and carried: a value held across the unrolled
    tiles is spilled and reloaded, and the backward measured 15 % slower
    that way on the v5e (PERF.md, PR 40).

    causal=False computes full (unmasked) attention: the off-diagonal pair
    of ring attention, where the causal structure is decided per K/V shard
    at the ring level, not per element."""
    n = q_ref.shape[1] // tile
    for i in range(n):
        cols = range(i + 1 if causal else n)
        s = [_score_tile(q_ref, k_ref, i, j, tile, scale, causal) for j in cols]
        m = functools.reduce(jnp.maximum, [jnp.max(sj, axis=-1) for sj in s])
        p = [jnp.exp(sj - m[:, None]) for sj in s]  # masked entries underflow to 0
        l = sum(jnp.sum(pj, axis=-1) for pj in p)
        pv = sum(
            jax.lax.dot_general(
                pj.astype(v_ref.dtype), v_ref[0, _rows(j, tile), :],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            for j, pj in zip(cols, p)
        )
        # every row sees >= 1 key (l >= 1): finalize's guards are no-ops
        out, lse = finalize(m, l, pv, dtype=o_ref.dtype)
        o_ref[0, _rows(i, tile), :] = out
        lse_ref[0, _rows(i, tile), :] = jnp.broadcast_to(
            lse[:, None], (tile, lse_ref.shape[2])
        )


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc, *, scale, block_q, block_k, causal):
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, M_INIT)
        l_sc[:] = jnp.zeros_like(l_sc)

    def _compute():
        q = q_ref[0]  # (block_q, C)
        k = k_ref[0]  # (block_k, C)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k) f32
        if causal:
            s = _masked(s, iq, ik, block_q, block_k)

        # shared online-softmax update (ops/online_softmax.online_block):
        # alpha underflows to 0 at first visit, masked entries' p to 0
        m_new, alpha, p, l_new = online_block(m_sc[:, 0], l_sc[:, 0], s)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_sc[:] = acc_sc[:] * alpha[:, None] + pv
        m_sc[:] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new[:, None], l_sc.shape)

    if causal:
        # causal: KV block strictly above the diagonal contributes nothing
        pl.when(ik * block_k <= iq * block_q + (block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        out, lse = finalize(m_sc[:, 0], l_sc[:, 0], acc_sc[:], dtype=o_ref.dtype)
        o_ref[0] = out
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _flash_forward(
    q: Array, k: Array, v: Array, block_q: int, block_k: int, causal: bool = True
) -> tp.Tuple[Array, Array]:
    B, H, T, C = q.shape
    bq, bk = _block_sizes(T, block_q, block_k)
    scale = 1.0 / math.sqrt(C)
    qf = q.reshape(B * H, T, C)
    kf = k.reshape(B * H, T, C)
    vf = v.reshape(B * H, T, C)
    if _tiled(T, bk):
        kernel = functools.partial(_fwd_kernel_tiled, scale=scale, tile=bq, causal=causal)
        grid = (B * H,)
        bq = bk  # the whole head rides one grid step; the kernel walks its tiles
        idx_q = idx_k = lambda b: (b, 0, 0)
        scratch = []
        params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    else:
        kernel = functools.partial(
            _fwd_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal
        )
        grid = (B * H, T // bq, T // bk)
        idx_q = lambda b, iq, ik: (b, iq, 0)
        idx_k = lambda b, iq, ik: (b, ik, 0)
        scratch = [
            pltpu.VMEM((bq, C), jnp.float32),
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
        ]
        params = _COMPILER_PARAMS

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, C), idx_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, C), idx_k, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, C), idx_k, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, C), idx_q, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, _STATS_LANES), idx_q, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((B * H, T, C), q.dtype, q),
            _out_struct((B * H, T, _STATS_LANES), jnp.float32, q),
        ],
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=_interpret(),
    )(qf, kf, vf)
    return out.reshape(B, H, T, C), lse.reshape(B, H, T, _STATS_LANES)


# ----------------------------------------------------------------------
# backward
# ----------------------------------------------------------------------


def _bwd_kernel_tiled(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, scale, tile, causal,
):
    """Fully-fused backward for T <= 1024 in one KV block: dQ, dK and dV
    from ONE score/probability reconstruction a tile (five products, one
    exp), over the tiles the forward walks. One grid step a head, the
    operands whole in VMEM and read where used (`_fwd_kernel_tiled`); f32
    accumulators, cast once."""
    n = q_ref.shape[1] // tile
    blk = lambda ref, i: ref[0, _rows(i, tile), :]

    def tile_grads(i: int, j: int):
        """(dQ_i, dK_j, dV_j) parts of score tile (i, j)."""
        q, do = blk(q_ref, i), blk(do_ref, i)
        s = _score_tile(q_ref, k_ref, i, j, tile, scale, causal)
        p = jnp.exp(s - blk(lse_ref, i)[:, :1])  # masked entries underflow to 0
        dv = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, blk(v_ref, j), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # delta = rowsum(dO * O) from tiles already in VMEM; the same
        # expression in every tile of a row block, which Mosaic makes once
        delta = jnp.sum(
            blk(o_ref, i).astype(jnp.float32) * do.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq = jax.lax.dot_general(
            ds, blk(k_ref, j), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dq, dk, dv

    dk, dv = [0.0] * n, [0.0] * n
    for i in range(n):
        dq = 0.0
        for j in range(i + 1 if causal else n):
            dq_ij, dk_ij, dv_ij = tile_grads(i, j)
            dq += dq_ij
            dk[j] += dk_ij
            dv[j] += dv_ij
        dq_ref[0, _rows(i, tile), :] = dq.astype(dq_ref.dtype)
    for j in range(n):
        dk_ref[0, _rows(j, tile), :] = dk[j].astype(dk_ref.dtype)
        dv_ref[0, _rows(j, tile), :] = dv[j].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dq_sc, delta_sc,
    *, scale, block_q, block_k, causal,
):
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)
        # delta = rowsum(dO ⊙ O): computed once per Q tile from tiles already
        # in VMEM (no separate pass, no broadcast side buffer)
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        delta = jnp.sum(o * do, axis=-1)  # (block_q,)
        delta_sc[:] = jnp.broadcast_to(delta[:, None], delta_sc.shape)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _masked(s, iq, ik, block_q, block_k)
        lse = lse_ref[0][:, 0]  # (block_q,)
        p = jnp.exp(s - lse[:, None])  # masked entries underflow to 0
        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        ds = p * (dp - delta_sc[:, 0][:, None]) * scale
        dq_sc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ik * block_k <= iq * block_q + (block_q - 1))(_compute)
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref, dk_sc, dv_sc,
    *, scale, block_q, block_k, causal,
):
    ik, iq = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            s = _masked(s, iq, ik, block_q, block_k)
        lse = lse_ref[0][:, 0]
        p = jnp.exp(s - lse[:, None])  # (bq, bk)
        do = do_ref[0]
        dv_sc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, C)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        delta = jnp.sum(
            o_ref[0].astype(jnp.float32) * do.astype(jnp.float32), axis=-1
        )  # (block_q,)
        ds = p * (dp - delta[:, None]) * scale  # (bq, bk)
        dk_sc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, C)

    if causal:
        # causal: only Q blocks at/below the diagonal see this KV block
        pl.when(iq * block_q + (block_q - 1) >= ik * block_k)(_compute)
    else:
        _compute()

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_backward(block_q, block_k, residuals, g, causal=True):
    q, k, v, out, lse = residuals  # q/k/v/out (B,H,T,C); lse (B,H,T,8) f32
    B, H, T, C = q.shape
    bq, bk = _block_sizes(T, block_q, block_k)
    scale = 1.0 / math.sqrt(C)

    qf, kf, vf = (a.reshape(B * H, T, C) for a in (q, k, v))
    of = out.reshape(B * H, T, C)
    dof = g.reshape(B * H, T, C)
    lsef = lse.reshape(B * H, T, _STATS_LANES)

    if _tiled(T, bk):
        full_spec = pl.BlockSpec((1, T, C), lambda b: (b, 0, 0), memory_space=pltpu.VMEM)
        stat_spec = pl.BlockSpec(
            (1, T, _STATS_LANES), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel_tiled, scale=scale, tile=bq, causal=causal),
            grid=(B * H,),
            in_specs=[full_spec] * 5 + [stat_spec],
            out_specs=[full_spec] * 3,
            out_shape=[
                _out_struct((B * H, T, C), q.dtype, q),
                _out_struct((B * H, T, C), k.dtype, q),
                _out_struct((B * H, T, C), v.dtype, q),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=_interpret(),
        )(qf, kf, vf, of, dof, lsef)
        return (
            dq.reshape(B, H, T, C),
            dk.reshape(B, H, T, C),
            dv.reshape(B, H, T, C),
        )

    q_spec = pl.BlockSpec((1, bq, C), lambda b, iq, ik: (b, iq, 0), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, bk, C), lambda b, iq, ik: (b, ik, 0), memory_space=pltpu.VMEM)
    stat_q_spec = pl.BlockSpec(
        (1, bq, _STATS_LANES), lambda b, iq, ik: (b, iq, 0), memory_space=pltpu.VMEM
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal),
        grid=(B * H, T // bq, T // bk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, stat_q_spec],
        out_specs=[q_spec],
        out_shape=[_out_struct((B * H, T, C), q.dtype, q)],
        scratch_shapes=[
            pltpu.VMEM((bq, C), jnp.float32),
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(qf, kf, vf, of, dof, lsef)[0]

    # dk/dv: KV tile is the outer loop, Q sweep is innermost
    q_spec2 = pl.BlockSpec((1, bq, C), lambda b, ik, iq: (b, iq, 0), memory_space=pltpu.VMEM)
    k_spec2 = pl.BlockSpec((1, bk, C), lambda b, ik, iq: (b, ik, 0), memory_space=pltpu.VMEM)
    stat_q_spec2 = pl.BlockSpec(
        (1, bq, _STATS_LANES), lambda b, ik, iq: (b, iq, 0), memory_space=pltpu.VMEM
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=bq, block_k=bk, causal=causal),
        grid=(B * H, T // bk, T // bq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, q_spec2, stat_q_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            _out_struct((B * H, T, C), k.dtype, q),
            _out_struct((B * H, T, C), v.dtype, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, C), jnp.float32),
            pltpu.VMEM((bk, C), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=_interpret(),
    )(qf, kf, vf, of, dof, lsef)
    return (
        dq.reshape(B, H, T, C),
        dk.reshape(B, H, T, C),
        dv.reshape(B, H, T, C),
    )


# ----------------------------------------------------------------------
# public ops
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(
    q: Array, k: Array, v: Array, block_q: int = 512, block_k: int = 1024
) -> Array:
    """Causal flash attention over (B, H, T, C). Block sizes that do not
    tile T are adjusted by `_block_sizes` (KV block widens to T, Q block
    falls back to the KV block) rather than raising. Where block_k holds
    the sequence and T <= 1024, block_q is the tiled kernels' score tile
    (the dispatcher, ops.attention.flash_block_sizes, passes 256)."""
    out, _ = _flash_forward(q, k, v, block_q, block_k)
    return out


def _fwd_rule(q, k, v, block_q, block_k):
    out, lse = _flash_forward(q, k, v, block_q, block_k)
    # Named so a remat policy can keep the kernel's residuals: with
    # {attn_out, attn_lse} (plus the rotated q/k/v named in the model) saved,
    # the backward pass never re-runs the forward kernel.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


flash_attention.defvjp(_fwd_rule, _flash_backward)


def flash_attention_bthc(
    q: Array, k: Array, v: Array, block_q: int = 512, block_k: int = 1024
) -> Array:
    """(B, T, H, C) wrapper: transposes to head-major around the kernel.

    Kept for sequence-major callers; the per-head (B, H, T, C) layout is the
    primary one (Mosaic requires the last two block dims to tile cleanly,
    which rules out singleton-head blocks on sequence-major arrays, and a
    heads-fused sequence-major kernel measured slower than the per-head grid
    plus explicit transposes)."""
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        block_q, block_k,
    )
    return out.transpose(0, 2, 1, 3)
