"""Pallas TPU flash attention (causal, FlashAttention-2 style) with custom VJP.

Replaces the reference's materialized T×T attention (reference model.py:71-77)
— the O(T²) memory wall that caps its context at 1024 — with tiled
online-softmax kernels:

  * forward: grid (B*H, n_q, n_k), KV innermost. TPU grid steps execute
    sequentially over the minor dimension, so the (m, l, acc) running
    statistics live in VMEM scratch across the KV sweep of each Q tile.
    Blocks strictly above the causal diagonal are predicated off with
    pl.when; diagonal-straddling blocks are masked elementwise; fully-valid
    blocks skip the mask entirely (the common case at long T).
  * backward: two kernels — dQ (grid over KV for each Q tile) and dK/dV
    (grid over Q for each KV tile) — recomputing p = exp(s - lse) from the
    saved log-sum-exp rather than storing T×T probabilities. The
    delta = rowsum(dO ⊙ O) softmax-jacobian correction is computed in-kernel
    from the O / dO tiles already in VMEM: no separate delta pass and no
    broadcast side buffers.
  * lse is stored 8 lanes wide (f32), not broadcast to a 128-lane buffer —
    16x less statistics traffic than a full-tile store.

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
# historically and the backward kernels below use them directly.
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
    (the dispatcher-side policy, ops.attention.flash_block_sizes, differs:
    it always picks bq=min(512, bk) and is only reached when the block
    divides T). Deterministic in (T, block_q, block_k), so the forward and
    backward passes of the custom VJP always agree. Widened blocks are
    bounded by the f32 score-tile budget (bq*bk <= 1M elements = 4 MB, the
    size the fused T=1024 backward already proves fits the ~16 MB scoped
    VMEM alongside its operand tiles): past that, an explicit error beats a
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


def _masked(s: Array, iq, ik, block_q: int, block_k: int) -> Array:
    """Apply the causal mask elementwise (straight-line select — a lax.cond
    that skips it on fully-valid blocks measured slower end-to-end: Mosaic
    pipelines the unconditional kernel body better than the branchy one)."""
    row = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(row >= col, s, MASK)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _fwd_kernel_single(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q, block_k, causal):
    """Specialization for n_k == 1 (block_k covers the whole sequence): the
    softmax over each row is complete in one visit, so the online-softmax
    running statistics — scratch init, alpha rescale, m/l carry, separate
    finalize — all vanish. This is the hot configuration for T <= block_k.

    causal=False computes full (unmasked) attention — the off-diagonal
    pair case of ring attention, where the causal structure is decided per
    K/V shard at the ring level, not per element."""
    iq = pl.program_id(1)
    q = q_ref[0]  # (block_q, C)
    k = k_ref[0]  # (block_k, C)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (block_q, block_k) f32
    if causal:
        s = _masked(s, iq, 0, block_q, block_k)
    # One online_block step from the empty state IS the direct softmax:
    # alpha underflows to 0, l = sum(p), and every row has >= 1 valid key
    # so finalize's safe_l/lse guards are bitwise no-ops (l >= 1).
    m, _, p, l = online_block(
        jnp.full(s.shape[:-1], M_INIT, jnp.float32),
        jnp.zeros(s.shape[:-1], jnp.float32),
        s,
    )
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out, lse = finalize(m, l, pv, dtype=o_ref.dtype)
    o_ref[0] = out
    lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


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
    single = T // bk == 1

    if single:
        kernel = functools.partial(
            _fwd_kernel_single, scale=scale, block_q=bq, block_k=bk, causal=causal
        )
        grid = (B * H, T // bq)
        idx_q = lambda b, iq: (b, iq, 0)
        idx_k = lambda b, iq: (b, 0, 0)
        scratch = []
        params = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))
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


def _bwd_fused_single(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
    *, scale, seq_len, causal,
):
    """Fully-fused backward for T <= block: computes dQ, dK and dV from ONE
    score/probability reconstruction — versus the two-kernel split, this
    saves a full QK^T matmul, a mask+exp pass and a second round of
    q/k/v/o/do DMAs. Grid is (B*H,): one grid step per head."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (T, T) f32
    if causal:
        s = _masked(s, 0, 0, seq_len, seq_len)
    lse = lse_ref[0][:, 0]
    p = jnp.exp(s - lse[:, None])  # (T, T)
    pb = p.astype(do.dtype)
    dv_ref[0] = jax.lax.dot_general(
        pb, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(o_ref[0].astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)  # (T, T) bf16
    dq_ref[0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)


def _bwd_dq_kernel_single(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *, scale, block_q, block_k, causal
):
    """n_k == 1 specialization: no accumulation scratch, one straight pass."""
    iq = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    do = do_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _masked(s, iq, 0, block_q, block_k)
    lse = lse_ref[0][:, 0]
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(
        do, v_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    delta = jnp.sum(
        o_ref[0].astype(jnp.float32) * do.astype(jnp.float32), axis=-1
    )
    ds = p * (dp - delta[:, None]) * scale
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)


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

    if T // bk == 1 and T <= 1024:
        # One fused kernel for the whole backward: the (T, T) f32 score tile
        # plus its bf16 shadows fit VMEM up to T=1024.
        full_spec = pl.BlockSpec((1, T, C), lambda b: (b, 0, 0), memory_space=pltpu.VMEM)
        stat_spec = pl.BlockSpec(
            (1, T, _STATS_LANES), lambda b: (b, 0, 0), memory_space=pltpu.VMEM
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_single, scale=scale, seq_len=T, causal=causal),
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

    if T // bk == 1:  # single KV step: stateless dq kernel, 2D grid
        q_spec = pl.BlockSpec((1, bq, C), lambda b, iq: (b, iq, 0), memory_space=pltpu.VMEM)
        k_spec = pl.BlockSpec((1, bk, C), lambda b, iq: (b, 0, 0), memory_space=pltpu.VMEM)
        stat_q_spec = pl.BlockSpec(
            (1, bq, _STATS_LANES), lambda b, iq: (b, iq, 0), memory_space=pltpu.VMEM
        )
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel_single, scale=scale, block_q=bq, block_k=bk, causal=causal),
            grid=(B * H, T // bq),
            in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, stat_q_spec],
            out_specs=[q_spec],
            out_shape=[_out_struct((B * H, T, C), q.dtype, q)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=_interpret(),
        )(qf, kf, vf, of, dof, lsef)[0]
    else:
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

    # dk/dv: KV tile is the outer loop, Q sweep is innermost. (T <= 1024
    # always takes the fused branch above, so this is the long-context path
    # and keeps the tiled Q sweep — a full-sequence Q block would blow the
    # VMEM budget exactly where this branch is reachable.)
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
    falls back to the KV block) rather than raising."""
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
