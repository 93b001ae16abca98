"""Softmax cross-entropy with integer labels, computed in float32.

Matches reference train.py:72-77: logits are cast to float32 before the loss
(bf16 logits would lose too much precision in the logsumexp), and the result
is the mean over all positions. Implemented directly (no optax dependency in
the ops layer) with the standard stable logsumexp formulation — XLA fuses this
with the lm_head matmul's epilogue.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.obs import STEP_SCOPES

Array = jax.Array
_LM_HEAD_LOSS = STEP_SCOPES[1]


def cross_entropy_loss(logits: Array, labels: Array) -> Array:
    """Mean CE over all positions. logits (..., V) any float dtype, labels (...) ints."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - label_logits)


def fused_linear_cross_entropy(
    hidden: Array,
    lm_head: Array,
    labels: Array,
    chunk_tokens: int = 8192,
    remat_chunks: tp.Optional[bool] = None,
) -> Array:
    """`_fused_linear_cross_entropy` under the `lm_head_loss` named scope
    (obs.STEP_SCOPES): opened here, not at the call sites, so the gspmd,
    shard_map, pipeline and eval losses all carry it, forward and backward
    (no custom backward rule: jvp / transpose / remat keep the scope)."""
    with jax.named_scope(_LM_HEAD_LOSS):
        return _fused_linear_cross_entropy(
            hidden, lm_head, labels, chunk_tokens, remat_chunks
        )


def _fused_linear_cross_entropy(
    hidden: Array,
    lm_head: Array,
    labels: Array,
    chunk_tokens: int,
    remat_chunks: tp.Optional[bool],
) -> Array:
    """Mean CE of `hidden @ lm_head.T` against integer labels WITHOUT ever
    materializing the full (B*T, V) float32 logits.

    At GPT-2 vocab (50304 padded) the full-batch f32 logits are the single
    biggest training buffer (B=32, T=1024 → 6.6 GB on one chip — more than
    all layer activations combined). Token-chunked `lax.scan` with a
    per-chunk `jax.checkpoint` bounds that to chunk_tokens×V and recomputes
    each chunk's logits in the backward pass (the lm_head matmul is ~8% of
    total step FLOPs at 124M, so the recompute is cheap for a ~6 GB saving).

    Numerics match `cross_entropy_loss(GPT.apply(...))` exactly: the matmul
    runs in the compute dtype (same einsum as the unfused lm_head), is cast
    to f32, and per-token losses are summed in f32 then averaged.
    """
    B, T, D = hidden.shape
    N = B * T
    h = hidden.reshape(N, D)
    l = labels.reshape(N)
    chunk = min(chunk_tokens, N)
    n_chunks, rem = divmod(N, chunk)

    def chunk_fn(hc, lc):
        logits = jnp.einsum("nd,vd->nv", hc, lm_head)  # compute dtype
        # Hand-rolled streaming logsumexp: the bf16 logits stay the only
        # materialized (chunk, V) buffer. jax.nn.logsumexp would cast the
        # whole array to f32 first — and because that f32 copy then has two
        # consumers (the reduce and the label gather), XLA materializes it:
        # a 1.6 GB write+read per 8192-token chunk at GPT-2 vocab. Keeping
        # the cast inside the reduction's element function fuses it away.
        m = jnp.max(logits, axis=-1)  # (chunk,) — max is a selection: exact
        # elementwise f32 cast + subtract fused into the exp-sum reduction
        # (single consumer), numerically identical to casting logits first
        shifted = logits.astype(jnp.float32) - m.astype(jnp.float32)[:, None]
        sumexp = jnp.sum(jnp.exp(shifted), axis=-1)  # f32 accumulator
        lse = m.astype(jnp.float32) + jnp.log(sumexp)
        label_logits = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - label_logits.astype(jnp.float32))

    # With remat_chunks the logits are recomputed in the backward pass
    # (bounds live memory to one chunk×V buffer — for memory-tight shapes);
    # without it the bf16 chunk logits are stored, which at 124M/B<=32 is
    # cheaper than re-running the lm_head matmul + reductions (~2 HBM passes
    # vs ~1.7 TFLOP per chunk). Default (None) is auto: past the same
    # 8-chunk threshold that flips the python loop to lax.map, remat turns
    # on — at-scale microbatches (llama7b_32k, openwebtext_xl: ~128 chunks)
    # would otherwise keep every chunk's bf16 logits live, the full
    # (B*T, V) buffer the fused loss exists to avoid. An explicit
    # True/False always wins (the A/B knob stays honest).
    if remat_chunks is None:
        remat_chunks = n_chunks > 8
    chunked = jax.checkpoint(chunk_fn) if remat_chunks else chunk_fn
    total = jnp.zeros((), jnp.float32)
    if n_chunks <= 8:
        # Static python loop: no stacked (n_chunks, chunk, D) input copy.
        for i in range(n_chunks):
            total = total + chunked(
                h[i * chunk : (i + 1) * chunk], l[i * chunk : (i + 1) * chunk]
            )
    else:
        # Pod-scale batches (openwebtext_xl microsteps hit 128 chunks): one
        # rolled lax.map body keeps HLO size and compile time bounded; the
        # stacking copy amortizes at that scale.
        bulk = n_chunks * chunk
        per_chunk = jax.lax.map(
            lambda hl: chunked(*hl),
            (h[:bulk].reshape(n_chunks, chunk, D), l[:bulk].reshape(n_chunks, chunk)),
        )
        total = total + jnp.sum(per_chunk)
    if rem:  # non-divisible tail goes through the same math
        total = total + chunked(h[n_chunks * chunk :], l[n_chunks * chunk :])
    return total / N
