"""Causal multi-head attention: reference-exact naive path + O(T) blockwise path.

Numerics of the naive path match reference model.py:71-77 exactly: scores are
computed in the compute dtype (bf16 on TPU — this matmul is the MXU hot op),
cast to float32, scaled by 1/sqrt(head_dim), masked with -inf below the
diagonal, softmaxed in float32, then cast back for the PV matmul.

The blockwise path (`impl='blockwise'`) is a pure-jnp online-softmax
(flash-style) formulation with O(T) memory — the long-context route for
platforms without the Pallas kernel (midgpt_tpu.kernels.flash_attention,
`impl='flash'`), selected by name, and the parity oracle for testing it.

All impls take q, k, v of shape (B, H, T, C) and return (B, H, T, C).
"""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp

from midgpt_tpu.ops.dropout import dropout

Array = jax.Array

NEG_INF = float("-inf")


def visible_mask(
    col: Array,
    counts: Array,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """THE visibility rule every attention path shares (broadcasting bool).

    A row with `counts` visible keys keeps column `col` iff col < counts
    and — under a sliding window — col is within the last `sliding_window`
    of them OR inside the `attn_sinks` always-visible prefix
    (StreamingLLM-style sinks). With sliding_window == 0 this is the plain
    causal/length mask, bit-identical to the pre-window repo. Used by the
    training paths here, the paged gather fallbacks
    (kernels/decode_attention.py) and the dense decode/prefill masks
    (models/gpt.py); the Pallas template spells the same expression as
    straight-line selects in-kernel (kernels/attention_template.py)."""
    keep = col < counts
    if sliding_window:
        w = col >= counts - sliding_window
        if attn_sinks:
            w |= col < attn_sinks
        keep &= w
    return keep


def naive_causal_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    dropout_rate: float = 0.0,
    key: tp.Optional[Array] = None,
    inference: bool = True,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """Materialized-scores attention, fp32 softmax. (B,H,T,C) -> (B,H,T,C).
    sliding_window/attn_sinks restrict each row to its windowed visible set
    (visible_mask); 0 is the reference causal mask, unchanged."""
    *_, T, C = q.shape
    rows = jnp.arange(T)[:, None]
    cols = jnp.arange(T)[None, :]
    # row t sees count = t + 1 keys; cols < rows + 1 == tril
    mask = visible_mask(cols, rows + 1, sliding_window, attn_sinks)
    scores = jnp.einsum("bhqc,bhkc->bhqk", q, k)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32) / math.sqrt(C), axis=-1)
    probs = probs.astype(q.dtype)
    probs = dropout(probs, dropout_rate, key, inference)
    return jnp.einsum("bhqk,bhkc->bhqc", probs, v)


def blockwise_causal_attention(
    q: Array,
    k: Array,
    v: Array,
    block_size: int = 512,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """Online-softmax causal attention with O(T * block) memory.

    Scans over KV blocks for each Q block, keeping running (max, sum, acc)
    statistics in float32. Equivalent to the naive path up to fp summation
    order. Block pairs entirely above the diagonal are masked out (compute is
    not skipped — under `lax.scan` the shape must be static; the Pallas kernel
    is the one that actually skips them).
    """
    B, H, T, C = q.shape
    blk = min(block_size, T)
    T_orig = T
    if T % blk != 0:
        # Pad to a block multiple (arbitrary-length prompts in prefill). The
        # causal mask zeroes padded keys for real queries; padded query rows
        # are sliced off below.
        pad = blk - T % blk
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
        T = T + pad
    n_blk = T // blk
    scale = 1.0 / math.sqrt(C)

    qb = q.reshape(B, H, n_blk, blk, C)
    kb = k.reshape(B, H, n_blk, blk, C)
    vb = v.reshape(B, H, n_blk, blk, C)

    # Row/col indices within a (blk, blk) tile, used to build per-pair masks.
    row_ids = jnp.arange(blk)[:, None]
    col_ids = jnp.arange(blk)[None, :]

    def q_block_fn(qi: int, q_i: Array) -> Array:
        # q_i: (B, H, blk, C)
        def kv_step(carry, j):
            acc, m, denom = carry  # (B,H,blk,C) f32, (B,H,blk) f32, (B,H,blk) f32
            k_j = kb[:, :, j]
            v_j = vb[:, :, j]
            s = jnp.einsum("bhqc,bhkc->bhqk", q_i, k_j).astype(jnp.float32) * scale
            # causal (optionally windowed) mask on GLOBAL indices: row
            # g_row sees count = g_row + 1 keys (visible_mask above)
            gmask = visible_mask(
                j * blk + col_ids,
                qi * blk + row_ids + 1,
                sliding_window,
                attn_sinks,
            )
            s = jnp.where(gmask & (j <= qi), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard: fully-masked rows keep m_new == -inf; exp(-inf - -inf) → use where
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            denom_new = denom * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkc->bhqc", p.astype(q.dtype), v_j
            ).astype(jnp.float32)
            return (acc_new, m_new, denom_new), None

        # Derive the init from q_i (not fresh constants) so that inside an
        # enclosing shard_map the carry inherits q's varying-manual-axes
        # annotation — a constant init trips scan's carry-type check there
        # (the Ulysses-inside-ZeRO-3 composition hits exactly this).
        # Known trade-off (ADVICE r4): non-finite q makes this init NaN
        # (inf*0), so the max/denom guards no longer protect fully-masked
        # rows in that case — harmless, since non-finite q already poisons
        # the output, and the train step's health gate catches it. If a
        # newer JAX drops the varying-axes restriction, revert to constant
        # inits.
        zeros_c = (q_i * 0).astype(jnp.float32)  # (B, H, blk, C)
        zeros_r = jnp.sum(zeros_c, axis=-1)  # (B, H, blk)
        init = (zeros_c, zeros_r + NEG_INF, zeros_r)
        (acc, _, denom), _ = jax.lax.scan(kv_step, init, jnp.arange(n_blk))
        # max() guards fully-masked (padded) query rows against 0/0 NaN.
        return (acc / jnp.maximum(denom, 1e-30)[..., None]).astype(q.dtype)

    if n_blk <= 8:
        outs = [q_block_fn(qi, qb[:, :, qi]) for qi in range(n_blk)]
        out = jnp.stack(outs, axis=2)
    else:
        # Long sequences (the 32K config's non-TPU path is 32+ Q blocks): one
        # rolled body instead of n_blk unrolled copies of the KV scan in HLO
        # — bounds compile time and program size; identical math (q_block_fn
        # only uses qi in elementwise index comparisons).
        out = jax.lax.map(
            lambda qi: q_block_fn(qi, qb[:, :, qi]), jnp.arange(n_blk)
        ).transpose(1, 2, 0, 3, 4)
    out = out.reshape(B, H, T, C)
    return out[:, :, :T_orig]


def flash_kernel_usable(T: int, block_size: int) -> bool:
    """True when the Pallas kernel can serve this shape on this backend:
    the block tiles T, and the backend is a TPU (or a test has switched the
    kernels' interpret mode on, kernels/flash_attention.RUN_INTERPRET_OFF_TPU)."""
    import importlib

    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
    blk = min(block_size, T)
    return T % blk == 0 and (jax.default_backend() == "tpu" or fa.RUN_INTERPRET_OFF_TPU)


def flash_or_blockwise(impl: str, T: int, block_size: int) -> str:
    """`impl`, except that a 'flash' which cannot serve T here becomes
    'blockwise' — the same online softmax in plain jnp — BY NAME. For the
    two callers whose shapes the model config does not fix: KV-cache
    prefill (prompts come in lengths no tile divides) and the dense
    attention inside Ulysses (T is the gathered sequence of whatever mesh
    it runs on). multihead_attention itself never trades a configured
    'flash' for another impl: the train step raises instead."""
    if impl == "flash" and not flash_kernel_usable(T, block_size):
        return "blockwise"
    return impl


def flash_block_sizes(T: int, block_size: int) -> tp.Tuple[int, int]:
    """(block_q, block_k) for the flash kernel — the single place the tile
    policy lives. KV blocks use the largest block the sequence allows.
    Where one KV block holds the sequence, block_q is the square score tile
    of the tiled kernels (kernels/flash_attention.py, T <= 1024): 256, which
    forms 10/16 of the score matrix at T=1024 and on the v5e measured under
    512 (12/16), forward (-9 %) and backward (-3 %), at head widths 64 and
    128 alike (PERF.md, PR 40), so the rule reads no width. Over several KV
    blocks Q tiles are 512 (the f32 score tile + scratch stay inside VMEM).
    Either falls back to block_k when it does not divide T (e.g. T=384)."""
    bk = min(block_size, T)
    bq = min(256 if bk == T else 512, bk)
    if T % bq:
        bq = bk
    return bq, bk


def multihead_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    impl: str = "naive",
    dropout_rate: float = 0.0,
    key: tp.Optional[Array] = None,
    inference: bool = False,
    block_size: int = 512,
    layout: str = "bhtc",
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """Dispatch causal attention; output layout matches the input layout.

    layout: 'bhtc' (head-major, what the naive/blockwise math uses) or
    'bthc' (sequence-major — the layout the fused QKV projection produces;
    the flash kernel consumes it natively, so the training hot path never
    transposes heads).
    impl: 'naive' (materialized T×T, reference semantics), 'blockwise'
    (O(T) jnp online softmax), or 'flash' (Pallas TPU kernel).
    Attention-probability dropout (reference model.py:78) is only supported
    on the naive path; the fused kernels take dropout_rate == 0 (all
    openwebtext-scale reference configs train with dropout 0.0).
    """
    if layout not in ("bhtc", "bthc"):
        raise ValueError(f"unknown attention layout {layout!r}")
    if impl not in ("naive", "blockwise", "flash", "ring", "ulysses"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl in ("ring", "ulysses"):
        # The mesh-bound sequence-parallel implementations are injected by
        # the training runtime (GPT.hidden attn_fn). Reached without one —
        # sampling or evaluating such a checkpoint on a single host — the
        # unsharded math is identical to blockwise online softmax.
        impl = "blockwise"
    if impl != "naive" and dropout_rate != 0.0 and not inference:
        raise NotImplementedError(f"attention dropout requires impl='naive', got {impl!r}")
    if sliding_window and impl == "flash":
        # the flash kernel carries no window mask (GPTConfig validates this
        # at construction; defensive for direct callers)
        raise NotImplementedError(
            "sliding_window requires impl 'naive' or 'blockwise'"
        )

    T = q.shape[2] if layout == "bhtc" else q.shape[1]
    blk = min(block_size, T)
    if impl == "flash":
        import importlib

        # the real module (the package re-exports a same-named function)
        fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")

        if not flash_kernel_usable(T, block_size):
            # A configured kernel that cannot be served is an error, not a
            # quiet downgrade: a run that believes it measures the Pallas
            # kernel must not be timing plain jnp instead.
            raise ValueError(
                f"attn_impl='flash' cannot serve T={T} with block {blk} on "
                f"the {jax.default_backend()!r} backend: the Pallas kernel "
                "needs a block that divides T and a TPU. Configure "
                "attn_impl='blockwise' (same online softmax, plain jnp) to "
                "run this shape or backend."
            )
        bq, bk = flash_block_sizes(T, block_size)
        if layout == "bthc":
            return fa.flash_attention_bthc(q, k, v, bq, bk)
        return fa.flash_attention(q, k, v, bq, bk)

    if layout == "bthc":  # naive/blockwise math is head-major
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if impl == "naive":
        out = naive_causal_attention(
            q, k, v, dropout_rate=dropout_rate, key=key, inference=inference,
            sliding_window=sliding_window, attn_sinks=attn_sinks,
        )
    else:
        out = blockwise_causal_attention(
            q, k, v, block_size=blk,
            sliding_window=sliding_window, attn_sinks=attn_sinks,
        )
    return out.transpose(0, 2, 1, 3) if layout == "bthc" else out


def flash_attention_sharded(
    q: Array,  # (B, H, T, C) global arrays
    k: Array,
    v: Array,
    mesh: jax.sharding.Mesh,
    block_size: int,
    batch_axes: tp.Tuple[str, ...] = ("data", "fsdp"),
    head_axis: tp.Optional[str] = None,
) -> Array:
    """impl='flash' under a multi-device GSPMD program. A Mosaic kernel
    cannot be partitioned by the compiler ("wrap the call in a shard_map"),
    so the batch axis is mapped over `batch_axes` (and heads over
    `head_axis`, e.g. 'tp') by hand: every device runs the kernel on its own
    (B/n, H/t, T, C) block. Causal attention is independent per (sequence,
    head), so the body needs no collective. Same contract as the ring and
    Ulysses wrappers: head-major in, head-major out, bound to the mesh by
    the training runtime (training/train.py)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    spec = P(batch_axes, head_axis, None, None)
    fn = shard_map(
        lambda q, k, v: multihead_attention(
            q, k, v, impl="flash", inference=True, block_size=block_size,
            layout="bhtc",
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
