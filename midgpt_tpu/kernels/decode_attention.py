"""Paged decode/verify attention for the continuous-batching engine, in
bf16 and int8-quantized cache modes, with optional split-K sequence
partitioning.

Decode-time attention reads K/V through a per-slot PAGE TABLE instead of a
contiguous (B, S, ...) cache: physical pages of `page_size` tokens live in a
shared (L, H, num_pages, page_size, C) pool (models/gpt.py PagedKVCache), and
slot b's logical page j is pool page `page_table[b, j]` of the layer being
read (the kernels take the whole pool and the layer index; the gather
lowerings slice the layer, `layer_pages`). Each slot masks to
its own true length, so one compiled program serves any mix of request
lengths — the two levers the serving layer needs (vLLM-style paged memory +
FlashAttention-style work partitioning, PAPERS.md) under XLA's static-shape
constraint.

Both compiled variants — plain decode (one query row per slot) and the
multi-row spec (T rows with per-row visible-key counts: the k+1 rows of
speculative verify, GPT.verify_step_paged, and the T_c rows of a prefill
chunk, GPT.prefill_paged_chunk) — are instantiations of ONE parameterized
kernel (kernels/attention_template.py): shared scalar-prefetched page
translation, shared online-softmax sweep (ops/online_softmax.py), shared
int8 fused-dequant read path. `split_k > 1` additionally partitions each
slot's visible key sequence over a parallel grid dimension — per-partition
raw (m, l, acc) partials merged outside the kernel — which is what keeps
the chip busy when a single long request is the whole batch (the T>=4k
single-slot regime; docs/SERVING.md "Split-K decode").

Off-TPU the dispatchers use the XLA gather fallbacks below, which mirror
the contiguous `GPT.decode_step` attention op-for-op (same einsum shapes,
same mask-then-scale-then-f32-softmax order, dequantizing right after the
page gather in int8 mode) so paged decode stays token-exact with the
single-request engine on the CPU test mesh. The split-K gather sibling
keeps the unsplit pass's fat q.K score matmul and partitions only the
softmax STATISTICS: scores reshape into split_k independent partitions,
one online-softmax block sweeps each, and partials merge with the SAME
ops/online_softmax.merge_partials math as the kernel path. Deliberately so:
a host core executes partitions sequentially either way, so the gather
split lowering aims for structure-neutrality, while the kernel's grid
dimension is what a long-T decode on hardware would gain from (no cell
runs T > 1024: PERF.md §7). The kernels themselves run in interpret mode only under
their parity tests (tests/test_decode_attention.py, tests/test_split_k.py
and tests/test_quant_cache.py — interpret is too slow for the serving
tests' inner loop).
"""

from __future__ import annotations

import math
import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from midgpt_tpu.kernels.attention_template import (
    normalize_split_k,
    paged_attention_template,
)
from midgpt_tpu.kernels.flash_attention import M_INIT, MASK
from midgpt_tpu.ops.attention import visible_mask
from midgpt_tpu.ops.online_softmax import finalize, merge_partials, online_block
from midgpt_tpu.ops.quant import dequantize_q8

Array = jax.Array


def _repeat_kv_heads(a: Array, groups: int, axis: int) -> Array:
    """Broadcast K/V heads to the query head count (GQA gather lowerings).
    Query head h reads K/V head h // groups — same consecutive-grouping
    convention as the template's reshape spec (attention_template.py)."""
    return a if groups == 1 else jnp.repeat(a, groups, axis=axis)


def paged_attention_kernel(
    q: Array,  # (B, H_q, C) — one query token per slot
    k_pages: Array,  # (L, H_kv, num_pages, page_size, C) — the whole pool
    v_pages: Array,  # (one layer's (H_kv, P, ps, C) when layer is None)
    page_table: Array,  # (B, max_pages) int32
    lengths: Array,  # (B,) int32 — valid tokens per slot (0 = inactive)
    k_scale: tp.Optional[Array] = None,  # (L, num_pages, H_kv, page_size) f32
    v_scale: tp.Optional[Array] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
    layer: tp.Optional[Array] = None,  # () int — the pool's layer to read
) -> Array:
    """Paged decode attention via the kernel template. Returns (B, H_q, C).
    int8 pools require both scale side buffers; bf16 pools take none.
    Plain decode is the template's n_rows == 1 spec: the per-row count IS
    the slot length. GQA (H_q > H_kv) and the sliding-window/sink mask are
    template specs too — the query-group fold and the windowed column mask
    live in attention_template.py, shared with the verify variant."""
    out = paged_attention_template(
        q[:, :, None, :],  # (B, H_q, 1, C)
        k_pages, v_pages, page_table,
        lengths[:, None],  # (B, 1) counts
        k_scale, v_scale, split_k=split_k,
        sliding_window=sliding_window, attn_sinks=attn_sinks, layer=layer,
    )
    return out[:, :, 0, :]


def _gather_pages(
    pages: Array,  # (H, num_pages, page_size, C)
    scales: tp.Optional[Array],  # (num_pages, H, page_size) f32 | None
    page_table: Array,  # (B, max_pages) int32
    out_dtype,
) -> Array:
    """Gather every slot's pages contiguous -> (B, H, S, C), dequantizing
    right after the gather in int8 mode (the CPU sibling of the kernels'
    in-VMEM dequant; ops/quant.py — exact, so gather and kernel read
    identical values from the same pool)."""
    H, _, page_size, C = pages.shape
    B, max_pages = page_table.shape
    S = max_pages * page_size
    flat = page_table.reshape(-1)
    g = jnp.take(pages, flat, axis=1)  # (H, B*max_pages, page_size, C)
    g = g.reshape(H, B, S, C).transpose(1, 0, 2, 3)  # (B, H, S, C)
    if scales is None:
        return g
    sg = jnp.take(scales, flat, axis=0)  # (B*max_pages, H, page_size)
    sg = sg.reshape(B, max_pages, H, page_size).transpose(0, 2, 1, 3)
    return dequantize_q8(g, sg.reshape(B, H, S)).astype(out_dtype)


def paged_attention_gather(
    q: Array,  # (B, H_q, C)
    k_pages: Array,  # (H_kv, num_pages, page_size, C)
    v_pages: Array,
    page_table: Array,  # (B, max_pages) int32
    lengths: Array,  # (B,) int32
    k_scale: tp.Optional[Array] = None,
    v_scale: tp.Optional[Array] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """XLA fallback: gather each slot's pages contiguous (dequantized in
    int8 mode), then run the exact attention ops of the contiguous
    `GPT.decode_step` (same einsum shapes, -inf mask BEFORE the
    1/sqrt(C)-scaled f32 softmax) so paged and contiguous decode agree
    token-for-token on CPU.

    split_k == 1 is that classic single pass, byte-for-byte unchanged.
    split_k > 1 keeps the SAME fat q.K score matmul and partitions only
    the softmax statistics: the masked f32 scores reshape into split_k
    independent partitions, one online-softmax block sweeps each, and
    partials merge with the same ops/online_softmax.merge_partials the
    kernel path uses — gather and kernel split lowerings share their
    merge math exactly. No scan, and no partitioned score matmul either:
    on a single host core a sequential partition loop only adds loop
    overhead and a partition-shaped dot defeats XLA's fusion of the long
    masked-softmax axis (both measured on an earlier toolchain, not re-measured
    — the parallel win
    belongs to the kernel's grid dimension on real hardware), while the
    stats-only split is within noise of the unsplit pass; greedy decode
    streams stay token-identical to it (tests/test_split_k.py)."""
    B, H, C = q.shape
    page_size = k_pages.shape[2]
    groups = H // k_pages.shape[0]  # GQA: query heads per K/V head
    max_pages = page_table.shape[1]
    S = max_pages * page_size
    split_k = normalize_split_k(split_k, max_pages)
    if split_k == 1:
        kg = _repeat_kv_heads(
            _gather_pages(k_pages, k_scale, page_table, q.dtype), groups, 1
        )
        vg = _repeat_kv_heads(
            _gather_pages(v_pages, v_scale, page_table, q.dtype), groups, 1
        )
        scores = jnp.einsum("bhqc,bhkc->bhqk", q[:, :, None], kg)  # (B, H, 1, S)
        valid = visible_mask(
            jnp.arange(S)[None, None, None, :],
            lengths[:, None, None, None],
            sliding_window,
            attn_sinks,
        )
        scores = jnp.where(valid, scores, float("-inf"))
        probs = jax.nn.softmax(
            scores.astype(jnp.float32) / math.sqrt(C), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhqk,bhkc->bhqc", probs, vg)[:, :, 0]

    part_len = (max_pages // split_k) * page_size
    scale = 1.0 / math.sqrt(C)
    kg = _repeat_kv_heads(
        _gather_pages(k_pages, k_scale, page_table, q.dtype), groups, 1
    )
    vg = _repeat_kv_heads(
        _gather_pages(v_pages, v_scale, page_table, q.dtype), groups, 1
    )
    s = jnp.einsum("bhc,bhkc->bhk", q, kg).astype(jnp.float32) * scale
    s = jnp.where(
        visible_mask(
            jnp.arange(S)[None, None], lengths[:, None, None],
            sliding_window, attn_sinks,
        ),
        s,
        MASK,
    )
    # Fat dot above, partitioned statistics below: scores reshape into
    # split_k independent partitions, each swept by one online block from
    # the init stats — exactly the kernel's single-block partition sweep.
    s = s.reshape(B, H, split_k, part_len)
    m = jnp.full((B, H, split_k), M_INIT, jnp.float32)
    l = jnp.zeros((B, H, split_k), jnp.float32)
    m, _, p, l = online_block(m, l, s)
    acc = jnp.einsum(
        "bhsk,bhskc->bhsc", p.astype(vg.dtype),
        vg.reshape(B, H, split_k, part_len, C),
    ).astype(jnp.float32)
    m, l, acc = merge_partials(m, l, acc, axis=2)
    out, _ = finalize(m, l, acc, dtype=q.dtype)
    return out


def _tp_shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """Full-MANUAL shard_map over the serving mesh: every named axis is
    manual (only 'tp' exceeds size 1 on a serve mesh, parallel/serve_tp.py),
    so the body is a plain per-shard trace — exactly what a Pallas kernel
    needs. check_vma off: paged attention is pointwise in heads, there is no
    replication to certify."""
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=frozenset(mesh.axis_names),
        check_vma=False,
    )


def layer_pages(
    pool: Array, scales: tp.Optional[Array], layer: tp.Optional[Array]
) -> tp.Tuple[Array, tp.Optional[Array]]:
    """Layer `layer`'s pages (H, P, ps, C) and scales (P, H, ps) | None of
    a whole pool: the XLA gather lowerings' read (a dynamic slice the
    gather fuses with). `layer=None` means the operands already are one
    layer's. The kernel path never calls this: slicing a layer out for a
    custom call is a layer-sized copy (PagedKVCache "Layout contract")."""
    if layer is None:
        return pool, scales
    kp = jax.lax.dynamic_index_in_dim(pool, layer, axis=0, keepdims=False)
    sp = (
        None
        if scales is None
        else jax.lax.dynamic_index_in_dim(scales, layer, axis=0, keepdims=False)
    )
    return kp, sp


def _kernel_call(
    kernel, q, q_spec, k_pages, v_pages, page_table, counts, k_scale,
    v_scale, layer, mesh, static,
) -> Array:
    """Call a template-instantiating kernel wrapper, per tp shard through a
    full-manual shard_map when the serving mesh has tp > 1: each shard
    holds H_q/tp query heads and H_kv/tp heads of the pool (+ int8 scale
    rows); page table, counts and the layer index ride in replicated."""
    if mesh is None or mesh.shape["tp"] == 1:
        return kernel(
            q, k_pages, v_pages, page_table, counts, k_scale, v_scale,
            layer=layer, **static,
        )
    lead = (None,) * (k_pages.ndim - 4)  # a whole pool's layer dim
    pool = P(*lead, "tp", None, None, None)  # (.., H_kv, pages, ps, C)
    in_specs = [q_spec, pool, pool, P(), P()]
    args = [q, k_pages, v_pages, page_table, counts]
    if k_scale is not None:
        in_specs += [P(*lead, None, "tp", None)] * 2  # (.., pages, H_kv, ps)
        args += [k_scale, v_scale]
    if layer is not None:
        in_specs.append(P())
        args.append(layer)

    def per_shard(q, k, v, pt, cnt, *rest):
        ly = rest[-1] if layer is not None else None
        ks, vs = rest[:2] if k_scale is not None else (None, None)
        return kernel(q, k, v, pt, cnt, ks, vs, layer=ly, **static)

    return _tp_shard_map(per_shard, mesh, tuple(in_specs), q_spec)(*args)


def resolve_paged_impl(impl: str) -> str:
    """'auto' -> the Pallas template ('kernel') on a TPU backend, the XLA
    gather lowering ('gather') elsewhere; explicit names pass through. The
    serving engine resolves ONCE at construction and says which one it will
    compile, so the choice is never invisible (sampling/serve.py)."""
    if impl == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "gather"
    return impl


def paged_attention(
    q: Array,
    k_pages: Array,
    v_pages: Array,
    page_table: Array,
    lengths: Array,
    impl: str = "auto",
    k_scale: tp.Optional[Array] = None,
    v_scale: tp.Optional[Array] = None,
    mesh: tp.Optional[Mesh] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
    layer: tp.Optional[Array] = None,
) -> Array:
    """Dispatch: Pallas kernel on TPU, XLA gather elsewhere (interpret mode
    is orders of magnitude too slow for the serving loop — same policy as
    ops/attention.py for the flash kernel).

    With a tp>1 serving mesh the kernel is invoked PER SHARD through a
    full-manual shard_map: each tp shard holds H_q/tp query heads and
    H_kv/tp heads of the page pool (+ int8 scale rows) — under GQA the
    shard boundary lands between whole K/V-head GROUPS, since H_q/tp =
    groups * (H_kv/tp), so each shard's query heads read exactly its own
    pool heads (requires n_kv_heads % tp == 0, validated by the engine) —
    the page table and lengths ride in replicated, and the per-head
    online-softmax sweep needs no collective at all: the head axis is
    embarrassingly parallel, and the tp all-reduce PAYLOAD the pool feeds
    shrinks with the pool while the COUNT stays two per layer. split_k
    rides the grid (kernel) or the batched partition axis (gather) INSIDE
    each head shard, so tensor parallelism, GQA, the window mask and
    split-K all compose with zero new collectives. The gather lowering
    ignores `mesh`: it is plain jnp, and GSPMD partitions it from the
    operand shardings alone."""
    impl = resolve_paged_impl(impl)
    static = dict(
        split_k=split_k, sliding_window=sliding_window, attn_sinks=attn_sinks
    )
    if impl == "kernel":
        return _kernel_call(
            paged_attention_kernel, q, P(None, "tp", None), k_pages, v_pages,
            page_table, lengths, k_scale, v_scale, layer, mesh, static,
        )
    if impl == "gather":
        k_pages, k_scale = layer_pages(k_pages, k_scale, layer)
        v_pages, v_scale = layer_pages(v_pages, v_scale, layer)
        return paged_attention_gather(
            q, k_pages, v_pages, page_table, lengths, k_scale, v_scale,
            **static,
        )
    raise ValueError(f"unknown paged attention impl {impl!r}")


# ----------------------------------------------------------------------
# Multi-row paged verify attention (speculative decoding)
# ----------------------------------------------------------------------


def paged_verify_attention_kernel(
    q: Array,  # (B, T, H_q, C)
    k_pages: Array,  # (L, H_kv, num_pages, page_size, C) — the whole pool
    v_pages: Array,  # (one layer's (H_kv, P, ps, C) when layer is None)
    page_table: Array,  # (B, max_pages) int32
    counts: Array,  # (B, T) int32 — keys visible to row t of slot b
    k_scale: tp.Optional[Array] = None,
    v_scale: tp.Optional[Array] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
    layer: tp.Optional[Array] = None,
) -> Array:
    """Multi-row paged attention via the kernel template (n_rows == T).
    Returns (B, T, H, C). q is transposed head-major ONCE outside the
    kernel (a single small XLA transpose per verify forward) so the kernel
    works in the pool's native (H, ...) layout with no in-kernel
    transposes. Each row t masks to its OWN visible-key count cnt[b, t]
    (the caller passes lengths + t + 1, which is what makes the
    speculative chunk causal through the page table —
    GPT.verify_step_paged)."""
    out = paged_attention_template(
        q.transpose(0, 2, 1, 3),  # (B, H_q, T, C)
        k_pages, v_pages, page_table, counts,
        k_scale, v_scale, split_k=split_k,
        sliding_window=sliding_window, attn_sinks=attn_sinks, layer=layer,
    )
    return out.transpose(0, 2, 1, 3)  # (B, T, H_q, C)


def paged_verify_attention_gather(
    q: Array,  # (B, T, H_q, C)
    k_pages: Array,
    v_pages: Array,
    page_table: Array,
    counts: Array,  # (B, T) int32
    k_scale: tp.Optional[Array] = None,
    v_scale: tp.Optional[Array] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> Array:
    """XLA gather lowering of the multi-row attention (speculative verify
    and a prefill chunk off the TPU): pages gathered contiguous once
    (dequantized in int8 mode), then per-row count masks over the shared
    buffer.
    Same mask-then-scale-then-f32-softmax order as
    `paged_attention_gather`, so speculative greedy verify stays
    token-exact with plain paged decode (pinned by tests/test_spec.py).
    split_k > 1 is the same stats-only split as the decode gather (fat
    score matmul kept, one online block per scores partition,
    merge_partials outside), applied per row after the per-row count
    mask."""
    B, T, H, C = q.shape
    page_size = k_pages.shape[2]
    groups = H // k_pages.shape[0]  # GQA: query heads per K/V head
    max_pages = page_table.shape[1]
    S = max_pages * page_size
    split_k = normalize_split_k(split_k, max_pages)
    if split_k == 1:
        kg = _repeat_kv_heads(
            _gather_pages(k_pages, k_scale, page_table, q.dtype), groups, 1
        )
        vg = _repeat_kv_heads(
            _gather_pages(v_pages, v_scale, page_table, q.dtype), groups, 1
        )
        scores = jnp.einsum("bthc,bhkc->bhtk", q.astype(kg.dtype), kg)
        valid = visible_mask(
            jnp.arange(S)[None, None, None, :],
            counts[:, None, :, None],
            sliding_window,
            attn_sinks,
        )
        scores = jnp.where(valid, scores, float("-inf"))
        probs = jax.nn.softmax(
            scores.astype(jnp.float32) / math.sqrt(C), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhtk,bhkc->bthc", probs, vg)  # (B, T, H, C)

    part_len = (max_pages // split_k) * page_size
    scale = 1.0 / math.sqrt(C)
    kg = _repeat_kv_heads(
        _gather_pages(k_pages, k_scale, page_table, q.dtype), groups, 1
    )
    vg = _repeat_kv_heads(
        _gather_pages(v_pages, v_scale, page_table, q.dtype), groups, 1
    )
    s = jnp.einsum("bthc,bhkc->bhtk", q.astype(kg.dtype), kg).astype(
        jnp.float32
    ) * scale  # (B, H, T, S) — the unsplit fat dot
    s = jnp.where(
        visible_mask(
            jnp.arange(S)[None, None, None],
            counts[:, None, :, None],
            sliding_window,
            attn_sinks,
        ),
        s,
        MASK,
    )
    s = s.reshape(B, H, T, split_k, part_len)
    m = jnp.full((B, H, T, split_k), M_INIT, jnp.float32)
    l = jnp.zeros((B, H, T, split_k), jnp.float32)
    m, _, p, l = online_block(m, l, s)
    acc = jnp.einsum(
        "bhtsk,bhskc->bhtsc", p.astype(vg.dtype),
        vg.reshape(B, H, split_k, part_len, C),
    ).astype(jnp.float32)
    m, l, acc = merge_partials(m, l, acc, axis=3)
    out, _ = finalize(m, l, acc, dtype=q.dtype)  # (B, H, T, C)
    return out.transpose(0, 2, 1, 3)  # (B, T, H, C)


def paged_verify_attention(
    q: Array,  # (B, T, H, C) — T = k+1 speculative positions per slot
    k_pages: Array,  # (H, num_pages, page_size, C)
    v_pages: Array,
    page_table: Array,  # (B, max_pages) int32
    counts: Array,  # (B, T) int32 — keys visible to row t of slot b
    impl: str = "auto",
    k_scale: tp.Optional[Array] = None,
    v_scale: tp.Optional[Array] = None,
    mesh: tp.Optional[Mesh] = None,
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
    layer: tp.Optional[Array] = None,
) -> Array:
    """Batched multi-row paged attention, for speculative verification
    (GPT.verify_step_paged: every slot scores its k+1 candidate positions)
    and for prefill (GPT.prefill_paged_chunk: the T_c rows of every slot's
    chunk), against each slot's own pages in ONE call. Row t of slot b
    attends to counts[b, t] keys, nondecreasing in t — the caller passes
    lengths[b] + t + 1, which makes
    the chunk causal through the cache: all rows' K/V are written before
    the read, and the per-row count hides the later rows. Under a sliding
    window each row additionally masks to the last `sliding_window` of its
    own visible keys (+ the `attn_sinks` prefix) — the window slides per
    ROW, so the speculative chunk stays causal-consistent with plain
    windowed decode.

    Dispatch mirrors `paged_attention`: the template-instantiated multi-row
    kernel on TPU (bf16 and int8 — interpret-mode parity in
    tests/test_quant_cache.py and tests/test_split_k.py), the XLA gather
    lowering elsewhere; on a tp>1 mesh the kernel runs per shard over
    H_q/tp query heads and H_kv/tp pool heads via the same full-manual
    shard_map, collective-free, with split_k riding inside each shard."""
    impl = resolve_paged_impl(impl)
    static = dict(
        split_k=split_k, sliding_window=sliding_window, attn_sinks=attn_sinks
    )
    if impl == "kernel":
        return _kernel_call(
            paged_verify_attention_kernel, q, P(None, None, "tp", None),
            k_pages, v_pages, page_table, counts, k_scale, v_scale, layer,
            mesh, static,
        )
    if impl == "gather":
        k_pages, k_scale = layer_pages(k_pages, k_scale, layer)
        v_pages, v_scale = layer_pages(v_pages, v_scale, layer)
        return paged_verify_attention_gather(
            q, k_pages, v_pages, page_table, counts, k_scale, v_scale,
            **static,
        )
    raise ValueError(f"unknown paged verify attention impl {impl!r}")
