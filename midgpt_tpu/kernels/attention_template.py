"""Unified paged-attention Pallas kernel TEMPLATE.

One parameterized kernel body serves every paged-attention variant the
serving engine compiles, where kernels/decode_attention.py previously
hand-wrote a skeleton per variant (plain decode and multi-row verify, each
duplicating the page translation, the online-softmax sweep, and the int8
dequant read path). The template's axes of variation are *specs*, not new
kernels:

  * `n_rows` — query rows per slot: 1 for plain decode, k+1 for
    speculative verify (each row masks to its own visible-key count);
  * `quantized` — bf16/f32 pages read as they are vs int8 pages with fused
    in-VMEM f32-scale dequant (the scale rows of a block arrive as one
    (H, block tokens) tile, gathered through the same page table);
  * `split_k` — 1 emits the finalized output in-kernel (the classic
    sweep); s > 1 partitions the visible key sequence across a second
    grid dimension, each partition sweeping max_pages/s pages and
    emitting RAW (m, l, acc) online-softmax partials that are merged
    outside the kernel with ops/online_softmax.merge_partials — the
    FlashAttention-2-style work partitioning.

Skeleton (shared by every mode):

  grid (B, split_k, blocks), blocks innermost. A grid step is a COMPUTE
  BLOCK of `n` pages (`n * page_size` tokens), not a page: a step costs
  ~0.2-0.35 us whatever it moves, an 8-token page moves 50-65 KB (0.07 us
  of HBM time), so a sweep by pages is its step count (PERF.md PR 27).
  Pages are scattered in the pool, so a block is not a BlockSpec: the K
  and V pools stay in HBM whole (`memory_space=pl.ANY`, the (L, H, P, ps,
  C) pool in the one layout it lives in — no layer is ever sliced out for
  the call) and the kernel copies the pages itself, one async copy a page
  for K and one for V ((H, ps, C) at `[layer, :, table[b, j]]`) into rows
  `j * ps` of a (2, H, n * ps, C) VMEM buffer, waits, and runs ONE body —
  scores, mask, ops/online_softmax.online_block, PV — over the block. The
  page table, the per-row counts and the layer index ride
  PrefetchScalarGridSpec scalar prefetch.

  Double buffering: before a live block waits for its own copies it finds
  the NEXT live block of the whole call (a scalar scan over grid steps,
  across slot and partition boundaries) and starts that one's copies into
  the other buffer; only the call's first live block fetches for itself.
  The state (which buffer, which step is in flight) lives in SMEM scratch
  and the grid runs in order on one core, so every axis is "arbitrary".

  The sweep is bounded by each slot's OWN length, not the table's width: a
  block whose first token is at or past the last row's count, or that lies
  wholly behind every row's window and past the sink prefix, starts no
  copy, waits for none and computes nothing (it still costs its grid
  step). Inside a live block the same rule holds a page at a time.

  NEVER-DEREFERENCE RULE. With copies issued by hand this is a safety rule
  and not only a saving: `table[b, j]` is read ONLY for a page some row can
  see. Entries past a slot's length and window-reclaimed entries (-1,
  sampling/pages.py PagePool.reclaim) may hold anything. A page that is not
  fetched leaves its rows of the buffer as they were — zeros from the
  call's first step, later another page's values, always finite — and its
  columns are masked, so they add exactly 0 (tests/test_decode_attention.py
  poisons every such entry).

  `n` is derived, never configured (`block_pages`): the largest power of
  two that divides the pages a partition sweeps and keeps one buffer under
  1 MiB — 32 pages = 256 tokens at both benchmark shapes (H=12 or 16, 128
  lanes, bf16: 0.75 / 1 MiB a buffer, 3 / 4 MiB for K and V twice), a
  narrower table being one block. The engine's counters
  (`decode.blocks_swept`, `decode.blocks_live`) call the same function.
  No `pallas_call(name=...)` and no named scope around the call: the
  benchmark finds the kernel by the name its caller's scope gives it.

  Online-softmax running statistics live in VMEM scratch across each
  partition's sweep; `pl.when` predicates and two scalar loops (page
  copies; the next-live-block scan) are the only control flow — the vector
  body is straight-line (graftcheck GC001, suppressed on those two lines).
  A page copy cannot slice a lane-padded row, so a pool whose channel dim
  is not whole 128-lane rows (off the PagedKVCache "Layout contract") is
  padded by the wrapper, at a pool-sized copy per call.

Split-K partial buffers fold the partition axis into the slot axis
((B*split_k, H, R, C) f32 acc + (B*split_k, H, R, 8) stats) so every
block's last two dims either span the full array dim or are the 8-lane
statistics tile — Mosaic-tileable with no 5-D layouts. The merge is
per-(slot, head, row) elementwise math: under a tensor-parallel shard_map
it runs inside each head shard with ZERO new collectives.

Variants ARE specs over this template, not new sweeps:

  * GQA/MQA — q arrives with H_q = groups * H_kv heads (query head h
    reads K/V head h // groups, consecutive grouping); the wrapper FOLDS
    the group axis into the row axis — q (B, H_q, R, C) reshapes (free:
    contiguous) to (B, H_kv, groups*R, C) and counts tile per group — so
    the kernel body runs unchanged over the pool's H_kv heads with
    groups*R rows per tile. The fold preserves the nondecreasing-counts
    sweep bound (the last tiled row is still a maximal count) and the
    per-row mask (each folded row carries its own count).
  * sliding window (+ attention sinks) — a wider column-mask expression
    (straight-line selects, no lax.cond): a row with `count` visible keys
    keeps cols in [count - sliding_window, count) ∪ [0, attn_sinks), and
    the sweep additionally SKIPS blocks (and, inside a live block, pages)
    that are fully behind every row's window and past the sink prefix —
    the resident work per row is O(window), which is what makes long
    windowed sessions O(1) in T.
  * latent (MLA, absorbed) — `v_lanes` = n: there is ONE pool, whose row is
    a token's normed latent followed by its rotated shared key, stored once;
    K is the whole row and V is a VIEW of its leading n lanes. The kernel
    copies each page once, into the K buffers, and reads the values as
    `k[..., :n]`: no V operand, no V copy, no V buffer. The 128 query heads
    (the up-projection's key half folded into q, models/pangu_ultra.py) are
    the GQA fold's rows over the pool's one head; with one query row a head
    they share one count, read as a scalar instead of a row a sublane.
    `scale` is the published 1 / sqrt(nope + rope), which is not q's width here.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from midgpt_tpu.kernels.flash_attention import _STATS_LANES, _interpret
from midgpt_tpu.ops.online_softmax import (
    M_INIT,
    MASK,
    finalize,
    merge_partials,
    online_block,
)

Array = jax.Array


def normalize_split_k(split_k: int, max_pages: int) -> int:
    """Largest pow2 <= split_k that divides the page-table width.

    Serving page buckets are pow2 (or the pow2-capped max), so any pow2
    split <= max_pages divides it; the loop is the general-case guard for
    direct kernel callers with odd table widths."""
    s = max(1, split_k)
    s = min(s, max_pages)
    s = 1 << (s.bit_length() - 1)  # pow2 floor (applied after the clamp)
    while max_pages % s:
        s //= 2
    return s


# One buffer of a block (K or V, one of the two slots) holds at most this
# many bytes: a grid step's fixed 0.2-0.35 us is then a tenth of the 2.6 us
# its K and V take to arrive at HBM speed (measured: 32 pages a block run
# within 2 % of 64, 16 pages 10-12 % slower; PERF.md PR 27), and K and V
# double-buffered (4 buffers) stay a quarter of the 16 MiB scoped VMEM.
_BLOCK_BYTES = 1024 * 1024
# ... and the f32 score tile (H, rows padded to 8 sublanes, block tokens)
# stays under this, which only ever binds with many folded rows.
_SCORE_BYTES = 512 * 1024


def block_pages(
    n_heads: int,  # pool heads the call sees (one tp shard's)
    lanes: int,  # the pool's channel dim
    itemsize: int,  # bytes per pool element
    page_size: int,
    table_pages: int,  # pages one (slot, partition) sweeps
    n_rows: int,  # query rows per pool head (GQA groups folded in)
) -> int:
    """Pages per compute block: the largest power of two that divides
    `table_pages`, keeps one buffer at or under `_BLOCK_BYTES` and the score
    tile under `_SCORE_BYTES`. An int8 block is sized by the f32 tile it
    dequantizes into. A pure function of what the call sees — the engine's
    counters call it with the same arguments (ROADMAP D5: derived, not
    configured)."""
    page_bytes = n_heads * page_size * lanes * (4 if itemsize == 1 else itemsize)
    rows = -(-n_rows // 8) * 8
    cap = min(
        _BLOCK_BYTES // page_bytes,
        _SCORE_BYTES // (n_heads * rows * 4 * page_size),
        table_pages,
    )
    n = 1 << (max(1, cap).bit_length() - 1)
    while table_pages % n:
        n //= 2
    return n


def block_vmem_bytes(
    n_heads: int, lanes: int, itemsize: int, page_size: int, n: int, v_view: bool = False
) -> int:
    """VMEM the block buffers of one call hold: K and V, two slots each; K
    alone where V is a view of K's lanes (`v_lanes`: a latent pool)."""
    return (2 if v_view else 4) * n_heads * n * page_size * lanes * itemsize


def _seen(tok0, width, first, last, sliding_window: int, attn_sinks: int):
    """Which spans [tok0, tok0 + width) hold a key some row can see, given
    the keys visible to the FIRST and the LAST row (counts are nondecreasing:
    the last row bounds the sweep, the first row's window start is the
    minimum). The kernel's `block_live` and per-page rule, on numpy or jax
    arrays that broadcast."""
    seen = tok0 < last
    if sliding_window:
        ahead = tok0 + width > first - sliding_window
        if attn_sinks:
            ahead = ahead | (tok0 < attn_sinks)
        seen = seen & ahead
    return seen


def block_census(
    first: np.ndarray,  # (N,) keys visible to each slot's FIRST row
    last: np.ndarray,  # (N,) ... and to its last row
    table_pages: int,
    n: int,
    page_size: int,
    sliding_window: int = 0,
    attn_sinks: int = 0,
) -> tp.Tuple[int, int]:
    """(swept, live) grid steps of one call over N slots (or of several
    calls, their slots concatenated), for the engine's counters. Partitions
    lie end to end over the table, so a slot's blocks start at every n-th
    page whatever `split_k` is."""
    tok0 = np.arange(table_pages // n)[None, :] * (n * page_size)
    live = _seen(
        tok0, n * page_size, np.asarray(first)[:, None], np.asarray(last)[:, None],
        sliding_window, attn_sinks,
    )
    return live.size, int(live.sum())


def _tpl_kernel(
    pt_ref,  # (B, max_pages) int32 scalar-prefetch: page table
    cnt_ref,  # (B, R) int32 scalar-prefetch: visible keys per row
    layer_ref,  # (1,) int32 scalar-prefetch: the pool's layer to read
    q_ref,  # (1, H, R, C) — head-major rows
    k_hbm,  # (L, H, P, page_size, C) — the whole pool, left in HBM
    *rest,  # v_hbm (absent with `v_lanes`: V is a view of K's pages);
    # int8 mode: ks_ref, vs_ref (1, 1, H, tokens) f32; then outputs
    # split_k == 1: o_ref (1, H, R, C)
    # split_k > 1:  o_ref (1, H, R, C) f32, m_ref/l_ref (1, H, R, 8) f32
    # then scratch: acc_sc (H, R, C) f32, m_sc/l_sc (H, R, 8) f32,
    #   st_ref (2,) int32 SMEM, sem (2, 2) DMA, k_buf/v_buf (2, H, tokens, C)
    #   (no v_buf with `v_lanes`)
    scale: float,
    page_size: int,
    n_rows: int,
    split_k: int,
    pages_per_split: int,
    n: int,  # pages per compute block (divides pages_per_split)
    quantized: bool,
    sliding_window: int,
    attn_sinks: int,
    v_lanes: int = 0,  # > 0: V is K's leading `v_lanes` lanes (one pool, one copy a page)
    one_count: bool = False,  # every row of a slot sees the same keys (one query row, heads folded)
):
    v_hbm = v_buf = None
    if not v_lanes:
        v_hbm, *rest = rest
    if quantized:
        ks_ref, vs_ref, *rest = rest
    if split_k > 1:
        o_ref, m_ref, l_ref, *rest = rest
    else:
        o_ref, *rest = rest
    if v_lanes:
        acc_sc, m_sc, l_sc, st_ref, sem, k_buf = rest
    else:
        acc_sc, m_sc, l_sc, st_ref, sem, k_buf, v_buf = rest
    # The scalar code below is spelled in lax primitives on int32: a jnp
    # operator costs five times as much to trace, and `//`, `%` and `clip`
    # each leave a nested jit for Mosaic to lower, in every one of the
    # engine's few dozen decode programs (set-up, PERF.md PR 27).
    I = np.int32
    add, sub, mul, lt = jax.lax.add, jax.lax.sub, jax.lax.mul, jax.lax.lt
    b, si, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_slots, n_blocks = pl.num_programs(0), pages_per_split // n
    per_slot = split_k * n_blocks
    step = add(mul(b, I(per_slot)), add(mul(si, I(n_blocks)), i))
    n_steps = mul(n_slots, I(per_slot))
    page0 = add(mul(si, I(pages_per_split)), mul(i, I(n)))  # the block's first page
    tokens = n * page_size
    layer = layer_ref[0]

    @pl.when(step == 0)
    def _first_step():
        # st_ref = [buffer slot of the next live block, the step whose
        # copies are already in flight]. A page that is never fetched
        # leaves its rows of the buffer as they were: zeros here, another
        # page's finite values later, so a masked column's 0 * v is 0.
        st_ref[0] = I(0)
        st_ref[1] = I(-1)
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
        if v_buf is not None:
            v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    @pl.when(i == 0)
    def _init():
        acc_sc[:] = jnp.zeros(acc_sc.shape, acc_sc.dtype)
        m_sc[:] = jnp.full(m_sc.shape, M_INIT, m_sc.dtype)
        l_sc[:] = jnp.zeros(l_sc.shape, l_sc.dtype)

    def ahead(bb, tok0, width):
        """[tok0, tok0 + width) is not wholly BEHIND every row's window
        (counts are nondecreasing, so row 0's window start is the minimum),
        or holds sink tokens."""
        a = lt(sub(cnt_ref[bb, 0], I(sliding_window)), add(tok0, I(width)))
        if attn_sinks:
            a = jax.lax.bitwise_or(a, lt(tok0, I(attn_sinks)))
        return a

    def block_live(bb, first_page):
        """Does the block of slot `bb` that starts at `first_page` hold a
        key any row can see? The last row's count bounds the sweep (counts
        are nondecreasing)."""
        tok0 = mul(first_page, I(page_size))
        lv = lt(tok0, cnt_ref[bb, n_rows - 1])
        if sliding_window:
            lv = jax.lax.bitwise_and(lv, ahead(bb, tok0, tokens))
        return lv

    def block_at(f):
        """(slot, first page) of grid step f."""
        bb, r = jax.lax.div(f, I(per_slot)), jax.lax.rem(f, I(per_slot))
        if split_k == 1:
            return bb, mul(r, I(n))
        return bb, add(
            mul(jax.lax.div(r, I(n_blocks)), I(pages_per_split)),
            mul(jax.lax.rem(r, I(n_blocks)), I(n)),
        )

    def for_each_page(bb, first_page, slot, fn):
        """fn(j, page, slot) for each page j of a block that a row can see.
        ONLY those entries of the page table are ever read: past a slot's
        length, and behind its window, an entry may be anything."""
        seen = sub(cnt_ref[bb, n_rows - 1], mul(first_page, I(page_size)))
        hi = jax.lax.min(jax.lax.div(add(seen, I(page_size - 1)), I(page_size)), I(n))

        def body(j, carry):
            def go():
                fn(j, pt_ref[bb, add(first_page, j)], slot)

            if sliding_window:
                tok0 = mul(add(first_page, j), I(page_size))
                pl.when(ahead(bb, tok0, page_size))(go)
            else:
                go()
            return carry

        jax.lax.fori_loop(I(0), hi, body, None)  # graftcheck: disable=GC001 — a scalar loop that issues DMAs; the vector body stays straight-line

    def copies(j, page, slot):
        rows = pl.ds(pl.multiple_of(mul(j, I(page_size)), page_size), page_size)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, :, page], k_buf.at[slot, :, rows], sem.at[0, slot])
        if v_buf is None:
            return [k_copy]
        return [
            k_copy,
            pltpu.make_async_copy(v_hbm.at[layer, :, page], v_buf.at[slot, :, rows], sem.at[1, slot]),
        ]

    def start(j, page, slot):
        for c in copies(j, page, slot):
            c.start()

    def wait(j, page, slot):
        for c in copies(j, page, slot):
            c.wait()

    @pl.when(block_live(b, page0))
    def _compute():
        slot = st_ref[0]

        @pl.when(st_ref[1] != step)
        def _fetch_own():  # the call's first live block: nobody fetched it
            for_each_page(b, page0, slot, start)

        # The next live block's copies fly while this one is computed,
        # across the slot boundary too.
        def dead(f):
            bb, first_page = block_at(jax.lax.min(f, sub(n_steps, I(1))))
            return jax.lax.bitwise_and(
                lt(f, n_steps), jax.lax.bitwise_not(block_live(bb, first_page))
            )

        nxt = jax.lax.while_loop(dead, lambda f: add(f, I(1)), add(step, I(1)))  # graftcheck: disable=GC001 — a scalar search over grid steps; the vector body stays straight-line

        @pl.when(lt(nxt, n_steps))
        def _fetch_next():
            for_each_page(*block_at(nxt), sub(I(1), slot), start)
            st_ref[1] = nxt

        st_ref[0] = sub(I(1), slot)
        for_each_page(b, page0, slot, wait)

        tok0 = mul(page0, I(page_size))
        if one_count:
            counts = cnt_ref[b, 0]  # a scalar: no row-a-sublane vector of 128 equal counts
        else:
            counts = jnp.stack([cnt_ref[b, t] for t in range(n_rows)])  # (R,)
        q = q_ref[0]  # (H, R, C)
        k = k_buf[slot]  # (H, tokens, C)
        v = k[:, :, :v_lanes] if v_lanes else v_buf[slot]
        if quantized:
            # Dequantize in VMEM: the block's f32 scales broadcast over C
            # (exact — int8 * f32, ops/quant.py), then the same dots as
            # the bf16 path in f32.
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32) * ks_ref[0, 0][:, :, None]
            v = v.astype(jnp.float32) * vs_ref[0, 0][:, :, None]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # (H, R, tokens) f32
        col = tok0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # ops/attention.visible_mask spelled as straight-line selects
        # (no lax.cond — graftcheck GC001): causal/length bound, then the
        # window [count - W, count) widened by the sink prefix [0, sinks).
        # (the broadcast is spelled at each use: one shared value is another program text)
        see = (lambda: counts) if one_count else (lambda: counts[None, :, None])
        keep = col < see()
        if sliding_window:
            w = col >= see() - sliding_window
            if attn_sinks:
                w |= col < attn_sinks
            keep &= w
        s = jnp.where(keep, s, MASK)

        m_new, alpha, prob, l_new = online_block(m_sc[:, :, 0], l_sc[:, :, 0], s)
        pv = jax.lax.dot_general(
            prob.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (H, R, C)
        acc_sc[:] = acc_sc[:] * alpha[:, :, None] + pv
        m_sc[:] = jnp.broadcast_to(m_new[:, :, None], m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new[:, :, None], l_sc.shape)

    @pl.when(i == n_blocks - 1)
    def _emit():
        if split_k > 1:
            # Raw partials out; merge_partials + finalize run outside.
            o_ref[0] = acc_sc[:]
            m_ref[0] = m_sc[:]
            l_ref[0] = l_sc[:]
        else:
            out, _ = finalize(m_sc[:, :, 0], l_sc[:, :, 0], acc_sc[:])
            o_ref[0] = out.astype(o_ref.dtype)


# jit(inline=True): the 12 or 24 layers of a serving program call this with
# the same shapes, and a decode program's set-up is mostly tracing and lowering
# those calls (PERF.md PR 24). The jit traces the wrapper and the kernel body
# once per shape and process; inlined, it adds no equation and no scope of its
# own (the custom call keeps its caller's innermost scope for a name, which
# the benchmark's reader matches), and the pallas_call equations of one
# program share one body, which lowers once.
@functools.partial(
    jax.jit,
    static_argnames=("split_k", "sliding_window", "attn_sinks", "pages_per_block", "v_dim", "v_lanes", "scale"),
    inline=True,
)
def paged_attention_template(
    q: Array,  # (B, H_q, R, C) — head-major query rows (H_q >= pool heads)
    k_pages: Array,  # (L, H_kv, num_pages, page_size, C) — the WHOLE pool
    v_pages: tp.Optional[Array],  # (or one layer's (H_kv, P, ps, C) with layer=None); None with `v_lanes`
    page_table: Array,  # (B, max_pages) int32
    counts: Array,  # (B, R) int32 — keys visible to row r of slot b
    k_scale: tp.Optional[Array] = None,  # (L, num_pages, H_kv, page_size) f32
    v_scale: tp.Optional[Array] = None,  # (or (P, H_kv, ps) with layer=None)
    split_k: int = 1,
    sliding_window: int = 0,
    attn_sinks: int = 0,
    layer: tp.Optional[Array] = None,  # () int — which layer of the pool
    pages_per_block: tp.Optional[int] = None,  # tests and sweeps; else derived
    v_dim: tp.Optional[int] = None,  # V's head width where it is not q's (K 192 / V 128)
    v_lanes: tp.Optional[int] = None,  # V is the leading `v_lanes` lanes of K's pages (a latent pool; `v_pages` None)
    scale: tp.Optional[float] = None,  # the scores' factor where it is not q's width's rsqrt
) -> Array:
    """Instantiate the template for one (n_rows, quantized, split_k,
    kv_groups, window) spec.

    Returns (B, H_q, R, C) in q.dtype — (B, H_q, R, v_dim) where the V pool
    has a head width (and lanes) of its own: the K and V page copies, the V
    buffers, the accumulator and the output then take V's lanes, and nothing
    else in the sweep knows (scores scale by q's width). With `v_lanes` there
    is one pool and no V operand: the kernel reads the values out of the K
    buffer's leading lanes (module docstring, "latent"), the output is (B,
    H_q, R, v_lanes). int8 pools require both scale side
    buffers; bf16/f32 pools take none. split_k is normalized to a pow2
    divisor of the table width; split_k == 1 is the classic in-kernel
    finalize, split_k > 1 emits per-partition partials and merges them
    here (f32, ops/online_softmax) before the final dtype cast.

    GQA/MQA is inferred from the shapes: when q carries groups = H_q/H_kv
    query heads per pool head, the group axis folds into the row axis
    (module docstring) and unfolds on the way out — the kernel body and
    every BlockSpec see plain H_kv-head geometry. sliding_window/attn_sinks
    are static mask/sweep parameters (0 = full causal, bit-identical to
    the windowless template).

    The pool is NEVER sliced outside the kernel: with `layer` given the
    operands are the whole 5-D K/V pools (and 4-D scale buffers), the layer
    index rides as a third scalar-prefetch operand and every page
    BlockSpec squeezes a leading layer dim that its index map fills in, so
    the custom call reads the pool in the one layout it lives in and XLA
    has no layer-sized slice to materialise (PagedKVCache docstring,
    "Layout contract"). `layer=None` is the one-layer form the kernel
    tests call: the 4-D operands gain a unit layer dim (a bitcast)."""
    if v_lanes:
        if v_pages is not None or k_scale is not None:
            raise ValueError("v_lanes: V is a view of the K pool (no v_pages), and no int8 pool is wired")
        v_pages = k_pages  # shapes only below: the call carries one pool
    if layer is None:
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    B, HQ, R, C = q.shape
    _, H, _, page_size, lanes = k_pages.shape
    lanes_v = v_pages.shape[-1]
    scale = 1.0 / math.sqrt(C) if scale is None else scale
    if v_lanes and (lanes % 128 or v_lanes % 128 or v_lanes > lanes):
        # the view is a slice of whole 128-lane rows of the K buffer; a pool off
        # the layout contract takes the XLA path (models/pangu_ultra.py)
        raise ValueError(f"v_lanes={v_lanes} of a pool of {lanes} lanes: both must be whole 128-lane rows")
    if lanes % 128 or lanes_v % 128:
        # Off the layout contract (a pool allocated without `kernel_layout`:
        # direct callers, the benchmark's correctness check): the chip keeps
        # such rows lane-padded and a page copy cannot slice them, so the
        # pool is padded to whole rows here — a pool-sized copy per call,
        # which a ServeEngine pool never takes.
        widen = lambda n: [(0, 0)] * 4 + [(0, -n % 128)]
        k_pages, v_pages = jnp.pad(k_pages, widen(lanes)), jnp.pad(v_pages, widen(lanes_v))
        lanes, lanes_v = k_pages.shape[-1], v_pages.shape[-1]
    if lanes > C:
        # A kernel-path pool is allocated at whole 128-lane rows and holds
        # zeros past head_dim (PagedKVCache "Layout contract"): q meets it
        # there with zeros, which add nothing to q.k, and the output's
        # extra lanes (p @ zeros) are dropped on the way out.
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, lanes - C)])
    C_out, C, Cv = v_lanes or v_dim or C, lanes, v_lanes or lanes_v
    groups = HQ // H
    if groups > 1:
        # Fold: head h = kv*groups + g, so (B, HQ, R, C) is contiguously
        # (B, H, groups, R, C); folded row g*R + r keeps row r's count.
        q = q.reshape(B, H, groups * R, C)
        counts = jnp.tile(counts, (1, groups))
    R_full, R = R, groups * R
    max_pages = page_table.shape[1]
    split_k = normalize_split_k(split_k, max_pages)
    pps = max_pages // split_k
    quantized = k_scale is not None
    n = pages_per_block or block_pages(
        H, C, k_pages.dtype.itemsize, page_size, pps, R
    )
    if pps % n:
        raise ValueError(f"pages_per_block {n} does not divide {pps} pages")
    tokens = n * page_size

    # The pools stay in HBM whole: the kernel copies the pages it needs.
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, H, R, C), lambda b, si, i, pt, cnt, ly: (b, 0, 0, 0)),
        hbm,
        hbm,
    ]
    operands = [q, k_pages, v_pages]
    if v_lanes:
        in_specs, operands = in_specs[:2], operands[:2]
    if quantized:
        # The scale rows are 1/C of the pages' bytes in (H, page_size)
        # pieces, narrower than any copy the chip makes: XLA gathers each
        # slot's rows through the table into one (H, tokens) tile a block,
        # which rides an ordinary BlockSpec. The never-dereference rule
        # holds here as a select: an entry no row can see is read as page 0
        # and its scales as 0, so the block dequantizes to finite values.
        seen = _seen(
            jnp.arange(max_pages, dtype=jnp.int32)[None, :] * page_size, page_size,
            counts[:, :1], counts[:, -1:], sliding_window, attn_sinks,
        )

        def block_scales(scales):
            g = scales[layer, jnp.where(seen, page_table, 0)]  # (B, max_pages, H, ps)
            g = jnp.where(seen[:, :, None, None], g, 0.0)
            g = g.reshape(B, max_pages // n, n, H, page_size)
            return g.transpose(0, 1, 3, 2, 4).reshape(B, max_pages // n, H, tokens)

        scale_spec = pl.BlockSpec(
            (1, 1, H, tokens),
            lambda b, si, i, pt, cnt, ly: (b, si * (pps // n) + i, 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [block_scales(k_scale), block_scales(v_scale)]
    scratch = [
        pltpu.VMEM((H, R, Cv), jnp.float32),
        pltpu.VMEM((H, R, _STATS_LANES), jnp.float32),
        pltpu.VMEM((H, R, _STATS_LANES), jnp.float32),
        pltpu.SMEM((2,), jnp.int32),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((2, H, tokens, C), k_pages.dtype),
        pltpu.VMEM((2, H, tokens, Cv), v_pages.dtype),
    ]
    if v_lanes:
        scratch.pop()  # the values are read out of the K buffers

    if split_k > 1:
        # Partition axis folded into the slot axis: 4-D partial buffers
        # whose trailing block dims span the full array dims (Mosaic rule).
        part_idx = lambda b, si, i, pt, cnt, ly: (b * split_k + si, 0, 0, 0)
        out_specs = [
            pl.BlockSpec((1, H, R, Cv), part_idx),
            pl.BlockSpec((1, H, R, _STATS_LANES), part_idx),
            pl.BlockSpec((1, H, R, _STATS_LANES), part_idx),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((B * split_k, H, R, Cv), jnp.float32),
            jax.ShapeDtypeStruct((B * split_k, H, R, _STATS_LANES), jnp.float32),
            jax.ShapeDtypeStruct((B * split_k, H, R, _STATS_LANES), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec(
            (1, H, R, Cv), lambda b, si, i, pt, cnt, ly: (b, 0, 0, 0)
        )
        out_shape = jax.ShapeDtypeStruct((B, H, R, Cv), q.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, split_k, pps // n),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _tpl_kernel, scale=scale, page_size=page_size, n_rows=R,
            split_k=split_k, pages_per_split=pps, n=n, quantized=quantized,
            sliding_window=sliding_window, attn_sinks=attn_sinks,
            **(dict(v_lanes=v_lanes, one_count=R_full == 1) if v_lanes else {}),
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # Every axis runs in order on one core: a block's copies are
            # started by the live block BEFORE it, whichever slot or
            # partition that one belongs to.
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=_interpret(),
    )(
        page_table.astype(jnp.int32),
        counts.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *operands,
    )
    if split_k == 1:
        out = out.reshape(B, HQ, R_full, Cv) if groups > 1 else out
        return out[..., :C_out]
    o, m, l = out
    o = o.reshape(B, split_k, H, R, Cv)
    m = m.reshape(B, split_k, H, R, _STATS_LANES)[..., 0]
    l = l.reshape(B, split_k, H, R, _STATS_LANES)[..., 0]
    m, l, acc = merge_partials(m, l, o, axis=1)
    merged, _ = finalize(m, l, acc)
    merged = merged.astype(q.dtype)
    merged = merged.reshape(B, HQ, R_full, Cv) if groups > 1 else merged
    return merged[..., :C_out]
