"""Paged decode attention (kernels/decode_attention.py): the Pallas kernel
(interpret mode — no TPU in CI), the XLA gather fallback, and a dense
masked reference must agree on arbitrary page tables and ragged lengths."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.kernels import attention_template as at
from midgpt_tpu.kernels.decode_attention import (
    paged_attention,
    paged_attention_gather,
    paged_attention_kernel,
    paged_verify_attention_gather,
    paged_verify_attention_kernel,
)
from midgpt_tpu.ops.quant import quantize_q8

B, H, C = 3, 2, 128  # C spans the full Mosaic lane dim
PS, NP, MP = 8, 7, 4  # page_size, pool pages, max logical pages/slot


def _problem(seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, C), dtype)
    k_pages = jax.random.normal(keys[1], (H, NP, PS, C), dtype)
    v_pages = jax.random.normal(keys[2], (H, NP, PS, C), dtype)
    # Non-trivial allocation: slots own disjoint, non-contiguous pages;
    # unallocated logical pages point at the sink (0).
    page_table = jnp.asarray(
        [[3, 1, 0, 0], [5, 2, 6, 0], [4, 0, 0, 0]], jnp.int32
    )
    lengths = jnp.asarray([11, 24, 1], jnp.int32)  # ragged, page-unaligned
    return q, k_pages, v_pages, page_table, lengths


def _dense_reference(q, k_pages, v_pages, page_table, lengths):
    """Materialize each slot's logical K/V and run plain masked attention."""
    out = []
    for b in range(B):
        kb = np.concatenate(
            [np.asarray(k_pages)[:, p] for p in np.asarray(page_table)[b]], axis=1
        )  # (H, MP*PS, C)
        vb = np.concatenate(
            [np.asarray(v_pages)[:, p] for p in np.asarray(page_table)[b]], axis=1
        )
        n = int(lengths[b])
        s = np.einsum("hc,hkc->hk", np.asarray(q)[b], kb) / math.sqrt(C)
        s[:, n:] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hk,hkc->hc", p, vb))
    return np.stack(out)


def test_gather_fallback_matches_dense_reference():
    q, kp, vp, pt, ln = _problem()
    got = paged_attention_gather(q, kp, vp, pt, ln)
    np.testing.assert_allclose(
        np.asarray(got), _dense_reference(q, kp, vp, pt, ln), atol=2e-5, rtol=2e-5
    )


def test_kernel_interpret_matches_gather():
    """The Mosaic kernel (interpret mode off-TPU) must reproduce the gather
    fallback — including mid-page masking and the length-0/sink-read path —
    so the serving engine can switch impl by backend without parity drift."""
    q, kp, vp, pt, ln = _problem(seed=1)
    want = np.asarray(paged_attention_gather(q, kp, vp, pt, ln))
    got = np.asarray(paged_attention_kernel(q, kp, vp, pt, ln))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_zero_length_slot_is_finite_zero():
    """A just-admitted (length 0) slot must emit zeros, not NaN (the
    l == 0 safe-divide in the kernel epilogue)."""
    q, kp, vp, pt, _ = _problem(seed=2)
    ln = jnp.asarray([0, 5, 0], jnp.int32)
    got = np.asarray(paged_attention_kernel(q, kp, vp, pt, ln))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], 0.0)
    np.testing.assert_array_equal(got[2], 0.0)


def test_dispatcher_selects_gather_off_tpu():
    q, kp, vp, pt, ln = _problem(seed=3)
    auto = paged_attention(q, kp, vp, pt, ln, impl="auto")
    gather = paged_attention_gather(q, kp, vp, pt, ln)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(gather))
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        paged_attention(q, kp, vp, pt, ln, impl="nope")


# ----------------------------------------------------------------------
# Compute blocks of several pages, fetched by the kernel's own copies
# (kernels/attention_template.py "Skeleton"): every variant against the
# gather reference over MULTI-BLOCK tables whose dead entries are poisoned.
# ----------------------------------------------------------------------

BLK_H, BLK_PS, BLK_MP = 2, 8, 8  # pool heads, page size, table width
# one short of / at / one past the 16-token block boundary, 0, 1, mid, full
BLK_LENGTHS = (0, 1, 15, 16, 17, 40, 64)
BENIGN = 1  # a zero page: where the CLEAN table parks what no row can see


# A prefill call's rows, (start, n_valid) of a 16-token chunk each: a first
# chunk, a ragged one, an EMPTY row (one key of its first page: the engine's
# sink), later chunks at and off a page boundary, the table's last tokens.
CHUNK_ROWS = ((0, 16), (0, 5), (0, 0), (16, 16), (23, 9), (40, 16), (48, 16))


def _block_problem(seed, groups=1, n_rows=1, quantized=False, lengths=BLK_LENGTHS,
                   table_pages=BLK_MP, window=0, sinks=0, chunks=None, dtype=jnp.float32):
    """(q, k_pages, v_pages, clean table, dirty table, counts, scales).

    `chunks`: rows of a prefill call instead of `lengths`; row t of a chunk
    sees start + t + 1 keys, clamped at the chunk's last valid token, an
    empty row one (GPT.prefill_paged_chunk's `attn_counts`).

    Live pages are out of order and non-contiguous in the pool. Pages 0, 2
    and the pool's last are POISON (NaN; NaN scales on an int8 pool): the
    dirty table points every entry no row can see — past the slot's last
    visible key, or reclaimed behind its window — at -1, at a poison page
    or out of range. One fetched poison page puts NaN into the output; the
    clean table parks the same entries on a zero page for the reference."""
    rng = np.random.default_rng(seed)
    B = len(lengths if chunks is None else chunks)
    n_pool = B * table_pages + 4
    real = 3 + rng.permutation(B * table_pages).reshape(B, table_pages)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, n_rows, BLK_H * groups, C), dtype)
    k_pages = jax.random.normal(keys[1], (BLK_H, n_pool, BLK_PS, C), dtype)
    v_pages = jax.random.normal(keys[2], (BLK_H, n_pool, BLK_PS, C), dtype)
    poison = np.asarray([0, 2, n_pool - 1])
    scales = (None, None)
    if quantized:
        k_pages, ks = quantize_q8(k_pages)  # (H, P, ps, C) int8, (H, P, ps) f32
        v_pages, vs = quantize_q8(v_pages)
        ks, vs = ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)  # (P, H, ps)
        scales = (ks.at[poison].set(jnp.nan), vs.at[poison].set(jnp.nan))
        k_pages, v_pages = k_pages.at[:, BENIGN].set(0), v_pages.at[:, BENIGN].set(0)
    else:
        k_pages = k_pages.at[:, poison].set(jnp.nan).at[:, BENIGN].set(0.0)
        v_pages = v_pages.at[:, poison].set(jnp.nan).at[:, BENIGN].set(0.0)
    if chunks is None:
        lengths = np.asarray(lengths)
        counts = lengths[:, None] + (np.arange(n_rows)[None, :] + 1) * (lengths[:, None] > 0)
    else:
        start, n_valid = np.asarray(chunks).T[:, :, None]
        counts = np.maximum(np.minimum(start + np.arange(n_rows), start + n_valid - 1) + 1, 1)
    page0 = np.arange(table_pages)[None, :] * BLK_PS
    seen = page0 < counts[:, -1:]
    if window:
        seen &= (page0 + BLK_PS > counts[:, :1] - window) | (page0 < sinks)
    junk = rng.choice([-1, 0, 2, n_pool - 1, n_pool + 5], size=real.shape)
    clean = np.where(seen, real, BENIGN)
    dirty = np.where(seen, real, junk)
    return (q, k_pages, v_pages, jnp.asarray(clean, jnp.int32),
            jnp.asarray(dirty, jnp.int32), jnp.asarray(counts, jnp.int32), scales)


BLOCK_CASES = {
    # id: (problem kwargs, template kwargs)
    "decode": ({}, dict(pages_per_block=2)),
    "decode_block_of_4": ({}, dict(pages_per_block=4)),
    "table_narrower_than_a_block": (dict(table_pages=2, lengths=(0, 1, 9, 16)), {}),
    "derived_width": ({}, {}),
    "verify5": (dict(n_rows=5, lengths=(0, 1, 11, 12, 13, 40, 59)), dict(pages_per_block=2)),
    "int8": (dict(quantized=True), dict(pages_per_block=2)),
    "int8_verify5": (dict(quantized=True, n_rows=5, lengths=(0, 1, 11, 12, 13, 40, 59)),
                     dict(pages_per_block=2)),
    "split2": ({}, dict(pages_per_block=2, split_k=2)),
    "split4": ({}, dict(pages_per_block=2, split_k=4)),
    "split2_int8": (dict(quantized=True), dict(pages_per_block=2, split_k=2)),
    "gqa4": (dict(groups=4), dict(pages_per_block=2)),
    "gqa4_verify5_split2": (dict(groups=4, n_rows=5, lengths=(0, 1, 11, 12, 13, 40, 59)),
                            dict(pages_per_block=2, split_k=2)),
    "window_sinks_reclaimed": (dict(window=24, sinks=4),
                               dict(pages_per_block=2, sliding_window=24, attn_sinks=4)),
    "window_reclaimed_split2": (dict(window=24),
                                dict(pages_per_block=2, sliding_window=24, split_k=2)),
    # the multi-row spec at a prefill chunk's width (GPT.prefill_paged_chunk)
    "chunk16": (dict(n_rows=16, chunks=CHUNK_ROWS), dict(pages_per_block=2)),
    "chunk16_derived_width": (dict(n_rows=16, chunks=CHUNK_ROWS), {}),
    "chunk16_bf16": (dict(n_rows=16, chunks=CHUNK_ROWS, dtype=jnp.bfloat16), dict(pages_per_block=2)),
    "chunk16_int8": (dict(n_rows=16, chunks=CHUNK_ROWS, quantized=True), dict(pages_per_block=2)),
    "chunk16_window_sinks": (dict(n_rows=16, chunks=CHUNK_ROWS, window=24, sinks=4),
                             dict(pages_per_block=2, sliding_window=24, attn_sinks=4)),
    "chunk16_gqa2": (dict(n_rows=16, chunks=CHUNK_ROWS, groups=2), dict(pages_per_block=2)),
}


@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_kernel_blocks_match_gather_and_never_read_dead_entries(case):
    """The kernel over the DIRTY table equals the gather reference over the
    clean one: blocks that span scattered pages, lengths around a block
    boundary, every variant over several blocks, and no table entry read
    that no row can see (a poison page would put NaN in the output)."""
    pkw, tkw = BLOCK_CASES[case]
    q, kp, vp, clean, dirty, counts, (ks, vs) = _block_problem(
        sum(map(ord, case)), **pkw
    )
    ref_kw = {k: v for k, v in tkw.items() if k != "pages_per_block"}
    want = np.asarray(
        paged_verify_attention_gather(q, kp, vp, clean, counts, ks, vs, **ref_kw)
    )
    got = np.asarray(
        at.paged_attention_template(
            q.transpose(0, 2, 1, 3), kp, vp, dirty, counts, ks, vs, **tkw
        )
    ).transpose(0, 2, 1, 3)
    got, want = got.astype(np.float32), want.astype(np.float32)
    assert np.isfinite(got).all()
    empty = np.asarray(counts)[:, -1] == 0  # the reference's softmax of nothing
    np.testing.assert_array_equal(got[empty], 0.0)
    # bf16: the reference rounds its scores to bf16, the kernel keeps them f32
    tol = 3e-5 if q.dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[~empty], want[~empty], atol=tol, rtol=tol)


def test_verify_wrapper_runs_the_blocked_template():
    """The (B, T, H, C) wrapper the engine's verify path calls, on a table
    of several derived-width blocks."""
    q, kp, vp, clean, dirty, counts, _ = _block_problem(7, n_rows=3, lengths=(5, 33, 64))
    want = np.asarray(paged_verify_attention_gather(q, kp, vp, clean, counts))
    got = np.asarray(paged_verify_attention_kernel(q, kp, vp, dirty, counts))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize(
    "shape, n, vmem",
    [
        # serve_124m_sample: 12 heads x 128 lanes (64 channels, padded), bf16
        (dict(n_heads=12, lanes=128, itemsize=2, page_size=8, table_pages=32, n_rows=1), 32, 3 << 20),
        (dict(n_heads=12, lanes=128, itemsize=2, page_size=8, table_pages=4, n_rows=1), 4, 384 << 10),
        # serve_xl_chat: 16 heads x 128 channels; the 1,024-token bucket splits in 2
        (dict(n_heads=16, lanes=128, itemsize=2, page_size=8, table_pages=64, n_rows=1), 32, 4 << 20),
        (dict(n_heads=16, lanes=128, itemsize=2, page_size=8, table_pages=128, n_rows=1), 32, 4 << 20),
        # an odd table (direct callers): the largest pow2 that divides it
        (dict(n_heads=16, lanes=128, itemsize=2, page_size=8, table_pages=24, n_rows=1), 8, 1 << 20),
        # a prefill chunk's 16 rows keep decode's block at both shapes
        (dict(n_heads=12, lanes=128, itemsize=2, page_size=8, table_pages=32, n_rows=16), 32, 3 << 20),
        (dict(n_heads=16, lanes=128, itemsize=2, page_size=8, table_pages=128, n_rows=16), 32, 4 << 20),
        # wider chunks: the f32 score tile (heads x rows x block tokens) halves the block past 40 / 32 rows
        (dict(n_heads=12, lanes=128, itemsize=2, page_size=8, table_pages=128, n_rows=64), 16, 3 << 19),
        (dict(n_heads=16, lanes=128, itemsize=2, page_size=8, table_pages=128, n_rows=128), 8, 1 << 20),
    ],
    ids=["124m_t32", "124m_t4", "xl_t64", "xl_t128", "odd_table", "124m_chunk16", "xl_chunk16",
         "124m_chunk64", "xl_chunk128"],
)
def test_block_width_is_derived_from_the_shapes(shape, n, vmem):
    """Pages a block, and the VMEM its buffers take, at the two benchmark
    shapes: a pure function of what the call sees (ROADMAP D5)."""
    assert at.block_pages(**shape) == n
    assert shape["table_pages"] % n == 0
    got = at.block_vmem_bytes(
        shape["n_heads"], shape["lanes"], shape["itemsize"], shape["page_size"], n
    )
    assert got == vmem and got <= 4 << 20  # a quarter of the 16 MiB scoped default


def test_block_census_is_the_kernels_live_rule():
    """The engine's counter arithmetic: blocks a call sweeps and blocks
    that hold a visible key, window and sinks included."""
    last = np.asarray([0, 1, 128, 129, 300])
    assert at.block_census(last, last, 64, 16, 8) == (5 * 4, 0 + 1 + 1 + 2 + 3)
    # window 100: [200, 300) is visible; block 1 ([128, 256)) and 2 are live,
    # block 0 only through its 4 sink tokens
    assert at.block_census(last[4:], last[4:], 64, 16, 8, 100) == (4, 2)
    assert at.block_census(last[4:], last[4:], 64, 16, 8, 100, 4) == (4, 3)
