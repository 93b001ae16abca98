"""A prefill chunk's attention through the paged-attention template's multi-row
spec (GPT.prefill_paged_chunk, `attn_impl="kernel"`: interpret mode here)
against the XLA gather lowering of the same arithmetic: logits and every
written pool row, for the (B,) form the engine's batched call makes and the
scalar one-row form that hands back all T_c logits, at the cells' chunk of 16
and at a wider one; and an engine on either lowering serving the same tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
from midgpt_tpu.sampling.serve import ServeEngine

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=64, n_kv_heads=2)
PS, T_C = 8, 16


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _prefill(params, impl, tokens, start, n_valid, table, cache_dtype=jnp.float32):
    """Two chunks a row (the second reads what the first wrote): logits of
    the second call and the pool, at `head_dim` lanes whatever the layout."""
    T_C = tokens.shape[-1]
    cache = PagedKVCache.init(CFG, 20, PS, cache_dtype, kernel_layout=impl == "kernel")
    first = jnp.minimum(start, T_C)  # rows past their first chunk: [0, T_C) came before
    _, cache = GPT.prefill_paged_chunk(
        CFG, params, tokens[0], jnp.zeros_like(start), first, cache, table, attn_impl=impl
    )
    logits, cache = GPT.prefill_paged_chunk(
        CFG, params, tokens[1], start, n_valid, cache, table, attn_impl=impl
    )
    pools = [np.asarray(a[..., : CFG.head_dim], np.float32) for a in (cache.k, cache.v)]
    scales = [np.asarray(a) for a in (cache.k_scale, cache.v_scale) if a is not None]
    return np.asarray(logits, np.float32), pools + scales


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8], ids=["f32", "int8"])
def test_batched_chunks_through_the_kernel_match_the_gather(params, cache_dtype, chunk):
    """Four rows: a second chunk, a ragged second chunk, a first chunk, an
    EMPTY row on the sink page (GQA groups 2: 32 or 64 rows a pool head; at
    64 the template's block is narrower than a decode step's)."""
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 4, chunk)), jnp.int32)
    start = jnp.asarray([chunk, chunk, 0, 0], jnp.int32)
    n_valid = jnp.asarray([chunk, 7, 11, 0], jnp.int32)
    pages = 2 * chunk // PS  # a row's table: both chunks
    table = np.zeros((4, pages), np.int32)
    live = 1 + rng.permutation(19)  # out of order, no page shared; page 0 is the sink
    table[0], table[1], table[2, :2] = live[:pages], live[pages : 2 * pages], live[2 * pages : 2 * pages + 2]
    table = jnp.asarray(table)
    want, want_pools = _prefill(params, "gather", tokens, start, n_valid, table, cache_dtype)
    got, got_pools = _prefill(params, "kernel", tokens, start, n_valid, table, cache_dtype)
    assert got.shape == (4, CFG.vocab_size)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-4, rtol=2e-4)  # the empty row's are garbage
    assert np.isfinite(got).all()
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_allclose(a[:, :, 1:], b[:, :, 1:], atol=1e-5, rtol=1e-5)  # page 0: the sink


def test_the_one_row_call_hands_back_every_rows_logits(params):
    """Scalar `start` / `n_valid`: (1, T_c, V), the valid rows' compared."""
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 1, T_C)), jnp.int32)
    table = jnp.asarray([[5, 2, 9, 4]], jnp.int32)
    args = (tokens, jnp.asarray(16, jnp.int32), jnp.asarray(13, jnp.int32), table)
    want, want_pools = _prefill(params, "gather", *args)
    got, got_pools = _prefill(params, "kernel", *args)
    assert got.shape == (1, T_C, CFG.vocab_size)
    np.testing.assert_allclose(got[0, :13], want[0, :13], atol=2e-4, rtol=2e-4)
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_allclose(a[:, :, 1:], b[:, :, 1:], atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("cache_dtype", ["float32", "int8"])
def test_an_engine_serves_the_same_tokens_on_either_lowering(params, cache_dtype):
    """The engine end to end, greedy: prompts of one to three chunks, a call
    with an empty row, decode reading what prefill wrote. `attn_impl` alone
    picks the prefill's lowering (interpret mode here), as for decode."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32) for n in (21, 9, 40)]

    def served(impl):
        eng = ServeEngine(CFG, params, page_size=PS, prefill_chunk=T_C, decode_chunk=4, temperature=0.0,
                          max_slots=2, num_pages=17, cache_dtype=cache_dtype, attn_impl=impl)
        uids = [eng.submit(p, 4) for p in prompts]
        done = eng.run()
        assert eng.prefill_calls > 1
        return [done[u].tokens.tolist() for u in uids]

    assert served("kernel") == served("gather")
