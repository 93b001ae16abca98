"""One prefill program a round (sampling/serve.py `_prefill_round`): the
chunks of the prefilling slots ride as rows of one `(prefill_width,
prefill_chunk)` batch and the program hands back one logits row a slot.

* the model function: a batch of rows with different `start`, `n_valid` and
  page counts, one of them empty, leaves the pool and the last-valid-row
  logits that the one-row calls run in turn leave (plain / GQA / window +
  sinks / int8 pool / Trinity's two kinds of pool with routed layers);
* the engine: N prompts admitted in one round go in ceil(N / W) programs,
  greedy tokens are `generate`'s, and a slot evicted while the batch's pages
  are being found is not in the batch;
* the width rule, and the token rows each family states for it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.models.ouro import Ouro
from midgpt_tpu.models.trinity import Trinity
from midgpt_tpu.obs import Observability
from midgpt_tpu.sampling import serve
from midgpt_tpu.sampling.engine import generate
from midgpt_tpu.ops.moe import moe_prefill_rows
from midgpt_tpu.sampling.serve import ServeEngine, prefill_width
from test_trinity import toy

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32)
TRINITY = toy()  # three window layers of 8 beside a global one, a dense and four routed FFNs of 16 experts (test_trinity.py)
VARIANTS = {
    "plain": (CFG, jnp.float32),
    "gqa": (dataclasses.replace(CFG, n_kv_heads=2), jnp.float32),
    "window_sinks": (dataclasses.replace(CFG, n_kv_heads=2, sliding_window=16, attn_sinks=4), jnp.float32),
    "int8_pool": (CFG, jnp.int8),
    "trinity_two_kinds": (TRINITY, jnp.float32),
}
PS, T_C = 8, 16


def _chunk(seq, start, n):
    buf = np.zeros((1, T_C), np.int32)
    buf[0, :n] = seq[start : start + n]
    return jnp.asarray(buf)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_batched_rows_equal_one_row_calls_in_turn(variant):
    """Trinity: the same rows over TWO kinds of pool, row 1's window pages
    behind `start - sliding_window` RECLAIMED in the batch's window table
    (parked on the sink page, as `PagePool.table` hands them) and whole in its
    own call's: equal logits say they are never read."""
    cfg, pool_dtype = VARIANTS[variant]
    model = cfg.model()
    two_kinds = model is Trinity
    params = model.init(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(5)
    # row: (start, n_valid, pages its own call's table holds: every row another bucket); row 2 is empty
    rows = [(0, 16, 2), (24, 5, 4), (0, 0, 1), (8, 16, 4)]
    seqs = [rng.integers(0, cfg.vocab_size, 64).astype(np.int32) for _ in rows]
    MP = 8  # the batch's table: the bucket of nobody's own call
    tables = np.zeros((len(rows), MP), np.int32)  # the empty row: the sink page
    for r in (0, 1, 3):
        tables[r] = 1 + r * MP + rng.permutation(MP)  # a slot's pages lie anywhere
    reclaimed = tables.copy()
    reclaimed[1, : (24 + 1 - TRINITY.sliding_window) // PS] = 0  # what no query from position 24 on can see
    assert (reclaimed[1] == 0).sum() == 2
    # the family's table argument: the one table, or every kind's (global, window)
    tabs = lambda t, window=None: (jnp.asarray(t), jnp.asarray(t if window is None else window)) if two_kinds else jnp.asarray(t)
    prefill = jax.jit(lambda t, s, n, c, tab: model.prefill_paged_chunk(cfg, params, t, s, n, c, tab))
    i32 = lambda a: jnp.asarray(a, jnp.int32)

    # what the slots already hold: rows 1 and 3 start mid-prompt
    n_pages = 1 + len(rows) * MP
    cache = model.init_cache(cfg, (n_pages,) * len(model.cache_kinds(cfg)), PS, pool_dtype)
    for r, (start, _, _) in enumerate(rows):
        for pos in range(0, start, T_C):
            n = min(T_C, start - pos)
            _, cache = prefill(_chunk(seqs[r], pos, n), i32(pos), i32(n), cache, tabs(tables[r : r + 1]))

    want_cache, want = cache, {}
    for r, (start, n, pages) in enumerate(rows):
        if n:
            lg, want_cache = prefill(_chunk(seqs[r], start, n), i32(start), i32(n), want_cache,
                                     tabs(tables[r : r + 1, :pages]))
            # the scalar call: every row's logits, or (Trinity) the last valid row's alone
            assert lg.shape == (1, 1 if two_kinds else T_C, cfg.vocab_size)
            want[r] = np.asarray(lg)[0, 0 if two_kinds else n - 1]

    tokens = jnp.concatenate([_chunk(seqs[r], start, n) for r, (start, n, _) in enumerate(rows)])
    got, got_cache = prefill(tokens, i32([s for s, _, _ in rows]), i32([n for _, n, _ in rows]),
                             cache, tabs(tables, reclaimed))
    assert got.shape == (len(rows), cfg.vocab_size)
    for r, row in want.items():
        np.testing.assert_allclose(np.asarray(got)[r], row, rtol=2e-5, atol=2e-5, err_msg=f"row {r}")
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        if a.dtype == jnp.int8:  # a rounding tie may fall either way
            assert np.max(np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))) <= 1
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)
    # the empty row wrote nothing: the sink page is as it was
    for a, b in zip(got_cache.pool_arrays(), cache.pool_arrays()):
        np.testing.assert_array_equal(np.asarray(a[:, :, 0]), np.asarray(b[:, :, 0]))
    if two_kinds:  # nothing dropped, and the rows that are no tokens counted nowhere
        assert int(got_cache.counters[1][2]) == 0 and np.isfinite(np.asarray(got)).all()


def _trinity_cell():
    from midgpt_tpu.config import load_config

    return load_config("trinity_mini").model_config


WIDTHS = {
    # serve_xl_chat, serve_124m_sample, serve_mimo_v2_5_mixed's shapes, and fewer slots than the ridge asks for
    "xl_chat": (16, 16, None, 16), "124m_sample": (48, 16, None, 16), "chunk_of_512_dense": (32, 512, None, 1),
    "few_slots": (3, 16, None, 3),
    # the families' own statements: a dense family asks for the ridge, Trinity for the rows that bring each of
    # 128 experts 128 pairs at top-8 (serve_trinity_mini_reason: 64 slots, chunks of 512)
    "gpt_states_the_ridge": (16, 16, lambda: GPT.prefill_rows(CFG, serve.PREFILL_ROWS), 16),
    "ouro_states_the_ridge": (12, 128, lambda: Ouro.prefill_rows(None, serve.PREFILL_ROWS), 2),
    "trinity_mini_reason": (64, 512, lambda: Trinity.prefill_rows(_trinity_cell(), serve.PREFILL_ROWS), 4),
    "trinity_two_slots": (2, 512, lambda: Trinity.prefill_rows(_trinity_cell(), serve.PREFILL_ROWS), 2),
    "trinity_chunk_of_2048": (64, 2048, lambda: Trinity.prefill_rows(_trinity_cell(), serve.PREFILL_ROWS), 1),
    "trinity_toy_dense_rows_lead": (64, 16, lambda: Trinity.prefill_rows(dataclasses.replace(TRINITY, moe_top_k=16), 256), 16),
}


@pytest.mark.parametrize("case", sorted(WIDTHS))
def test_width_rule(case):
    max_slots, chunk, rows, want = WIDTHS[case]
    assert prefill_width(max_slots, chunk, None if rows is None else rows()) == want
    if case == "trinity_mini_reason":
        assert rows() == moe_prefill_rows(256, 8, 128) == 2048


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _assert_generates(params, done, trace):
    for uid, (prompt, m) in trace.items():
        ref = generate(CFG, params, jnp.asarray(prompt)[None], m, temperature=0.0)
        np.testing.assert_array_equal(done[uid].tokens, np.asarray(ref[0]), err_msg=f"request {uid}")


@pytest.mark.parametrize("ridge_rows, width, calls", [(32, 2, 3), (256, 5, 1)])
def test_a_round_of_n_admissions_is_ceil_n_over_w_programs(params, monkeypatch, ridge_rows, width, calls):
    monkeypatch.setattr(serve, "PREFILL_ROWS", ridge_rows)
    obs = Observability()
    eng = ServeEngine(CFG, params, max_slots=5, page_size=PS, prefill_chunk=T_C, decode_chunk=4,
                      temperature=0.0, cache_dtype=jnp.float32, obs=obs)
    assert eng.prefill_width == width
    rng = np.random.default_rng(2)
    trace = {}
    for n, m in zip((5, 23, 16, 37, 3), (6, 5, 9, 4, 7)):
        prompt = rng.integers(0, CFG.vocab_size, n).astype(np.int32)
        trace[eng.submit(prompt, m)] = (prompt, m)
    eng.step()  # all five admitted, each advanced by one chunk
    assert (eng.prefill_chunks, eng.prefill_calls) == (5, calls)
    counters = eng.stats()["obs"]["counters"]
    assert (counters["prefill.chunks"], counters["prefill.calls"]) == (5, calls)
    done = eng.run()
    _assert_generates(params, done, trace)
    hist = eng.stats()["obs"]["histograms"]
    assert hist["round_prefill_calls"]["n"] == eng.rounds and hist["round_prefill_calls"]["max"] == calls
    assert eng.stats()["obs"]["counters"]["prefill.calls"] == eng.prefill_calls < eng.prefill_chunks


def test_a_slot_evicted_while_pages_are_found_is_not_in_the_batch(params):
    """The young request sits at the LOWER slot index, so the round finds
    its pages first; the older one then runs the pool dry and evicts it. Its
    row must not ride the call (its pages are freed), and everybody's
    tokens are still `generate`'s."""
    eng = ServeEngine(CFG, params, max_slots=2, page_size=PS, num_pages=9, prefill_chunk=PS,
                      decode_chunk=2, temperature=0.0, cache_dtype=jnp.float32)
    rng = np.random.default_rng(4)
    trace, uids = {}, []
    # a: done after a round, frees slot 0 for c; b: 7 pages of prompt at slot 1
    for n, m in ((4, 2), (56, 4), (40, 4)):
        prompt = rng.integers(0, CFG.vocab_size, n).astype(np.int32)
        uids.append(eng.submit(prompt, m))
        trace[uids[-1]] = (prompt, m)
    a, b, c = uids
    calls, evictions = [], []
    real_call, real_evict = eng._prefill_call, eng._evict

    def call(rows):
        assert all(eng.slots[i] is slot for i, slot, _ in rows), "a freed slot rode the batch"
        calls.append((eng.rounds, [slot.request.uid for _, slot, _ in rows]))
        real_call(rows)

    def evict(victim):
        evictions.append((eng.rounds, victim.request.uid, eng.slots.index(victim), victim.prefilling))
        real_evict(victim)

    eng._prefill_call, eng._evict = call, evict
    done = eng.run()
    assert evictions and evictions[0][1:] == (c, 0, True), evictions
    at = evictions[0][0]
    assert [u for r, u in calls if r == at] == [[b]]  # c had its pages, and lost them
    assert [u for r, u in calls if r == at - 1] == [[c, b]]
    _assert_generates(params, done, trace)
