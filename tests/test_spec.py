"""Speculative decoding (sampling/spec.py + serve engine wiring): greedy
token parity with the plain engine (the acceptance pin), exactness of the
rejection sampler against a deliberately wrong draft (statistical), the
page-aligned rollback invariants, and the zero-in-loop-pool-copy HLO pin
on the compiled verify program."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
from midgpt_tpu.sampling.engine import generate, warp_logits
from midgpt_tpu.sampling.serve import ServeEngine
from midgpt_tpu.sampling.spec import self_draft, speculative_accept

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=4, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def draft(params):
    return self_draft(CFG, params, 1)


def _trace(seed=0, lengths=(5, 23, 11, 37), max_new=(10, 12, 20, 8)):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
        for n, m in zip(lengths, max_new)
    ]


def test_self_draft_shares_embeddings(params):
    dcfg, dparams = self_draft(CFG, params, 2)
    assert dcfg.n_layer == 2 and dcfg.block_size == CFG.block_size
    assert dparams.wte is params.wte and dparams.lm_head is params.lm_head
    np.testing.assert_array_equal(
        np.asarray(dparams.blocks.attn.wqkv),
        np.asarray(params.blocks.attn.wqkv[:2]),
    )
    for bad in (0, CFG.n_layer):
        with pytest.raises(ValueError, match="n_draft_layers"):
            self_draft(CFG, params, bad)


def test_verify_step_paged_matches_sequential_decode(params):
    """The verify forward (k+1 positions per slot, one batched paged
    forward) must produce the same logits and cache writes as k+1
    sequential decode_step_paged calls — it IS the target's scoring of the
    speculative chain."""
    ps, n_pages, mp, K1 = 8, 25, 8, 4
    cache = PagedKVCache.init(CFG, num_pages=n_pages, page_size=ps, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, 11), rng.integers(0, 96, 7)]
    pages = [[1, 2, 3], [4, 5]]
    for pr, pg in zip(prompts, pages):
        row = np.zeros((1, mp), np.int32)
        row[0, : len(pg)] = pg
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, : len(pr)] = pr
        _, cache = GPT.prefill_paged_chunk(
            CFG, params, jnp.asarray(chunk), jnp.asarray(0, jnp.int32),
            jnp.asarray(len(pr), jnp.int32), cache, jnp.asarray(row),
        )
    table = np.zeros((2, mp), np.int32)
    table[0, :3] = pages[0]
    table[1, :2] = pages[1]
    lengths = np.asarray([11, 7], np.int32)
    tokens = np.concatenate(
        [np.asarray([[p[-1]] for p in prompts], np.int32),
         rng.integers(0, 96, (2, K1 - 1)).astype(np.int32)],
        axis=1,
    )
    act = jnp.asarray([True, True])

    ref_logits, c, lens = [], cache, jnp.asarray(lengths)
    for t in range(K1):
        lg, c = GPT.decode_step_paged(
            CFG, params, jnp.asarray(tokens[:, t]), c, jnp.asarray(table),
            lens, act, attn_impl="gather",
        )
        ref_logits.append(lg)
        lens = lens + 1
    ref = jnp.stack(ref_logits, axis=1)

    v_logits, v_cache = GPT.verify_step_paged(
        CFG, params, jnp.asarray(tokens), cache, jnp.asarray(table),
        jnp.asarray(lengths), act, attn_impl="gather",
    )
    np.testing.assert_allclose(
        np.asarray(v_logits), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(v_cache.k), np.asarray(c.k), atol=2e-5, rtol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(v_cache.v), np.asarray(c.v), atol=2e-5, rtol=2e-5
    )


@pytest.mark.slow  # both rows are among the suite's slowest compiles
# (~56 s dedicated, more shared); full suite only per the tier-1 870 s
# gate budget — the cheaper spec unit tests keep tier-1 coverage
@pytest.mark.parametrize(
    "shared",
    (True, False),
    ids=("shared", "dedicated"),
)
def test_spec_greedy_parity_with_generate(params, draft, shared):
    """THE acceptance pin: greedy speculative serving is token-for-token
    identical to engine.generate across a mixed-length trace — chunked
    prefill, draft/verify rounds, adaptive k, rollback and slot churn
    included — in both draft-cache modes (prefix layers sharing the target
    pool, and a dedicated draft pool)."""
    dcfg, dparams = draft
    trace = _trace()
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, prefill_chunk=16,
        temperature=0.0, cache_dtype=jnp.float32,
        draft_params=dparams, draft_config=dcfg, draft_shares_cache=shared,
        spec_k_max=4,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(
            done[u].tokens, np.asarray(ref[0]), err_msg=f"request {u}"
        )
    stats = eng.spec_stats()
    assert stats["rounds"] > 0 and stats["tokens_per_verify"] >= 1.0
    assert eng.allocator.free_count == eng.allocator.num_pages - 1


@pytest.mark.slow
def test_spec_greedy_parity_separate_draft_model(params):
    """A draft with DIFFERENT weights (an independently initialized model —
    a deliberately wrong draft) must still produce exactly the target's
    greedy tokens: the draft only proposes, the verify forward decides."""
    dcfg = dataclasses.replace(CFG, n_layer=1)
    dparams = GPT.init(dcfg, jax.random.PRNGKey(99))
    trace = _trace(seed=1, lengths=(9, 17), max_new=(12, 9))
    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, temperature=0.0,
        cache_dtype=jnp.float32, draft_params=dparams, draft_config=dcfg,
        spec_k_max=4,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(done[u].tokens, np.asarray(ref[0]))
    # a wrong draft shows up as low acceptance, never as wrong tokens
    assert eng.spec_stats()["accept_rate"] < 0.9


@pytest.mark.slow
def test_spec_parity_under_eviction(params, draft):
    """Pool pressure during speculative rounds forces recompute-style
    preemption; parity must survive it (same pin the plain engine has)."""
    dcfg, dparams = draft
    rng = np.random.default_rng(3)
    trace = [(rng.integers(0, 96, 8).astype(np.int32), 40) for _ in range(3)]
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=10,
        temperature=0.0, cache_dtype=jnp.float32,
        draft_params=dparams, draft_config=dcfg, draft_shares_cache=True,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    for (p, m), u in zip(trace, uids):
        ref = generate(CFG, params, jnp.asarray(p)[None], m, temperature=0.0)
        np.testing.assert_array_equal(done[u].tokens, np.asarray(ref[0]))


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_spec_rollback_is_page_aligned(params):
    """After every speculative round, a live slot holds EXACTLY
    ceil(length / page_size) pages — rejected tail pages went back to the
    free list, the partial last page keeps its stale (masked) columns, and
    nothing was rewritten on device. A wrong-weights draft forces frequent
    rejection so the rollback path actually runs."""
    dcfg = dataclasses.replace(CFG, n_layer=1)
    dparams = GPT.init(dcfg, jax.random.PRNGKey(99))
    rng = np.random.default_rng(5)
    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, prefill_chunk=16,
        temperature=0.0, cache_dtype=jnp.float32,
        draft_params=dparams, draft_config=dcfg, spec_k_max=4,
        spec_adapt=False,  # keep k at 4: maximal speculative overhang
    )
    uids = [
        eng.submit(rng.integers(0, 96, n).astype(np.int32), m)
        for n, m in ((11, 20), (19, 16))
    ]
    rejected_rounds = 0
    while not eng.idle:
        eng.step()
        held = 0
        for slot in eng.slots:
            if slot is None:
                continue
            assert len(slot.pages[0]) == -(-slot.length // eng.page_size), (
                slot.length, slot.pages[0],
            )
            held += len(slot.pages[0])
        # conservation: every page is either free or held by a live slot
        assert eng.allocator.free_count + held == eng.allocator.num_pages - 1
        rejected_rounds += eng._spec_drafted > eng._spec_accepted
    assert rejected_rounds > 0, "draft never rejected — rollback untested"
    assert set(eng.finished) == set(uids)


def test_spec_statistical_rejection_sampler():
    """Satellite pin: with a deliberately WRONG draft distribution, the
    token the sampler emits at a position is still distributed as the
    warped TARGET softmax — 10k vectorized draws, total-variation
    tolerance. This is the Leviathan exactness guarantee as a number."""
    V, K, B = 16, 2, 10_000
    rng = np.random.default_rng(7)
    t_log = rng.normal(0.0, 1.5, (1, K + 1, V)).astype(np.float32)
    # wrong draft: an independent draw — far from the target
    q_log = rng.normal(0.0, 1.5, (1, K, V)).astype(np.float32)
    p = np.asarray(jax.nn.softmax(jnp.asarray(t_log[0]), axis=-1))
    q = np.asarray(jax.nn.softmax(jnp.asarray(q_log[0]), axis=-1))
    tv_pq = 0.5 * np.abs(p[0] - q[0]).sum()
    assert tv_pq > 0.25, f"test has no power: draft too close (TV={tv_pq})"

    # drafts sampled FROM the draft distribution (its job in the protocol)
    drafts = np.stack(
        [rng.choice(V, size=B, p=q[i]) for i in range(K)], axis=1
    ).astype(np.int32)
    n_accept, out = speculative_accept(
        jnp.asarray(np.broadcast_to(t_log, (B, K + 1, V))),
        jnp.asarray(np.broadcast_to(q[None], (B, K, V))),
        jnp.asarray(drafts),
        jax.random.PRNGKey(0),
        temperature=1.0,
    )
    out = np.asarray(out)
    first = out[:, 0]  # accepted d_1 or its correction: must be ~ p_1
    emp = np.bincount(first, minlength=V) / B
    tv = 0.5 * np.abs(emp - p[0]).sum()
    assert tv < 0.03, f"emitted dist deviates from target: TV={tv}"
    # and it must NOT follow the draft (the wrong distribution)
    tv_q = 0.5 * np.abs(emp - q[0]).sum()
    assert tv_q > 0.15, f"emitted dist tracks the DRAFT: TV={tv_q}"

    # greedy degenerates to argmax equality: emitted = target argmax chain
    n0, out0 = speculative_accept(
        jnp.asarray(np.broadcast_to(t_log, (4, K + 1, V))),
        jnp.asarray(np.broadcast_to(q[None], (4, K, V))),
        jnp.asarray(drafts[:4]),
        None,
        temperature=0.0,
    )
    first0 = np.asarray(out0)[:, 0]
    tgt0 = int(np.argmax(t_log[0, 0]))
    ok = (drafts[:4, 0] == tgt0) | (first0 == tgt0)
    assert ok.all()


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_spec_eos_finishes_mid_round(params, draft):
    """EOS inside an accepted speculative chain truncates the request at
    the EOS token, frees the slot, and discards the rest of the round."""
    dcfg, dparams = draft
    p = _trace()[0][0]
    probe = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, temperature=0.0,
        cache_dtype=jnp.float32, draft_params=dparams, draft_config=dcfg,
        draft_shares_cache=True,
    )
    u = probe.submit(p, 10)
    gen = probe.run()[u].tokens[len(p):]
    # the first token value whose occurrence index is unique-so-far keeps
    # the expected stop position well-defined (greedy chains repeat fast)
    eos_idx = next(i for i in range(len(gen)) if gen[i] not in gen[:i])
    eos = int(gen[eos_idx])

    eng = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, temperature=0.0,
        cache_dtype=jnp.float32, draft_params=dparams, draft_config=dcfg,
        draft_shares_cache=True,
    )
    u2 = eng.submit(p, 10, eos_id=eos)
    out = eng.run()[u2].tokens
    assert out[-1] == eos and len(out) == len(p) + eos_idx + 1
    assert eng.allocator.free_count == eng.allocator.num_pages - 1
    assert eng.idle


def test_spec_engine_validation(params, draft):
    dcfg, dparams = draft
    with pytest.raises(ValueError, match="come together"):
        ServeEngine(CFG, params, draft_params=dparams)
    with pytest.raises(ValueError, match="power of two"):
        ServeEngine(
            CFG, params, draft_params=dparams, draft_config=dcfg, spec_k_max=3
        )
    with pytest.raises(ValueError, match="spec_k_min"):
        ServeEngine(
            CFG, params, draft_params=dparams, draft_config=dcfg,
            spec_k_max=2, spec_k_min=4,
        )
    with pytest.raises(ValueError, match="block_size"):
        ServeEngine(
            CFG, params, draft_params=dparams,
            draft_config=dataclasses.replace(dcfg, block_size=128),
        )
    with pytest.raises(ValueError, match="layer-prefix"):
        ServeEngine(
            CFG, params, draft_params=dparams,
            draft_config=dataclasses.replace(dcfg, n_head=1, n_embd=16),
            draft_shares_cache=True,
        )


def test_spec_config_validation():
    from midgpt_tpu.config import ExperimentConfig, MeshConfig

    base = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
        warmup_steps=1, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
        beta2=0.99, weight_decay=0.0, eval_interval=5,
        param_dtype="float32", compute_dtype="float32", g_accum_iters=1,
        shard_model=False, mesh=MeshConfig(data=-1, fsdp=1), model_config=CFG,
    )
    ExperimentConfig(**base, spec_layers=2, spec_k_max=8)  # valid
    with pytest.raises(ValueError, match="spec_layers"):
        ExperimentConfig(**base, spec_layers=CFG.n_layer)
    with pytest.raises(ValueError, match="power of two"):
        ExperimentConfig(**base, spec_k_max=6)
    with pytest.raises(ValueError, match="spec_k_min"):
        ExperimentConfig(**base, spec_k_min=8, spec_k_max=4)


def test_verify_program_has_no_in_loop_pool_copies():
    """ISSUE acceptance HLO pin, via the shared census helper the audit CLI
    uses: the verify program's layer loop (decode_layer_scan=True — the
    lowering that HAS a while body) contains no pool-sized copies beyond
    the backend's per-scatter relayout allowance
    (budgets.LOOP_POOL_COPIES_PER_SCATTER), and neither does the unrolled
    lowering anywhere — the speculative writes alias through the carry
    exactly like decode's."""
    from midgpt_tpu.analysis import budgets
    from midgpt_tpu.analysis.hlo_audit import (
        loop_pool_copy_excess,
        pool_scatter_count,
    )
    from midgpt_tpu.sampling import serve

    B, ps, n_pages, K = 2, 8, 12, 2
    for scan in (True, False):
        cfg = dataclasses.replace(CFG, n_layer=2, decode_layer_scan=scan)
        L, H, C = cfg.n_layer, cfg.n_head, cfg.head_dim
        mp = cfg.block_size // ps
        abstract = jax.eval_shape(
            lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0)
        )
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), abstract
        )
        cache = jax.eval_shape(
            lambda: PagedKVCache.init(cfg, num_pages=n_pages, page_size=ps)
        )
        txt = (
            serve._spec_verify_chunk.lower(
                cfg,
                abstract,
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((K, B), jnp.int32),
                jax.ShapeDtypeStruct((K, B, cfg.vocab_size), jnp.float32),
                cache,
                jax.ShapeDtypeStruct((B, mp), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_),
                0.0,
                None,
                None,
                "gather",
                None,
            )
            .compile()
            .as_text()
        )
        pool = f"bf16[{L},{H},{n_pages},{ps},{C}]"
        census = loop_pool_copy_excess(txt, pool)
        offenders = {b: n for b, n in census.items() if n}
        assert not offenders, f"scan={scan}: in-loop pool copies {offenders}"
        if scan:
            assert census, "layer scan lowered without a while body?"
        else:
            # no loop at all: the whole program is held to the same budget
            n_copies = len(re.findall(rf"= {re.escape(pool)}[^=]*copy\(", txt))
            budget = budgets.LOOP_POOL_COPIES_PER_SCATTER * pool_scatter_count(
                txt.splitlines(), pool
            )
            assert n_copies <= budget, (
                f"unrolled verify copies the pool {n_copies}x (budget {budget})"
            )
