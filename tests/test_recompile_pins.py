"""graftcheck pass-2 recompile pins: the compile-behavior claims of PR 1's
serving engine and the training step, held by counter instead of comment.

* ServeEngine (SERVING.md): page tables / lengths / active masks are plain
  jit inputs and chunk shapes are padded/pow2-bucketed, so a CHANGING
  REQUEST MIX never recompiles — the decode program compiles exactly once,
  prefill once per pow2 page bucket, and replaying three further distinct
  mixes compiles nothing at all.
* Train step (training/train.py): the whole step is ONE XLA program; three
  steps, one compile.
* The compiled artifacts themselves: no all-gathers in the decode while
  body, fp32 master params + bf16 compute in the lowered train step
  (SURVEY.md §7.4) — via analysis.hlo_audit.run_audit, the same suite
  `python -m midgpt_tpu.analysis --audit` runs.

Mix design (why these exact numbers pin "exactly one decode program"):
decode_chunk=8 and every request's max_new_tokens ≡ 1 (mod 8) — the first
generated token is sampled host-side at end of prefill, so the decode-side
remainder is a multiple of 8 and every decode round runs a full chunk
(n_steps=8); prompts are 25..47 tokens with prompt+max_new <= block_size=64,
so the pow2 page bucket is pinned at the 8-page cap from the first decode
round and the pool (24 allocatable pages) never forces an eviction. Any
scheduler change that starts re-bucketing or splitting chunks shows up here
as a compile-count bump.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.analysis.hlo_audit import CompileCounter, jit_cache_size, run_audit
from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
from midgpt_tpu.sampling.serve import (
    ServeEngine,
    _serve_decode_chunk,
    _serve_prefill_chunk,
)
from midgpt_tpu.training.train import (
    describe_step_program,
    init_state,
    make_train_step,
)

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def _serve_mix(params, lengths, max_new, seed, max_slots=3):
    eng = ServeEngine(
        CFG,
        params,
        max_slots=max_slots,
        page_size=8,
        num_pages=25,  # full working set fits: no eviction churn in the pin
        prefill_chunk=16,
        decode_chunk=8,
        temperature=0.0,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(seed)
    uids = {
        eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m): (n, m)
        for n, m in zip(lengths, max_new)
    }
    done = eng.run()
    assert set(done) == set(uids)
    for uid, (n, m) in uids.items():
        assert len(done[uid].tokens) == n + m
    return eng


def test_prefill_is_one_program_a_page_bucket_however_many_slots_prefill(params):
    """The prefill program's width is the engine's (`prefill_width`), not the
    round's: a request served ALONE compiles every page bucket's program, and
    rounds with two, three and four prefilling slots compile nothing."""
    p0 = jit_cache_size(_serve_prefill_chunk)
    eng = _serve_mix(params, (47,), (9,), seed=0, max_slots=4)  # a width no other test here has
    assert eng.prefill_width == 4 and eng.prefill_calls == eng.prefill_chunks == 3
    assert jit_cache_size(_serve_prefill_chunk) - p0 == 3  # page buckets {2, 4, 8}
    with CompileCounter() as cc:
        for n in (2, 3, 4):
            eng = _serve_mix(params, (25, 34, 47, 40)[:n], (9, 17, 17, 9)[:n], seed=n, max_slots=4)
            assert eng.prefill_calls < eng.prefill_chunks  # slots did ride together
    assert cc.count == 0, f"a round with more prefilling slots compiled {cc.count} program(s)"
    assert jit_cache_size(_serve_prefill_chunk) - p0 == 3


def test_serve_mixes_exactly_one_decode_compile(params):
    """The acceptance pin: >= 3 distinct request mixes, 1 decode-program
    compile total — and zero compiles of any kind after the first mix."""
    d0 = jit_cache_size(_serve_decode_chunk)
    p0 = jit_cache_size(_serve_prefill_chunk)
    eng = _serve_mix(params, (25, 34, 47), (9, 17, 17), seed=0)
    d1 = jit_cache_size(_serve_decode_chunk)
    assert d1 - d0 == 1, "decode must be ONE program (fixed n_steps x bucket)"
    # prefill compiles once per pow2 page bucket the mix touches: {2, 4, 8}
    assert jit_cache_size(_serve_prefill_chunk) - p0 == 3
    stats = eng.compile_stats()
    assert stats["decode"] == d1 and stats["prefill"] == p0 + 3

    with CompileCounter() as cc:
        _serve_mix(params, (26, 33, 40), (9, 17, 9), seed=1)
        _serve_mix(params, (29, 41, 45), (17, 9, 17), seed=2)
        _serve_mix(params, (31, 38, 47), (17, 17, 9), seed=3)
    assert cc.count == 0, f"request-mix change recompiled {cc.count} program(s)"
    assert jit_cache_size(_serve_decode_chunk) == d1


def test_spec_mixes_one_draft_and_verify_program_per_k_bucket(params):
    """Satellite pin: across 4 request mixes with varying acceptance
    patterns (different seeds — acceptance is DATA, so it must never be a
    compile key), the engine compiles exactly one draft program and one
    verify program per k-bucket. Mix design mirrors the decode pin above:
    prompts 31..47 pin the page bucket at the 8-page cap from the first
    speculative round even at k=1 (length + k + 1 >= 33), prompt + max_new
    <= 60 keeps capacity from ever clamping k, and the 25-page pool never
    evicts. k is pinned per engine (spec_adapt=False, k_min=k_max) the way
    decode lengths are pow2-bucketed."""
    from midgpt_tpu.sampling.serve import _spec_draft_chunk, _spec_verify_chunk
    from midgpt_tpu.sampling.spec import self_draft

    dcfg, dparams = self_draft(CFG, params, 1)

    def spec_mix(k, seed, lengths=(31, 38, 45), max_new=(13, 9, 15)):
        eng = ServeEngine(
            CFG,
            params,
            max_slots=3,
            page_size=8,
            num_pages=25,
            prefill_chunk=16,
            temperature=0.0,
            cache_dtype=jnp.float32,
            draft_params=dparams,
            draft_config=dcfg,
            draft_shares_cache=True,
            spec_k_max=k,
            spec_k_min=k,
            spec_adapt=False,
        )
        rng = np.random.default_rng(seed)
        uids = {
            eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
            for n, m in zip(lengths, max_new)
        }
        done = eng.run()
        assert set(done) == uids
        return eng

    d0 = jit_cache_size(_spec_draft_chunk)
    v0 = jit_cache_size(_spec_verify_chunk)
    spec_mix(4, seed=0)  # k-bucket 4, acceptance pattern A
    spec_mix(4, seed=1, lengths=(33, 40, 47), max_new=(9, 11, 13))  # pattern B
    assert jit_cache_size(_spec_draft_chunk) - d0 == 1, "draft: one program per k"
    assert jit_cache_size(_spec_verify_chunk) - v0 == 1, "verify: one program per k"
    spec_mix(1, seed=2)  # second k-bucket
    assert jit_cache_size(_spec_draft_chunk) - d0 == 2
    assert jit_cache_size(_spec_verify_chunk) - v0 == 2
    with CompileCounter() as cc:
        spec_mix(4, seed=3, lengths=(32, 39, 46), max_new=(11, 13, 9))
    assert cc.count == 0, f"4th mix recompiled {cc.count} program(s)"
    stats = ServeEngine.compile_stats()
    assert stats["spec_draft"] == jit_cache_size(_spec_draft_chunk)
    assert stats["spec_verify"] == jit_cache_size(_spec_verify_chunk)


@pytest.mark.slow  # heavy long-tail (~10 s of int8 compiles): full suite
# only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_int8_cache_is_a_program_key_but_compiles_once_per_bucket(params):
    """Satellite pin (int8 KV-cache PR): the cache dtype IS part of the
    program key — the int8 pool's avals (s8 pages + f32 scale leaves)
    lower distinct decode/draft/verify programs from bf16's — but each
    dtype still compiles exactly one decode program and one draft+verify
    program per k-bucket, and a second int8 mix with a different request
    pattern compiles NOTHING. Mix design mirrors the plain pins above
    (non-evicting 25-page pool, pow2-pinned buckets)."""
    from midgpt_tpu.sampling.serve import _spec_draft_chunk, _spec_verify_chunk
    from midgpt_tpu.sampling.spec import self_draft

    def int8_mix(lengths, max_new, seed, spec=False):
        kw = {}
        if spec:
            dcfg, dparams = self_draft(CFG, params, 1)
            kw = dict(
                draft_params=dparams, draft_config=dcfg,
                draft_shares_cache=True, spec_k_max=4, spec_k_min=4,
                spec_adapt=False,
            )
        eng = ServeEngine(
            CFG, params, max_slots=3, page_size=8, num_pages=25,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype="int8", **kw,
        )
        rng = np.random.default_rng(seed)
        uids = {
            eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
            for n, m in zip(lengths, max_new)
        }
        assert set(eng.run()) == uids

    d0 = jit_cache_size(_serve_decode_chunk)
    sd0 = jit_cache_size(_spec_draft_chunk)
    sv0 = jit_cache_size(_spec_verify_chunk)
    int8_mix((25, 34, 47), (9, 17, 17), seed=0)
    assert jit_cache_size(_serve_decode_chunk) - d0 == 1, (
        "int8 decode must be ONE new program"
    )
    int8_mix((31, 38, 45), (13, 9, 15), seed=1, spec=True)
    assert jit_cache_size(_spec_draft_chunk) - sd0 == 1
    assert jit_cache_size(_spec_verify_chunk) - sv0 == 1
    with CompileCounter() as cc:
        int8_mix((26, 33, 40), (9, 17, 9), seed=2)
        int8_mix((33, 40, 47), (9, 11, 13), seed=3, spec=True)
    assert cc.count == 0, f"int8 request-mix change recompiled {cc.count}"


def test_prefix_cache_compiles_zero_new_programs(params):
    """Tentpole pin (prefix-cache PR): cross-request sharing is page-table
    indirection over existing jit inputs, so a cache-ON engine serving
    hit, miss, and COW-duplicate admissions compiles NOTHING a cache-off
    engine at the same geometry didn't already compile. Warm-then-count on
    a non-25-page pool so this pin composes with the pristine-baseline
    pins above. Mix design: all prompts 28 tokens / budget 9 so both modes
    touch the same pow2 page buckets (a trie-matched admission can only
    SKIP early prefill buckets, never reach a new one)."""

    def mix(prefix, seed):
        eng = ServeEngine(
            CFG, params, max_slots=3, page_size=8, num_pages=31,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32, prefix_cache=prefix,
        )
        rng = np.random.default_rng(seed)
        head = rng.integers(0, CFG.vocab_size, 24).astype(np.int32)
        tails = [rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
                 for _ in range(2)]
        prompts = [np.concatenate([head, t]) for t in tails]
        prompts.append(rng.integers(0, CFG.vocab_size, 28).astype(np.int32))
        uids = [eng.submit(p, 9) for p in prompts]
        assert set(eng.run()) == set(uids)
        # second wave against a warm trie: template hit, exact-duplicate
        # COW truncation, and a plain unique miss
        uids += [eng.submit(p, 9) for p in (prompts[0], prompts[2])]
        assert set(eng.run()) == set(uids)
        if prefix:
            assert eng.prefix_stats()["hit_rate"] > 0.0
            assert eng.cow_pages >= 1, "the duplicate must take the COW path"
        return eng

    mix(False, seed=0)  # warm every program this geometry/mix reaches
    with CompileCounter() as cc:
        mix(True, seed=0)  # same trace, cache on: hits + COW + misses
        mix(True, seed=1)  # fresh content, cold trie again
    assert cc.count == 0, f"prefix cache compiled {cc.count} new program(s)"


def test_obs_toggle_compiles_zero_new_programs(params):
    """Tentpole pin (observability PR): the flight recorder is host-side
    only — clock reads and ring appends around the jit calls, never
    through them — so an obs-ON engine compiles NOTHING an obs-off engine
    at the same geometry didn't already compile, and no span/metric state
    ever becomes a jit static. Warm-then-count on the 31-page pool so this
    pin composes with the pristine-baseline pins above."""
    from midgpt_tpu.obs import Observability

    def mix(obs, seed):
        eng = ServeEngine(
            CFG, params, max_slots=3, page_size=8, num_pages=31,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32, obs=obs,
        )
        rng = np.random.default_rng(seed)
        uids = [
            eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
            for n, m in zip((25, 34, 47), (9, 17, 17))
        ]
        assert set(eng.run()) == set(uids)
        return eng

    mix(None, seed=0)  # warm every program this geometry/mix reaches
    with CompileCounter() as cc:
        eng = mix(Observability(), seed=0)  # same mix, recorder on
        mix(Observability(), seed=1)  # fresh content, same buckets
    assert cc.count == 0, f"obs toggle compiled {cc.count} new program(s)"
    assert eng.stats()["obs"]["round_decomp"]["rounds"] > 0


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_hot_swap_and_ops_ticks_compile_zero_new_programs(params):
    """Tentpole pin (model-ops PR): a same-shape blue/green hot-swap is a
    pointer flip — the candidate params are device_put onto the LIVE
    params' shardings and params are traced args of every serving jit, so
    the swap compiles NOTHING; obs-on ModelOps controller ticks are pure
    host reads (allocator counters, backlog arithmetic) and also compile
    nothing. Warm-then-count on a fresh 51-page pool: the identical
    two-wave schedule runs once swap-free to warm every program this
    geometry reaches, then twice with the swap and the controller live
    under a CompileCounter. All three submissions land in slots before
    the swap stages, so the admission pause cannot alter the schedule."""
    from midgpt_tpu.obs import Observability
    from midgpt_tpu.sampling.ops import ModelOps

    # COMMITTED initial params (like a restored engine's): the staged
    # candidate is device_put onto the live shardings, and a committed
    # vs uncommitted input is a distinct executable key — an engine
    # born from uncommitted arrays would recompile once on the first
    # swap for that reason alone, not because of the swap protocol.
    params_a = jax.device_put(params, jax.devices()[0])
    params_b = GPT.init(CFG, jax.random.PRNGKey(7))

    def mix(swap, seed):
        eng = ServeEngine(
            CFG, params_a, max_slots=3, page_size=8, num_pages=51,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32, obs=Observability(),
        )
        mops = ModelOps(eng, clock=lambda: 0.0, apply=False)
        rng = np.random.default_rng(seed)
        for wave in range(2):
            uids = {
                eng.submit(
                    rng.integers(0, CFG.vocab_size, n).astype(np.int32), m
                )
                for n, m in zip((25, 34, 47), (9, 17, 17))
            }
            for _ in range(3):
                eng.step()
            mops.tick()  # advisory mid-wave tick: host-only
            if swap:
                eng.hot_swap(params_b, version=f"v{wave}")
            done = eng.run()  # drains the wave; a staged swap flips here
            assert uids <= set(done)
        mops.tick()
        return eng

    mix(False, seed=0)  # warm every program this geometry/schedule reaches
    d0 = jit_cache_size(_serve_decode_chunk)
    p0 = jit_cache_size(_serve_prefill_chunk)
    eng = mix(True, seed=0)  # same trace, swap + controller live: the
    # SERVING programs must not grow (params are traced args; the swap's
    # per-leaf-shape transfer helpers warm here like any host glue)
    assert eng.hot_swaps == 2, "both staged swaps must have flipped"
    assert jit_cache_size(_serve_decode_chunk) == d0, (
        "a same-shape hot-swap recompiled the decode program"
    )
    assert jit_cache_size(_serve_prefill_chunk) == p0, (
        "a same-shape hot-swap recompiled a prefill bucket"
    )
    with CompileCounter() as cc:
        mix(True, seed=1)  # full replay, swap + ticks included
    assert cc.count == 0, f"hot-swap/ops ticks compiled {cc.count} program(s)"


@pytest.mark.slow  # heavy long-tail (~9 s, two fresh pool geometries):
# full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_resize_compiles_bounded_then_zero_on_replay(params):
    """Satellite pin (model-ops PR): a live pool resize may compile only
    the migration's pow2-bucketed gather/scatter programs and the
    destination geometry's fresh-pool fills — a constant, not a function
    of the resident count — and an identical resize schedule replayed on
    a fresh engine compiles NOTHING at all (both geometries, the
    migration, and the post-resize serving all replay from cache).
    Geometries 57 -> 71 are this pin's own (program-shape keys)."""

    def mix(seed, counter=None):
        eng = ServeEngine(
            CFG, params, max_slots=3, page_size=8, num_pages=57,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32,
        )
        rng = np.random.default_rng(seed)
        uids = {
            eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
            for n, m in zip((25, 34, 47), (9, 17, 17))
        }
        for _ in range(3):
            eng.step()
        if counter is not None:
            with counter:
                rec = eng.resize(71)
        else:
            rec = eng.resize(71)
        assert rec["pages_migrated"] >= 1
        assert set(eng.run()) == uids
        return eng

    resize_cc = CompileCounter()
    mix(seed=0, counter=resize_cc)  # warm pass; count the resize alone
    # one gather + one adoption scatter + the new pool's zero-fills per
    # pool (f32: no scale leaves) — the sink-padded pow2 bucket keeps the
    # gather/scatter shapes off the resident count, so this is a small
    # constant, not O(pages)
    assert 0 < resize_cc.count <= 10, (
        f"resize compiled {resize_cc.count} programs — the migration must "
        "stay a bounded set of bucket-shaped gathers/scatters"
    )
    with CompileCounter() as cc:
        mix(seed=1)
    assert cc.count == 0, f"resize replay compiled {cc.count} program(s)"


@pytest.mark.slow  # heavy long-tail (~10 s, cold geometry-61 compiles):
# full suite only; the audit-suite group census stays tier-1
def test_overlap_modes_compile_one_group_program_per_bucket(params):
    """Tentpole pin (round-overlap PR): the fused group program compiles
    exactly once per (geometry, round_group bucket) — round_group is a
    pow2-bucketed static (`_round_group_bucket`), so group:3 reuses
    group:2's program — and flipping the overlap mode off<->double<->group
    on warm programs compiles NOTHING: overlap is host-side dispatch
    restructuring over the same jit inputs. Geometry 61 is this pin's own
    fresh pool (tests/test_overlap.py warms 39; the baselines above own
    25/31/51/57/71)."""
    from midgpt_tpu.sampling.serve import _serve_decode_group

    def mix(overlap, round_group, seed):
        eng = ServeEngine(
            CFG, params, max_slots=3, page_size=8, num_pages=61,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32, overlap=overlap,
            round_group=round_group,
        )
        rng = np.random.default_rng(seed)
        uids = {
            eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
            for n, m in zip((25, 34, 47), (9, 17, 17))
        }
        assert set(eng.run()) == uids
        return eng

    mix("off", 1, seed=0)  # warm prefill buckets + the classic decode
    g0 = jit_cache_size(_serve_decode_group)
    mix("double", 1, seed=1)
    g1 = jit_cache_size(_serve_decode_group)
    assert g1 - g0 == 1, "double-buffering must be ONE group program (k=1)"
    mix("group", 2, seed=2)
    g2 = jit_cache_size(_serve_decode_group)
    assert g2 - g1 == 1, "group:2 must be ONE more program (k-bucket 2)"
    eng = mix("group", 3, seed=3)  # 3 buckets down to 2: same program
    assert eng.round_group == 2
    assert jit_cache_size(_serve_decode_group) == g2, (
        "round_group=3 must bucket to the k=2 program, not compile a third"
    )
    with CompileCounter() as cc:
        mix("off", 1, seed=4)
        mix("double", 1, seed=5)
        mix("group", 2, seed=6)
    assert cc.count == 0, f"overlap mode flip compiled {cc.count} program(s)"
    assert ServeEngine.compile_stats()["decode_group"] == g2


def test_train_step_compiles_exactly_once():
    cfg = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-2,
        batch_size=8,
        warmup_steps=5,
        min_lr=1e-3,
        lr_decay_steps=60,
        max_steps=60,
        beta2=0.99,
        weight_decay=1e-4,
        eval_interval=30,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        mesh=MeshConfig(data=2, fsdp=4, sp=1),
        fsdp_min_size=0,
        model_config=CFG,
    )
    mesh = make_mesh(cfg.mesh)
    p, opt, specs, optimizer = init_state(cfg, mesh)
    step, _, _ = make_train_step(cfg, optimizer, mesh, specs)
    rng = np.random.default_rng(0)
    T = CFG.block_size

    def batch(i):
        x = rng.integers(0, CFG.vocab_size, (1, 8, T), dtype=np.int32)
        return make_global_batch(x, mesh, batch_spec()), make_global_batch(
            np.roll(x, -1, -1), mesh, batch_spec()
        )

    key = jax.random.PRNGKey(0)
    # Warm step 0 exactly as the train loop calls it: the sticky-loss
    # carrier is a COMMITTED mesh-replicated f32 scalar from the start
    # (training/train.py). Both an uncommitted zeros() and the bare-float
    # default would give step 0 a different input aval than step 1+ and
    # compile the whole step twice — the original shipped loop did exactly
    # that, and this pin is what caught it.
    loss = jax.device_put(
        jnp.zeros((), jnp.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    x, y = batch(0)
    k0 = jax.random.fold_in(key, 0)
    # The loop's one-time "what did the step compile to" line
    # (describe_step_program) lowers+compiles ahead of the first call; the
    # call must then REUSE that executable — described and run, one compile.
    with CompileCounter() as cc0:
        line = describe_step_program(step, (p, opt, x, y, k0, loss), CFG.attn_impl)
        p, opt, loss = step(p, opt, x, y, k0, loss)
    assert cc0.count == 1, f"describing the step cost a second compile: {cc0.count}"
    assert line.startswith("train step program: 0 Mosaic kernel call(s)"), line
    assert jit_cache_size(step) == 1
    with CompileCounter() as cc:
        for i in (1, 2):
            x, y = batch(i)
            p, opt, loss = step(p, opt, x, y, jax.random.fold_in(key, i), loss)
    assert cc.count == 0, "train step recompiled on a later step"
    assert jit_cache_size(step) == 1
    assert np.isfinite(float(loss))


def test_audit_suite_passes_on_cpu_mesh():
    """run_audit = what `python -m midgpt_tpu.analysis --audit` executes:
    fp32 master params + bf16 compute on the lowered train step, and a
    collective-free decode while body. Raises on violation.

    Every numeric budget asserted here is read from the declarative
    manifest (analysis/budgets.py) — the same module run_audit lowers
    against — so this pin and the audit cannot drift apart; the manifest
    is the single place a serving mode's budget is declared."""
    from midgpt_tpu.analysis import budgets

    report = run_audit()
    fp = report["train_step_fp32_master"]
    assert fp["n_reduced"] == 0 and fp["n_f32"] > 0 and fp["has_bf16_compute"]
    assert report["decode_while_bodies"], "decode program lost its scan?"
    assert all(n == 0 for n in report["decode_while_bodies"].values())
    # speculative-verify extensions: collective-free layer loop and the
    # zero-in-loop-cache-copy census on BOTH serving programs
    assert report["verify_while_bodies"], "verify program lost its layer scan?"
    assert all(n == 0 for n in report["verify_while_bodies"].values())
    zero = budgets.LOOP_POOL_COPY_BUDGET
    assert all(n == zero for n in report["decode_loop_pool_copies"].values())
    assert all(n == zero for n in report["verify_loop_pool_copies"].values())
    # mesh-sharded serving extensions: per-program in-loop collective
    # census on the tp lowerings — exactly the megatron activation
    # all-reduce budget the manifest declares per program, no other
    # collective op anywhere in a loop, and zero per-shard pool/scale
    # copies
    assert report["tp_mesh"] == budgets.tp_mesh_shape()
    for name in budgets.TP_PROGRAMS:
        assert (
            report[f"{name}_loop_all_reduces"]
            == budgets.tp_loop_all_reduce_budget(name)
        ), name
        assert report[f"{name}_loop_pool_copies"] == zero, name
    # split-K extensions: sequence partitioning is a softmax-statistics
    # restructure, so the split lowerings must add ZERO pool traffic (no
    # pool- or scale-sized copy in any decode/verify loop) and zero
    # collectives beyond the megatron all-reduces the unsplit tp program
    # carries (tp_decode_split is asserted with the rest of TP_PROGRAMS)
    assert report["split_decode_while_bodies"], "split decode lost its scan?"
    for key in budgets.SPLIT_ZERO_COLLECTIVE_KEYS + budgets.SPLIT_ZERO_COPY_KEYS:
        assert all(n == zero for n in report[key].values()), key
    # round-overlap extensions: the fused multi-round group program must
    # add ZERO in-loop pool/scale traffic and zero collectives at every
    # audited k — a group multiplies any in-loop copy cost by k, so the
    # census is the load-bearing claim of the fusion (budgets.py)
    for key in budgets.GROUP_ZERO_COLLECTIVE_KEYS + budgets.GROUP_ZERO_COPY_KEYS:
        assert report[key], f"{key}: group program lost its scan?"
        assert all(n == zero for n in report[key].values()), key
    # attention-variant extensions (docs/SERVING.md "Attention variants"):
    # the KV-head-shrunk GQA/MQA pools still alias through every decode
    # loop carry (f32 AND int8+scales), window masking adds zero pool
    # traffic, and GQA under tp pays exactly the same megatron all-reduce
    # budget as MHA — grouping moves pool bytes, never collectives
    for key in budgets.VARIANT_ZERO_COLLECTIVE_KEYS + budgets.VARIANT_ZERO_COPY_KEYS:
        assert all(n == zero for n in report[key].values()), key
    assert report["tp_decode_gqa_loop_all_reduces"] == (
        budgets.tp_loop_all_reduce_budget("tp_decode_gqa", budgets.AUDIT_GQA_TP)
    )
    assert report["tp_decode_gqa_loop_pool_copies"] == zero
