"""Attention variants (docs/SERVING.md "Attention variants"): GQA/MQA
grouped KV heads and sliding-window(+sinks) masking as first-class config
knobs, train-to-serve.

Three layers of pinning, mirroring tests/test_split_k.py:

* kernel level — the unified template (kernels/attention_template.py) over
  the full variant matrix {MHA, GQA, MQA, window+sinks} x {f32, int8} x
  {split_k 1/2/4} x {decode, multi-row verify}, in interpret mode, against
  an independent dense einsum oracle (the mask spelled out from the spec,
  not imported from ops/attention.visible_mask);
* engine level — a GQA ServeEngine's greedy streams bit-match
  engine.generate under int8, forced split-K, a tp=2 mesh, and a sliding
  window with sinks; window page reclamation keeps the resident page set
  bounded while the conservation law holds; and a GQA config survives the
  full train -> checkpoint -> restore_for_sampling -> serve loop;
* contract level — config validation negative paths, and the recompile
  pin: variant geometry is a PROGRAM key (an MHA and a GQA engine compile
  disjoint programs) while request-mix changes compile nothing.

Pool geometry note: engine tests use num_pages=37/39/43/45, disjoint from the
pristine 25-page pins (tests/test_recompile_pins.py), the 29/31-page tp
geometries, and split-K's 33/35.
"""

import math

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.analysis.hlo_audit import CompileCounter
from midgpt_tpu.kernels.attention_template import paged_attention_template
from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
from midgpt_tpu.ops.quant import quantize_q8
from midgpt_tpu.parallel.serve_tp import make_serve_mesh
from midgpt_tpu.sampling.engine import generate
from midgpt_tpu.sampling.ops import assert_conserved
from midgpt_tpu.sampling.serve import ServeEngine

B, C = 2, 128  # C spans the full Mosaic lane dim
PS, NP, MP = 8, 7, 4  # page_size, pool pages, max logical pages/slot

# Every variant is a (query heads, KV heads, window, sinks) spec over ONE
# template — the module's design claim. MQA is the extreme grouping (any
# head-fold indexing bug surfaces), window+sinks rides on GQA so masking
# and grouping are exercised together.
VARIANTS = {
    "mha": dict(hq=2, hkv=2, window=0, sinks=0),
    "gqa": dict(hq=4, hkv=2, window=0, sinks=0),
    "mqa": dict(hq=4, hkv=1, window=0, sinks=0),
    "window": dict(hq=4, hkv=2, window=10, sinks=3),
}


# ----------------------------------------------------------------------
# Kernel level: template variant matrix vs dense oracle
# ----------------------------------------------------------------------


def _problem(hkv, hq, n_rows, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, hq, n_rows, C), jnp.float32)
    k_pages = jax.random.normal(keys[1], (hkv, NP, PS, C), jnp.float32)
    v_pages = jax.random.normal(keys[2], (hkv, NP, PS, C), jnp.float32)
    rng = np.random.default_rng(seed)
    page_table = jnp.asarray(rng.integers(0, NP, (B, MP)), jnp.int32)
    # ragged, page-unaligned base lengths; verify rows extend one key each
    base = jnp.asarray([19, MP * PS - n_rows], jnp.int32)
    counts = base[:, None] + jnp.arange(n_rows)[None] + 1
    return q, k_pages, v_pages, page_table, counts


def _quantize(pages):
    qp, s = quantize_q8(pages.transpose(1, 0, 2, 3))
    return qp.transpose(1, 0, 2, 3), s


def _dense_oracle(q, k_pages, v_pages, page_table, counts, window, sinks):
    """Per-(slot, head, row) masked softmax attention, the mask written out
    from the spec: visible = [0, n) ∩ ([n - W, n) ∪ [0, sinks))."""
    Bq, HQ, R, Cd = q.shape
    groups = HQ // k_pages.shape[0]
    out = np.zeros((Bq, HQ, R, Cd), np.float32)
    for b in range(Bq):
        kb = np.concatenate(
            [np.asarray(k_pages)[:, p] for p in np.asarray(page_table)[b]],
            axis=1,
        )  # (H_kv, MP*PS, C)
        vb = np.concatenate(
            [np.asarray(v_pages)[:, p] for p in np.asarray(page_table)[b]],
            axis=1,
        )
        col = np.arange(kb.shape[1])
        for h in range(HQ):
            kv = h // groups
            for r in range(R):
                n = int(counts[b, r])
                keep = col < n
                if window:
                    w = col >= n - window
                    if sinks:
                        w |= col < sinks
                    keep &= w
                s = (np.asarray(q)[b, h, r] @ kb[kv].T) / math.sqrt(Cd)
                s = np.where(keep, s, -np.inf)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[b, h, r] = p @ vb[kv]
    return out


# int8 and split_k=4 are the heavy long tail (every cell is an interpret-
# mode pallas run); the f32 x split {1,2} slice keeps full variant x mode
# coverage inside the tier-1 870 s gate and the marked cells still run in
# the unfiltered suite.
@pytest.mark.parametrize("mode", ["decode", "verify"])
@pytest.mark.parametrize(
    "split", [1, 2, pytest.param(4, marks=pytest.mark.slow)]
)
@pytest.mark.parametrize(
    "quant",
    [False, pytest.param(True, marks=pytest.mark.slow)],
    ids=["f32", "int8"],
)
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_template_variant_matrix_matches_dense_oracle(
    variant, quant, split, mode
):
    """The acceptance matrix: every (variant, dtype, split, row-count) spec
    instantiated from the ONE template agrees with the dense oracle. The
    kernel body never sees query heads or window state — grouping folds
    into the row axis, the window is a static mask — so a pass here pins
    that the folds/masks compose rather than special-case."""
    v = VARIANTS[variant]
    n_rows = 1 if mode == "decode" else 3
    q, kp, vp, pt, cnt = _problem(v["hkv"], v["hq"], n_rows)
    kw = {}
    if quant:
        kq, ks = _quantize(kp)
        vq, vs = _quantize(vp)
        kp_in, vp_in = kq, vq
        kw = dict(k_scale=ks, v_scale=vs)
        # oracle runs on the dequantized pools — quantization error is the
        # representation's, not the kernel's, so it must cancel exactly
        kp = kq.astype(jnp.float32) * ks.transpose(1, 0, 2)[:, :, :, None]
        vp = vq.astype(jnp.float32) * vs.transpose(1, 0, 2)[:, :, :, None]
    else:
        kp_in, vp_in = kp, vp
    got = np.asarray(
        paged_attention_template(
            q, kp_in, vp_in, pt, cnt, split_k=split,
            sliding_window=v["window"], attn_sinks=v["sinks"], **kw,
        )
    )
    want = _dense_oracle(q, kp, vp, pt, cnt, v["window"], v["sinks"])
    assert got.shape == (B, v["hq"], n_rows, C)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_template_full_window_is_bit_identical_to_windowless():
    """window >= every count must lower to the same math as no window at
    all (the mask predicate is vacuously true) — the guarantee that lets
    the engine keep ONE template with window as a static parameter."""
    q, kp, vp, pt, cnt = _problem(hkv=2, hq=4, n_rows=1, seed=5)
    base = np.asarray(paged_attention_template(q, kp, vp, pt, cnt))
    wide = np.asarray(
        paged_attention_template(
            q, kp, vp, pt, cnt, sliding_window=MP * PS, attn_sinks=0
        )
    )
    np.testing.assert_array_equal(wide, base)


# ----------------------------------------------------------------------
# Engine level: GQA/window serving, bit-exact and page-bounded
# ----------------------------------------------------------------------

GQA_CFG = GPTConfig(
    block_size=128, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    n_kv_heads=2,
)
WIN_CFG = GPTConfig(
    block_size=128, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
    n_kv_heads=2, sliding_window=16, attn_sinks=4,
)


def _trace(cfg, seed=0, n=4, lo=5, hi=30, budget_hi=18):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, size=n)
    return (
        [rng.integers(1, cfg.vocab_size, size=int(l)).tolist() for l in lens],
        [int(b) for b in rng.integers(5, budget_hi, size=n)],
    )


def _serve_vs_generate(cfg, params, *, dtype=jnp.float32, split_k=1,
                       mesh=None, num_pages=37, trace=None):
    eng = ServeEngine(
        cfg, params, max_slots=3, page_size=8, num_pages=num_pages,
        prefill_chunk=16, decode_chunk=8, temperature=0.0,
        cache_dtype=dtype, split_k=split_k, mesh=mesh,
    )
    prompts, budgets = trace or _trace(cfg)
    uids = [eng.submit(p, m) for p, m in zip(prompts, budgets)]
    done = eng.run()
    for uid, p, m in zip(uids, prompts, budgets):
        ref = generate(
            cfg, params, jnp.asarray(p, jnp.int32)[None], m, temperature=0.0
        )[0]
        np.testing.assert_array_equal(
            np.asarray(done[uid].tokens), np.asarray(ref)
        )
    return eng


@pytest.fixture(scope="module")
def gqa_params():
    return GPT.init(GQA_CFG, jax.random.PRNGKey(0))


# Every feature cell pays a full engine + generate-oracle compile
# (~13 s each on the 1-core host), so the whole parametrization is
# slow-tier; the cheap tier-1 engine representative for this subsystem
# is the recompile-pin test below (runs real MHA and GQA traffic), and
# the f32 template-matrix cells keep the kernel parity gate non-slow.
@pytest.mark.parametrize(
    "feature",
    [
        pytest.param("plain", marks=pytest.mark.slow),
        pytest.param("int8", marks=pytest.mark.slow),
        pytest.param("split", marks=pytest.mark.slow),
        pytest.param("tp", marks=pytest.mark.slow),
        pytest.param("window", marks=pytest.mark.slow),
    ],
)
def test_gqa_engine_greedy_matches_generate(gqa_params, feature):
    """The serving acceptance pin: a GQA engine's paged streams are
    bit-identical to the dense-cache generate path — grouping changes the
    pool geometry, never a token — and the property composes with int8
    pools, forced split-K, a tp=2 mesh (whole query groups per shard), and
    window+sinks masking."""
    kw = {}
    cfg, params = GQA_CFG, gqa_params
    if feature == "int8":
        kw["dtype"] = "int8"
    elif feature == "split":
        kw["split_k"] = 4
    elif feature == "tp":
        kw["mesh"] = make_serve_mesh(tp_size=2)
    elif feature == "window":
        cfg = WIN_CFG
        params = GPT.init(WIN_CFG, jax.random.PRNGKey(0))
    _serve_vs_generate(cfg, params, **kw)


def test_gqa_byte_budget_scales_pages_and_reduces_preemptions(gqa_params):
    """THE capacity claim of grouping (docs/SERVING.md "Attention variants"):
    a GQA page is group-factor smaller (PagedKVCache.page_bytes), so at one
    pool_hbm_bytes the GQA pool admits >= 0.75 x groups the pages of MHA (the
    floor absorbs the sizing's rounding; here it is exact), and on a trace
    that oversubscribes the MHA pool the GQA engine preempts STRICTLY less.
    Capacity is the only thing that differs: each engine's streams, the
    preempted ones included, are bit-identical to dense-cache generate on
    its own params (different projection layouts are different models)."""
    mha_cfg = dataclasses.replace(GQA_CFG, n_kv_heads=None)
    mha_params = GPT.init(mha_cfg, jax.random.PRNGKey(0))
    groups = GQA_CFG.n_head // GQA_CFG.kv_heads
    budget = PagedKVCache.page_bytes(mha_cfg, 8, jnp.float32) * 10  # MHA: 10 pages
    rng = np.random.default_rng(3)
    # 3 x 4 pages of demand: over MHA's 9 allocatable pages, inside GQA's 19
    trace = [(rng.integers(1, GQA_CFG.vocab_size, 8).tolist(), 24) for _ in range(3)]

    def run(cfg, params):
        eng = ServeEngine(
            cfg, params, max_slots=3, page_size=8, pool_hbm_bytes=budget,
            prefill_chunk=8, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32,
        )
        uids = [eng.submit(p, m) for p, m in trace]
        done = eng.run()
        for uid, (p, m) in zip(uids, trace):
            ref = generate(cfg, params, jnp.asarray(p, jnp.int32)[None], m, temperature=0.0)[0]
            np.testing.assert_array_equal(np.asarray(done[uid].tokens), np.asarray(ref))
        assert_conserved(eng, "after the oversubscribed trace")
        return eng

    mha, gqa = run(mha_cfg, mha_params), run(GQA_CFG, gqa_params)
    assert mha.allocator.num_pages == 10
    assert gqa.allocator.num_pages >= 0.75 * groups * mha.allocator.num_pages
    assert mha.preemptions > 0, "the trace must oversubscribe the MHA pool"
    assert gqa.preemptions < mha.preemptions, (gqa.preemptions, mha.preemptions)


@pytest.mark.slow  # long stream + generate oracle: ~14 s on the 1-core host
def test_window_engine_reclaims_pages_and_stays_bounded(monkeypatch):
    """Unbounded-session decode: a windowed engine streams far past
    sliding_window with (a) greedy parity against generate — reclamation
    must never free a page the mask can still see, conservative-by-one
    rule included; (b) a RESIDENT page bound at every append — the live
    (non-sentinel) page set never exceeds sink pages + window pages + the
    active page + the one-token conservatism; (c) the allocator
    conservation law intact afterwards (reclaimed pages really returned);
    (d) a nonzero window_reclaimed_pages counter on stats()."""
    params = GPT.init(WIN_CFG, jax.random.PRNGKey(0))
    W, sinks, ps = WIN_CFG.sliding_window, WIN_CFG.attn_sinks, 8
    bound = -(-sinks // ps) + -(-W // ps) + 2
    live_high = []
    orig = ServeEngine._append_token

    def spy(self, slot_i, slot, tok, t):
        ok = orig(self, slot_i, slot, tok, t)
        live_high.append(sum(p >= 0 for p in slot.pages[0]))
        return ok

    monkeypatch.setattr(ServeEngine, "_append_token", spy)
    # one long stream: 12-token prompt + 56 new tokens = 4x+ the window
    eng = _serve_vs_generate(
        WIN_CFG, params, num_pages=39,
        trace=([list(range(1, 13))], [56]),
    )
    assert live_high, "spy never fired — decode path changed?"
    assert max(live_high) <= bound, (
        f"resident pages peaked at {max(live_high)} > bound {bound} — "
        "reclamation is not keeping up with the window"
    )
    assert eng.stats()["window_reclaimed_pages"] > 0
    assert_conserved(eng, "after windowed run")


@pytest.mark.slow  # full train-step compile: heavy long-tail, full suite only
def test_gqa_trains_checkpoints_restores_and_serves(tmp_path):
    """The end-to-end acceptance loop: a GQA config takes real optimizer
    steps on the training mesh, checkpoints, restores through the sampling
    path (restore_for_sampling), and the restored params serve greedy
    bit-exact against generate. Pins that the wkv leaf survives the
    save/restore round-trip — a pytree-structure regression here would
    silently drop the K/V projection."""
    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.sampling.engine import restore_for_sampling
    from midgpt_tpu.training.checkpoint import CheckpointManager
    from midgpt_tpu.training.train import init_state, make_train_step

    mc = GPTConfig(
        block_size=32, vocab_size=64, n_layer=2, n_head=4, n_embd=32,
        n_kv_heads=2,
    )
    cfg = ExperimentConfig(
        rundir="", data_dir="", learning_rate=1e-2, batch_size=8,
        warmup_steps=2, min_lr=1e-3, lr_decay_steps=10, max_steps=10,
        beta2=0.99, weight_decay=0.0, eval_interval=5, param_dtype="float32",
        compute_dtype="float32", g_accum_iters=1, shard_model=True,
        fsdp_min_size=0, mesh=MeshConfig(data=2, fsdp=4, sp=1),
        model_config=mc,
    )
    mesh = make_mesh(cfg.mesh)
    params, opt_state, specs, optimizer = init_state(cfg, mesh)
    step, *_ = make_train_step(cfg, optimizer, mesh, specs)
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    for i in range(3):
        x = rng.integers(0, mc.vocab_size, (1, 8, 32), dtype=np.int32)
        y = np.roll(x, -1, axis=-1)
        key, k = jax.random.split(key)
        params, opt_state, loss = step(
            params, opt_state,
            make_global_batch(x, mesh, batch_spec()),
            make_global_batch(y, mesh, batch_spec()), k,
        )
    assert np.isfinite(float(loss))

    mngr = CheckpointManager(str(tmp_path), max_to_keep=1, save_interval_steps=1)
    mngr.save(3, {"params": params}, force=True)
    mngr.wait()
    mngr.close()
    restored, ckpt_step = restore_for_sampling(str(tmp_path), cfg)
    assert ckpt_step == 3
    assert jax.tree.structure(restored) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    host = jax.device_get(restored)
    _serve_vs_generate(
        mc, host, num_pages=43,
        trace=_trace(mc, seed=1, n=3, lo=4, hi=12, budget_hi=10),
    )


# ----------------------------------------------------------------------
# Contract level: validation negative paths + the recompile pin
# ----------------------------------------------------------------------


def test_config_validation_negative_paths():
    base = dict(block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32)
    with pytest.raises(ValueError, match="n_kv_heads"):
        GPTConfig(**base, n_kv_heads=3)  # not a divisor of n_head
    with pytest.raises(ValueError, match="n_kv_heads"):
        GPTConfig(**base, n_kv_heads=0)
    with pytest.raises(ValueError, match="sliding_window"):
        GPTConfig(**base, sliding_window=64)  # must be < block_size
    with pytest.raises(ValueError, match="sliding_window"):
        GPTConfig(**base, sliding_window=-8)
    with pytest.raises(ValueError, match="attn_sinks"):
        GPTConfig(**base, attn_sinks=4)  # sinks require a window
    with pytest.raises(ValueError, match="exceeds"):
        GPTConfig(**base, sliding_window=60, attn_sinks=8)


def test_tp_divisibility_negative_paths():
    from midgpt_tpu.config import ExperimentConfig, MeshConfig

    mqa = GPTConfig(
        block_size=32, vocab_size=64, n_layer=2, n_head=4, n_embd=32,
        n_kv_heads=1,
    )
    with pytest.raises(ValueError, match="KV heads"):
        ExperimentConfig(
            rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
            warmup_steps=1, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
            beta2=0.99, weight_decay=0.0, eval_interval=5,
            param_dtype="float32", compute_dtype="float32", g_accum_iters=1,
            shard_model=True, fsdp_min_size=0,
            mesh=MeshConfig(data=1, fsdp=1, tp=2), model_config=mqa,
        )
    params = GPT.init(mqa, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="KV heads"):
        ServeEngine(
            mqa, params, max_slots=2, page_size=8, num_pages=9,
            prefill_chunk=8, decode_chunk=4, temperature=0.0,
            cache_dtype=jnp.float32, mesh=make_serve_mesh(tp_size=2),
        )


def test_variant_geometry_is_a_program_key_mix_changes_compile_nothing(
    gqa_params,
):
    """The recompile pin, extended per docs/SERVING.md: MHA and GQA pools
    have different shapes, so an MHA engine and a GQA engine compile
    DISJOINT decode programs (geometry is a static program key, never
    runtime state) — and once both are warm, any further mix of requests
    through either engine compiles NOTHING. Mix design follows
    tests/test_split_k.py's forced-split pin: prompts 25..47 with
    max_new ≡ 1 (mod 8) pin the pow2 page bucket at the 8-page cap from
    the first decode round, so mix changes exercise only data. Pool
    geometry 45 is this test's own (cold for BOTH variants regardless of
    run order — the parity tests above warm the 37-page programs)."""
    mha_cfg = GPTConfig(
        block_size=128, vocab_size=96, n_layer=2, n_head=4, n_embd=32
    )
    mha_params = GPT.init(mha_cfg, jax.random.PRNGKey(0))

    def run_mix(cfg, params, lengths, max_new, seed):
        eng = ServeEngine(
            cfg, params, max_slots=3, page_size=8, num_pages=45,
            prefill_chunk=16, decode_chunk=8, temperature=0.0,
            cache_dtype=jnp.float32,
        )
        rng = np.random.default_rng(seed)
        uids = {
            eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
            for n, m in zip(lengths, max_new)
        }
        assert set(eng.run()) == uids

    # warm the MHA programs at this geometry
    run_mix(mha_cfg, mha_params, (25, 34, 47), (9, 17, 17), seed=0)
    with CompileCounter() as cc:
        run_mix(GQA_CFG, gqa_params, (25, 34, 47), (9, 17, 17), seed=0)
    assert cc.count > 0, (
        "GQA first run compiled nothing — it reused an MHA program? "
        "pool geometry must be a program key"
    )
    with CompileCounter() as cc:
        run_mix(mha_cfg, mha_params, (26, 33, 40), (9, 17, 9), seed=1)
        run_mix(GQA_CFG, gqa_params, (29, 41, 45), (17, 9, 17), seed=2)
        run_mix(mha_cfg, mha_params, (31, 38, 47), (17, 17, 9), seed=3)
    assert cc.count == 0, (
        f"request-mix change recompiled {cc.count} program(s) — variant "
        "mix must be free once both geometries are warm"
    )
