"""Int8 quantized paged KV cache (ops/quant.py + PagedKVCache int8 mode +
kernels/decode_attention.py dual-mode kernels + ServeEngine byte budget):

* quantizer invariants and the einsum-dequant oracle parity of BOTH Pallas
  kernels (decode and the multi-row verify kernel) in interpret mode,
  bf16/f32 and int8;
* end-to-end int8 serving: deterministic under recompute-style preemption
  (the PR-1 decode-time-eviction regression scenario, quantized), greedy
  speculative == greedy plain on the SAME int8 cache, stochastic runs;
* byte-budgeted paging: at a fixed pool_hbm_bytes an int8 pool admits
  exactly 2x the pages of bf16 and suffers strictly fewer preemptions on
  the same oversubscribed trace;
* the compiled-artifact pin: zero pool-sized AND zero scale-buffer-sized
  copies inside the int8 decode/verify loops (the aliasing-scatter
  property extended to the side buffers).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.kernels.decode_attention import (
    paged_attention_gather,
    paged_attention_kernel,
    paged_verify_attention,
    paged_verify_attention_gather,
    paged_verify_attention_kernel,
)
from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
from midgpt_tpu.ops.quant import Q8_MAX, dequantize_q8, quantize_q8
from midgpt_tpu.sampling.serve import ServeEngine, normalize_cache_dtype

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


# ----------------------------------------------------------------------
# quantizer
# ----------------------------------------------------------------------


def test_quantize_roundtrip_invariants():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 17)) * 3.0
    q, s = quantize_q8(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    qn = np.asarray(q, np.int32)
    assert np.abs(qn).max() <= Q8_MAX  # -128 never produced
    np.testing.assert_allclose(
        np.asarray(s),
        np.abs(np.asarray(x)).max(-1) / Q8_MAX,
        rtol=1e-6,
    )
    err = np.abs(np.asarray(dequantize_q8(q, s)) - np.asarray(x))
    # round-to-nearest: at most half a quantization step, elementwise
    assert (err <= np.asarray(s)[..., None] * 0.5 + 1e-7).all()
    # an all-zero vector stores scale 0 and dequantizes to exact zeros
    q0, s0 = quantize_q8(jnp.zeros((2, 4)))
    assert float(jnp.abs(dequantize_q8(q0, s0)).max()) == 0.0


# ----------------------------------------------------------------------
# kernels vs the einsum dequant oracle (interpret mode off-TPU)
# ----------------------------------------------------------------------

B, H, C = 3, 2, 128  # C spans the full Mosaic lane dim
PS, NP, MP = 8, 7, 4


def _quantized_problem(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, C), jnp.float32)
    kf = jax.random.normal(keys[1], (H, NP, PS, C), jnp.float32)
    vf = jax.random.normal(keys[2], (H, NP, PS, C), jnp.float32)
    # quantize per (page, head, position) over C -> scale layout (P, H, ps)
    kq, ks = quantize_q8(kf.transpose(1, 0, 2, 3))
    vq, vs = quantize_q8(vf.transpose(1, 0, 2, 3))
    kq, vq = kq.transpose(1, 0, 2, 3), vq.transpose(1, 0, 2, 3)
    pt = jnp.asarray([[3, 1, 0, 0], [5, 2, 6, 0], [4, 0, 0, 0]], jnp.int32)
    ln = jnp.asarray([11, 24, 1], jnp.int32)
    return q, kq, vq, ks, vs, pt, ln


def _dense_dequant_oracle(q, kq, vq, ks, vs, pt, ln, counts=None):
    """Materialize each slot's logical K/V by EXACT dequantization
    (int8 * f32, ops/quant.py) and run plain masked attention — the
    oracle both lowerings must reproduce."""
    import math

    kd = np.asarray(dequantize_q8(kq.transpose(1, 0, 2, 3), ks))  # (P,H,ps,C)
    vd = np.asarray(dequantize_q8(vq.transpose(1, 0, 2, 3), vs))
    out = []
    qn = np.asarray(q)
    multi = qn.ndim == 4  # (B, T, H, C) verify problem
    for b in range(qn.shape[0]):
        kb = np.concatenate([kd[p] for p in np.asarray(pt)[b]], axis=1)  # (H,S,C)
        vb = np.concatenate([vd[p] for p in np.asarray(pt)[b]], axis=1)
        kb = kb.transpose(0, 1, 2) if kb.ndim == 3 else kb
        rows = qn[b] if multi else qn[b][None]  # (T, H, C)
        row_counts = (
            np.asarray(counts)[b] if counts is not None
            else np.asarray([int(ln[b])])
        )
        os = []
        for t, row in enumerate(rows):
            s = np.einsum("hc,hkc->hk", row, kb) / math.sqrt(C)
            s[:, row_counts[t]:] = -np.inf
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            os.append(np.einsum("hk,hkc->hc", p, vb))
        out.append(np.stack(os))
    out = np.stack(out)  # (B, T, H, C)
    return out if multi else out[:, 0]


def test_gather_int8_matches_dense_dequant_oracle():
    q, kq, vq, ks, vs, pt, ln = _quantized_problem()
    got = paged_attention_gather(q, kq, vq, pt, ln, k_scale=ks, v_scale=vs)
    want = _dense_dequant_oracle(q, kq, vq, ks, vs, pt, ln)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_decode_kernel_int8_matches_oracle():
    """The Mosaic decode kernel's in-VMEM dequant must reproduce the
    einsum dequant oracle (both dequantize the same int8+scale pairs
    exactly, so only softmax-order float noise separates them)."""
    q, kq, vq, ks, vs, pt, ln = _quantized_problem(seed=1)
    got = np.asarray(
        paged_attention_kernel(q, kq, vq, pt, ln, k_scale=ks, v_scale=vs)
    )
    want = _dense_dequant_oracle(q, kq, vq, ks, vs, pt, ln)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("quantized", (False, True), ids=("f32", "int8"))
def test_verify_kernel_matches_gather(quantized):
    """The multi-row Pallas verify kernel (the compiled verify path on
    TPU) against the gather lowering, ragged per-row counts included —
    bf16/f32 and int8 modes."""
    T = 3
    q, kq, vq, ks, vs, pt, ln = _quantized_problem(seed=2)
    qv = jax.random.normal(jax.random.PRNGKey(7), (B, T, H, C), jnp.float32)
    counts = jnp.asarray([[9, 10, 11], [22, 23, 24], [1, 1, 1]], jnp.int32)
    if quantized:
        kp, vp, scales = kq, vq, dict(k_scale=ks, v_scale=vs)
    else:
        keys = jax.random.split(jax.random.PRNGKey(3), 2)
        kp = jax.random.normal(keys[0], (H, NP, PS, C), jnp.float32)
        vp = jax.random.normal(keys[1], (H, NP, PS, C), jnp.float32)
        scales = {}
    want = np.asarray(
        paged_verify_attention_gather(qv, kp, vp, pt, counts, **scales)
    )
    got = np.asarray(
        paged_verify_attention_kernel(qv, kp, vp, pt, counts, **scales)
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    if quantized:
        oracle = _dense_dequant_oracle(qv, kq, vq, ks, vs, pt, ln, counts)
        np.testing.assert_allclose(got, oracle, atol=2e-5, rtol=2e-5)


def test_verify_dispatcher_validates_impl():
    q, kq, vq, ks, vs, pt, ln = _quantized_problem(seed=3)
    qv = jnp.zeros((B, 2, H, C))
    counts = jnp.ones((B, 2), jnp.int32)
    with pytest.raises(ValueError, match="unknown paged verify"):
        paged_verify_attention(qv, kq, vq, pt, counts, impl="nope")


# ----------------------------------------------------------------------
# end-to-end int8 serving
# ----------------------------------------------------------------------


def _run_engine(params, trace, **kw):
    eng = ServeEngine(
        CFG, params, page_size=8, prefill_chunk=16, decode_chunk=8,
        temperature=0.0, **kw,
    )
    uids = [eng.submit(p, m) for p, m in trace]
    done = eng.run()
    assert set(done) == set(uids)
    return eng, [done[u].tokens for u in uids]


@pytest.mark.slow
def test_int8_serving_deterministic_under_eviction(params):
    """The PR-1 decode-time-eviction regression scenario, quantized: an
    oversubscribed int8 pool forces recompute-style preemption mid-decode
    (older slot growth evicts the youngest ACTIVE slot), and the outputs
    must equal an un-preempted int8 run token for token — preemption
    re-prefills the same tokens, which re-quantize to the same int8
    values, so the quantized engine is exactly as deterministic as the
    bf16 one (pinned here; the bf16 pin is
    tests/test_serving.py::test_serve_decode_time_eviction_of_active_slot)."""
    rng = np.random.default_rng(3)
    # same shape as the PR-1 scenario (3 short prompts, decode-dominated),
    # 24 new tokens instead of 40: 3 x 4 pages of demand against a 9-page
    # pool still forces decode-time eviction every run, at ~60% of the cost
    trace = [
        (rng.integers(0, CFG.vocab_size, 8).astype(np.int32), 24)
        for _ in range(3)
    ]
    big, ref = _run_engine(
        params, trace, max_slots=3, num_pages=33, cache_dtype="int8"
    )
    assert big.preemptions == 0, "reference run must not preempt"
    small, out = _run_engine(
        params, trace, max_slots=3, num_pages=10, cache_dtype="int8"
    )
    assert small.preemptions > 0, "10-page pool must force eviction"
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_int8_spec_greedy_matches_plain_int8(params):
    """Greedy speculative serving on the int8 cache == greedy plain int8
    serving, token for token: the draft's speculative writes and the
    verify rewrite quantize identical values (same inputs through the same
    quantized prefix cache), so acceptance decisions replay plain decode
    exactly — the quantized analogue of tests/test_spec.py's parity pin."""
    from midgpt_tpu.sampling.spec import self_draft

    dcfg, dparams = self_draft(CFG, params, 1)
    rng = np.random.default_rng(5)
    trace = [
        (rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
        for n, m in ((5, 12), (19, 10))
    ]
    _, ref = _run_engine(
        params, trace, max_slots=2, num_pages=25, cache_dtype="int8"
    )
    _, out = _run_engine(
        params, trace, max_slots=2, num_pages=25, cache_dtype="int8",
        draft_params=dparams, draft_config=dcfg, draft_shares_cache=True,
        # pin k at 4: parity holds for any k, and one k-bucket means one
        # draft+verify compile instead of one per adaptive halving
        spec_k_max=4, spec_k_min=4, spec_adapt=False,
    )
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


def test_int8_stochastic_serving_runs(params):
    """temperature > 0 through the quantized cache: in-vocab tokens of the
    right length (the existing statistical pins cover the sampler itself —
    it consumes logits, not cache bytes)."""
    rng = np.random.default_rng(11)
    trace = [(rng.integers(0, CFG.vocab_size, 7).astype(np.int32), 9)]
    eng = ServeEngine(
        CFG, params, max_slots=1, num_pages=17, temperature=0.8, top_k=20,
        seed=7, cache_dtype="int8",
    )
    u = eng.submit(*trace[0])
    out = eng.run()[u].tokens
    assert len(out) == 7 + 9
    assert (out >= 0).all() and (out < CFG.vocab_size).all()


# ----------------------------------------------------------------------
# byte-budgeted paging
# ----------------------------------------------------------------------


@pytest.mark.slow
def test_byte_budget_doubles_pages_and_reduces_preemptions(params):
    """THE capacity claim: at a fixed pool_hbm_bytes, the int8 pool admits
    exactly 2x the pages of bf16 (the budget covers the K/V pools;
    PagedKVCache.page_bytes documents that the f32 scale side buffer rides
    on top and cache_hbm_bytes reports it), and on the same oversubscribed
    trace the int8 engine preempts strictly less while every request still
    completes."""
    budget = PagedKVCache.page_bytes(CFG, 8, jnp.bfloat16) * 10  # bf16: 10pg
    e_bf = ServeEngine(
        CFG, params, page_size=8, pool_hbm_bytes=budget, cache_dtype="bf16"
    )
    e_i8 = ServeEngine(
        CFG, params, page_size=8, pool_hbm_bytes=budget, cache_dtype="int8"
    )
    assert e_bf.allocator.num_pages == 10
    assert e_i8.allocator.num_pages == 20
    # the side buffer is the documented +4/head_dim on top, not hidden
    kv_bytes = sum(
        a.nbytes for a in (e_i8.cache.k, e_i8.cache.v)
    )
    assert e_i8.cache_hbm_bytes() > kv_bytes

    rng = np.random.default_rng(3)
    # 3 x 4 pages of demand: oversubscribes bf16's 9 allocatable pages
    # (evicts), fits int8's 19 (doesn't)
    trace = [
        (rng.integers(0, CFG.vocab_size, 8).astype(np.int32), 24)
        for _ in range(3)
    ]
    eng_bf, out_bf = _run_engine(
        params, trace, max_slots=3, pool_hbm_bytes=budget, cache_dtype="bf16"
    )
    eng_i8, out_i8 = _run_engine(
        params, trace, max_slots=3, pool_hbm_bytes=budget, cache_dtype="int8"
    )
    assert eng_bf.preemptions > eng_i8.preemptions, (
        eng_bf.preemptions, eng_i8.preemptions,
    )
    for (p, m), toks in zip(trace, out_i8):
        assert len(toks) == len(p) + m


def test_pool_sizing_validation(params):
    with pytest.raises(ValueError, match="not both"):
        ServeEngine(CFG, params, num_pages=10, pool_hbm_bytes=1 << 20)
    with pytest.raises(ValueError, match="unknown cache dtype"):
        ServeEngine(CFG, params, cache_dtype="fp4")
    assert normalize_cache_dtype("bf16") == jnp.bfloat16
    assert normalize_cache_dtype(jnp.float32) == jnp.float32


def test_kv_cache_dtype_config_validation():
    from midgpt_tpu.config import ExperimentConfig, MeshConfig

    base = dict(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
        warmup_steps=1, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
        beta2=0.99, weight_decay=0.0, eval_interval=5,
        param_dtype="float32", compute_dtype="float32", g_accum_iters=1,
        shard_model=False, mesh=MeshConfig(data=-1, fsdp=1), model_config=CFG,
    )
    ExperimentConfig(**base, kv_cache_dtype="int8")  # valid
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ExperimentConfig(**base, kv_cache_dtype="fp8")


# ----------------------------------------------------------------------
# compiled-artifact pin
# ----------------------------------------------------------------------


def test_int8_programs_have_no_in_loop_pool_or_scale_copies():
    """ISSUE acceptance HLO pin: with the int8 cache, the decode chunk's
    while body and the verify program's layer loop contain no POOL-sized
    and no SCALE-buffer-sized copies beyond the backend's per-scatter
    relayout allowance (budgets.LOOP_POOL_COPIES_PER_SCATTER) — the
    quantizing scatters alias through the donated carry exactly like the
    bf16 writes (the census covers the side buffers too;
    `python -m midgpt_tpu.analysis --audit` runs the same checks)."""
    from midgpt_tpu.analysis import budgets
    from midgpt_tpu.analysis.hlo_audit import (
        loop_pool_copy_excess,
        pool_scatter_count,
    )
    from midgpt_tpu.sampling import serve

    B_, ps, n_pages, K = 2, 8, 12, 2
    cfg = dataclasses.replace(CFG, decode_layer_scan=True)
    L, H_, C_ = cfg.n_layer, cfg.n_head, cfg.head_dim
    mp = cfg.block_size // ps
    abstract = jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), abstract
    )
    cache = jax.eval_shape(
        lambda: PagedKVCache.init(cfg, num_pages=n_pages, page_size=ps,
                                  dtype=jnp.int8)
    )
    pool = f"s8[{L},{H_},{n_pages},{ps},{C_}]"
    scale = f"f32[{L},{n_pages},{H_},{ps}]"

    decode_txt = (
        serve._serve_decode_chunk.lower(
            cfg,
            abstract,
            jax.ShapeDtypeStruct((B_,), jnp.int32),
            cache,
            jax.ShapeDtypeStruct((B_, mp), jnp.int32),
            jax.ShapeDtypeStruct((B_,), jnp.int32),
            jax.ShapeDtypeStruct((B_,), jnp.bool_),
            4,
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    verify_txt = (
        serve._spec_verify_chunk.lower(
            cfg,
            abstract,
            jax.ShapeDtypeStruct((B_,), jnp.int32),
            jax.ShapeDtypeStruct((K, B_), jnp.int32),
            jax.ShapeDtypeStruct((K, B_, cfg.vocab_size), jnp.float32),
            cache,
            jax.ShapeDtypeStruct((B_, mp), jnp.int32),
            jax.ShapeDtypeStruct((B_,), jnp.int32),
            jax.ShapeDtypeStruct((B_,), jnp.bool_),
            0.0,
            None,
            None,
            "gather",
            None,
        )
        .compile()
        .as_text()
    )
    for name, txt in (("decode", decode_txt), ("verify", verify_txt)):
        for label, shape in (("pool", pool), ("scale", scale)):
            census = loop_pool_copy_excess(txt, shape)
            assert census, f"{name}: no while body found"
            offenders = {b: n for b, n in census.items() if n}
            assert not offenders, f"{name} {label} in-loop copies: {offenders}"
            # and nowhere else either: entry copies of the pool are allowed
            # in general but the quantized pools should alias end to end
            # (the same per-scatter relayout allowance, program-wide)
            n_total = len(re.findall(rf"= {re.escape(shape)}[^=]*copy\(", txt))
            budget = 2 + budgets.LOOP_POOL_COPIES_PER_SCATTER * pool_scatter_count(
                txt.splitlines(), shape
            )
            assert n_total <= budget, (
                f"{name}: {n_total} {label}-sized copies (budget {budget})"
            )
