"""KV-cache decode parity: incremental decoding must reproduce the full
forward pass, and generation must match a no-cache reference loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.gpt import GPT, GPTConfig, KVCache
from midgpt_tpu.sampling.engine import generate, sample_logits

CFG = GPTConfig(block_size=32, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


def test_prefill_matches_forward(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)
    full = GPT.apply(CFG, params, tokens, inference=True)
    cache = KVCache.init(CFG, 2, dtype=jnp.float32)
    logits, cache = GPT.prefill(CFG, params, tokens, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full), atol=2e-5, rtol=2e-5)
    assert int(cache.length) == 16


def test_decode_step_matches_forward(params):
    """Prefill T tokens then decode 5 more one-by-one; logits at each new
    position must match a fresh full forward over the growing sequence."""
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (2, 10), 0, CFG.vocab_size)
    extra = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, CFG.vocab_size)

    cache = KVCache.init(CFG, 2, dtype=jnp.float32)
    _, cache = GPT.prefill(CFG, params, tokens, cache)

    seq = tokens
    for i in range(5):
        tok = extra[:, i]
        logits, cache = GPT.decode_step(CFG, params, tok, cache)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        full = GPT.apply(CFG, params, seq, inference=True)[:, -1]
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full), atol=3e-5, rtol=3e-5,
            err_msg=f"decode step {i}",
        )


def test_generate_greedy_matches_no_cache_loop(params):
    """Greedy generation with the cache == greedy windowed full-forward loop
    (the reference's scheme, reference sample.py:68-95)."""
    prompt = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, CFG.vocab_size)
    n_new = 12
    out = generate(CFG, params, prompt, n_new, temperature=0.0)

    seq = prompt
    for _ in range(n_new):
        logits = GPT.apply(CFG, params, seq[:, -CFG.block_size :], inference=True)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_generate_past_block_size(params):
    """Generation must keep going past the cache/window capacity."""
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 30), 0, CFG.vocab_size)
    n_new = 10  # 30 + 10 > block_size=32 -> exercises the overflow path
    out = generate(CFG, params, prompt, n_new, temperature=0.0)
    assert out.shape == (1, 40)
    assert bool((out[:, :30] == prompt).all())


@pytest.mark.slow
def test_generate_overflow_compiles_once(params, monkeypatch):
    """Generation past the cache must not retrace per token OR per call:
    the overflow window is a static (B, S) slice served by the module-level
    `_window_forward` jit, so GPT.apply traces exactly ONCE across many
    overflow tokens and repeated generate() calls (the fast path's
    prefill/decode jits don't go through GPT.apply at all)."""
    from midgpt_tpu.sampling import engine

    jax.clear_caches()  # drop any _window_forward entry from earlier tests
    calls = {"n": 0}
    orig_apply = GPT.apply

    def counting_apply(*a, **k):
        calls["n"] += 1
        return orig_apply(*a, **k)

    monkeypatch.setattr(GPT, "apply", staticmethod(counting_apply))
    prompt = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, CFG.vocab_size)
    out = engine.generate(CFG, params, prompt, 40, temperature=0.0)
    assert out.shape == (2, 48)  # 8 + 40 > S=32: 15+ overflow tokens
    out2 = engine.generate(CFG, params, prompt, 44, temperature=0.0)
    assert out2.shape == (2, 52)
    assert calls["n"] == 1, f"overflow forward traced {calls['n']} times"


def test_prefill_blockwise_arbitrary_length(params):
    """Prefill must handle prompt lengths that are not block multiples
    (regression: blockwise path used to require divisibility)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, attn_impl="blockwise", attn_block_size=16)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 13), 0, CFG.vocab_size)
    full = GPT.apply(CFG, params, tokens, inference=True)
    logits, cache = GPT.prefill(cfg, params, tokens, KVCache.init(cfg, 1, jnp.float32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_generate_exact_fill_uses_cache(params):
    """Generation that exactly fills the context must stay on the cache path
    (regression: off-by-one guard dropped the last cache slot)."""
    prompt = jax.random.randint(jax.random.PRNGKey(6), (1, 8), 0, CFG.vocab_size)
    n_new = CFG.block_size - 8  # lands exactly on S
    out = generate(CFG, params, prompt, n_new, temperature=0.0)
    seq = prompt
    for _ in range(n_new):
        logits = GPT.apply(CFG, params, seq[:, -CFG.block_size :], inference=True)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))


def test_restore_for_sampling_sharded_over_virtual_mesh(params, tmp_path):
    """Mesh-aware sampling restore: the checkpoint loads straight into
    fsdp-sharded arrays on the 8-device virtual mesh (no single-device
    staging — how the 7B-class checkpoints must load), values match the
    saved params exactly, and greedy generation from the sharded restore
    reproduces the unsharded model's output."""
    import numpy as np

    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.sampling.engine import generate, restore_for_sampling
    from midgpt_tpu.training.checkpoint import CheckpointManager

    mngr = CheckpointManager(str(tmp_path), max_to_keep=1, save_interval_steps=1)
    mngr.save(0, {"params": params}, force=True)
    mngr.wait()
    mngr.close()

    cfg = ExperimentConfig(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
        warmup_steps=1, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
        beta2=0.99, weight_decay=0.0, eval_interval=5, param_dtype="float32",
        compute_dtype="float32", g_accum_iters=1, shard_model=True,
        fsdp_min_size=0, model_config=CFG,
    )
    restored, step = restore_for_sampling(str(tmp_path), cfg)
    assert step == 0
    shard_specs = [str(l.sharding.spec) for l in jax.tree.leaves(restored)]
    assert any("fsdp" in s for s in shard_specs), shard_specs
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    prompt = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, CFG.vocab_size)
    out_sharded = generate(CFG, restored, prompt, 6, temperature=0.0)
    out_ref = generate(CFG, params, prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out_sharded), np.asarray(out_ref))


def test_sample_logits_modes():
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]])
    key = jax.random.PRNGKey(0)
    assert int(sample_logits(logits, key, temperature=0.0)[0]) == 1
    # top_k=1 forces the argmax regardless of temperature
    assert int(sample_logits(logits, key, temperature=2.0, top_k=1)[0]) == 1
    # high temperature with full vocab still returns a valid index
    idx = int(sample_logits(logits, key, temperature=5.0)[0])
    assert 0 <= idx < 4


def test_top_p_nucleus():
    """top-p keeps the smallest prefix of descending-prob tokens reaching p:
    a tiny p degenerates to the argmax token; p=1.0 is a no-op filter."""
    from midgpt_tpu.sampling.engine import sample_logits

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # p below the top token's mass -> only token 0 survives, any key
    for seed in range(5):
        tok = sample_logits(logits, jax.random.PRNGKey(seed), 1.0, top_p=0.3)
        assert int(tok[0]) == 0
    # p covering the top two -> samples only from {0, 1}
    seen = set()
    for seed in range(20):
        tok = sample_logits(logits, jax.random.PRNGKey(seed), 1.0, top_p=0.75)
        seen.add(int(tok[0]))
    assert seen <= {0, 1} and 0 in seen
    # p=1.0 leaves the distribution untouched (same draws as unfiltered)
    for seed in range(5):
        a = sample_logits(logits, jax.random.PRNGKey(seed), 1.0, top_p=1.0)
        b = sample_logits(logits, jax.random.PRNGKey(seed), 1.0)
        assert int(a[0]) == int(b[0])


def test_decode_layer_scan_matches_unrolled(params):
    """GPTConfig.decode_layer_scan swaps the decode layer loop's lowering
    (Python-unrolled DUS chain vs rolled lax.scan — compile-time/copy
    trade-off documented on the config field); both must produce the same
    logits and cache. Under ONE spelling of the QKV projection: the unrolled
    loop always takes the per-third einsum, the scan keeps the config's (PR
    62; the two spellings against each other:
    test_per_third_projection_matches_the_flat_matmul_in_the_paged_programs)."""
    cfg_unroll = dataclasses.replace(CFG, qkv_proj="split3")
    cfg_scan = dataclasses.replace(cfg_unroll, decode_layer_scan=True)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 9), 0, CFG.vocab_size)
    extra = jax.random.randint(jax.random.PRNGKey(12), (2, 3), 0, CFG.vocab_size)

    caches = {}
    for name, cfg in (("unroll", cfg_unroll), ("scan", cfg_scan)):
        cache = KVCache.init(cfg, 2, dtype=jnp.float32)
        _, cache = GPT.prefill(cfg, params, tokens, cache)
        logits = []
        for i in range(3):
            l, cache = GPT.decode_step(cfg, params, extra[:, i], cache)
            logits.append(l)
        caches[name] = (jnp.stack(logits), cache)
    np.testing.assert_allclose(
        np.asarray(caches["scan"][0]), np.asarray(caches["unroll"][0]),
        atol=1e-6, rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(caches["scan"][1].k), np.asarray(caches["unroll"][1].k),
        atol=1e-6,
    )


def test_paged_decode_matches_contiguous_token_for_token(params):
    """ISSUE acceptance pin: greedy decode through the paged cache + page
    table samples the SAME tokens as the contiguous-cache engine, for a
    fixed seed, across chunked prefill and per-slot positions."""
    from midgpt_tpu.sampling.serve import ServeEngine

    prompt = jax.random.randint(jax.random.PRNGKey(13), (1, 19), 0, CFG.vocab_size)
    ref = generate(CFG, params, prompt, 10, temperature=0.0)

    eng = ServeEngine(
        CFG, params, max_slots=2, page_size=8, prefill_chunk=8,
        decode_chunk=4, temperature=0.0, cache_dtype=jnp.float32,
    )
    uid = eng.submit(np.asarray(prompt[0]), 10)
    out = eng.run()[uid].tokens
    np.testing.assert_array_equal(out, np.asarray(ref[0]))


def test_serve_decode_chunk_has_no_in_loop_cache_copies():
    """The r5 structural pin, extended to the PAGED serve step (ISSUE
    acceptance): inside the compiled serve chunk's decode loop, no
    pool-sized copy may appear — the per-slot column writes must lower to
    in-place scatters aliasing through the loop carry. One-time entry
    copies outside the loop are allowed (same allowance as the contiguous
    pin below)."""
    import re

    from midgpt_tpu.models.gpt import PagedKVCache
    from midgpt_tpu.sampling import serve
    from midgpt_tpu.utils.hlo import hlo_computations, while_body_names

    cfg = GPTConfig(
        block_size=256, vocab_size=96, n_layer=4, n_head=2, n_embd=64
    )
    B, ps, n_pages = 4, 8, 40
    L, H, C = cfg.n_layer, cfg.n_head, cfg.head_dim
    max_pages = cfg.block_size // ps
    abstract = jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), abstract
    )
    cache = jax.eval_shape(
        lambda: PagedKVCache.init(cfg, num_pages=n_pages, page_size=ps)
    )
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pt = jax.ShapeDtypeStruct((B, max_pages), jnp.int32)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_)
    fn = jax.jit(
        lambda p, t, c, table, lens, a: serve._serve_decode_chunk(
            cfg, p, t, c, table, lens, a, 8, 0.0, None, None, "gather", None
        )
    )
    txt = fn.lower(abstract, tok, cache, pt, ln, act).compile().as_text()
    bodies = while_body_names(txt)
    shape = re.escape(f"bf16[{L},{H},{n_pages},{ps},{C}]")
    offenders = [
        (name, l)
        for name, lines in hlo_computations(txt).items()
        if name in bodies
        for l in lines
        if re.search(rf"= {shape}[^=]*copy\(", l)
    ]
    assert not offenders, (
        "pool-sized copies inside the serve decode loop body — the paged KV "
        f"cache no longer aliases through the carry: {offenders[:2]}"
    )


def test_decode_chunk_has_no_in_loop_cache_copies():
    """Structural pin of the r5 decode restructure: inside the chunked
    decode loop, NO full-cache-sized copy may appear — the per-token column
    writes must alias through the loop carry. The r1-r4 structure (cache as
    inner-scan xs + stacked ys) copied both (L, B, H, S, C) buffers every
    token (2.5 ms/token measured on v5e at 124M/B=8); a rolled inner layer
    scan still paid 2 copies/step at the carry boundary. One-time entry
    copies outside the loop are allowed."""
    import re

    from midgpt_tpu.sampling import engine
    from midgpt_tpu.utils.hlo import hlo_computations, while_body_names

    cfg = GPTConfig(
        block_size=256, vocab_size=96, n_layer=4, n_head=2, n_embd=64
    )
    B, L, H, S, C = 4, cfg.n_layer, cfg.n_head, cfg.block_size, cfg.head_dim
    abstract = jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0))
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), abstract
    )
    cache = jax.eval_shape(lambda: KVCache.init(cfg, B))
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    fn = jax.jit(
        lambda p, t, c, k: engine._decode_chunk(cfg, p, t, c, 1.0, 50, None, 8, k)
    )
    txt = fn.lower(abstract, tok, cache, key).compile().as_text()
    bodies = while_body_names(txt)
    shape = re.escape(f"bf16[{L},{B},{H},{S},{C}]")
    offenders = [
        (name, l)
        for name, lines in hlo_computations(txt).items()
        if name in bodies
        for l in lines
        if re.search(rf"= {shape}[^=]*copy\(", l)
    ]
    assert not offenders, (
        "full-cache copies inside the decode loop body — the KV cache no "
        f"longer aliases through the carry: {offenders[:2]}"
    )


# ----------------------------------------------------------------------
# The serving programs' QKV projection (PR 62): the per-third einsum over
# the (3, D, D) layer as it lies, against the parent's one flat matmul.
# ----------------------------------------------------------------------


def _lowered_decode_step(cfg):
    from midgpt_tpu.models.gpt import PagedKVCache

    p = jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: PagedKVCache.init(cfg, 5, 8, jnp.float32))
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
    step = jax.jit(GPT.decode_step_paged, static_argnums=(0,), static_argnames=("attn_impl",))
    return step.lower(cfg, p, arr((2,)), cache, arr((2, 2)), arr((2,)), arr((2,), jnp.bool_), attn_impl="gather").as_text()


def test_the_serving_layer_loop_chooses_the_projection_and_no_caller_does(params):
    """`GPT._decode_layer_loop` alone decides how the serving programs
    contract `wqkv` (PR 62): unrolled, it indexes the layer out of the stacked
    parameters and takes the per-third einsum whatever `config.qkv_proj`
    says, so both settings lower to one text (tests/test_chip_compile.py
    holds `weight_copies` to 0 on the chip's compile of it); scanned, it is
    handed one layer and keeps the config's own. The engine rewrites nothing:
    its config, and so its jit keys, are the caller's."""
    from midgpt_tpu.sampling.serve import ServeEngine

    split3 = dataclasses.replace(CFG, qkv_proj="split3")
    assert CFG.qkv_proj == "fused"
    assert _lowered_decode_step(CFG) == _lowered_decode_step(split3)
    scan = lambda c: dataclasses.replace(c, decode_layer_scan=True)
    assert _lowered_decode_step(scan(CFG)) != _lowered_decode_step(scan(split3))
    eng = ServeEngine(CFG, params, max_slots=2, page_size=8, prefill_chunk=8,
                      cache_dtype=jnp.float32)
    assert eng.config == CFG


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("heads", ["mha", "gqa2"])
def test_per_third_projection_matches_the_flat_matmul_in_the_paged_programs(heads, program):
    """Same bf16 operands and float32 accumulation in another order of
    contractions: the logits of a prefill chunk (two rows, the second
    ragged) and of the decode step after it agree within bf16 rounding
    between the unrolled layer loop (the per-third einsum over the indexed
    layer, what every preset of the benchmark serves) and the scanned loop
    at `qkv_proj="fused"` (one flat matmul, the parent's spelling in both
    loops), for the MHA parameter set (`wqkv` (3, D, D)) and the GQA one
    (`wqkv` (1, D, D) beside `wkv` (2, KVD, D))."""
    from midgpt_tpu.models.gpt import PagedKVCache

    cfg = dataclasses.replace(CFG, n_head=4, n_kv_heads=2 if heads == "gqa2" else None)
    assert cfg.qkv_proj == "fused"
    p = GPT.cast_params(GPT.init(cfg, jax.random.PRNGKey(5)), jnp.bfloat16)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, cfg.vocab_size)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    start, n_valid = jnp.zeros((2,), jnp.int32), jnp.asarray([8, 5], jnp.int32)
    logits = {}
    for spelling in ("flat", "per_third"):
        c = dataclasses.replace(cfg, decode_layer_scan=spelling == "flat")
        cache = PagedKVCache.init(c, 5, 8, jnp.bfloat16)
        out, cache = GPT.prefill_paged_chunk(c, p, tokens, start, n_valid, cache, table, attn_impl="gather")
        if program == "decode":
            out, cache = GPT.decode_step_paged(
                c, p, tokens[:, 0], cache, table, n_valid, jnp.ones((2,), jnp.bool_), attn_impl="gather"
            )
        logits[spelling] = np.asarray(out, np.float32)
    assert logits["flat"].shape == (2, cfg.vocab_size)
    np.testing.assert_allclose(logits["per_third"], logits["flat"], atol=3e-2, rtol=3e-2)  # tests/test_decode_attention.py's bf16 tolerance
