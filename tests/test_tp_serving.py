"""Mesh-sharded serving: tp greedy parity, recompile pins, and
disaggregated prefill/decode parity (docs/SERVING.md "Mesh-sharded
serving").

The load-bearing claim is BIT-parity: a tp-sharded engine partitions
head-aligned einsums whose megatron all-reduce restores the same f32
activations a single chip computes, and the paged KV pool shards on the
head axis without crossing shards — so the token streams must be
IDENTICAL to the single-chip engine's, across cache dtype, prefix cache,
and self-draft speculation. Any divergence means a wrong PartitionSpec or
a torn collective, not numerical noise.

Pool geometry: num_pages=29/31 here, NOT 25 — pool size is a jit
program-key dim and tests/test_recompile_pins.py counts compiles of the
25-page geometry from a pristine baseline (alphabetical ordering runs it
first, but keeping the geometries disjoint makes the pins order-proof).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.analysis.hlo_audit import CompileCounter, jit_cache_size
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.parallel.serve_tp import make_serve_mesh
from midgpt_tpu.sampling.disagg import DisaggServe
from midgpt_tpu.sampling.serve import (
    ServeEngine,
    _serve_decode_chunk,
    _spec_draft_chunk,
    _spec_verify_chunk,
)
from midgpt_tpu.sampling.spec import self_draft

CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


@pytest.fixture(scope="module")
def params():
    return GPT.init(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh():
    return make_serve_mesh(tp_size=2)


def _trace(seed, n=4):
    rng = np.random.default_rng(seed)
    lens = rng.integers(5, 30, size=n)
    return (
        [rng.integers(1, CFG.vocab_size, size=int(l)).tolist() for l in lens],
        [int(b) for b in rng.integers(5, 18, size=n)],
    )


def _run(params, *, mesh=None, dtype=jnp.float32, prefix=False, spec=False,
         seed=0, num_pages=29, **kw):
    skw = {}
    if spec:
        dcfg, dparams = self_draft(CFG, params, 1)
        skw = dict(draft_params=dparams, draft_config=dcfg,
                   draft_shares_cache=True, spec_k_max=4)
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=num_pages,
        prefill_chunk=8, decode_chunk=8, temperature=0.0, cache_dtype=dtype,
        prefix_cache=prefix, mesh=mesh, **skw, **kw,
    )
    prompts, budgets = _trace(seed)
    uids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    done = eng.run()
    return eng, [done[u].tokens.tolist() for u in uids]


@pytest.mark.parametrize(
    # int8 rows carry the heaviest tp compiles (33 s measured r22); the
    # prefix-f32 row keeps tp-parity coverage inside the tier-1 870 s
    # gate and test_disagg_parity[int8] keeps a cheap int8 tp pin non-slow.
    "dtype",
    [jnp.float32, pytest.param("int8", marks=pytest.mark.slow)],
    ids=["f32", "int8"],
)
@pytest.mark.parametrize(
    # noprefix rows pay the full-prefill compiles
    "prefix",
    [pytest.param(False, marks=pytest.mark.slow), True],
    ids=["noprefix", "prefix"],
)
def test_tp_greedy_parity(params, mesh, dtype, prefix):
    """tp=2 token streams bit-identical to single-chip, per cache dtype and
    prefix-cache mode (prefix sharing is host-side page-table indirection —
    orthogonal to sharding, and the composition must stay exact)."""
    _, ref = _run(params, dtype=dtype, prefix=prefix)
    _, out = _run(params, mesh=mesh, dtype=dtype, prefix=prefix)
    assert out == ref


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float32, "int8"], ids=["f32", "int8"])
def test_tp_spec_parity(params, mesh, dtype):
    """Self-draft speculation under tp: draft, verify, and rollback all run
    on sharded pools, and greedy spec output is defined to equal plain
    greedy decoding — so the tp spec stream must match the single-chip
    PLAIN stream too, not just the single-chip spec stream."""
    _, plain = _run(params, dtype=dtype)
    _, ref = _run(params, dtype=dtype, spec=True)
    eng, out = _run(params, mesh=mesh, dtype=dtype, spec=True)
    assert out == ref
    assert out == plain
    assert eng.spec_stats()["accept_rate"] >= 0.0  # counters alive under tp


def _pin_mix(params, mesh, lengths, max_new, seed, *, dtype=jnp.float32,
             spec=False, **kw):
    """Bucket-pinned mix (design from tests/test_recompile_pins.py): budgets
    ≡ 1 (mod decode_chunk=8) so every decode round runs a full chunk — one
    decode program per (dtype, mesh); prompts 25..47 pin the pow2 page
    bucket; prompt + max_new <= block_size=64; 31-page pool never evicts."""
    skw = {}
    if spec:
        dcfg, dparams = self_draft(CFG, params, 1)
        skw = dict(draft_params=dparams, draft_config=dcfg,
                   draft_shares_cache=True, spec_k_max=4, spec_k_min=4,
                   spec_adapt=False)
    eng = ServeEngine(
        CFG, params, max_slots=3, page_size=8, num_pages=31,
        prefill_chunk=16, decode_chunk=8, temperature=0.0, cache_dtype=dtype,
        mesh=mesh, **skw, **kw,
    )
    rng = np.random.default_rng(seed)
    uids = {
        eng.submit(rng.integers(0, CFG.vocab_size, n).astype(np.int32), m)
        for n, m in zip(lengths, max_new)
    }
    assert set(eng.run()) == uids


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_tp_mix_change_compiles_nothing(params, mesh):
    """Recompile pin (mirrors tests/test_recompile_pins.py): the tp engine
    compiles one decode program per cache dtype and one draft+verify
    program per k-bucket, then serves further distinct mixes — and a
    scheduler swap — with ZERO compiles. The mesh is a static jit arg, so
    tp programs are new cache entries; request mix, page tables, and the
    host-side scheduler must not be. Geometry: num_pages=31 (the tp
    31-page programs are cold here even after the parity tests above)."""
    from midgpt_tpu.sampling.scheduler import SLOScheduler

    d0 = jit_cache_size(_serve_decode_chunk)
    sd0 = jit_cache_size(_spec_draft_chunk)
    sv0 = jit_cache_size(_spec_verify_chunk)
    _pin_mix(params, mesh, (25, 34, 47), (9, 17, 17), seed=1)
    assert jit_cache_size(_serve_decode_chunk) - d0 == 1
    _pin_mix(params, mesh, (25, 34, 47), (9, 17, 17), seed=2, dtype="int8")
    assert jit_cache_size(_serve_decode_chunk) - d0 == 2  # dtype IS a key
    _pin_mix(params, mesh, (31, 38, 45), (13, 9, 15), seed=3, spec=True)
    assert jit_cache_size(_spec_draft_chunk) - sd0 == 1
    assert jit_cache_size(_spec_verify_chunk) - sv0 == 1
    with CompileCounter() as cc:
        _pin_mix(params, mesh, (26, 33, 40), (9, 17, 9), seed=4)
        _pin_mix(params, mesh, (29, 41, 45), (17, 9, 17), seed=5,
                 dtype="int8")
        _pin_mix(params, mesh, (33, 40, 47), (9, 11, 13), seed=6, spec=True)
        _pin_mix(params, mesh, (31, 38, 47), (17, 17, 9), seed=7,
                 scheduler=SLOScheduler(min_headroom_s=0.0))
    assert cc.count == 0, f"tp mix/scheduler change recompiled {cc.count}"


def test_tp_stats_and_per_shard_bytes(params, mesh):
    """Observability: stats() carries the mesh shape (how serve_slo lines
    distinguish sharded runs) and the head-axis pool split is exact —
    per-shard bytes * tp == pool bytes."""
    eng, _ = _run(params, mesh=mesh)
    st = eng.stats()
    assert st["mesh"] == {"data": 1, "tp": 2}
    assert st["cache_hbm_bytes_per_shard"] * 2 == st["cache_hbm_bytes"]
    eng1, _ = _run(params)
    assert eng1.mesh_shape() is None


def test_tp_rejects_indivisible_heads(params, mesh):
    with pytest.raises(ValueError, match="n_head"):
        ServeEngine(
            dataclasses.replace(CFG, n_head=3, n_embd=48),
            GPT.init(dataclasses.replace(CFG, n_head=3, n_embd=48),
                     jax.random.PRNGKey(0)),
            max_slots=2, page_size=8, num_pages=29, temperature=0.0,
            cache_dtype=jnp.float32, mesh=make_serve_mesh(tp_size=2),
        )


def test_tp_kernel_shard_map_parity(mesh):
    """The Pallas paged decode / multi-row verify kernels invoked per-shard
    through shard_map (interpret mode on CPU) match the gather reference —
    the lowering path the TPU tp engine takes (kernels/decode_attention.py)."""
    from midgpt_tpu.kernels.decode_attention import (
        paged_attention,
        paged_verify_attention,
    )

    rng = np.random.default_rng(0)
    B, H, C, ps, NP, MP = 2, 4, 128, 8, 9, 4
    q = jnp.asarray(rng.normal(size=(B, H, C)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(H, NP, ps, C)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(H, NP, ps, C)), jnp.float32)
    pt = jnp.asarray(rng.integers(1, NP, size=(B, MP)), jnp.int32)
    lengths = jnp.asarray([11, 25], jnp.int32)
    ref = paged_attention(q, kp, vp, pt, lengths, impl="gather")
    out = paged_attention(q, kp, vp, pt, lengths, impl="kernel", mesh=mesh)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    T = 3
    qv = jnp.asarray(rng.normal(size=(B, T, H, C)), jnp.float32)
    counts = lengths[:, None] + jnp.arange(1, T + 1)[None, :]
    refv = paged_verify_attention(qv, kp, vp, pt, counts, impl="gather")
    outv = paged_verify_attention(qv, kp, vp, pt, counts, impl="kernel",
                                  mesh=mesh)
    np.testing.assert_allclose(outv, refv, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, "int8"], ids=["f32", "int8"])
def test_disagg_parity(params, dtype):
    """Disaggregated prefill/decode: token streams bit-identical to a
    monolithic prefix-cache engine — the handoff moves finished page
    prefixes between pools byte-for-byte, and the decode engine re-admits
    through the ordinary trie-match path. Real handoffs must happen (the
    queue's page counter moves) and nothing may fall back to re-prefill."""
    kw = dict(max_slots=3, num_pages=29, page_size=8, prefill_chunk=8,
              decode_chunk=8, temperature=0.0, cache_dtype=dtype)
    prompts, budgets = _trace(seed=0)
    mono = ServeEngine(CFG, params, prefix_cache=True, **kw)
    mu = [mono.submit(p, b) for p, b in zip(prompts, budgets)]
    mdone = mono.run()

    dis = DisaggServe(CFG, params, **kw)
    du = [dis.submit(p, b) for p, b in zip(prompts, budgets)]
    ddone = dis.run()

    for a, b in zip(mu, du):
        assert mdone[a].tokens.tolist() == ddone[b].tokens.tolist()
    st = dis.stats()
    assert st["queue"]["pages_copied"] > 0
    assert st["fallback_reprefills"] == 0


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_disagg_on_role_mesh(params):
    """Roles on the data axis of a (data=2, tp=2) mesh over 4 devices:
    prefill row 0, decode row 1, both tp-sharded — still bit-identical to
    an unsharded monolithic engine."""
    kw = dict(max_slots=3, num_pages=29, page_size=8, prefill_chunk=8,
              decode_chunk=8, temperature=0.0, cache_dtype=jnp.float32)
    prompts, budgets = _trace(seed=0)
    mono = ServeEngine(CFG, params, prefix_cache=True, **kw)
    mu = [mono.submit(p, b) for p, b in zip(prompts, budgets)]
    mdone = mono.run()

    dis = DisaggServe(
        CFG, params, mesh=make_serve_mesh(tp_size=2, data=2), **kw
    )
    du = [dis.submit(p, b) for p, b in zip(prompts, budgets)]
    ddone = dis.run()
    for a, b in zip(mu, du):
        assert mdone[a].tokens.tolist() == ddone[b].tokens.tolist()
    assert dis.prefill.mesh_shape() == {"data": 1, "tp": 2}
    assert dis.decode.mesh_shape() == {"data": 1, "tp": 2}
