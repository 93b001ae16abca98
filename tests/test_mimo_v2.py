"""models/mimo_v2.py (window + global attention over two kinds of paged cache,
sink bias, K 192 / V 128, partial rotary, sigmoid-routed experts) against the
plain float32 reference that lies beside its benchmark configuration, and the
serving engine over it. CPU, toy widths, float32 under "highest" (conftest)."""

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.mimo_v2 import GLOBAL, WINDOW, MimoV2, MimoV2Config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel):
    spec = importlib.util.spec_from_file_location("bench_" + os.path.basename(rel)[:-3], os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


reference = _load("benchmarks/configs/mimo_v2_5_ep16_reference.py")


def toy(**kw):
    base = dict(
        block_size=128, vocab_size=97, n_layer=7, n_head=4, n_embd=64,
        layer_pattern=(0, 1, 1, 1, 1, 0, 1), moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
        head_dim=24, v_head_dim=16, n_kv_heads=1, swa_head_dim=24, swa_v_head_dim=16, swa_n_kv_heads=2,
        sliding_window=8, dense_width=96, n_experts=16, n_experts_held=8, expert_offset=4, moe_top_k=4,
        expert_width=40,
    )
    return MimoV2Config(**{**base, **kw})


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, MimoV2.init(c, jax.random.PRNGKey(0))


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_full_forward_matches_the_reference(model):
    c, params = model
    seq = _tokens(45)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    got = np.asarray(MimoV2.apply(c, params, jnp.asarray(seq[None])))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_references_8_bit_rounding_moves_the_logits(model):
    """`round_to` rounds every matrix (behind a barrier, so that the compiler
    cannot drop the narrowing): the reading the cell's limits must refuse."""
    c, params = model
    seq, cfg = jnp.asarray(_tokens(30)), dataclasses.asdict(c)
    want = np.asarray(reference.logits(params, seq, cfg, last=9))
    got = np.asarray(reference.logits(params, seq, cfg, last=9, round_to=jnp.float8_e4m3fn))
    assert want.shape == (9, c.vocab_size)
    assert np.sqrt(np.mean((got - want) ** 2)) / np.std(want) > 5e-2


@pytest.mark.parametrize("ps,chunk,prompt", [(4, 10, 37), (8, 6, 29), (4, 16, 41), (2, 7, 33)])
def test_paged_prefill_and_decode_match_the_reference(model, ps, chunk, prompt):
    """(a) a prompt longer than window + chunk, prefilled in chunks that do not
    divide it over pages that do not divide the chunk, then 8 decode steps: the
    logits of the 9 last positions are the reference's full forward's."""
    c, params = model
    T = prompt + 8
    assert prompt > c.sliding_window + chunk and prompt % chunk
    seq = _tokens(T, seed=prompt)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    n_pages = -(-T // ps)
    cache = MimoV2.init_cache(c, (n_pages + 1, n_pages + 1), ps, jnp.float32)
    tab = jnp.asarray(np.arange(1, n_pages + 1, dtype=np.int32)[None])
    pre = jax.jit(lambda p, t, s, n, ca: MimoV2.prefill_paged_chunk(c, p, t, s, n, ca, (tab, tab)))
    dec = jax.jit(lambda p, t, ca, ln: MimoV2.decode_step_paged(c, p, t, ca, (tab, tab), ln, jnp.asarray([True])))
    pos, got = 0, []
    while pos < prompt:
        n = min(chunk, prompt - pos)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :n] = seq[pos:pos + n]
        lg, cache = pre(params, jnp.asarray(buf), jnp.asarray(pos, jnp.int32), jnp.asarray(n, jnp.int32), cache)
        pos += n
    assert lg.shape == (1, 1, c.vocab_size)  # the last valid row's logits alone
    got.append(np.asarray(lg)[0, 0])
    for i in range(prompt, T):
        lg, cache = dec(params, jnp.asarray(seq[i:i + 1]), cache, jnp.asarray([i], jnp.int32))
        got.append(np.asarray(lg)[0])
    np.testing.assert_allclose(np.stack(got), want[prompt - 1:], atol=2e-5)
    counters = MimoV2.serve_counters(c, cache)
    assert counters["moe.decode_steps"] == 8 and counters["moe.dropped"] == 0


def test_window_pages_behind_the_window_are_never_read(model):
    """The window table's entries behind the window may be anything (the
    engine frees those pages): poisoning the pages they name changes nothing."""
    c, params = model
    ps, prompt = 4, 40
    seq = _tokens(prompt + 1, seed=5)
    n_pages = -(-(prompt + 1) // ps)
    cache = MimoV2.init_cache(c, (n_pages + 1, n_pages + 1), ps, jnp.float32)
    tab = np.arange(1, n_pages + 1, dtype=np.int32)[None]
    lg, cache = MimoV2.prefill_paged_chunk(c, params, jnp.asarray(seq[None, :prompt]), jnp.asarray(0, jnp.int32),
                                           jnp.asarray(prompt, jnp.int32), cache, (jnp.asarray(tab), jnp.asarray(tab)))
    step = lambda ca, wt: MimoV2.decode_step_paged(c, params, jnp.asarray(seq[prompt:]), ca, (jnp.asarray(tab), jnp.asarray(wt)),
                                                   jnp.asarray([prompt], jnp.int32), jnp.asarray([True]))[0]
    clean = np.asarray(step(cache, tab))
    dead = (prompt + 1 - c.sliding_window) // ps  # pages wholly behind the decode step's window
    assert dead >= 5
    poisoned = dataclasses.replace(cache, pools=(cache.pools[0], tuple(a.at[:, :, 1:dead + 1].set(jnp.nan) for a in cache.pools[1])))
    parked = tab.copy()
    parked[0, :dead] = 0  # as the engine parks reclaimed entries: on the sink page
    np.testing.assert_array_equal(np.asarray(step(poisoned, parked)), clean)


@pytest.mark.parametrize("held", [4, 8, 16])
def test_the_expert_shares_add_up_to_the_uncut_layer(held):
    """(b) over all offsets the held experts' partial results sum to the
    uncut routed layer of the reference (there is no shared expert, so nothing
    is counted once), by the program's serving path and by the reference's."""
    c = toy(n_experts_held=16, expert_offset=0)
    p = MimoV2.init(c, jax.random.PRNGKey(3)).layers[2].mlp
    h = jax.random.normal(jax.random.PRNGKey(4), (23, c.n_embd))
    whole = np.asarray(reference.moe_layer(p, h, dataclasses.asdict(c)))
    total_prog, total_ref = 0.0, 0.0
    for off in range(0, 16, held):
        share = dataclasses.replace(p, w_gate=p.w_gate[off:off + held], w_up=p.w_up[off:off + held], w_down=p.w_down[off:off + held])
        cs = dataclasses.replace(c, n_experts_held=held, expert_offset=off)
        y, _, stats = MimoV2._moe(cs, share, h)
        assert int(stats["dropped"]) == 0
        total_prog = total_prog + np.asarray(y)
        total_ref = total_ref + np.asarray(reference.moe_layer(share, h, dataclasses.asdict(cs)))
    np.testing.assert_allclose(total_prog, whole, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, atol=2e-5)


def _explicit_attention(q, k, v, window, sink, scale):
    """q (T, H, dq), k (S, Hkv, dq), v (S, Hkv, dv), query t at position S - T + t: a loop with an explicit mask."""
    T, H, _ = q.shape
    S, n_kv, dv = v.shape
    out = np.zeros((T, H, dv), np.float64)
    for t in range(T):
        i = S - T + t
        for h in range(H):
            kv = h // (H // n_kv)
            js = [j for j in range(S) if j <= i and (not window or j > i - window)]
            a = np.array([float(q[t, h] @ k[j, kv]) * scale for j in js])
            terms = np.exp(a - a.max())
            denom = terms.sum() + (np.exp(sink[h] - a.max()) if sink is not None else 0.0)
            out[t, h] = sum(w * v[j, kv].astype(np.float64) for w, j in zip(terms / denom, js))
    return out


@pytest.mark.parametrize("kind", [GLOBAL, WINDOW])
def test_paged_gather_attention_against_an_explicit_mask(kind):
    """(c) the sink term in the denominator and the window's edge (i - W < j <=
    i), on the XLA gather path, rows at several positions."""
    c = toy()
    n_kv, dq, dv, _, window = c.attn_geometry(kind)
    ps, S, R = 4, 27, 3
    rng = np.random.default_rng(2)
    k, v = rng.normal(size=(S, n_kv, dq)).astype(np.float32), rng.normal(size=(S, n_kv, dv)).astype(np.float32)
    q = rng.normal(size=(R, c.n_head, dq)).astype(np.float32)
    sink = rng.normal(size=(c.n_head,)).astype(np.float32) if kind == WINDOW else None
    n_pages = -(-S // ps)
    pool = lambda a: jnp.zeros((1, n_kv, n_pages + 1, ps, a.shape[-1])).at[0, :, 1:].set(
        jnp.asarray(np.pad(a, ((0, n_pages * ps - S), (0, 0), (0, 0)))).reshape(n_pages, ps, n_kv, -1).transpose(2, 0, 1, 3))
    params = dataclasses.replace(MimoV2.init(c, jax.random.PRNGKey(0)).layers[1].attn, sink=None if sink is None else jnp.asarray(sink))
    ids = jnp.asarray(np.arange(1, n_pages + 1, dtype=np.int32)[None])
    counts = jnp.asarray(np.arange(S - R + 1, S + 1, dtype=np.int32)[None])
    got = MimoV2._paged_attention(c, kind, params, jnp.asarray(q[None]), pool(k), pool(v), 0, ids, jnp.zeros((1,), jnp.int32), counts)
    want = _explicit_attention(q, k, v, window, sink, 1.0 / np.sqrt(dq))
    np.testing.assert_allclose(np.asarray(got)[0].reshape(R, c.n_head, dv), want, atol=2e-5)


@pytest.mark.parametrize("split_k", [1, 2])
def test_paged_kernel_with_k_and_v_of_different_widths(split_k):
    """(c) the Pallas template (interpret mode) at the published head widths:
    K pages of 256 lanes (192 + padding) beside V pages of 128, 16 query rows a
    K/V head, against the explicit mask; lengths that end inside a page."""
    from midgpt_tpu.kernels.attention_template import paged_attention_template
    from midgpt_tpu.models.gpt import pool_lanes

    B, H, n_kv, dq, dv, ps, pages = 2, 16, 1, 192, 128, 8, 4
    rng = np.random.default_rng(1)
    lengths = np.array([29, 9], np.int32)
    q = rng.normal(size=(B, H, dq)).astype(np.float32)
    k, v = rng.normal(size=(B, 32, n_kv, dq)).astype(np.float32), rng.normal(size=(B, 32, n_kv, dv)).astype(np.float32)
    kp = np.zeros((1, n_kv, B * pages + 1, ps, pool_lanes(dq)), np.float32)
    vp = np.zeros((1, n_kv, B * pages + 1, ps, pool_lanes(dv)), np.float32)
    table = 1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages)
    for b in range(B):
        kp[0, :, table[b], :, :dq] = k[b].reshape(pages, ps, n_kv, dq).transpose(0, 2, 1, 3)
        vp[0, :, table[b], :, :dv] = v[b].reshape(pages, ps, n_kv, dv).transpose(0, 2, 1, 3)
    out = paged_attention_template(jnp.asarray(q[:, :, None]), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
                                   jnp.asarray(lengths[:, None]), split_k=split_k, layer=jnp.asarray(0), v_dim=dv)
    assert out.shape == (B, H, 1, dv)
    for b in range(B):
        n = int(lengths[b])
        want = _explicit_attention(q[b][None], k[b, :n], v[b, :n], 0, None, 1.0 / np.sqrt(dq))
        np.testing.assert_allclose(np.asarray(out)[b, :, 0], want[0], atol=2e-5)


def test_paged_write_kernel_with_k_and_v_of_different_lanes():
    """kernels/paged_write.py (interpret mode) stores K rows of 256 lanes and V
    rows of 128 where the XLA scatter stores them, bit for bit."""
    from midgpt_tpu.models.gpt import _paged_write

    L, H, P, ps = 2, 2, 5, 8
    rng = np.random.default_rng(0)
    ck, cv = jnp.asarray(rng.normal(size=(L, H, P, ps, 256)), jnp.float32), jnp.asarray(rng.normal(size=(L, H, P, ps, 128)), jnp.float32)
    k, v = jnp.asarray(rng.normal(size=(6, H, 192)), jnp.float32), jnp.asarray(rng.normal(size=(6, H, 128)), jnp.float32)
    pages, offs = jnp.asarray([1, 1, 1, 3, P, 4], jnp.int32), jnp.asarray([5, 6, 7, 0, 2, 3], jnp.int32)
    got = _paged_write((ck, cv, None, None), jnp.asarray(1), pages, offs, k, v, "kernel")
    pad = lambda a, n: jnp.pad(a, ((0, 0), (0, 0), (0, n - a.shape[-1])))
    want = _paged_write((ck, cv, None, None), jnp.asarray(1), pages, offs, pad(k, 256), v, "gather")
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_partial_rotary_rotates_the_leading_channels_only():
    from midgpt_tpu.ops.rope import apply_rope_leading, rope_table

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 24))
    sin, cos = rope_table(8, 64, 1e4)
    pos = jnp.asarray([[3, 4, 5, 6, 7], [0, 9, 1, 8, 2]])
    y = np.asarray(apply_rope_leading(x, sin, cos, pos))
    np.testing.assert_array_equal(y[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_allclose(np.asarray(apply_rope_leading(x, sin, cos, pos[0]))[0], y[0], atol=1e-6)
    want = np.asarray(reference._rotate(jnp.pad(x[1, 1:2], ((9, 0), (0, 0), (0, 0))), 1e4, 8))[9]  # a row at position 9
    np.testing.assert_allclose(y[1, 1], want, atol=1e-5)


@pytest.mark.parametrize("rows,block", [(40, 8), (40, 16), (3, 8), (64, 64)])
def test_serving_dispatch_is_the_training_dispatchs_result(rows, block):
    """`moe_experts_serving` (rows sorted by expert, one grouped matmul over
    their blocks, each token's rows summed back) gives what `moe_experts`
    gives, counts what it counts and drops nothing, whatever the row block;
    with no pair routed here no block is in use and the result is zero."""
    from midgpt_tpu.ops.moe import moe_capacity, moe_experts, moe_experts_serving, moe_row_block, route

    key = jax.random.PRNGKey(1)
    x, wr = jax.random.normal(key, (rows, 32)), jax.random.normal(jax.random.fold_in(key, 1), (16, 32))
    wg, wu, wd = (jax.random.normal(jax.random.fold_in(key, i), s) / 6 for i, s in ((2, (4, 24, 32)), (3, (4, 24, 32)), (4, (4, 32, 24))))
    idx, w = route(x, wr, jnp.zeros((16,)), top_k=4, scale=1.0)
    n_tiles, t = moe_capacity(rows, 4, 16, 4, 2.0)
    a, sa = moe_experts(x, idx, w, wg, wu, wd, offset=8, n_tiles=n_tiles, tile=t)
    b, sb = jax.jit(lambda *args: moe_experts_serving(*args, offset=8, block_rows=block))(x, idx, w, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(sa["counts"]), np.asarray(sb["counts"]))
    assert int(sb["dropped"]) == 0 and int(sb["counts"].sum()) > 0
    assert int(sb["visits"]) == int(np.sum(-(-np.asarray(sb["counts"]) // block)))
    none, sn = moe_experts_serving(x, idx, w, wg, wu, wd, offset=400, block_rows=block)  # an offset no pair reaches
    assert not np.asarray(none).any() and int(sn["counts"].sum()) == 0 and int(sn["dropped"]) == 0 and int(sn["visits"]) == 0
    # the row block is derived from the call's shapes: four times the mean pairs an expert, from a register's rows to 256
    assert [moe_row_block(n, 8, 256, 4) for n in (32, 512, 4096, 65536)] == [8, 64, 256, 256]
    assert [moe_row_block(n, 8, 256, 2) for n in (32, 512, 4096, 65536)] == [16, 64, 256, 256]


def test_the_reference_blocks_its_queries_without_changing_its_result(model, monkeypatch):
    """The reference computes QUERY_BLOCK rows of scores at a time and can hand
    out chosen rows: both are the plain whole-sequence result."""
    c, params = model
    seq, cfg = jnp.asarray(_tokens(45)), dataclasses.asdict(c)
    want = np.asarray(reference.logits(params, seq, cfg))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)  # 45 rows: three blocks, the last ragged
    np.testing.assert_allclose(np.asarray(reference.logits(params, seq, cfg)), want, atol=1e-5)
    rows = np.asarray([0, 17, 44, 31])
    np.testing.assert_allclose(np.asarray(reference.logits(params, seq, cfg, rows=rows)), want[rows], atol=1e-5)


# ---------------------------------------------------------------------------
# (g) the GPT's serving programs did not move
# ---------------------------------------------------------------------------

# sha256 of `.lower(...).as_text()` (StableHLO, no locations) on the CPU backend with the XLA
# gather lowering, taken on the parent of PR 30 (commit 0410ecb) by this same function; the two
# `prefill16` entries: taken at PR 35, whose program samples each row's first token at its end (temperature 0.8, as the
# serving cells run it) and hands back (tokens (B,), rows (B, V)); PR 31 had made it a batch of B rows. The four SAMPLED
# entries (`decode8`, `prefill16`, both presets): taken again at PR 37, whose sampled programs take the engine's key, split
# their own off it first and hand back the engine's next (a key in, a key out; the tokens are the parent's bit for bit:
# tests/test_sampled_streams.py). The greedy `decode1_greedy` and `verify5` (its key is made on the device by the draft
# program now, the program itself untouched) are the parent's of PR 30, byte for byte. The two `prefill16` entries: taken
# again at PR 54, whose prefill attends through `paged_verify_attention` (here its gather lowering: the layer sliced, then one
# gather of the pages, where the parent gathered with the layer in the indices; the same arithmetic in the same order, and the
# tokens are the parent's bit for bit: tests/test_prefill_width1_round.py, tests/test_sampled_streams.py). The four
# `openwebtext_xl` entries (`rope_style` "interleaved"): taken again at PR 57, whose `apply_rope_positions` (the serving
# programs' rotary) rolls lanes and selects by the channel's parity over tables widened before their rows are taken (ops/rope.py),
# where the parent sliced stride-2 channels and stacked them: a permutation and a sign, the same values to the bit
# (tests/test_rope.py against the stride-2 spelling, which the training entry points keep; the two token goldens above pass
# untouched). The four `openwebtext` entries (`rope_style` "split") did not move. ALL EIGHT: taken again at PR 62, whose
# unrolled serving layer loop contracts `wqkv` by the per-third einsum whatever `config.qkv_proj` says (models/gpt.py
# `_decode_layer_loop`): each text is what the parent (91b716c) lowers at `qkv_proj="split3"`, byte for byte (the spelling its
# engines ran under a tp > 1 mesh; same operands and accumulation, tests/test_sampling.py holds the logits to the flat matmul's).
GPT_PROGRAM_HASHES = json.load(open(os.path.join(os.path.dirname(__file__), "golden", "gpt_serving_programs_pr29.json")))


@pytest.mark.parametrize("name", sorted(GPT_PROGRAM_HASHES))
def test_gpt_serving_programs_lower_to_the_parents_text(name):
    from midgpt_tpu.config import load_config
    from midgpt_tpu.models.gpt import GPT, PagedKVCache
    from midgpt_tpu.sampling import serve

    preset, program, impl = name.split(".")
    cfg = load_config(preset).model_config
    sds = lambda a, dt=None: jax.ShapeDtypeStruct(a.shape, dt or a.dtype)
    params = jax.tree.map(lambda a: sds(a, jnp.bfloat16), jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: PagedKVCache.init(cfg, 257, 8, jnp.bfloat16)))
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
    B, T, key = 8, 16, arr((2,), jnp.uint32)
    table, lengths, active = arr((B, T)), arr((B,)), arr((B,), jnp.bool_)
    with jax.default_matmul_precision("default"):
        if program == "decode8":
            low = serve._serve_decode_chunk.lower(cfg, params, arr((B,)), cache, table, lengths, active, 8, 0.8, None, None, impl, key)
        elif program == "decode1_greedy":
            low = serve._serve_decode_chunk.lower(cfg, params, arr((B,)), cache, table, lengths, active, 1, 0.0, None, None, impl, None)
        elif program == "prefill16":
            low = serve._serve_prefill_chunk.lower(cfg, params, arr((B, 16)), arr((B,)), arr((B,)), cache, arr((B, T)), None, impl,
                                                   0.8, None, None, key)
        else:
            low = serve._spec_verify_chunk.lower(cfg, params, arr((B,)), arr((4, B)), arr((4, B, cfg.vocab_size), jnp.float32),
                                                 cache, table, lengths, active, 0.8, None, None, impl, key)
    assert hashlib.sha256(low.as_text().encode()).hexdigest() == GPT_PROGRAM_HASHES[name]


# sha256 taken on the parent of PR 39 (commit c9c92be), which gave `kernels/attention_template.py` a V that is a view
# of K's lanes and `kernels/paged_write.py` a pool of one array, and moved MimoV2's serving MoE call and expert
# counters into `ops/moe.py`: MimoV2's serving programs (published widths, toy depth, the gather lowering, StableHLO
# text) and the traced kernels (the jaxpr of the wrapper and of the pallas_call's body, interpret mode) at the GPT's
# and MimoV2's shapes are the parent's, byte for byte. The two `mimo_v2_5.*` entries were taken again in PR 61, whose
# one cache class renames the programs' results (`jax.result_info = "result[0].gk"` -> `"result[0].pools[0][0]"`): with
# those attributes taken out both texts are the parent's (e22f869), byte for byte; the eight others did not move.
SERVING_HASHES_PR37 = json.load(open(os.path.join(os.path.dirname(__file__), "golden", "serving_programs_pr37.json")))


def _program_text(name):
    from midgpt_tpu.config import load_config
    from midgpt_tpu.kernels.attention_template import paged_attention_template
    from midgpt_tpu.kernels.paged_write import paged_write_kernel
    from midgpt_tpu.sampling import serve

    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    pool = lambda hkv, lanes, dt=bf16: arr((2, hkv, 65, 8, lanes), dt)
    scales = lambda h: arr((2, 65, h, 8), f32)
    kind, what = name.split(".", 1)
    if kind == "mimo_v2_5":
        mc = dataclasses.replace(load_config("mimo_v2_5").model_config, n_layer=3, n_experts_held=2, vocab_size=512)
        m = mc.model()
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        params = jax.tree.map(sds, jax.eval_shape(lambda k: m.cast_params(m.init(mc, k), bf16), jax.random.PRNGKey(0)))
        cache = jax.tree.map(sds, jax.eval_shape(lambda: m.init_cache(mc, (257, 97), 32, bf16)))
        B, T, key = 8, 16, arr((2,), jnp.uint32)
        if what == "decode8.gather":
            return serve._serve_decode_chunk.lower(mc, params, arr((B,)), cache, (arr((B, T)), arr((B, T))), arr((B,)),
                                                   arr((B,), jnp.bool_), 8, 0.8, None, None, "gather", key, None, 2).as_text()
        return serve._serve_prefill_chunk.lower(mc, params, arr((1, 64)), arr(()), arr(()), cache, (arr((1, T)), arr((1, T))),
                                                None, "gather", 0.8, None, None, key).as_text()
    if kind == "template":
        q, kp, vp, rows, ks, kw = {
            "gpt_h16c128_decode_split2": (arr((4, 16, 1, 128), bf16), pool(16, 128), pool(16, 128), 1, None, dict(split_k=2)),
            "gpt_h12c64_verify5": (arr((4, 12, 5, 64), bf16), pool(12, 128), pool(12, 128), 5, None, {}),
            "gpt_h12c64_int8": (arr((4, 12, 1, 64), bf16), pool(12, 128, i8), pool(12, 128, i8), 1, scales(12), {}),
            "gpt_gqa4_window_sinks": (arr((4, 16, 1, 128), bf16), pool(4, 128), pool(4, 128), 1, None,
                                      dict(sliding_window=32, attn_sinks=4)),
            "mimo_k192_v128_split2": (arr((4, 64, 1, 192), bf16), pool(4, 256), pool(4, 128), 1, None, dict(split_k=2, v_dim=128)),
        }[what]
        f = lambda *a: paged_attention_template(*a, layer=jnp.int32(1), **kw)
        return str(jax.make_jaxpr(f)(q, kp, vp, arr((4, 16)), arr((4, rows)), ks, ks))
    kp, vp, ks = {"gpt_h16c128": (pool(16, 128), pool(16, 128), None),
                  "gpt_h12c64_int8": (pool(12, 128, i8), pool(12, 128, i8), scales(12)),
                  "mimo_k256_v128": (pool(4, 256), pool(4, 128), None)}[what]
    H, n = kp.shape[1], 6
    a = [kp, vp, arr(()), arr((n,)), arr((n,)), arr((n, H, kp.shape[-1]), kp.dtype), arr((n, H, vp.shape[-1]), vp.dtype)]
    if ks is not None:
        a += [ks, ks, arr((n, H), f32), arr((n, H), f32)]
    return str(jax.make_jaxpr(paged_write_kernel)(*a))


@pytest.mark.parametrize("name", sorted(SERVING_HASHES_PR37))
def test_mimo_programs_and_the_shared_kernels_trace_to_the_parents_text(name):
    with jax.default_matmul_precision("default"):
        text = _program_text(name)
    assert hashlib.sha256(text.encode()).hexdigest() == SERVING_HASHES_PR37[name]
