"""models/pangu_ultra.py (latent attention served from a latent paged cache
stored once, absorbed decode, sandwich norms, routed experts beside a shared
expert) against the plain float32 reference in EXPANDED form that lies beside
its benchmark configuration. CPU, toy widths, float32 under "highest"
(conftest). The engine over it: tests/test_pangu_ultra_serving.py."""

import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models.pangu_ultra import LATENT, PanguUltra, PanguUltraConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel):
    spec = importlib.util.spec_from_file_location("bench_" + os.path.basename(rel)[:-3], os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


reference = _load("benchmarks/configs/openpangu_ultra_moe_ep16_reference.py")


def toy(**kw):
    base = dict(
        block_size=128, vocab_size=97, n_layer=4, n_head=4, n_embd=64, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, dense_width=96, first_k_dense=1,
        n_experts=16, n_experts_held=8, expert_offset=4, moe_top_k=4, expert_width=40,
    )
    return PanguUltraConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def model():
    c = toy()
    params = PanguUltra.init(c, jax.random.PRNGKey(0))
    # norm weights away from 1, so that a norm left out or misplaced shows
    bump = lambda path, a: a * (1.0 + 0.3 * jnp.sin(jnp.arange(a.size, dtype=a.dtype))) if a.ndim == 1 else a
    return c, jax.tree_util.tree_map_with_path(bump, params)


def _tokens(n, seed=0, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_full_forward_matches_the_reference(model):
    c, params = model
    seq = _tokens(45)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    got = np.asarray(PanguUltra.apply(c, params, jnp.asarray(seq[None])))[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_model_without_sandwich_norms_drops_the_two_post_norms():
    c = toy(sandwich_norm=False, n_layer=2)
    params = PanguUltra.init(c, jax.random.PRNGKey(1))
    assert params.layers[0].norm_post_attn is None and params.layers[1].norm_post_mlp is None
    seq = _tokens(20)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    np.testing.assert_allclose(np.asarray(PanguUltra.apply(c, params, jnp.asarray(seq[None])))[0], want, atol=2e-5)


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
    """A prompt prefilled in chunks that do not divide it over pages that do
    not divide the chunk (the sweep over cached latents, EXPANDED a block at a
    time), then 8 ABSORBED decode steps through the latent cache: the logits
    of the 9 last positions are the reference's un-absorbed full forward's."""
    c, params = model
    T = prompt + 8
    assert prompt % chunk
    seq = _tokens(T, seed=prompt)
    want = np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c)))
    n_pages = -(-T // ps)
    cache = PanguUltra.init_cache(c, (n_pages + 1,), ps, jnp.float32)
    assert [a.shape for a in cache.pool_arrays()] == [(c.n_layer, 1, n_pages + 1, ps, c.latent_dim)]  # ONE array
    tab = jnp.asarray(np.arange(1, n_pages + 1, dtype=np.int32)[None])
    pre = jax.jit(lambda p, t, s, n, ca: PanguUltra.prefill_paged_chunk(c, p, t, s, n, ca, tab))
    dec = jax.jit(lambda p, t, ca, ln: PanguUltra.decode_step_paged(c, p, t, ca, tab, ln, jnp.asarray([True])))
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
    counters = PanguUltra.serve_counters(c, cache)
    assert counters["moe.decode_steps"] == 8 and counters["moe.dropped"] == 0
    assert counters["kv.latent_bytes_per_token"] == c.n_layer * c.latent_dim * 4  # a row a layer, stored once


def test_the_prefill_sweep_runs_over_several_key_blocks(model, monkeypatch):
    """With blocks of 8 keys a 37-token prompt's last chunk sweeps five: the result is the one-block result."""
    import midgpt_tpu.models.pangu_ultra as pu

    c, params = model
    seq = _tokens(37, seed=3)
    tab = jnp.asarray(np.arange(1, 11, dtype=np.int32)[None])

    def run():
        cache = PanguUltra.init_cache(c, (11,), 4, jnp.float32)
        pre = jax.jit(lambda p, t, ca: PanguUltra.prefill_paged_chunk(c, p, t, jnp.asarray(0, jnp.int32), jnp.asarray(37, jnp.int32), ca, tab))
        return np.asarray(pre(params, jnp.asarray(seq[None]), cache)[0])

    whole = run()
    monkeypatch.setattr(pu, "PREFILL_KEY_BLOCK", 8)
    np.testing.assert_allclose(run(), whole, atol=2e-5)


def _cached_rows(c, p, h, positions):
    """(latent rows (T, latent_dim), q_n, q_r (T, H, .)) of hidden states h (T, D) at `positions`."""
    rope = PanguUltra._rope(c)
    q_n, q_r = PanguUltra._q(c, p, h[None], rope, positions)
    return PanguUltra._latent(c, p, h[None], rope, positions)[0], q_n[0], q_r[0]


def test_absorbed_attention_is_the_expanded_attention(model):
    """Attention alone: the folded query against LATENT rows, values read as
    the rows' leading channels and W_uv applied after the sum, gives what K
    and V of every head give (the published form), on the same cached rows."""
    c, params = model
    p = params.layers[1].attn
    S, ps = 27, 4
    h = jax.random.normal(jax.random.PRNGKey(5), (S, c.n_embd))
    rows, q_n, q_r = _cached_rows(c, p, h, jnp.arange(S))
    k, v = PanguUltra._expand(c, p, rows)  # (S, H, 24), (S, H, 16)
    q = jnp.concatenate([q_n, q_r], axis=-1)[-1]  # the last position's query, (H, 24)
    s = jnp.einsum("hc,shc->hs", q, k) / math.sqrt(c.qk_head_dim)
    want = PanguUltra._out(c, p, jnp.einsum("hs,shc->hc", jax.nn.softmax(s, axis=-1), v), absorbed=False)
    n_pages = -(-S // ps)
    pool = jnp.zeros((1, 1, n_pages + 1, ps, c.latent_dim)).at[0, 0, 1:].set(
        jnp.pad(rows, ((0, n_pages * ps - S), (0, 0))).reshape(n_pages, ps, c.latent_dim))
    table = jnp.asarray(np.arange(1, n_pages + 1, dtype=np.int32)[None])
    q_abs = PanguUltra._absorb_q(c, p, q_n[-1], q_r[-1])[None]  # (1, H, latent_dim)
    o = PanguUltra._absorbed_attention(c, q_abs, pool, 0, table, jnp.asarray([S], jnp.int32))
    assert o.shape == (1, c.n_head, c.kv_lora_rank)
    np.testing.assert_allclose(np.asarray(PanguUltra._out(c, p, o, absorbed=True))[0], np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("split_k", [1, 2])
def test_the_template_reads_v_as_a_view_of_the_latent_row(split_k):
    """The Pallas template (interpret mode) at the PUBLISHED geometry: 128
    query rows against the pool's one head of 640 lanes (576 values + padding),
    V the leading 512 lanes of the same pages (`v_lanes`: no V operand),
    scores scaled by 1 / sqrt(192); against the XLA gather path of the same
    arithmetic; lengths that end inside a page, one slot a single key."""
    from midgpt_tpu.kernels.attention_template import paged_attention_template
    from midgpt_tpu.models.gpt import pool_lanes

    c = PanguUltraConfig(block_size=64, vocab_size=8, n_layer=1, n_head=128, n_embd=8)  # the published attention widths
    assert (c.latent_dim, pool_lanes(c.latent_dim), c.kv_lora_rank) == (576, 640, 512)
    B, ps, pages = 3, 8, 4
    rng = np.random.default_rng(1)
    lengths = np.array([29, 9, 1], np.int32)
    q = rng.normal(size=(B, c.n_head, c.latent_dim)).astype(np.float32) / 4
    rows = rng.normal(size=(B, pages * ps, c.latent_dim)).astype(np.float32)
    pool = np.zeros((2, 1, B * pages + 1, ps, 640), np.float32)
    table = 1 + np.arange(B * pages, dtype=np.int32).reshape(B, pages)
    for b in range(B):
        pool[1, 0, table[b], :, :576] = rows[b].reshape(pages, ps, 576)
    out = paged_attention_template(jnp.asarray(q[:, :, None]), jnp.asarray(pool), None, jnp.asarray(table),
                                   jnp.asarray(lengths[:, None]), split_k=split_k, layer=jnp.asarray(1),
                                   v_lanes=c.kv_lora_rank, scale=1.0 / math.sqrt(c.qk_head_dim))
    assert out.shape == (B, c.n_head, 1, c.kv_lora_rank)
    want = PanguUltra._absorbed_attention(c, jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(table), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out)[:, :, 0], np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="v_lanes"):  # a pool off the layout contract: the view cannot slice it
        paged_attention_template(jnp.asarray(q[:, :, None]), jnp.asarray(pool[..., :576]), None, jnp.asarray(table),
                                 jnp.asarray(lengths[:, None]), layer=jnp.asarray(1), v_lanes=512)


def test_paged_write_kernel_over_one_pool():
    """kernels/paged_write.py (interpret mode) with ONE pool array stores 576-value
    rows in 640 lanes where the XLA scatter stores them, bit for bit; a row
    whose page is out of range is dropped."""
    from midgpt_tpu.models.gpt import _paged_write

    L, P, ps = 2, 5, 8
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(L, 1, P, ps, 640)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(6, 1, 576)), jnp.float32)
    pages, offs = jnp.asarray([1, 1, 1, 3, P, 4], jnp.int32), jnp.asarray([5, 6, 7, 0, 2, 3], jnp.int32)
    got = _paged_write((pool, None, None, None), jnp.asarray(1), pages, offs, rows, None, "kernel")
    want = _paged_write((pool, None, None, None), jnp.asarray(1), pages, offs, jnp.pad(rows, ((0, 0), (0, 0), (0, 64))), None, "gather")
    assert got[1:] == (None, None, None) and want[1:] == (None, None, None)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert not np.array_equal(np.asarray(got[0]), np.asarray(pool))


@pytest.mark.parametrize("held", [1, 4, 16])
def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(held):
    """THE SHARE TEST: over all offsets the held experts' routed parts, plus
    the shared expert counted ONCE, sum to the uncut expert layer of the
    reference (16 shares of one expert at `held` 1), by the program's serving
    path and by the reference's."""
    c = toy(n_experts_held=16, expert_offset=0)
    p = PanguUltra.init(c, jax.random.PRNGKey(3)).layers[2].mlp
    h = jax.random.normal(jax.random.PRNGKey(4), (23, c.n_embd))
    cfg = dataclasses.asdict(c)
    whole = np.asarray(reference.moe_layer(p, h, cfg))
    shared = np.asarray(reference._swiglu(h, p.shared, reference._f32))
    total_prog, total_ref = shared.copy(), shared.copy()
    for off in range(0, 16, held):
        share = dataclasses.replace(p, w_gate=p.w_gate[off:off + held], w_up=p.w_up[off:off + held], w_down=p.w_down[off:off + held])
        cs = dataclasses.replace(c, n_experts_held=held, expert_offset=off)
        y, _, stats = PanguUltra._moe(cs, share, h)  # this share's routed part + the shared expert
        assert int(stats["dropped"]) == 0
        total_prog = total_prog + np.asarray(y) - shared
        total_ref = total_ref + np.asarray(reference.moe_layer(share, h, dataclasses.asdict(cs), include_shared=False))
    np.testing.assert_allclose(total_prog, whole, atol=2e-5)
    np.testing.assert_allclose(total_ref, whole, atol=2e-5)
    assert np.abs(shared).max() > 0.1 and np.abs(whole - shared).max() > 0.1  # both terms carry weight


def test_the_reference_blocks_its_queries_without_changing_its_result(model, monkeypatch):
    c, params = model
    seq, cfg = jnp.asarray(_tokens(45)), dataclasses.asdict(c)
    want = np.asarray(reference.logits(params, seq, cfg))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)  # 45 rows: three blocks, the last ragged
    np.testing.assert_allclose(np.asarray(reference.logits(params, seq, cfg)), want, atol=1e-5)
    rows = np.asarray([0, 17, 44, 31])
    np.testing.assert_allclose(np.asarray(reference.logits(params, seq, cfg, rows=rows)), want[rows], atol=1e-5)


def test_training_is_refused_by_name_and_the_config_round_trips():
    from midgpt_tpu.config import from_json, load_config, to_json

    exp = load_config("openpangu_ultra_moe")
    mc = exp.model_config
    assert mc.moe_layers == tuple(range(3, 61)) and mc.latent_dim == 576 and mc.qk_head_dim == 192
    assert from_json(to_json(exp)).model_config == mc
    with pytest.raises(NotImplementedError, match="cannot train"):
        mc.check_training("launch.py")
    assert mc.check_serving("sample.py") is None
    assert PanguUltra.cache_kinds(mc) == ((LATENT, 0, 0),) and PanguUltra.verify_step_paged is None


def test_the_benchmark_share_counts_what_the_issue_reckoned():
    """The cut the configuration file makes, under eval_shape: 4.919 B
    parameters (9.84 GB in bf16), a pool row of 1,280 B a token a layer at the
    published widths on the kernel path; the catalog's keys value for value."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/openpangu_ultra_moe_ep16.json")))
    from midgpt_tpu.config import load_config

    mc = dataclasses.replace(load_config(cfg["repo_config"]).model_config, **cfg["overrides"]["model_config"])
    ran = dataclasses.asdict(mc)
    assert all(ran[k] == v for k, v in cfg["model"].items())
    shapes = jax.eval_shape(lambda k: PanguUltra.init(mc, k), jax.random.PRNGKey(0))
    n = PanguUltra.count_params(shapes)
    assert n == 4_919_139_840 and abs(2 * n / 1e9 - 9.84) < 0.005
    cache = jax.eval_shape(lambda: PanguUltra.init_cache(mc, (16 * 528 + 1,), 32, jnp.bfloat16, kernel_layout=True))
    per_token_layer = sum(a.size * a.dtype.itemsize for a in cache.pool_arrays()) / (16 * 528 + 1) / 32 / mc.n_layer
    assert per_token_layer == 1280  # the latent stored once: not 128 heads x (192 + 128) x 2 = 81,920
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    for row in ([json.loads(l) for l in open(path)] if os.path.exists(path) else []):
        if row["name"] == "openPangu-Ultra-MoE-718B":
            assert cfg["source"] == row["source_url"]
            for k, v in row["config"].items():
                assert k in cfg["reduced"] or cfg[k] == v, k


def test_block_buffers_of_a_view_are_counted_once():
    """`block_vmem_bytes` with V a view of K: two buffers, not four, at the latent geometry's derived block."""
    from midgpt_tpu.kernels import attention_template as at

    shape = dict(n_heads=1, lanes=640, itemsize=2, page_size=32, table_pages=128, n_rows=128)
    n = at.block_pages(**shape)
    assert n == 16  # 512 tokens a block: one 640-lane buffer of 0.625 MiB
    assert at.block_vmem_bytes(1, 640, 2, 32, n, v_view=True) == 2 * 512 * 640 * 2
    assert at.block_vmem_bytes(1, 640, 2, 32, n) == 2 * at.block_vmem_bytes(1, 640, 2, 32, n, v_view=True)
