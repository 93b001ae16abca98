"""models/dots3.py (dots3-note: latent attention of two geometries, the full
layers' indexer selecting the exact top-k cached tokens a query, sliding layers
over a window with a wider latent, a headwise gate, the latent rescale, routed
experts beside a shared one, over two kinds of latent paged cache of which one
holds TWO arrays) against the plain float32 reference that lies beside its
benchmark configuration, and the serving engine over it. CPU, toy widths (top-k
8, window 5), float32 under "highest" (conftest): every tolerance below is
float32 rounding through five layers at logits of unit scale (readings 2e-6 to
7e-6), with half a decade of room; a `TEETH` difference is what a WRONG program
would show (readings 0.2 to 6), a thousand times the tolerance."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.models import dots3
from midgpt_tpu.models.dots3 import FULL, LATENT, SLIDING, WINDOW_LATENT, Dots3, Dots3Config
from midgpt_tpu.models.gpt import ServeCache
from midgpt_tpu.sampling.serve import ServeEngine
from test_mimo_v2 import ROOT, _load, _tokens

reference = _load("benchmarks/configs/dots3_note_ep16_reference.py")
ATOL, TEETH = 3e-5, 3e-2
CELL = "serve_dots3_note_longctx"


def toy(**kw):
    base = dict(
        block_size=128, vocab_size=97, n_layer=5, n_head=4, n_embd=64, layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        n_dense_layers=1, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        swa_n_head=2, swa_q_lora_rank=20, swa_kv_lora_rank=32, swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8,
        swa_v_head_dim=16, sliding_window=5, index_n_heads=3, index_head_dim=16, index_topk=8, dense_width=96,
        n_experts=16, n_experts_held=16, moe_top_k=4, expert_width=24,
    )
    return Dots3Config(**{**base, **kw})


def seeded(c, seed=0):
    """Seeded parameters with what `init` leaves at a constant made random: every
    gain, the index key's LayerNorm weight and bias, the router's selection bias."""
    params = jax.jit(lambda k: Dots3.init(c, k))(jax.random.PRNGKey(seed))  # one compile, not one a matrix
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 128))
    gain = lambda g: g * (1.0 + 0.2 * jax.random.normal(next(keys), g.shape))

    def layer(p):
        attn, mlp = dataclasses.replace(p.attn, q_norm=gain(p.attn.q_norm), kv_norm=gain(p.attn.kv_norm)), p.mlp
        if attn.index is not None:
            ix = attn.index
            attn = dataclasses.replace(attn, index=dataclasses.replace(
                ix, k_norm_w=gain(ix.k_norm_w), k_norm_b=0.1 * jax.random.normal(next(keys), ix.k_norm_b.shape)))
        if hasattr(mlp, "router_bias"):
            mlp = dataclasses.replace(mlp, router_bias=0.05 * jax.random.normal(next(keys), mlp.router_bias.shape))
        return dataclasses.replace(p, attn=attn, mlp=mlp, norm1=gain(p.norm1), norm2=gain(p.norm2))

    return dataclasses.replace(params, layers=tuple(layer(p) for p in params.layers), final_norm=gain(params.final_norm))


@pytest.fixture(scope="module")
def model():
    c = toy()
    return c, seeded(c)


def _ref(c, params, seq, **kw):
    return np.asarray(reference.logits(params, jnp.asarray(seq), dataclasses.asdict(c), **kw))


def _apply(c, params, seq):
    return np.asarray(Dots3.apply(c, params, jnp.asarray(seq[None])))[0]


@pytest.mark.parametrize("query_block", [256, 16], ids=["one_block_of_rows", "fifteen_blocks_of_four"])
def test_full_forward_matches_the_reference(model, query_block, monkeypatch):
    """60 tokens through all five layers: two full layers whose top-8 drops most
    of the context, three window layers whose band of 5 cuts it, the dense and
    the expert FFNs; with the reference's scores in ONE block of rows (any test's
    sequence fits one) and in the equal blocks it cuts a cell's sequences into."""
    monkeypatch.setattr(reference, "QUERY_BLOCK", query_block)
    monkeypatch.setattr(reference, "_JITS", {})  # the jitted half layers it keeps were traced under the other block
    c, params = model
    seq = _tokens(60)
    np.testing.assert_allclose(_apply(c, params, seq), _ref(c, params, seq), atol=ATOL)
    assert [c.attn_kind(i) for i in range(5)] == [LATENT, WINDOW_LATENT, WINDOW_LATENT, WINDOW_LATENT, LATENT]
    assert c.moe_layers == (1, 2, 3, 4) and c.pool_layers[4] == (LATENT, 1) and c.pool_layers[3] == (WINDOW_LATENT, 2)


# ---------------------------------------------------------------------------
# one mechanism at a time: the program is the reference's, and the reference
# with the mechanism taken out or done wrongly is far from both
# ---------------------------------------------------------------------------


def _faulty_selection(case, chunk=10):
    """The reference's I replaced so that its exact top-k picks what a WRONG program would attend."""
    true = reference.index_scores

    def index_scores(p, u, c_q, cfg, base, r0, n, f=reference._f32):
        T = u.shape[0]
        t, s = (r0 + jnp.arange(n))[:, None], jnp.arange(T)[None, :]
        if case == "takes_the_newest_k":
            return jnp.broadcast_to(s.astype(jnp.float32), (n, T))
        own = (s // chunk == t // chunk) & (s < t)  # "ignores_the_chunks_own_earlier_rows": only the cache before the chunk, and itself
        return jnp.where(own, -1e30, true(p, u, c_q, cfg, base, r0, n, f))

    return index_scores


@pytest.fixture(scope="module")
def full_layer(model):
    """A ONE-layer model of the full kind, 60 tokens: (config, params, tokens, the program's logits, the reference's)."""
    c1, params = _one_layer(model[0], FULL, seed=3)
    seq = _tokens(60, seed=3)
    return c1, params, seq, _apply(c1, params, seq), _ref(c1, params, seq)


@pytest.mark.parametrize("case", ["skips_the_selection", "takes_the_newest_k", "ignores_the_chunks_own_earlier_rows"])
def test_the_selection_is_the_indexers_exact_topk(full_layer, case, monkeypatch):
    """The full layer's forward is the reference's at rows whose context is over
    3 x top-k, and a reference that selects WRONGLY (no selection; the newest k;
    a prefill chunk that scores only the cache before it) is far from it there:
    a program with that fault fails this test and the engine's below."""
    c, params, seq, got, want = full_layer
    np.testing.assert_allclose(got[3 * c.index_topk:], want[3 * c.index_topk:], atol=ATOL)
    if case == "skips_the_selection":
        wrong = _ref(c, params, seq, select="dense")
    else:
        monkeypatch.setattr(reference, "index_scores", _faulty_selection(case))
        monkeypatch.setattr(reference, "_JITS", {})  # the jitted half layers it keeps were traced with the true one
        wrong = _ref(c, params, seq)
    assert np.abs(wrong - want)[3 * c.index_topk:].max() > TEETH
    np.testing.assert_allclose(wrong[:c.index_topk], want[:c.index_topk], atol=ATOL)  # under top-k rows nothing is dropped


@pytest.mark.parametrize("scores,k,want", [
    ([1.0, 3.0, 3.0, 3.0, 0.0, 3.0], 3, [1, 2, 3]),  # four equal at the boundary: the three LOWER positions
    ([2.0, -0.0, 0.0, 5.0, 0.0, -1.0], 3, [0, 1, 3]),  # -0.0 equals 0.0: the lower position of the zeros
    ([0.5, -np.inf, 0.25, -np.inf, -np.inf, 0.75], 4, [0, 1, 2, 5]),  # fewer finite than k: the masked fill from below
    ([4.0, 1.0, 2.0, 3.0], 4, [0, 1, 2, 3]),
    # 96 scores of five levels (signed zeros and masked ones among them), the boundary inside a run of equals
    (np.random.RandomState(51).choice([-np.inf, -0.0, 0.0, 0.5, 2.0], 96).tolist(), 40, None),
], ids=["four_tied_at_the_boundary", "signed_zeros", "fewer_visible_than_k", "k_is_the_row", "both_forms_on_many_ties"])
def test_ties_go_to_the_lower_position(scores, k, want):
    """Both selections the program makes: the prefill's counting passes
    (`kth_largest` + `selected`, in ONE block and cut in blocks of two columns)
    and the decode's `jax.lax.top_k`, on the SAME rows with equal scores: each
    gives the stable order's set, so the two forms give identical sets."""
    x = jnp.asarray([scores, scores[::-1]], jnp.float32)
    stable = lambda row: sorted(np.argsort(-(np.asarray(row) + 0.0), kind="stable")[:k].tolist())
    wants = [sorted(want) if want is not None else stable(scores), stable(scores[::-1])]
    bits = dots3.sortable_bits(x)
    thr, need = dots3.kth_largest(bits, k)
    whole, _ = dots3.selected(bits, thr, need, jnp.zeros((2,), jnp.int32))
    parts, seen = [], jnp.zeros((2,), jnp.int32)
    for b in range(0, x.shape[1], 2):
        part, seen = dots3.selected(bits[:, b:b + 2], thr, need, seen)
        parts.append(part)
    _, top = jax.lax.top_k(x + 0.0, k)  # as `_index_scores` hands them over: -0.0 made +0.0
    for r in range(2):
        assert np.flatnonzero(np.asarray(whole[r])).tolist() == wants[r]
        assert np.flatnonzero(np.asarray(jnp.concatenate(parts, axis=1)[r])).tolist() == wants[r]
        assert sorted(np.asarray(top[r]).tolist()) == wants[r] == np.flatnonzero(np.asarray(whole[r])).tolist()


def _one_layer(c, kind, seed=1, **cfg_kw):
    """(config, params) of a ONE-layer model of `kind` with a dense FFN."""
    c1 = dataclasses.replace(c, n_layer=1, layer_types=(kind,), **cfg_kw)
    return c1, seeded(c1, seed)


@pytest.mark.parametrize("what", ["ranks_and_head_count", "rotary_base"])
def test_a_sliding_layer_has_its_own_geometry(model, what):
    """A sliding layer's parameters have the `swa_*` sizes, and its forward is
    the reference's with them; the reference run with the FULL layers' rotary
    base is far from it."""
    c, _ = model
    c1, params = _one_layer(c, SLIDING)
    seq = _tokens(30, seed=4)
    got = _apply(c1, params, seq)
    np.testing.assert_allclose(got, _ref(c1, params, seq), atol=ATOL)
    a, g = params.layers[0].attn, c1.geom(WINDOW_LATENT)
    if what == "ranks_and_head_count":
        assert (g.n_head, g.q_rank, g.kv_rank) == (2, 20, 32) != (c.n_head, c.q_lora_rank, c.kv_lora_rank)
        assert a.w_qa.shape == (20, 64) and a.w_kva.shape == (32 + 8, 64) and a.w_g.shape == (2, 64) and a.index is None
        assert a.w_qb.shape == (2 * (24 + 8), 20) and a.w_kvb.shape == (2 * (24 + 16), 32) and a.wo.shape == (64, 2 * 16)
    else:
        wrong = _ref(dataclasses.replace(c1, swa_rope_theta=c.rope_theta), params, seq)
        assert np.abs(wrong - got).max() > TEETH


@pytest.mark.parametrize("kind", [FULL, SLIDING])
@pytest.mark.parametrize("fault", ["no_gate", "a_gate_a_channel"])
def test_the_gate_is_one_scalar_a_head(model, kind, fault):
    """out = W_o [g_h o_h]: the forward is the reference's; without the gate, or
    with the heads' scalars laid over the output's CHANNELS (tiled, not
    repeated), the layer is far from it."""
    c, _ = model
    c1, params = _one_layer(c, kind, seed=2)
    seq = _tokens(30, seed=5)
    got = _apply(c1, params, seq)
    np.testing.assert_allclose(got, _ref(c1, params, seq), atol=ATOL)
    p, g = params.layers[0], c1.geom(c1.attn_kind(0))
    if fault == "no_gate":
        ungated = dataclasses.replace(params, layers=(dataclasses.replace(p, attn=dataclasses.replace(p.attn, w_g=None)),))
        assert np.abs(_ref(c1, ungated, seq) - got).max() > TEETH
        np.testing.assert_allclose(_apply(dataclasses.replace(c1, headwise_gate=False), ungated, seq),
                                   _ref(c1, ungated, seq), atol=ATOL)
    else:
        with jax.default_matmul_precision("highest"):
            cfg, eps = dataclasses.asdict(c1), c1.rms_norm_eps
            x = jnp.take(params.wte, jnp.asarray(seq), axis=0)
            u = reference._rms(x, p.norm1, eps)
            bare = dataclasses.replace(p.attn, w_g=None, wo=jnp.eye(g.n_head * g.v))  # the heads' outputs themselves
            o = reference.attention_layer(bare, u, cfg, kind == SLIDING)
            gate = jax.nn.sigmoid(u @ p.attn.w_g.T)  # (T, H)
            right = x + (o * jnp.repeat(gate, g.v, axis=1)) @ p.attn.wo.T
            wrong = x + (o * jnp.tile(gate, (1, g.v))) @ p.attn.wo.T
            logits = lambda h: np.asarray(reference._rms(reference.mlp_half(p, h, cfg, 0), params.final_norm, eps) @ params.lm_head.T)
        np.testing.assert_allclose(logits(right), got, atol=ATOL)
        assert np.abs(logits(wrong) - got).max() > TEETH


@pytest.mark.parametrize("rescale", [True, False])
def test_the_latents_are_rescaled_after_their_norms(model, rescale):
    """`mla_rescale`: r = sqrt(n_embd / rank) on c_q and c of both kinds (and so
    on the cached row and on the indexer's query), a full and a sliding layer;
    with the switch the other way the model is another one."""
    cr = dataclasses.replace(model[0], n_layer=2, layer_types=(FULL, SLIDING), n_dense_layers=2, mla_rescale=rescale)
    params = seeded(cr, seed=8)
    seq = _tokens(40, seed=6)
    got = _apply(cr, params, seq)
    np.testing.assert_allclose(got, _ref(cr, params, seq), atol=ATOL)
    assert np.abs(_ref(dataclasses.replace(cr, mla_rescale=not rescale), params, seq) - got).max() > TEETH
    assert cr.rescale(16) == (2.0 if rescale else 1.0)
    from midgpt_tpu.config import load_config

    pub = load_config("dots3_note").model_config
    assert np.allclose([pub.rescale(pub.q_lora_rank), pub.rescale(pub.kv_lora_rank), pub.rescale(pub.swa_kv_lora_rank)],
                       [5 ** 0.5, 10 ** 0.5, 5 ** 0.5])


def test_sixteen_shares_of_256_experts_add_up_to_the_uncut_layer():
    """The guide's share test at the published counts: 256 experts, top-8, as 16
    shares of 16 (`n_experts_held` / `expert_offset`). Each share routes over
    all 256 and computes its own experts' part; the parts, with the shared
    expert (which every chip computes alike) counted ONCE, add up to what the
    uncut reference gives for the whole layer; and the program's share IS the
    reference's (four of the sixteen: each compiles a grouped matmul)."""
    c = toy(n_layer=2, n_embd=32, layer_types=(FULL, SLIDING), n_experts=256, n_experts_held=256, moe_top_k=8, expert_width=8)
    p = seeded(c, seed=6).layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(7), (40, c.n_embd))
    cfg = dataclasses.asdict(c)
    cut = lambda lo: dataclasses.replace(p, w_gate=p.w_gate[lo:lo + 16], w_up=p.w_up[lo:lo + 16], w_down=p.w_down[lo:lo + 16])
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.moe_layer(p, x, cfg))
        shared = np.asarray(reference._swiglu(x, p.shared, reference._f32))
        part = jax.jit(lambda share, lo: reference.moe_layer(share, x, {**cfg, "expert_offset": lo}, include_shared=False))
        parts = [np.asarray(part(cut(16 * s), 16 * s)) for s in range(16)]
        for s in (0, 5, 10, 15):
            cs = dataclasses.replace(c, n_experts_held=16, expert_offset=16 * s)
            np.testing.assert_allclose(np.asarray(Dots3._moe(cs, cut(16 * s), x)[0]) - shared, parts[s], atol=ATOL)
    np.testing.assert_allclose(shared + sum(parts), whole, atol=ATOL)
    assert sum(float(np.abs(q).max()) > 1e-3 for q in parts) >= 15  # (nearly) every share adds something


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------


def _serve(c, params, prompts, n_new, **kw):
    """Serve `prompts` ({name: tokens}) together; ({name: [(row, logits)]}: the
    prefill program's at each prompt's last position, then the logits every
    later decode round starts from; {name: the tokens as served}; the engine)."""
    got = {}
    kw = {**dict(max_slots=3, page_size=4, prefill_chunk=10, decode_chunk=4, temperature=0.8, seed=5, cache_dtype="float32"), **kw}
    eng = ServeEngine(c, params, on_first_logits=lambda uid, row: got[uid].append((len(by_uid[uid][1]) - 1, np.array(row))), **kw)
    by_uid = {eng.submit(p, n_new): (name, p) for name, p in prompts.items()}
    got.update({uid: [] for uid in by_uid})
    while not eng.idle:
        fed = {s.request.uid: s.length for s in eng.slots if s is not None}
        for uid, row in eng.next_logits().items():
            got[uid].append((fed[uid], row))
        eng.step()
        assert eng.pool.conserved(eng.slots), eng.pool.ledger(eng.slots)
    names = {uid: name for uid, (name, _) in by_uid.items()}
    return ({names[u]: rows for u, rows in got.items()},
            {names[u]: np.asarray(eng.finished[u].tokens, np.int32) for u in by_uid}, eng)


def test_engine_prefill_then_decode_match_the_reference_past_topk_and_window(model):
    """Chunked prefill, then paged decode through `ServeEngine`, three requests
    on two slots sampled at a temperature, one context over 3 x top-k and over 2
    x window + chunk (so tokens are dropped by the selection, at prefill rows
    and at decode, and window pages are reclaimed while it is served), in a
    latent pool with room for the first two only: the third request takes a
    finished one's pages, latent rows AND index keys (still holding the old
    values past its own length), and selects nothing of the old one's. The
    logits the engine samples from are the REFERENCE's full forward's on the
    tokens the engine produced."""
    c, params = model
    pages = lambda p: -(-(p + 13 - 1) // 4)  # the last sampled token is never fed
    got, seqs, eng = _serve(c, params, {p: _tokens(p, seed=p) for p in (37, 50, 11)}, 13, max_slots=2,
                            num_pages=pages(37) + pages(50) + 1)
    assert isinstance(eng.cache, ServeCache) and [k.name for k in eng.kinds] == [LATENT, WINDOW_LATENT]
    lat, idx, wlat = eng.cache.pool_arrays()
    assert lat.shape[:2] == idx.shape[:2] == (2, 1) and wlat.shape[:2] == (3, 1) and lat.shape[2] == idx.shape[2] != wlat.shape[2]
    assert (lat.shape[-1], idx.shape[-1], wlat.shape[-1]) == (16 + 8, 16, 32 + 8) and eng.prefill_width == 1
    assert 50 > 3 * c.index_topk and 50 > 2 * c.sliding_window + eng.prefill_chunk
    counters = eng.serve_counters()
    assert counters["kv.window_latent_pages_reclaimed"] > 0 and counters["moe.dropped"] == 0 and eng.stats()["preemptions"] == 0
    assert counters["kv.latent_pages_live_max"] <= pages(37) + pages(50) < pages(37) + pages(50) + pages(11)  # pages reused
    # whole pages: the window, a chunk, and the pages the two ends lie in
    assert 0 < counters["kv.window_latent_tokens_per_slot_max"] <= c.sliding_window + eng.prefill_chunk + 2 * eng.page_size
    # every decoded token's context passes top-k 8: two full layers score its whole context and keep 8 rows each
    n, keys = counters["dsa.decode_tokens"], counters["dsa.keys_scored"]
    assert n >= 3 * 12 and counters["dsa.rows_selected"] == 2 * 8 * n and 2 * 11 * n < keys < 2 * (50 + 13) * n
    assert (counters["kv.latent_bytes_per_token"], counters["kv.index_bytes_per_token"],
            counters["kv.window_latent_bytes_per_token"]) == (2 * 24 * 4, 2 * 16 * 4, 3 * 40 * 4)
    for p, seq in seqs.items():
        want = _ref(c, params, seq)
        assert len(got[p]) >= 3 and got[p][0][0] == p - 1 and all(r >= p for r, _ in got[p][1:])
        for r, row in got[p]:
            np.testing.assert_allclose(row, want[r], atol=ATOL)


def test_window_decode_through_the_kernel_never_reads_behind_the_window(monkeypatch):
    """The decode step's kernel lowering (interpret mode; `v_lanes` AND
    `sliding_window` together, at a window latent of one whole 128-lane row as
    the view needs), blocks cut to two pages: the window layers' absorbed
    attention is the gather lowering's expanded one, on a pool padded to the
    kernel path's lanes and a table whose entries behind the window are
    POISONED (a reclaimed page is never read)."""
    import midgpt_tpu.kernels.attention_template as at

    c = toy(n_layer=2, layer_types=(FULL, SLIDING), n_dense_layers=2, swa_kv_lora_rank=128)
    params = seeded(c, seed=4)
    monkeypatch.setattr(at, "block_pages", lambda *a: 2)
    ps, MP, lengths = 4, 16, jnp.asarray([39, 36, 5])
    active = jnp.asarray([True, True, False])
    plain = Dots3.init_cache(c, (3 * MP + 1, 3 * MP + 1), ps, jnp.float32)
    padded = Dots3.init_cache(c, (3 * MP + 1, 3 * MP + 1), ps, jnp.float32, kernel_layout=True)
    assert [a.shape[-1] for a in plain.pool_arrays()] == [24, 16, 136] and [a.shape[-1] for a in padded.pool_arrays()] == [128, 128, 256]
    keys = iter(jax.random.split(jax.random.PRNGKey(8), 3))
    rows = [jax.random.normal(next(keys), a.shape) for a in plain.pool_arrays()]
    widen = lambda a, to: jnp.pad(a, [(0, 0)] * 4 + [(0, to.shape[-1] - a.shape[-1])])
    plain = dataclasses.replace(plain, pools=((rows[0], rows[1]), (rows[2],)))
    padded = dataclasses.replace(padded, pools=tuple(tuple(widen(r, a) for r, a in zip(rs, kind))
                                                     for rs, kind in zip(((rows[0], rows[1]), (rows[2],)), padded.pools)))
    table = 1 + np.arange(3 * MP, dtype=np.int32).reshape(3, MP)
    poisoned = table.copy()
    for b, n in enumerate((39, 36)):
        poisoned[b, : (n + 1 - c.sliding_window) // ps] = 10 ** 6  # behind every future window: never dereferenced
    tok = jnp.asarray(_tokens(3, seed=9))
    want, _ = Dots3.decode_step_paged(c, params, tok, plain, (table, table), lengths, active, attn_impl="gather")
    got, _ = Dots3.decode_step_paged(c, params, tok, padded, (table, poisoned), lengths, active, attn_impl="kernel")
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2], atol=ATOL)


# what a case of the prefill kernel's test changes of: 16 chunk rows from `start` (all real), a table of 16 pages of 4,
# distinct index scores, blocks of 16 keys (four, of which the sweep reaches ceil((start + 16) / 16)), two heads a group
_PREFILL_KERNEL_CASES = {
    "a_context_of_several_blocks": dict(start=41),
    "a_table_narrower_than_a_block": dict(start=4, pages=6, key_block=32),
    "equal_index_scores_across_the_kth_place": dict(start=37, levels=1.5),
    "a_chunk_with_pad_rows": dict(start=32, n_valid=5),
    "a_first_chunk": dict(start=0, n_valid=11),
    "entries_past_the_slots_length_poisoned": dict(start=20, poison=True),
    "no_mask_operand_pangus_causal_chunk": dict(start=27, n_valid=13, masked=False),
}


@pytest.mark.parametrize("case", list(_PREFILL_KERNEL_CASES))
def test_prefill_kernel_is_the_xla_sweep(case, monkeypatch):
    """kernels/latent_prefill.py in interpret mode against the XLA sweep it
    replaces on a TPU, at the rehearsal widths: a full layer's chunk of 16 rows
    over a slot's cached latents, the selection (exact top 8, ties to the lower
    position) and each row's visibility folded into the `keep` operand; without
    the operand, the causal chunk against `PanguUltra._prefill_sweep`. Page
    table entries past the slot's length are never dereferenced."""
    import types

    import midgpt_tpu.kernels.latent_prefill as lp
    from midgpt_tpu.models import pangu_ultra

    kw = dict(dict(pages=16, key_block=16, n_valid=16, levels=0, poison=False, masked=True), **_PREFILL_KERNEL_CASES[case])
    c = toy()
    g, T, ps, MP = c.geom(LATENT), 16, 4, kw["pages"]
    monkeypatch.setattr(lp, "KEY_BLOCK", kw["key_block"])
    monkeypatch.setattr(lp, "HEAD_GROUP", 2)
    monkeypatch.setattr(dots3, "KEY_BLOCK", 16)
    monkeypatch.setattr(pangu_ultra, "PREFILL_KEY_BLOCK", 16)
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    p = types.SimpleNamespace(w_kvb=jax.random.normal(ks[0], (g.n_head * (g.nope + g.v), g.kv_rank)) / 4.0)
    pool = jax.random.normal(ks[1], (2, 1, 3 * MP + 1, ps, g.latent_dim))
    q = jax.random.normal(ks[2], (T, g.n_head, g.qk))
    start, n_valid = jnp.asarray(kw["start"]), jnp.asarray(kw["n_valid"])
    counts = jnp.minimum(start + jnp.arange(T), start + n_valid - 1) + 1
    table = 1 + MP + np.arange(MP, dtype=np.int32)  # the slot's pages lie in the middle of the pool
    used = table.copy()
    if kw["poison"]:
        used[-(-int(counts[-1]) // ps):] = 10 ** 6
        assert (used != table).sum() >= 4
    assert int(counts[-1]) <= MP * ps and (kw["key_block"] > MP * ps or int(counts[-1]) > 2 * kw["key_block"] or kw["start"] == 0)
    if not kw["masked"]:
        pc = types.SimpleNamespace(n_head=g.n_head, qk_nope_head_dim=g.nope, qk_rope_head_dim=g.rope, v_head_dim=g.v,
                                   kv_lora_rank=g.kv_rank, qk_head_dim=g.qk, latent_dim=g.latent_dim)
        want = pangu_ultra.PanguUltra._prefill_sweep(pc, p, q, pool, 1, jnp.asarray(table), counts)
        got = lp.latent_prefill_attention(
            q, lp.slot_rows(pool, 1, jnp.asarray(used), counts[-1]), p.w_kvb.reshape(g.n_head, g.nope + g.v, g.kv_rank),
            counts[-1], None, start, n_valid, nope=g.nope, scale=1.0 / np.sqrt(g.qk))
    else:
        scores = jax.random.normal(ks[3], (T, MP * ps))
        if kw["levels"]:
            scores = jnp.floor(scores * kw["levels"])  # a few distinct values: a row's k-th place lies inside a run of equals
        scores = jnp.where(jnp.arange(MP * ps)[None] < counts[:, None], scores, -jnp.inf)  # as `_index_sweep` hands them on
        bits = dots3.sortable_bits(scores)
        thr, need = dots3.kth_largest(bits, c.index_topk)
        if kw["levels"]:
            assert int(jnp.sum(jnp.sum(bits == thr[:, None], axis=1) > need)) >= 12  # rows with equals on both sides of the cut
        want = Dots3._prefill_sparse_sweep(g, p, q, pool, 1, jnp.asarray(table), counts, scores, thr, need)
        got = Dots3._prefill_sparse_kernel(g, p, q, pool, 1, jnp.asarray(used), counts, scores, thr, need)
    assert got.shape == want.shape == (T, g.n_head, g.v) and float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


# ---------------------------------------------------------------------------
# the seam, the configuration, the traffic, the cell
# ---------------------------------------------------------------------------


def test_the_family_is_registered_served_and_not_trained():
    from midgpt_tpu.config import from_json, load_config, to_json

    exp = load_config("dots3_note")
    mc = exp.model_config
    assert mc.family == "dots3_note" and len(mc.layers_of(LATENT)) == 13 and len(mc.layers_of(WINDOW_LATENT)) == 33
    assert mc.moe_layers == tuple(range(1, 46)) and from_json(to_json(exp)).model_config == mc
    with pytest.raises(NotImplementedError, match="cannot train a dots3_note model: no backward is wired through the indexer"):
        mc.check_training("launch.py")
    assert mc.check_serving("sample.py") is None
    with pytest.raises(NotImplementedError, match="int8"):
        Dots3.init_cache(toy(), (3, 3), 4, jnp.int8)
    with pytest.raises(ValueError, match="layer_types"):
        toy(n_layer=4)
    # nothing outside the family's own files and the registry names it
    named = subprocess.run(["grep", "-rlE", "dots3|Dots3", "midgpt_tpu", "sample.py", "launch.py"], cwd=ROOT, capture_output=True, text=True)
    assert sorted(f for f in named.stdout.split() if not f.endswith(".pyc")) == [
        "midgpt_tpu/config.py", "midgpt_tpu/configs/dots3_note.py", "midgpt_tpu/models/__init__.py", "midgpt_tpu/models/dots3.py"]


def test_the_benchmark_configuration_counts_what_the_issue_reckoned():
    """The cut the configuration file makes, under eval_shape: 2,577,204,736
    parameters, every published key value for value but the four in `reduced`."""
    cfg = json.load(open(os.path.join(ROOT, "benchmarks/configs/dots3_note_ep16.json")))
    from midgpt_tpu.config import load_config

    over = {k: tuple(v) if isinstance(v, list) else v for k, v in cfg["overrides"]["model_config"].items()}
    mc = dataclasses.replace(load_config(cfg["repo_config"]).model_config, **over)
    ran = dataclasses.asdict(mc)
    assert all(ran[k] == v for k, v in cfg["model"].items()) and list(mc.layer_types) == cfg["layer_types"]
    assert Dots3.count_params(jax.eval_shape(lambda k: Dots3.init(mc, k), jax.random.PRNGKey(0))) == 2_577_204_736
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert cfg["n_routed_experts"] == mc.n_experts_held == 16 and mc.n_experts == cfg["published"]["n_routed_experts"] == 256
    assert [cfg["published"]["layer_types"][i] for i in cfg["published"]["layer_types_kept_indices"]] == cfg["layer_types"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    for row in ([json.loads(l) for l in open(catalog)] if os.path.exists(catalog) else []):
        if row["name"] == "dots3-note-prev":
            assert cfg["source"] == row["source_url"] and cfg["published"]["layer_types"] == row["config"]["layer_types"]
            assert all(cfg[k] == v for k, v in row["config"].items() if k not in cfg["reduced"])
    assert {"norm_placement", "mla_rescale", "gate", "indexer", "window", "rotary", "router", "router_bias", "block_size",
            "left_out", "weights"} <= set(cfg["assumed"])
    arith = _load("benchmarks/arithmetic_dots3.py")
    assert arith.expert_bytes(ran) == 47_185_920 and arith.kv_write_token(ran) == (0.0, 2.0 * (2 * (576 + 128) + 3 * 1088))
    assert arith.decode_attention_token(ran, "window_latent", 5000)[0] == 3 * 2 * 64 * (1088 + 1024) * 513.0
    assert arith.decode_attention_token(ran, "window_latent", 100)[1] == 3 * (100 * 1088 * 2 + 64 * 2112 * 2.0)
    assert arith.index_sweep_token(ran, 12000) == (2 * 16384.0 * 12000, 2 * 12000 * 128 * 2.0)
    assert arith.select_attention_token(ran, 12000)[0] == 2 * 278528.0 * 2048 == arith.decode_attention_token(ran, "latent", 12000)[0]
    assert arith.select_attention_token(ran, 1000)[1] == 2 * (1000 * 576 * 2 + 128 * 1088 * 2.0)
    assert arith.attention_weights(ran, "latent") == 144_049_920 - 1_536 - 256 and arith.attention_weights(ran, "window_latent") == 90_834_944 - 2_048
    assert round(arith.decode_step_weight_bytes(ran, 2, experts_touched=10) / 1e9, 2) == 3.83


def test_the_traffic_is_one_multiset_for_every_seed_and_the_benchmark_only_adds():
    loadgen = _load("benchmarks/loadgen.py")
    spec = json.load(open(os.path.join(ROOT, "benchmarks/traffic/longctx_sparse_closed.json")))
    a, b = loadgen.Traffic(spec, 1, 19008), loadgen.Traffic(spec, 2**31 + 12345, 19008)
    assert a.multiset() == b.multiset() and len(a.multiset()) == 64 and spec["kind"] == "serve_sparse"
    assert (min(a.prompt_lens), max(a.prompt_lens)) == (2048, 49152) and all(o % 8 == 0 for o in a.output_lens)
    assert max(p + o for p, o in a.multiset()) <= spec["max_total"] == 50176 and spec["prompt_len"]["hi"] == 49152
    # ISSUE 51's parameters (the ladder's first rung): every prompt reaches top-k 2,048, three of 64 sit on it, three pass the slot's pool share
    assert sum(p == 2048 for p in a.prompt_lens) == 3 == sum(p > 32768 for p in a.prompt_lens) and spec["prompt_len"]["median"] == 8192
    assert 11000 < np.mean(a.prompt_lens) < 11100 and spec["output_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.6, "lo": 64, "hi": 1024}
    e, check = spec["engine"], sorted(spec["check"]["prompts"])
    assert (e["max_slots"], e["page_size"], e["prefill_chunk"], e["decode_chunk"], e["pool_tokens_per_slot"]) == (32, 32, 512, 8, 32768)
    assert check[0] < 513 < 513 + 512 < check[1] < 2048 < check[2] < 4096 < 2 * 2048 + 512 < check[3]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # ORDER, not position: this cell's entries are one run each, after Trinity's (the PR before it appended those); what
    # later PRs append after them is theirs to pin
    names = [w["name"] for w in bench["workloads"]]
    cell = bench["workloads"][names.index(CELL)]
    assert names.index(CELL) == names.index("serve_trinity_mini_reason") + 1
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3_note_ep16", "longctx_sparse_closed", 1)
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index("dots3_note_ep16") == configs.index("trinity_mini_pp") + 1
    assert bench["configs"][configs.index("dots3_note_ep16")]["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
    assert {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])} == {"setup_s", "serve_tokens_per_s"}
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    at = bench["per_layer"].index(mine[0])  # one run of entries, in order, as PR 51 appended them (later PRs append after)
    assert mine == bench["per_layer"][at:at + len(mine)] and len(mine) == 10
    assert all(m["moves"] == "serve_tokens_per_s" for m in mine)
    for m in bench["end_to_end"] + bench["per_layer"]:  # in every list it joined, right after the cell before it
        w = m.get("workloads", [])
        if CELL in w and "serve_trinity_mini_reason" in w:
            assert w.index(CELL) == w.index("serve_trinity_mini_reason") + 1, m["name"]


def test_both_controls_are_refused_by_the_cells_own_limits(tmp_path):
    """The cell's control entry point at the rehearsal's toy size: the reference
    with 8-bit matrices AND the reference without the selection, each in the
    program's place through the same rows, `judge` and limits, come out NOT
    CORRECT while the program is correct (exit 0 says all three)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "serve_sparse_cell.py"), "--workload", CELL,
         "--seed", "3000000019", "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"program_correct": True, "control_correct": False}
    c1, c2 = (next(l for l in proc.stdout.splitlines() if l.startswith(f"[bench] control {n}:")) for n in (1, 2))
    assert "float8_e4m3fn" in c1 and c1.endswith("NOT CORRECT") and "WITHOUT the selection" in c2 and c2.endswith("NOT CORRECT")


def test_sample_py_serves_a_saved_checkpoint_of_the_family(tmp_path):
    """sample.py reaches the engine for this family through the same code as
    for the GPT (nothing names it): seeded parameters saved with the repo's
    checkpoint writer under the `dots3_note` preset at a toy size, restored
    through the family namespace, sampled greedily: the tokens are the full
    forward's argmax chain (9 new tokens: past top-k 8 and the window of 5)."""
    import pickle

    from midgpt_tpu.config import load_config, to_json
    from midgpt_tpu.training.checkpoint import CheckpointManager

    c = toy(vocab_size=65, block_size=64, n_layer=3, layer_types=(FULL, SLIDING, FULL))  # every kind of layer and FFN once
    params = seeded(c, seed=7)
    data = tmp_path / "data"
    data.mkdir()
    chars = [chr(33 + i) for i in range(65)]
    with open(data / "meta.pkl", "wb") as f:
        pickle.dump({"stoi": {ch: i for i, ch in enumerate(chars)}, "itos": dict(enumerate(chars))}, f)
    exp = load_config("dots3_note").replace(rundir=str(tmp_path), data_dir=str(data), compute_dtype="float32", model_config=c)
    (tmp_path / "config.json").write_text(to_json(exp))
    mngr = CheckpointManager(str(tmp_path), save_interval_steps=1)
    assert mngr.save(3, {"params": params}, force=True)
    mngr.wait()
    mngr.close()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}", "--start=AB#", "--num_samples=2",
         "--max_new_tokens=9", "--temperature=0.0", "--engine=continuous"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "restored checkpoint step 3" in proc.stdout
    new = json.loads(next(l for l in proc.stdout.splitlines() if l.startswith("new_tokens: "))[len("new_tokens: "):])
    seq = np.zeros((1, c.block_size), np.int32)
    seq[0, :3] = [32, 33, 2]  # "AB#" under the codec above
    with jax.default_matmul_precision("default"):  # as the entry point runs
        for i in range(3, 12):
            seq[0, i] = int(np.argmax(np.asarray(Dots3.apply(c, params, jnp.asarray(seq)))[0, i - 1]))
    assert new == [seq[0, 3:12].tolist()] * 2
