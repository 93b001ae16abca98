"""sampling/pages.py, the paged pool's one owner, on its own: the page
transport's round trip in every pool format, the page tables, the window rule
with the conservation law on a two-kind pool, and the sizing rule at the
serving cells' engine shapes, and the one cache class every family beside the
GPT answers `init_cache` with. CPU, toy widths. The engine over it:
tests/test_serving.py and the families' *_serving.py."""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from midgpt_tpu.config import load_config
from midgpt_tpu.models.gpt import CacheKind, GPTConfig, PagedKVCache, ServeCache, pool_lanes
from midgpt_tpu.sampling.pages import (
    PagePool,
    adopt_pages,
    join_pages,
    split_pages,
    take_pages,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = GPTConfig(block_size=64, vocab_size=96, n_layer=2, n_head=2, n_embd=32)


def _slot(pages, length=0, prompt=()):
    """What pages.py reads of a `serve._Slot`."""
    return types.SimpleNamespace(
        pages=[list(p) for p in pages], reclaimed_to=[0] * len(pages), length=length, n_shared=0,
        generated=[], request=types.SimpleNamespace(prompt=np.asarray(prompt, np.int32)),
    )


def _pool(config=CFG, **kw):
    args = dict(max_slots=3, num_pages=None, pool_hbm_bytes=None, page_size=4, burst=8,
                cache_dtype=jnp.dtype(jnp.float32), kernel_layout=False, prefill_width=2)
    return PagePool(config, **{**args, **kw})


@pytest.mark.parametrize("kernel_layout", [False, True], ids=["head_dim", "lanes"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=["bf16", "int8"])
def test_take_then_adopt_returns_the_bytes_it_took(dtype, kernel_layout):
    """(a) three pages (not a power of two: both sides pad to 4) out of one
    pool and into another at other physical pages: the same bytes, the int8
    scales with their pages, and no page beside the destinations written."""
    rng = np.random.default_rng(0)
    src = PagedKVCache.init(CFG, num_pages=9, page_size=4, dtype=dtype, kernel_layout=kernel_layout)

    def noise(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    src = dataclasses.replace(
        src, k=noise(src.k), v=noise(src.v),
        **({"k_scale": noise(src.k_scale), "v_scale": noise(src.v_scale)} if src.quantized else {}),
    )
    ids, dst = [7, 2, 5], [1, 6, 3]
    blocks = take_pages(src, ids)
    assert sorted(blocks) == (["k", "k_scale", "v", "v_scale"] if src.quantized else ["k", "v"])
    assert blocks["k"].shape == src.k.shape[:2] + (3,) + src.k.shape[3:]
    np.testing.assert_array_equal(blocks["v"][:, :, 1], np.asarray(src.v[:, :, 2]))
    rejoined = join_pages(split_pages(blocks))
    assert all(np.array_equal(rejoined[key], blocks[key]) for key in blocks)

    fresh = PagedKVCache.init(CFG, num_pages=9, page_size=4, dtype=dtype, kernel_layout=kernel_layout)
    new = adopt_pages(None, fresh, dst, blocks)
    back = take_pages(new, dst)
    for key in blocks:
        assert back[key].tobytes() == blocks[key].tobytes(), key
    untouched = take_pages(new, [p for p in range(9) if p not in dst])
    assert all(not np.asarray(b, np.float32).any() for b in untouched.values())


def test_table_parks_reclaimed_entries_on_the_sink_and_rows_pad_to_prefill_width():
    """(b) a -1 (window-reclaimed) entry reads page 0, as an empty slot's row
    and the columns past a slot's pages do; `tables(rows=...)` is those rows
    in that order, then sink rows up to `prefill_width`."""
    pool = _pool(prefill_width=4)
    slots = [_slot([[5, -1, 7]]), None, _slot([[2, 3, 4, 6, 8]])]
    np.testing.assert_array_equal(pool.table(slots, 4), [[5, 0, 7, 0], [0, 0, 0, 0], [2, 3, 4, 6]])
    assert pool.table(slots, 16).shape == (3, 16) and pool.table(slots, 16).dtype == np.int32
    np.testing.assert_array_equal(pool.tables(slots, 4), pool.table(slots, 4))
    picked = pool.tables(slots, 4, rows=[2, 0])
    np.testing.assert_array_equal(picked, [[2, 3, 4, 6], [5, 0, 7, 0], [0, 0, 0, 0], [0, 0, 0, 0]])
    assert [pool.bucket(t) for t in (0, 1, 4, 5, 17, 64, 1000)] == [1, 1, 1, 2, 8, 16, 16]


class _TwoKinds:
    """A family as the pool sees one: a kind that keeps the whole context and
    a windowed kind with a sink prefix (window 16, 6 sink tokens: two pages of
    4 stay). Its pools are page counters: nothing here runs on them."""

    block_size = 256

    def model(self):
        return self

    def cache_kinds(self, config):
        return (CacheKind("global", 0, 0), CacheKind("window", 16, 6))

    def init_cache(self, config, num_pages, page_size, dtype, kernel_layout=False):
        return tuple(np.zeros((n, page_size)) for n in num_pages)


def test_window_rule_frees_exactly_the_dead_pages_and_the_law_holds():
    """(c) on a two-kind pool the window rule frees the windowed kind's pages
    wholly below `length - window`, the sink prefix apart, leaves the other
    kind alone, counts what it freed, and the conservation law holds before,
    after and once the slot has left."""
    pool = _pool(_TwoKinds(), num_pages=41)
    assert [a.num_pages for a in pool.allocators] == [41, 1 + 3 * (-(-(16 + 8) // 4) + 1)]
    slot = _slot([pool.alloc(0, 12), pool.alloc(1, 12)], length=45)
    pool.note_growth(slot, 1)
    other = _slot([pool.alloc(0, 2), pool.alloc(1, 2)], length=5)
    slots = [slot, None, other]
    window_pages = list(slot.pages[1])
    assert pool.conserved(slots)
    free0 = [a.free_count for a in pool.allocators]

    pool.reclaim(slot)
    # positions below 45 - 16 = 29 are dead: pages 0..6 hold [0, 28); 0 and 1 hold the 6 sink tokens
    assert slot.pages[1] == window_pages[:2] + [-1] * 5 + window_pages[7:]
    assert slot.pages[0] == list(range(1, 13)) and slot.reclaimed_to == [0, 7]
    assert [a.free_count for a in pool.allocators] == [free0[0], free0[1] + 5]
    assert pool.kind_reclaimed == [0, 5] and pool.conserved(slots)
    assert pool.live_pages(slots)[1] == set(window_pages[:2] + window_pages[7:] + other.pages[1])
    pool.reclaim(slot)  # nothing new is dead: nothing moves
    assert pool.kind_reclaimed == [0, 5]
    assert pool.counters() == {
        "kv.global_pages_live": 14, "kv.global_pages_live_max": 14,
        "kv.window_pages_live": 9, "kv.window_pages_live_max": 14,
        "kv.window_pages_reclaimed": 5, "kv.window_tokens_per_slot_max": 48,
    }
    # a leak shows: a page neither free nor held breaks the law, in its kind alone
    leaked = slot.pages[1].pop()
    assert not pool.conserved(slots)
    assert [t["free"] + t["trie"] + t["live_only"] == t["allocatable"] for t in pool.ledger(slots)] == [True, False]
    slot.pages[1].append(leaked)

    pool.release(slot)
    pool.release(other)
    assert pool.conserved([None, None, None])
    assert [a.free_count for a in pool.allocators] == [a.num_pages - 1 for a in pool.allocators]


def _replace_nested(obj, tree):
    kw = {k: _replace_nested(getattr(obj, k), v) if isinstance(v, dict) else v for k, v in tree.items()}
    return dataclasses.replace(obj, **kw)


# (d) what the PARENT's ServeEngine (6936c74) gave each serving cell's allocators, by running it
@pytest.mark.parametrize("cell, pages", [
    ("serve_124m_sample", [3073]),
    ("serve_xl_chat", [2049]),
    ("serve_mimo_v2_5_mixed", [8705, 673]),
    ("serve_pangu_ultra_longctx", [8449]),
    ("serve_ouro_reason", [157]),
    ("serve_olmo_hybrid_docchat", [2689]),  # PR 59: 24 x 112 pages + the sink; its 25 state rows are no allocator's
])
def test_sizing_rule_gives_the_serving_cells_their_page_counts(cell, pages, monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (w,) = [w for w in json.load(f)["workloads"] if w["name"] == cell]
    with open(os.path.join(ROOT, "benchmarks", "configs", w["config"] + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json")) as f:
        es = json.load(f)["engine"]
    mc = _replace_nested(load_config(cfg["repo_config"]), cfg.get("overrides", {})).model_config
    monkeypatch.setattr(mc.model(), "init_cache", lambda *a, **k: None)  # the rule, not gigabytes of pool
    per_slot, ps = es.get("pool_tokens_per_slot"), int(es["page_size"])
    pool = PagePool(
        mc, max_slots=int(es["max_slots"]),
        # as benchmarks/serve_cell.py and serve_family_cell.py ask: tokens a slot, in pages, plus the sink
        num_pages=None if per_slot is None else int(es["max_slots"]) * -(-int(per_slot) // ps) + 1,
        pool_hbm_bytes=None, page_size=ps,
        burst=max(int(es["prefill_chunk"]), int(es["decode_chunk"])),  # round_group 1: the engines' default
        cache_dtype=jnp.dtype(jnp.bfloat16), kernel_layout=True, prefill_width=1,
    )
    assert [a.num_pages for a in pool.allocators] == pages
    assert len(pool.state_kinds) == (cell == "serve_olmo_hybrid_docchat") and len(pool.kinds) == len(pages)


# (e) the served families beside the GPT, each at its cell's rehearsal widths (benchmarks/configs/<file>.json)
FAMILY_FILES = {"mimo_v2": "mimo_v2_5_ep16", "trinity": "trinity_mini_pp", "pangu_ultra": "openpangu_ultra_moe_ep16",
                "ouro": "ouro_2p6b", "dots3": "dots3_note_ep16", "olmo_hybrid": "olmo_hybrid_7b_pp2"}


@pytest.mark.parametrize("family", sorted(FAMILY_FILES))
def test_every_family_answers_init_cache_with_the_one_class(family):
    """`init_cache` returns models/gpt.py `ServeCache`: kind i of `cache_kinds`
    gets `num_pages[i]` pages in every one of its arrays, the five-axis layout
    with the kernel path's lanes at `pool_lanes` of the width; a state kind's
    arrays have its shapes with the row axis second; the leaves are pools,
    then state, then counters (the order the serving programs' parameters
    keep, and the parent's five classes had); int8 is refused by name. Under
    `jax.eval_shape`: no array is made and nothing compiles."""
    with open(os.path.join(ROOT, "benchmarks", "configs", FAMILY_FILES[family] + ".json")) as f:
        cfg = json.load(f)
    mc = dataclasses.replace(load_config(cfg["repo_config"]).model_config, **cfg["rehearsal"]["overrides"]["model_config"])
    model = mc.model()
    assert model.__module__ == f"midgpt_tpu.models.{family}"
    kinds = model.cache_kinds(mc)
    paged = [k for k in kinds if isinstance(k, CacheKind)]
    state_kinds = kinds[len(paged):]
    assert paged and len(state_kinds) == (family == "olmo_hybrid")
    pages, rows, ps = [11, 7][: len(paged)], 5, 4
    counts = pages + [rows] * len(state_kinds)
    cache, lanes = (jax.eval_shape(lambda kl=kl: model.init_cache(mc, counts, ps, jnp.bfloat16, kernel_layout=kl))
                    for kl in (False, True))
    assert type(cache) is ServeCache and type(lanes) is ServeCache
    assert cache.page_size == ps and cache.num_pages == pages[0]

    assert len(cache.pools) == len(paged) and all(len(kind) >= 1 for kind in cache.pools)
    assert all(x is y for x, y in zip(cache.pool_arrays(), [a for kind in cache.pools for a in kind], strict=True))
    for kind, kind_lanes, n in zip(cache.pools, lanes.pools, pages, strict=True):
        for a, b in zip(kind, kind_lanes, strict=True):
            assert a.ndim == 5 and a.shape[2:4] == (n, ps) and a.dtype == jnp.bfloat16
            assert b.shape == (*a.shape[:4], pool_lanes(a.shape[4])) and b.dtype == a.dtype

    shapes = [sd for k in state_kinds for sd in k.shapes(jnp.bfloat16)]
    assert [(a.shape, a.dtype) for a in cache.state] == [((s[0], rows, *s[1:]), jnp.dtype(d)) for s, d in shapes]
    assert isinstance(cache.state, tuple) and isinstance(cache.counters, tuple) and len(cache.counters) >= 1
    assert all(c.ndim <= 2 for c in cache.counters)
    assert all(x is y for x, y in zip(jax.tree.leaves(cache), [*cache.pool_arrays(), *cache.state, *cache.counters], strict=True))

    with pytest.raises(NotImplementedError, match="int8"):
        model.init_cache(mc, counts, ps, jnp.int8)
