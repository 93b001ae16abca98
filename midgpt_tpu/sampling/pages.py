"""The paged pool's one owner: its format, its size, its books, its page tables.

`models/gpt.py` defines what a pool IS and the kernels read it: the GPT's
`PagedKVCache` (K and V of (L, H, P, ps, C), int8 scale side buffers of
(L, P, H, ps), page 0 the sink) and, for every other served family,
`ServeCache` (per paged kind a tuple of arrays in that same five-axis layout,
a state kind's rows, the family's counters). Two types: what MOVES pages
below (`take_pages`, `adopt_pages`, `migrate`, `poison`) knows the GPT's
alone, and the engine refuses those operations for the others by name.
Everything the HOST does with pages happens here, so that a change of layout,
of the sizing rule or of the conservation law has one place to be made:

  * the format, as far as the host moves whole pages: `take_pages` (device
    pool -> host blocks `{'k', 'v'[, 'k_scale', 'v_scale']}`),
    `adopt_pages` (host blocks -> pool, one donated scatter a pow2 bucket),
    `split_pages` / `join_pages` (blocks of n pages <-> n blocks of one page:
    the spill tier keeps single pages), `PagePool.poison`. No other module
    of `sampling/` or `robustness/` indexes a pool array or names one of its
    axes;
  * the size: `PagePool.__init__`'s rule, one pool and one `PageAllocator` a
    kind of cache the family's layers need (`models/__init__.py`
    `cache_kinds`);
  * the books: `alloc` / `free` by kind, `release` (the one funnel a
    departing slot's pages go through, the prefix trie's share included),
    `reclaim` (the window rule), the per-kind counters, and ONE statement of
    the conservation law (`live_pages`, `ledger`, `conserved`);
  * the page tables the serving programs take: `table`, `tables`, `bucket`.

State kinds. A family may also name a kind of cache that is NOT paged
(`models/gpt.py` `StateKind`: a recurrent layer's state, models/olmo_hybrid.py):
one ROW a slot, as large for a prompt of ten tokens as for one of ten thousand,
held in `ServeCache.state` (arrays whose SECOND axis is the row).
It is this module's like the pages are: sized here (`max_slots` rows and a sink
row, the last, which an empty place of a prefill call names), handed out here
(`claim_state`: slot i's row is row i, so that a decode step over the slots in
order updates the rows where they lie; `tables` holds every decode round's rows
to that and raises otherwise) and given back through `release`; `ledger` /
`conserved` hold it to the same law (rows free + rows live == rows), `counters`
reports `state.*`, `hbm_bytes` counts its arrays, and `tables` hands the serving
programs each slot's row index beside its page tables. A stale page is masked by
the slot's length; a stale row would be a wrong answer, so a row is RESET for
every request admitted to it: the family's prefill program begins a prompt's
first chunk (start 0) from zeros whatever the row holds (the seam's contract,
models/__init__.py), which a preempted request's recompute walks again; this
module runs nothing on the device for it and counts the admissions
(`state.resets`). What moves pages cannot move a row (the prefix
trie, speculation's rollback, spill, resize, migrate, the disaggregated
hand-off): the engine refuses each by name for such a family.

`ServeEngine` (sampling/serve.py) keeps the policy: whom to admit, whose
pages to take when the pool runs dry, what a round dispatches. A slot is the
engine's (`serve._Slot`); what this module reads of one is `pages` (per kind,
a LOGICAL list: entry j holds positions [j*ps, (j+1)*ps), -1 once the window
rule freed it), `reclaimed_to`, `length`, `n_shared`, `generated`,
`request.prompt` and, for a family with a state kind, `state_row` (written
here: -1 where the slot holds none). This module imports neither the engine
nor anything built on it.
"""

from __future__ import annotations

import dataclasses
import functools
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.models.gpt import CacheKind, PagedKVCache

# The page axis of each block key (and of the pool array it is taken from).
_PAGE_AXIS = {"k": 2, "v": 2, "k_scale": 1, "v_scale": 1}

Blocks = tp.Dict[str, np.ndarray]


class PageAllocator:
    """Free-list allocator over the pool's pages. Page 0 is the SINK
    (absorbs inactive-slot writes, models/gpt.py PagedKVCache) and is never
    handed out."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields 1, 2, ...
        self.free_min = len(self._free)  # the fewest pages ever free: the pool's peak is the rest

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> tp.Optional[tp.List[int]]:
        """n pages, or None (allocator unchanged) if the pool is short."""
        if n > len(self._free):
            return None
        self.free_min = min(self.free_min, len(self._free) - n)
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: tp.Iterable[int]) -> None:
        for p in pages:
            assert 0 < p < self.num_pages
            self._free.append(p)


def pow2_bucket(n: int) -> int:
    """The smallest power of two >= n (1 for n <= 1): the page counts the
    gather and the adoption scatter compile for, so that their compile keys
    are buckets and not resident counts (the serving jits' discipline)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def take_pages(cache: PagedKVCache, ids: tp.Sequence[int]) -> Blocks:
    """The content of physical pages `ids`, landed on the host: 'k' / 'v'
    (L, H, n, ps, C) and, int8 pools, 'k_scale' / 'v_scale' (L, n, H, ps).
    The device gather runs at the pow2 bucket of n, padded with the sink
    page (one cached program a bucket, whichever pages are asked for; a
    python-int slice would compile per index); the padding is cut off on
    the host."""
    ids = list(ids)
    n = len(ids)
    pad = pow2_bucket(n) - n
    idx = jnp.asarray(ids + [0] * pad, jnp.int32)
    arrays = {"k": cache.k, "v": cache.v}
    if cache.k_scale is not None:
        arrays.update(k_scale=cache.k_scale, v_scale=cache.v_scale)
    blocks = {key: np.asarray(jnp.take(a, idx, axis=_PAGE_AXIS[key])) for key, a in arrays.items()}
    if pad:
        blocks = {key: b.take(range(n), axis=_PAGE_AXIS[key]) for key, b in blocks.items()}
    return blocks


def split_pages(blocks: Blocks) -> tp.List[Blocks]:
    """Blocks of n pages as n blocks of ONE page with the page axis gone
    ('k' / 'v' (L, H, ps, C), scales (L, H, ps)): the unit the spill tier
    keys, checksums and ships (sampling/fleet.py)."""
    n = blocks["k"].shape[_PAGE_AXIS["k"]]
    return [
        {key: np.take(b, j, axis=_PAGE_AXIS[key]) for key, b in blocks.items()}
        for j in range(n)
    ]


def join_pages(pages: tp.Sequence[Blocks]) -> Blocks:
    """`split_pages` undone: single-page blocks, in order, as blocks of n."""
    return {
        key: np.stack([p[key] for p in pages], axis=_PAGE_AXIS[key])
        for key in pages[0]
    }


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _adopt_pages(mesh, cache, dst, blocks):
    """Scatter handed-off page blocks into the decode pool at physical
    pages `dst` ((n,) int32, padded to a power-of-two bucket with
    `num_pages` so pad writes drop under XLA oob-scatter semantics — the
    same funnel shape as the engine's K/V column writes). `blocks` carries
    'k'/'v' (L, H, n, ps, C) and, int8 pools, 'k_scale'/'v_scale'
    (L, n, H, ps); its key set and the dst bucket are the compile keys.
    The pool is donated: an adopt is an in-place page write, not a pool
    copy. `mesh` is static like the serving jits' trailing mesh arg and
    pins the sharded pool's out-sharding (serve._maybe_constrain)."""
    k = cache.k.at[:, :, dst].set(blocks["k"].astype(cache.k.dtype))
    v = cache.v.at[:, :, dst].set(blocks["v"].astype(cache.v.dtype))
    ks, vs = cache.k_scale, cache.v_scale
    if "k_scale" in blocks:
        ks = ks.at[:, dst].set(blocks["k_scale"])
        vs = vs.at[:, dst].set(blocks["v_scale"])
    new = PagedKVCache(k=k, v=v, k_scale=ks, v_scale=vs)
    if mesh is not None:
        from midgpt_tpu.parallel.serve_tp import constrain_cache

        new = constrain_cache(new, mesh)
    return new


def adopt_pages(mesh, cache: PagedKVCache, dst: tp.Sequence[int], blocks: Blocks) -> PagedKVCache:
    """`cache` with `blocks` (as `take_pages` gives them) written at physical
    pages `dst`: the ONE page-transport funnel (disagg hand-off, spill
    re-adoption, pool resize). `cache` is donated. Destinations and blocks
    are padded to the pow2 bucket of their count, the destinations with
    `num_pages` (out of range: the pad writes drop), the blocks with zeros."""
    dst = list(dst)
    pad = pow2_bucket(len(dst)) - len(dst)

    def padded(key: str, b: np.ndarray) -> np.ndarray:
        shape = list(b.shape)
        shape[_PAGE_AXIS[key]] = pad
        return np.concatenate([b, np.zeros(shape, b.dtype)], axis=_PAGE_AXIS[key])

    if pad:
        blocks = {key: padded(key, b) for key, b in blocks.items()}
    return _adopt_pages(
        mesh,
        cache,
        jnp.asarray(dst + [cache.num_pages] * pad, jnp.int32),
        {key: jnp.asarray(b) for key, b in blocks.items()},
    )


def keep_state(new, old):
    """`new` with `old`'s state rows: what a program that must commit nothing
    hands back (`ServeEngine.next_logits`: a K/V write is repeated by the round
    that follows, a state update applied twice is a wrong state). The GPT's
    `PagedKVCache` has no state and passes as it is."""
    return new if isinstance(new, PagedKVCache) else dataclasses.replace(new, state=old.state)


class PagePool:
    """The pools, allocators and books of one engine (module docstring).
    Takes what `ServeEngine` was given; adds no option of its own."""

    def __init__(
        self,
        config,
        *,
        max_slots: int,
        num_pages: tp.Optional[int],
        pool_hbm_bytes: tp.Optional[int],
        page_size: int,
        burst: int,
        cache_dtype,
        kernel_layout: bool,
        prefill_width: int,
        mesh=None,
        prefix_cache=None,  # Optional[sampling.prefix_cache.PrefixCache]
        draft_config=None,
        draft_shares_cache: bool = False,
    ):
        self.config = config
        model = config.model()
        kinds = model.cache_kinds(config)
        self.kinds = tuple(k for k in kinds if isinstance(k, CacheKind))  # the PAGED kinds
        self.state_kinds = tuple(k for k in kinds if not isinstance(k, CacheKind))
        self.max_slots = max_slots
        # state row i is slot i's; `state_held[i]`: whether a slot holds it now
        self.state_held = [False] * max_slots
        self.state_resets = 0
        self.state_rows_live_max = 0
        self.page_size = page_size
        self.cache_dtype = cache_dtype
        self.kernel_layout = kernel_layout
        self.prefill_width = prefill_width
        self.mesh = mesh
        self.prefix_cache = prefix_cache
        self.draft_config = draft_config
        self.max_pages_per_slot = -(-config.block_size // page_size)
        if pool_hbm_bytes is not None:
            # Byte-budgeted paging: the pool is sized by HBM SPEND, not page
            # count, so the page capacity follows the cache dtype — int8
            # admits 2x the pages of bf16 at the same budget (the int8 scale
            # side buffers ride on top, +4/head_dim; PagedKVCache.page_bytes
            # documents the accounting, hbm_bytes() reports the true
            # total).
            if num_pages is not None:
                raise ValueError("pass num_pages OR pool_hbm_bytes, not both")
            per_page = PagedKVCache.page_bytes(
                config, page_size, cache_dtype, kernel_layout=kernel_layout
            )
            num_pages = max(2, pool_hbm_bytes // per_page)  # sink + >= 1
        elif num_pages is None:
            # Default: half of what dedicated full-length caches would take
            # (+ the sink) — the continuous-batching bet that Σ used-lengths
            # stays well under n_slots * block_size.
            num_pages = 1 + max_slots * self.max_pages_per_slot // 2
        # A further kind's pool: as many pages as the first where it keeps
        # the whole context; where it keeps a window, what every slot can
        # hold at once, window + the longest write between two reclaims
        # (`burst`: a prefill chunk; a decode group) + a page of alignment,
        # so that this pool never runs dry before the first does.
        pool_pages = [num_pages] + [
            1 + max_slots * (-(-(k.window + burst) // page_size) + 1) if k.window else num_pages
            for k in self.kinds[1:]
        ]
        self.allocators = [PageAllocator(n) for n in pool_pages]
        # The window rule's counters (`reclaim`: the bounded-resident-set lever
        # that makes windowed decode O(window) in pool pages, not O(T)), per
        # kind: pages the rule freed (the first kind's is
        # stats()["window_reclaimed_pages"]); the most pages one slot ever held.
        self.kind_reclaimed = [0] * len(self.kinds)
        self.kind_slot_pages_max = [0] * len(self.kinds)
        # a state kind's count, after the paged kinds': its rows, the slots' and the sink
        self.cache = self._put(
            model.init_cache(
                config, pool_pages + [max_slots + 1] * len(self.state_kinds), page_size, cache_dtype,
                kernel_layout=kernel_layout,
            )
        )
        # A layer-prefix self-draft needs no pool of its own: draft layer i
        # IS target layer i, so the committed K/V it must attend to already
        # sit in the target pool's first n_draft layers, and its speculative
        # writes there are the same values the verify forward rewrites. The
        # draft then also skips prompt prefill entirely — the target's
        # prefill filled its layers. A separate draft model gets a dedicated
        # pool (same page table/allocator: one logical page, two pools).
        self.draft_cache = (
            None
            if draft_config is None or draft_shares_cache
            else self._fresh(draft_config, pool_pages[0])
        )

    def _put(self, cache):
        """`cache` sharded over the serving mesh's heads (as it is, unsharded)."""
        if self.mesh is None:
            return cache
        from midgpt_tpu.parallel import serve_tp as _stp

        return _stp.put_sharded(cache, _stp.serve_cache_specs(cache), self.mesh)

    def _fresh(self, config, num_pages: int) -> PagedKVCache:
        """A zeroed one-kind pool of `num_pages` in this pool's format."""
        return self._put(
            PagedKVCache.init(
                config, num_pages=num_pages, page_size=self.page_size,
                dtype=self.cache_dtype, kernel_layout=self.kernel_layout,
            )
        )

    # -- the books -------------------------------------------------------

    def alloc(self, kind: int, n: int) -> tp.Optional[tp.List[int]]:
        """n pages of `kind`, or None (nothing changed) if its pool is short."""
        return self.allocators[kind].alloc(n)

    def free(self, kind: int, pages: tp.Iterable[int]) -> None:
        self.allocators[kind].free(pages)

    def note_growth(self, slot, kind: int) -> None:
        """The most pages of a windowed kind one slot ever held (`counters`):
        its list less what the window rule has freed below `reclaimed_to`."""
        k = self.kinds[kind]
        if not k.window:
            return
        held = len(slot.pages[kind]) - max(0, slot.reclaimed_to[kind] - -(-k.sinks // self.page_size))
        self.kind_slot_pages_max[kind] = max(self.kind_slot_pages_max[kind], held)

    def claim_state(self, slot, index: int) -> None:
        """Slot `index` is admitted: where the family has a state kind, its
        row (row `index`) is held by `slot` until `release`, and reset by the
        request's first prefill chunk (module docstring). A family without
        one: nothing."""
        if not self.state_kinds:
            return
        assert not self.state_held[index], f"state row {index} is held by another slot"
        self.state_held[index] = True
        slot.state_row = index
        self.state_resets += 1
        self.state_rows_live_max = max(self.state_rows_live_max, sum(self.state_held))

    def release(self, slot) -> None:
        """The ONE funnel a departing slot's pages (and its state row) go
        through (finish, cancel, timeout, preemption). Cache off: straight back
        to the allocator. Cache on: the trie drops the slot's shared-page refs,
        absorbs its complete committed pages for future matches, and only
        the remainder (partial tails, content-duplicates) hits the free
        list — page conservation becomes free_count + trie pages ==
        num_pages - 1 (`conserved`)."""
        if self.state_kinds and slot.state_row >= 0:
            self.state_held[slot.state_row] = False
            slot.state_row = -1
        if self.prefix_cache is None:
            # -1 entries are window-reclaimed placeholders (already freed)
            for allocator, pages in zip(self.allocators, slot.pages):
                allocator.free(p for p in pages if p >= 0)
            return
        committed = np.concatenate(
            [slot.request.prompt, np.asarray(slot.generated, np.int32)]
        )[: slot.length]
        allocator = self.allocators[0]  # the trie holds pages of the first kind only
        allocator.free(self.prefix_cache.release(committed, slot.pages[0], slot.n_shared))

    def reclaim(self, slot) -> None:
        """Free this slot's pages that no FUTURE attention row can see.

        Page j (positions [j*ps, (j+1)*ps)) is dead once the youngest
        visible position has moved past it — counts only grow, so
        (j+1)*ps <= length - sliding_window is permanent — unless it holds
        sink-prefix tokens. Freed entries become -1 placeholders so the
        page list keeps its LOGICAL length (position -> table column stays
        the identity; the engine's `_ensure_pages` and the settle bound
        len(pages)*ps are untouched); `table` parks them on the sink page.
        Gated off under the prefix cache (the trie owns shared pages'
        lifetime) and speculative decoding (verify rollback re-reads recent
        history); conservation becomes free + live non-placeholder ==
        num_pages - 1."""
        if self.prefix_cache is not None or self.draft_config is not None:
            return
        ps = self.page_size
        for k, kind in enumerate(self.kinds):
            if not kind.window:
                continue
            pages = slot.pages[k]
            first_live = max(0, slot.length - kind.window) // ps  # pages below are dead
            # keep the sink prefix; below `reclaimed_to` everything is freed already
            start = max(-(-kind.sinks // ps), slot.reclaimed_to[k])
            dead = [j for j in range(start, first_live) if pages[j] >= 0]
            if first_live > slot.reclaimed_to[k]:
                slot.reclaimed_to[k] = first_live
            if not dead:
                continue
            self.allocators[k].free(pages[j] for j in dead)
            for j in dead:
                pages[j] = -1
            self.kind_reclaimed[k] += len(dead)

    def live_pages(self, slots) -> tp.List[tp.Set[int]]:
        """Per kind, the physical pages the slots hold now (-1 entries are
        window-reclaimed placeholders: already back on the free list)."""
        return [
            {p for s in slots if s is not None for p in s.pages[k] if p >= 0}
            for k in range(len(self.kinds))
        ]

    def ledger(self, slots) -> tp.List[tp.Dict[str, tp.Any]]:
        """Per kind, the terms of the conservation law: pages `free`, held by
        the `trie` (the first kind's, where there is a prefix cache),
        `live_only` (held by a slot and not by the trie) and `allocatable`
        (`num_pages - 1`: page 0 is the sink). A state kind's entry counts
        ROWS: `free` by this pool's books, `live_only` by what the slots say
        they hold, `allocatable` the slot count (the sink row apart)."""
        pc = self.prefix_cache
        out = []
        for k, (kind, a, live) in enumerate(zip(self.kinds, self.allocators, self.live_pages(slots))):
            held = pc.pages_held() if pc is not None and k == 0 else set()
            out.append({
                "kind": kind.name, "free": a.free_count, "trie": len(held),
                "live_only": len(live - held), "allocatable": a.num_pages - 1,
            })
        rows = {s.state_row for s in slots if s is not None and s.state_row >= 0} if self.state_kinds else ()
        for kind in self.state_kinds:
            out.append({
                "kind": kind.name, "free": self.state_held.count(False), "trie": 0,
                "live_only": len(rows), "allocatable": self.max_slots,
            })
        return out

    def conserved(self, slots) -> bool:
        """THE conservation law, of every kind: free + trie-held + live-only
        == num_pages - 1 (a state kind: rows free + rows live == the slots).
        It holds between any two calls of the engine."""
        return all(
            t["free"] + t["trie"] + t["live_only"] == t["allocatable"]
            for t in self.ledger(slots)
        )

    def counters(self) -> tp.Dict[str, float]:
        """`kv.<kind>_pages_live` (allocated now; `_pages_live_max`: at the peak)
        and, for a WINDOWED kind only (a kind without a window has no rule that
        frees a page mid-request, so it has no such counter),
        `kv.<kind>_pages_reclaimed` (freed by the window rule so far) and
        `kv.<kind>_tokens_per_slot_max` (the most one slot ever held, in
        tokens: bounded by window + the longest write + a page). With a state
        kind: `state.rows` (the slots'), `state.rows_live` (held now; `_max`:
        at the peak), `state.resets` (admissions that took a row: each is
        reset by the request's first prefill chunk) and
        `state.bytes_per_slot` (one row of every state array, as the arrays
        declare it; what the device's tiling adds is not in an array's size)."""
        out: tp.Dict[str, float] = {}
        for i, (k, a) in enumerate(zip(self.kinds, self.allocators)):
            out[f"kv.{k.name}_pages_live"] = a.num_pages - 1 - a.free_count
            out[f"kv.{k.name}_pages_live_max"] = a.num_pages - 1 - a.free_min
            if k.window:
                out[f"kv.{k.name}_pages_reclaimed"] = self.kind_reclaimed[i]
                out[f"kv.{k.name}_tokens_per_slot_max"] = (
                    self.kind_slot_pages_max[i] * self.page_size
                )
        if self.state_kinds:
            out["state.rows"] = self.max_slots
            out["state.rows_live"] = sum(self.state_held)
            out["state.rows_live_max"] = self.state_rows_live_max
            out["state.resets"] = self.state_resets
            out["state.bytes_per_slot"] = sum(a.nbytes // a.shape[1] for a in self.cache.state)
        return out

    def hbm_bytes(self) -> int:
        """Total device bytes of the target pool — K/V pages plus, in int8
        mode, the f32 scale side buffers (the honest spend a byte budget
        must be judged against)."""
        return sum(a.nbytes for a in jax.tree.leaves(self.cache))

    def hbm_bytes_per_shard(self) -> int:
        """Per-DEVICE bytes of the target pool. Every pool leaf (K/V pages
        and int8 scale side buffers) shards its head axis over 'tp' and
        replicates elsewhere, so a tp shard holds exactly total/tp — the
        number a per-chip HBM budget must be judged against: slot capacity
        per chip grows with the mesh (tests/test_tp_serving.py)."""
        n_tp = 1 if self.mesh is None else int(self.mesh.shape["tp"])
        return self.hbm_bytes() // n_tp

    # -- page tables -----------------------------------------------------

    def bucket(self, max_tokens: int) -> int:
        """Smallest power-of-two page count covering `max_tokens` positions.

        The serve step's attention (and its CPU gather fallback) is
        O(table_width x page_size) per slot; slicing the table to a bucket
        makes it O(longest-active-request) instead of O(block_size) — the
        used-length attention lever — while the pow2 bucketing keeps the
        compile set logarithmic, not per-length."""
        return min(pow2_bucket(-(-max_tokens // self.page_size)), self.max_pages_per_slot)

    def table(self, slots, n_pages: int, kind: int = 0) -> np.ndarray:
        """The (slots, n_pages) int32 page table of `kind`: row i is slot i's
        pages, zeros (the sink page) past them and for an empty slot."""
        table = np.zeros((len(slots), n_pages), np.int32)
        for i, s in enumerate(slots):
            if s is not None:
                pages = s.pages[kind][: table.shape[1]]
                table[i, : len(pages)] = pages
        # Window-reclaimed entries (-1 in slot.pages) park on the sink page:
        # the kernel sweep skips them and the mask hides their columns, but
        # the BlockSpec index map still needs a valid physical page.
        np.maximum(table, 0, out=table)
        return table

    def tables(self, slots, n_pages: int, rows: tp.Optional[tp.Sequence[int]] = None):
        """The round's page table as the serving programs take it, in numpy
        (its transfer rides the program's call, as every argument of a round
        does): the (slots, n_pages) table of the first kind, or, where the
        family has several kinds, the tuple of every kind's. `rows`: those
        slots' rows alone, in that order, then empty rows (the sink page) up
        to `prefill_width`. Where the family has a state kind the tuple ends
        with each table row's STATE ROW, (rows,) int32: the slot's, or the
        sink row (`max_slots`) for an empty slot or place."""
        def table(kind: int) -> np.ndarray:
            full = self.table(slots, n_pages, kind)
            if rows is None:
                return full
            picked = np.zeros((self.prefill_width, full.shape[1]), np.int32)
            picked[: len(rows)] = full[list(rows)]
            return picked

        if len(self.kinds) == 1 and not self.state_kinds:
            return table(0)
        tables = tuple(table(k) for k in range(len(self.kinds)))
        if not self.state_kinds:
            return tables
        picked = list(range(len(slots))) if rows is None else list(rows)
        state_rows = np.full((len(tables[0]),), self.max_slots, np.int32)
        for r, i in enumerate(picked):
            if slots[i] is not None and slots[i].state_row >= 0:
                state_rows[r] = slots[i].state_row
                # a decode round's table is the slots in order, and its program updates rows [0, slots) where they
                # lie without reading this vector (models/olmo_hybrid.py `decode_step_paged`): hold the rows to it
                if rows is None and state_rows[r] != r:
                    raise RuntimeError(f"slot {i} holds state row {state_rows[r]}: a decode round takes slot i's state from row i")
        return (*tables, state_rows)

    # -- whole-pool operations (one kind of page) --------------------------

    def poison(self, page: int) -> None:
        """Corrupt physical page `page` in place (NaN for float pools,
        saturated 127 for int8): the `poisoned_page` fault's HBM damage."""
        cache = self.cache
        bad = float("nan") if jnp.issubdtype(cache.k.dtype, jnp.floating) else 127
        self.cache = dataclasses.replace(
            cache,
            k=cache.k.at[:, :, page].set(bad),
            v=cache.v.at[:, :, page].set(bad),
        )

    def migrate(self, num_pages: int, old_ids: tp.List[int]) -> tp.Dict[int, int]:
        """Replace the pool by a fresh one of `num_pages` holding the content
        of resident pages `old_ids` (the draft pool alike; int8 scales travel
        with their pages), with a fresh allocator; returns {old physical page:
        new}. The caller (ops.resize_pool) has checked that they fit, and
        remaps the slots' lists and the trie."""
        allocator = PageAllocator(num_pages)
        new_ids: tp.List[int] = []
        if old_ids:
            got = allocator.alloc(len(old_ids))
            assert got is not None  # the caller checked len(old_ids) <= num_pages - 1
            new_ids.extend(got)

        def moved(cache, config):
            fresh = self._fresh(config, num_pages)
            if not old_ids:
                return fresh
            return adopt_pages(self.mesh, fresh, new_ids, take_pages(cache, old_ids))

        self.cache = moved(self.cache, self.config)
        if self.draft_cache is not None:
            self.draft_cache = moved(self.draft_cache, self.draft_config)
        self.allocators[0] = allocator
        return dict(zip(old_ids, new_ids))
