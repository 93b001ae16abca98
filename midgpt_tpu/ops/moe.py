"""Routed experts with real dispatch, for a chip that holds SOME of the experts.

`route` scores every token against all `n_experts` (the router keeps its
published width whatever is held here) and picks `top_k`. `moe_experts`
computes the part of the layer's result that the experts HELD HERE give:
experts `[offset, offset + n_held)`, `n_held` = the leading axis of the expert
weights. A token-expert pair whose expert lives on another chip is not
computed and adds nothing: on one chip of an expert-parallel job that is the
chip's share of the sum, and the exchange that would add the other shares is
no part of this op.

Two callers, one layout of tiles (a tile = up to `tile` pairs of ONE held
expert). Training (`moe_experts`, differentiable, thousands of rows): the tiles
in a buffer of a fixed size, batched matmuls over each tile's gathered expert
weights, an exact path behind it. Serving (`moe_experts_serving`, forward only,
a prefill chunk's or a decode step's rows): a loop over the tiles in use that
reads each tile's expert in place. Why two: at serving row counts the gathered
weights ARE the cost (v5e, 16 held experts of 3 x 2,048 x 4,096, 512 / 32 rows:
buffer 10.06 / 5.52 ms, every held expert over every row 2.54 / 1.12 ms, the
loop 1.54 / 0.63 ms; PERF.md §6 PR 30), and a loop whose trip count is data has
no transpose.

`moe_experts`' dispatch has static shapes and drops nothing:

  fast path   the held experts share ONE buffer of `n_tiles` tiles of `tile`
              rows. The pairs are laid out sorted by expert, each expert's run
              padded to whole tiles, so a tile belongs to one expert: tile t's
              expert comes from the cumulative tile counts, and row r of it is
              the token whose pair is the (j * tile + r + 1)-th of that expert
              (a binary search in the expert's cumulative count over the
              tokens; no sort, no scatter). Gather the tokens into (n_tiles,
              tile, D) and each tile's expert weights, three batched matmuls
              (SwiGLU), scatter-add the weighted rows back. Work is n_tiles *
              tile rows WHATEVER the skew between the experts: one expert
              taking every pair costs what a balanced router costs, as long
              as the pairs routed here fit the buffer.
  exact path  if the tiles needed (sum over the held experts of ceil(pairs /
              tile)) exceed `n_tiles`, `lax.cond` takes the other branch:
              every held expert over all the tokens (in blocks of tokens),
              masked by the pair weights. Work is n_held * N rows: slower,
              never wrong. Which branch ran is in the output (`overflowed`),
              and `dropped` counts assigned pairs that were not computed: 0 by
              construction, counted not assumed.

`moe_capacity` sizes the buffer as a static multiple (`capacity_factor`) of the
mean number of pairs routed here, plus one tile of padding an expert.
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp

Array = jax.Array
EXACT_TOKEN_BLOCK = 2048  # tokens the exact path computes at once, over every held expert


def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """W_down(SiLU(W_gate x) * W_up x); weights (out, in) like every linear here."""
    h = jax.nn.silu(jnp.einsum("...d,fd->...f", x, w_gate)) * jnp.einsum("...d,fd->...f", x, w_up)
    return jnp.einsum("...f,df->...d", h, w_down)


def route(
    x: Array, w_router: Array, bias: Array, *, top_k: int, scale: float, renormalize: bool = True
) -> tp.Tuple[Array, Array]:
    """Sigmoid router. x (N, D), w_router (E, D), bias (E,). Scores and the
    selection are float32 whatever x is: a near tie decided in bf16 picks
    another expert. The `top_k` largest of `s + bias` are selected; the
    weights are the selected `s` WITHOUT the bias, divided by their sum if
    `renormalize`, times `scale`. Returns (idx (N, k) int32, weights (N, k) f32)."""
    s = jax.nn.sigmoid(jnp.einsum(
        "nd,ed->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def moe_capacity(
    n_tokens: int, top_k: int, n_experts: int, n_held: int, capacity_factor: float
) -> tp.Tuple[int, int]:
    """(n_tiles, tile) of the dispatch buffer. `tile`: the power of two at or
    under the mean pairs an expert gets, within [8, 256] (256 rows fill the MXU;
    a toy size gets toy tiles). `n_tiles`: `capacity_factor` x the mean pairs
    routed to the experts held here, in tiles, plus one tile an expert for the
    padding of its run."""
    mean_expert = n_tokens * top_k / n_experts
    tile = 8
    while tile * 2 <= min(256, mean_expert):
        tile *= 2
    return int(-(-mean_expert * n_held * capacity_factor // tile)) + n_held, tile


def moe_experts(
    x: Array, idx: Array, weights: Array,
    w_gate: Array, w_up: Array, w_down: Array,
    *, offset: int, n_tiles: int, tile: int,
) -> tp.Tuple[Array, tp.Dict[str, Array]]:
    """The held experts' part of sum_e w_e SwiGLU_e(x). x (N, D); idx, weights
    (N, k) from `route`; w_gate, w_up (n_held, F, D), w_down (n_held, D, F);
    `n_tiles`, `tile` from `moe_capacity`. Returns (y (N, D) in x's dtype,
    {"counts" (n_held,) pairs assigned to each held expert, "dropped" () pairs
    assigned here and not computed, "overflowed" () whether the exact path ran})."""
    N, D = x.shape
    E_h = w_gate.shape[0]
    local = idx - offset  # (N, k); outside [0, E_h): another chip's expert
    held = (local >= 0) & (local < E_h)
    onehot = (local[..., None] == jnp.arange(E_h)) & held[..., None]  # (N, k, E_h)
    per_token = jnp.sum(onehot, axis=1, dtype=jnp.int32)  # (N, E_h) 0/1: an expert is picked once a token
    counts = jnp.sum(per_token, axis=0)
    w_tok = jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)  # (N, E_h) pair weight, 0 if not picked

    tiles_e = -(-counts // tile)  # tiles each held expert's run takes
    ends = jnp.cumsum(tiles_e)

    def fast(_):
        with jax.named_scope("moe_route"):
            t = jnp.arange(n_tiles, dtype=jnp.int32)
            e_t = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)  # tile's expert; E_h: unused
            e_c = jnp.minimum(e_t, E_h - 1)
            j = t - (ends - tiles_e)[e_c]  # tile's place in its expert's run
            # row r of tile t holds the token whose pair is the (j * tile + r +
            # 1)-th of expert e_t: a binary search in that expert's running
            # count. Past the expert's last pair the search gives N: a zero row.
            cum = jnp.cumsum(per_token, axis=0).T  # (E_h, N), non-decreasing
            want = j[:, None] * tile + jnp.arange(1, tile + 1, dtype=jnp.int32)
            tok = jax.vmap(lambda c, w: jnp.searchsorted(c, w, side="left"))(cum[e_c], want).astype(jnp.int32)
            tok = jnp.where((e_t < E_h)[:, None], tok, N)
            xe = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[tok]  # (n_tiles, tile, D)
            we = jnp.concatenate([w_tok, jnp.zeros((1, E_h), w_tok.dtype)])[tok, e_c[:, None]]
        with jax.named_scope("moe_experts"):
            h = jax.nn.silu(jnp.einsum("tcd,tfd->tcf", xe, w_gate[e_c])) * jnp.einsum("tcd,tfd->tcf", xe, w_up[e_c])
            ye = jnp.einsum("tcf,tdf->tcd", h, w_down[e_c])
        with jax.named_scope("moe_route"):
            ye = ye.astype(jnp.float32) * we[..., None]
            y = jnp.zeros((N + 1, D), jnp.float32).at[tok.reshape(-1)].add(ye.reshape(-1, D))[:N]
        return y.astype(x.dtype), jnp.sum(tok < N, dtype=counts.dtype)  # rows that hold a pair: counted

    @jax.checkpoint  # its residuals are its inputs: `lax.cond` keeps BOTH branches' residuals alive
    def exact(_):
        nb = -(-N // EXACT_TOKEN_BLOCK)
        pad = nb * EXACT_TOKEN_BLOCK - N
        xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, EXACT_TOKEN_BLOCK, D)
        wb = jnp.pad(w_tok, ((0, pad), (0, 0))).reshape(nb, EXACT_TOKEN_BLOCK, E_h)

        @jax.checkpoint
        def block(xw):  # every held expert over one block of tokens, masked by the pair weights
            xs, ws = xw
            with jax.named_scope("moe_experts"):
                h = jax.nn.silu(jnp.einsum("nd,efd->enf", xs, w_gate)) * jnp.einsum("nd,efd->enf", xs, w_up)
                out = jnp.einsum("enf,edf->end", h, w_down)
            return jnp.einsum("end,ne->nd", out.astype(jnp.float32), ws)

        y = jax.lax.map(block, (xb, wb)).reshape(nb * EXACT_TOKEN_BLOCK, D)[:N]
        return y.astype(x.dtype), jnp.sum(counts)

    overflowed = ends[-1] > n_tiles
    y, computed = jax.lax.cond(overflowed, exact, fast, None)
    return y, {"counts": counts, "dropped": jnp.sum(counts) - computed, "overflowed": overflowed}


def moe_serving_tile(n_tokens: int, top_k: int, n_experts: int) -> int:
    """Rows a tile of `moe_experts_serving`: the power of two at or over FOUR
    times the mean pairs an expert gets, within [8, 256]. A tile of few rows
    costs its expert's three matrices read once whatever it holds (until 256
    rows fill the MXU), so the cheaper tile is one that takes a whole run, the
    most loaded expert's too (2.4 to 2.9 times the mean in the serving cell),
    and not the mean run. On the v5e at 512 rows (mean 16): tiles of 8 / 16 /
    32 / 64 / 128 rows took 3.22 / 2.16 / 1.66 / 1.54 / 1.78 ms (PERF.md §6 PR
    30). Derived from the row count, not configured."""
    tile = 8
    while tile < min(256, 4 * n_tokens * top_k / n_experts):
        tile *= 2
    return tile


def moe_experts_serving(
    x: Array, idx: Array, weights: Array,
    w_gate: Array, w_up: Array, w_down: Array, *, offset: int, tile: int,
) -> tp.Tuple[Array, tp.Dict[str, Array]]:
    """`moe_experts`' result for a FORWARD-ONLY call of few rows (a prefill
    chunk, a decode step's slots): the same tiles (a tile = up to `tile` pairs
    of ONE held expert, its rows found by the same binary search), walked by a
    loop that runs as many times as there are tiles IN USE, each reading its
    expert's matrices in place (a dynamic slice, no gathered copy) and adding
    its weighted rows into the result. No buffer of a fixed size, so nothing
    overflows and no exact path exists; work is the held experts touched, not
    the held experts. `dropped` counts assigned pairs that were not computed:
    0 by construction, counted not assumed. Not differentiable (the trip count
    is data): training takes `moe_experts`."""
    N, D = x.shape
    E_h = w_gate.shape[0]
    local = idx - offset
    onehot = (local[..., None] == jnp.arange(E_h)) & ((local >= 0) & (local < E_h))[..., None]
    per_token = jnp.sum(onehot, axis=1, dtype=jnp.int32)  # (N, E_h) 0/1
    counts = jnp.sum(per_token, axis=0)
    with jax.named_scope("moe_route"):
        w_tok = jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)  # (N, E_h)
        tiles_e = -(-counts // tile)
        ends = jnp.cumsum(tiles_e)
        cum = jnp.cumsum(per_token, axis=0).T  # (E_h, N), non-decreasing
        xz = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
        wz = jnp.concatenate([w_tok, jnp.zeros((1, E_h), w_tok.dtype)])
        rows = jnp.arange(1, tile + 1, dtype=jnp.int32)

    def one_tile(t, carry):
        y, computed = carry
        with jax.named_scope("moe_route"):
            e = jnp.searchsorted(ends, t, side="right").astype(jnp.int32)  # < E_h: t < ends[-1]
            j = t - (ends[e] - tiles_e[e])  # the tile's place in its expert's run
            # row r holds the token whose pair is the (j * tile + r + 1)-th of expert e; past the last pair: N, a zero row
            tok = jnp.searchsorted(cum[e], j * tile + rows, side="left").astype(jnp.int32)
            xe, we = xz[tok], wz[tok, e]
        with jax.named_scope("moe_experts"):
            ye = swiglu(xe, w_gate[e], w_up[e], w_down[e])
        with jax.named_scope("moe_route"):
            y = y.at[tok].add(ye.astype(jnp.float32) * we[:, None])
        return y, computed + jnp.sum(tok < N, dtype=counts.dtype)

    y, computed = jax.lax.fori_loop(
        0, ends[-1], one_tile, (jnp.zeros((N + 1, D), jnp.float32), jnp.zeros((), counts.dtype)))
    return y[:N].astype(x.dtype), {"counts": counts, "dropped": jnp.sum(counts) - computed}


# ---------------------------------------------------------------------------
# What every SERVED family with routed experts shares (models/mimo_v2.py,
# models/pangu_ultra.py): the serving call and the expert layers' counters,
# which ride the family's cache as two small device arrays.
# ---------------------------------------------------------------------------


def moe_serving(
    x: Array, router: Array, bias: Array, w_gate: Array, w_up: Array, w_down: Array,
    *, top_k: int, scale: float, renormalize: bool, offset: int,
) -> tp.Tuple[Array, Array, tp.Dict[str, Array]]:
    """x (N, D) -> (the held experts' part of the routed layer (N, D), idx (N,
    k), stats): `route` under the `moe_route` scope, then `moe_experts_serving`
    at the tile the row count gives. The router keeps its published width
    (`router`'s leading axis) whatever is held here."""
    with jax.named_scope("moe_route"):
        idx, w = route(x, router, bias, top_k=top_k, scale=scale, renormalize=renormalize)
    y, stats = moe_experts_serving(x, idx, w, w_gate, w_up, w_down, offset=offset,
                                   tile=moe_serving_tile(x.shape[0], top_k, router.shape[0]))
    return y, idx, stats


def moe_counters_init(n_moe_layers: int, n_held: int) -> tp.Tuple[Array, Array]:
    """(counts (moe layers, n_held) int32: pairs of active slots' decode steps;
    totals (3,) int32: decode steps, held experts touched (summed over steps and
    layers), dropped), zeroed."""
    return jnp.zeros((n_moe_layers, n_held), jnp.int32), jnp.zeros((3,), jnp.int32)


def moe_count_decode(
    counts: Array, totals: Array, layer: int, idx: Array, active: Array, dropped: Array, *, offset: int,
) -> tp.Tuple[Array, Array]:
    """One routed layer of one decode step into the counters: `idx` (B, k) of
    the step's slots, of which only the `active` ones count."""
    local = idx - offset  # (B, k); the active slots' pairs, by held expert
    here = jnp.sum((local[..., None] == jnp.arange(counts.shape[1])) & active[:, None, None],
                   axis=(0, 1), dtype=jnp.int32)
    counts = counts.at[layer].add(here)
    totals = totals + jnp.stack([jnp.zeros((), jnp.int32), jnp.sum(here > 0, dtype=jnp.int32),
                                 dropped.astype(jnp.int32)])
    return counts, totals


def moe_count_dropped(totals: Array, dropped: Array) -> Array:
    """A prefill chunk's routed layer: only what it dropped is counted."""
    z = jnp.zeros((), jnp.int32)
    return totals + jnp.stack([z, z, dropped.astype(jnp.int32)])


def moe_serve_counters(counts: Array, totals: Array) -> tp.Dict[str, float]:
    """The expert layers' counters since the cache was made (a device read:
    not for the serving loop). Decode steps of active slots only."""
    counts = jax.device_get(counts).astype(float)
    steps, touched, dropped = (int(v) for v in jax.device_get(totals))
    n_moe = max(1, counts.shape[0])
    load = counts.max(axis=-1) / counts.mean(axis=-1).clip(1e-9) if counts.size else counts.sum(axis=-1)
    return {
        "moe.decode_steps": steps,
        "moe.pairs_here": counts.sum() / max(1, steps) / n_moe,  # a decode step a layer
        "moe.experts_touched": touched / max(1, steps) / n_moe,  # held experts with a pair, a step a layer
        "moe.load_max_over_mean": float(load.max()) if load.size else 0.0,  # worst layer, over the run
        "moe.dropped": dropped,
    }
