"""Routed experts with real dispatch, for a chip that holds SOME of the experts.

`route` scores every token against all `n_experts` (the router keeps its
published width whatever is held here) and picks `top_k`. `moe_experts`
computes the part of the layer's result that the experts HELD HERE give:
experts `[offset, offset + n_held)`, `n_held` = the leading axis of the expert
weights. A token-expert pair whose expert lives on another chip is not
computed and adds nothing: on one chip of an expert-parallel job that is the
chip's share of the sum, and the exchange that would add the other shares is
no part of this op.

Two callers, one idea (the pairs sorted by held expert, each expert's run
padded to whole tiles, so a tile belongs to ONE expert), two layouts. Training
(`moe_experts`, differentiable, thousands of rows): the tiles in a buffer of a
size the mean load sets, batched matmuls over each tile's GATHERED expert
weights, an exact path behind it for the call that overflows. Serving
(`moe_experts_serving`, forward only, a prefill chunk's or a decode step's
rows): the rows in a buffer that takes the most padding can need (nothing
overflows, no exact path), and ONE Pallas call over its row blocks that reads
each block's expert IN PLACE, the next block's in flight meanwhile
(`kernels/grouped_matmul.py`). Why two: at serving row counts the gathered
weights ARE the cost (v5e, 16 held experts of 3 x 2,048 x 4,096, 512 / 32 rows:
buffer 10.06 / 5.52 ms, every held expert over every row 2.54 / 1.12 ms, a loop
over the tiles in use 1.54 / 0.63 ms; PERF.md §6 PR 30), a serving step's cost
is the expert matrices it must stream once (PERF.md §6 PR 50: the loop read
them at 42 % of the chip's rate, the grouped matmul at twice that), and the
kernel has no transpose.

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


def moe_row_block(n_tokens: int, top_k: int, n_experts: int, itemsize: int) -> int:
    """Rows a block of `moe_experts_serving`: the power of two at or over FOUR
    times the mean pairs an expert gets, from the rows one vector register
    holds at this itemsize (8 of float32, 16 of bf16) to the 256 that fill the
    MXU. A block of few rows costs its expert's three matrices read once
    whatever it holds, so the cheaper block is one that takes a whole run, the
    most loaded expert's too (2.4 to 2.9 times the mean in the serving cells),
    and not the mean run. Derived from the call's shapes, not configured."""
    block = max(8, 32 // itemsize)
    while block < min(256, 4 * n_tokens * top_k / n_experts):
        block *= 2
    return block


def moe_prefill_rows(dense_rows: int, top_k: int, n_experts: int) -> int:
    """Token rows a BATCHED prefill call should bring a routed layer, where a
    dense weight wants `dense_rows` (the chip's ridge: sampling/serve.py
    `PREFILL_ROWS`, which is also the largest row block of `moe_row_block`).
    An expert multiplies `top_k / n_experts` of a call's rows, and its block
    costs the expert's three matrices streamed once whatever it holds; a run
    that passes the block starts a second one, which streams the expert again
    (`kernels/grouped_matmul.py`: F is sliced). The most loaded expert's run is
    2.4 to 2.9 times the mean, so the rows asked for are those that fill the
    block to HALF on average: `dense_rows / 2` pairs an expert (128: one pass
    of the v5e's 128-row MXU). Trinity: 128 * 128 / 8 = 2,048 token rows."""
    return dense_rows // 2 * n_experts // top_k


def moe_serving_plan(
    idx: Array, weights: Array, *, offset: int, n_held: int, block_rows: int,
) -> tp.Dict[str, Array]:
    """Where every pair of a serving call goes in a buffer of rows SORTED BY
    HELD EXPERT, each expert's run padded to whole blocks of `block_rows` (a
    block belongs to ONE expert), the blocks in use first. The buffer has
    P = round_up(N * k, block_rows) + n_held * block_rows rows: the most that
    padding can need whatever `idx` is, so nothing overflows. Static shapes, no
    loop, no sort (a pair's row is its expert's first row + its rank within the
    expert, the tokens before it that picked the expert), and the one scatter
    is of N * k scalars to rows no two pairs share. idx, weights (N, k) from
    `route`. Returns
      counts (n_held,)      pairs assigned to each held expert
      row (N, k)            each pair's row; P, past the buffer, for a pair not held here
      src (P,)              each row's token; N (no token: a zero row) on padding
      ws (P,) f32           each row's pair weight; 0 on padding
      block_expert (P / block_rows,)  each block's expert; the blocks past those
                            in use REPEAT the last used one (they fetch nothing new)
      blocks_used ()        blocks in use = sum over held experts of ceil(pairs / block_rows)
    """
    N, k = idx.shape
    P = (-(-N * k // block_rows) + n_held) * block_rows
    local = idx - offset
    onehot = (local[..., None] == jnp.arange(n_held)) & ((local >= 0) & (local < n_held))[..., None]  # (N, k, n_held)
    per_token = jnp.sum(onehot, axis=1, dtype=jnp.int32)  # (N, n_held) 0/1: an expert is picked once a token
    counts = jnp.sum(per_token, axis=0)
    blocks_e = -(-counts // block_rows)
    ends = jnp.cumsum(blocks_e)
    # a pair's row = its expert's first row + the earlier tokens that picked the expert (one-hot: no gather)
    at = (ends - blocks_e) * block_rows + jnp.cumsum(per_token, axis=0) - per_token  # (N, n_held)
    row = jnp.where(jnp.any(onehot, axis=-1), jnp.sum(jnp.where(onehot, at[:, None, :], 0), axis=-1), P)
    # a row's pair: scattered to rows that are all different (a pair not held here: its own row past the buffer, dropped)
    to = jnp.where(row < P, row, P + jnp.arange(N * k, dtype=jnp.int32).reshape(N, k)).reshape(-1)
    put = lambda init, vals: init.at[to].set(vals.reshape(-1), mode="drop", unique_indices=True)
    src = put(jnp.full((P,), N, jnp.int32), jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, k)))
    ws = put(jnp.zeros((P,), jnp.float32), weights.astype(jnp.float32))
    blocks = jnp.minimum(jnp.arange(P // block_rows, dtype=jnp.int32), ends[-1] - 1)  # past those in use: the last used
    block_expert = jnp.minimum(jnp.sum(ends <= blocks[:, None], axis=1, dtype=jnp.int32), n_held - 1)
    return {"counts": counts, "row": row, "src": src, "ws": ws, "block_expert": block_expert, "blocks_used": ends[-1]}


def moe_experts_serving(
    x: Array, idx: Array, weights: Array,
    w_gate: Array, w_up: Array, w_down: Array, *, offset: int, block_rows: int,
) -> tp.Tuple[Array, tp.Dict[str, Array]]:
    """`moe_experts`' result for a FORWARD-ONLY call of few rows (a prefill
    chunk, a decode step's slots), in three parts with static shapes: the plan
    (`moe_serving_plan`) and ONE gather of the tokens into rows sorted by
    expert; ONE Pallas call over the row blocks (`kernels/grouped_matmul.py`:
    the next block's expert is in flight while this block is multiplied, and an
    expert whose run is one block is streamed once a call); each token's k rows
    gathered back and summed in float32 (a pair not held here reads zeros: no
    scatter-add). The buffer takes the most that padding can need, so nothing
    overflows and no exact path exists; work is the held experts touched, not
    the held experts. `dropped` counts assigned pairs that were not computed:
    0 by construction, counted not assumed; `visits` the row blocks in use.
    Not differentiable: training takes `moe_experts`."""
    from midgpt_tpu.kernels.grouped_matmul import grouped_swiglu

    N = x.shape[0]
    with jax.named_scope("moe_route"):
        plan = moe_serving_plan(idx, weights, offset=offset, n_held=w_gate.shape[0], block_rows=block_rows)
        xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        xs = jnp.take(xz, plan["src"], axis=0, mode="clip")  # (P, D); no token: the zero row
    with jax.named_scope("moe_experts"):
        ys = grouped_swiglu(xs, plan["ws"], plan["block_expert"], plan["blocks_used"], w_gate, w_up, w_down,
                            block_rows=block_rows)
    with jax.named_scope("moe_route"):
        y = jnp.sum(ys.at[plan["row"]].get(mode="fill", fill_value=0), axis=1)  # (N, k, D) f32 -> (N, D)
        computed = jnp.sum(plan["src"] < N, dtype=plan["counts"].dtype)  # rows that hold a pair: counted
    return y.astype(x.dtype), {"counts": plan["counts"], "dropped": jnp.sum(plan["counts"]) - computed,
                               "visits": plan["blocks_used"]}


# ---------------------------------------------------------------------------
# What every SERVED family with routed experts shares (models/mimo_v2.py,
# models/pangu_ultra.py): the serving call and the expert layers' counters,
# which ride the family's cache as two small device arrays.
# ---------------------------------------------------------------------------


def moe_serving(
    x: Array, router: Array, bias: Array, w_gate: Array, w_up: Array, w_down: Array,
    *, top_k: int, scale: float, renormalize: bool, offset: int, valid: tp.Optional[Array] = None,
) -> tp.Tuple[Array, Array, tp.Dict[str, Array]]:
    """x (N, D) -> (the held experts' part of the routed layer (N, D), idx (N,
    k), stats): `route` under the `moe_route` scope, then `moe_experts_serving`
    at the row block the call's shapes give (`moe_row_block`). The router keeps
    its published width (`router`'s leading axis) whatever is held here. Every
    device op of it lies under `moe_route` or `moe_experts`, which are ONE cost
    (the benchmark divides the expert bytes a step must read by their sum).

    `valid` (N,) bool: the rows that are tokens (a batched prefill call's rows
    past `n_valid` and its empty places are not). A row that is not valid is
    routed NOWHERE: its `idx` comes back -1, which no chip holds, so the plan
    gives its pairs no row of the buffer (they read zeros back: its y is 0),
    they are in no expert's count and no block is in use for them. None: every
    row is a token, and the lowering is the one without the argument."""
    with jax.named_scope("moe_route"):
        idx, w = route(x, router, bias, top_k=top_k, scale=scale, renormalize=renormalize)
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, -1)
    y, stats = moe_experts_serving(
        x, idx, w, w_gate, w_up, w_down, offset=offset,
        block_rows=moe_row_block(x.shape[0], top_k, router.shape[0], w_gate.dtype.itemsize))
    return y, idx, stats


def moe_counters_init(n_moe_layers: int, n_held: int) -> tp.Tuple[Array, Array]:
    """(counts (moe layers, n_held) int32: pairs of active slots' decode steps;
    totals (4,) int32: decode steps, held experts touched (summed over steps and
    layers), dropped, row blocks in use (summed the same way)), zeroed."""
    return jnp.zeros((n_moe_layers, n_held), jnp.int32), jnp.zeros((4,), jnp.int32)


def moe_count_decode(
    counts: Array, totals: Array, layer: int, idx: Array, active: Array, stats: tp.Dict[str, Array], *, offset: int,
) -> tp.Tuple[Array, Array]:
    """One routed layer of one decode step into the counters: `idx` (B, k) of
    the step's slots, of which only the `active` ones count; `stats` what
    `moe_serving` gave for the layer (its row blocks cover every slot's row)."""
    local = idx - offset  # (B, k); the active slots' pairs, by held expert
    here = jnp.sum((local[..., None] == jnp.arange(counts.shape[1])) & active[:, None, None],
                   axis=(0, 1), dtype=jnp.int32)
    counts = counts.at[layer].add(here)
    totals = totals + jnp.stack([jnp.zeros((), jnp.int32), jnp.sum(here > 0, dtype=jnp.int32),
                                 stats["dropped"].astype(jnp.int32), stats["visits"].astype(jnp.int32)])
    return counts, totals


def moe_count_dropped(totals: Array, dropped: Array) -> Array:
    """A prefill chunk's routed layer: only what it dropped is counted."""
    z = jnp.zeros((), jnp.int32)
    return totals + jnp.stack([z, z, dropped.astype(jnp.int32), z])


def moe_serve_counters(counts: Array, totals: Array) -> tp.Dict[str, float]:
    """The expert layers' counters since the cache was made (a device read:
    not for the serving loop). Decode steps of active slots only."""
    counts = jax.device_get(counts).astype(float)
    steps, touched, dropped, visits = (int(v) for v in jax.device_get(totals))
    n_moe = max(1, counts.shape[0])
    load = counts.max(axis=-1) / counts.mean(axis=-1).clip(1e-9) if counts.size else counts.sum(axis=-1)
    return {
        "moe.decode_steps": steps,
        "moe.pairs_here": counts.sum() / max(1, steps) / n_moe,  # a decode step a layer
        "moe.experts_touched": touched / max(1, steps) / n_moe,  # held experts with a pair, a step a layer
        "moe.expert_visits": visits / max(1, steps) / n_moe,  # row blocks in use, a step a layer: each streams one expert
        "moe.load_max_over_mean": float(load.max()) if load.size else 0.0,  # worst layer, over the run
        "moe.dropped": dropped,
    }
