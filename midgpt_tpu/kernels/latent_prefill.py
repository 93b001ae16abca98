"""A prefill chunk's attention over a slot's cached LATENTS as one Pallas kernel.

The families served from a LATENT paged cache prefill in the EXPANDED form (as
models/pangu_ultra.py `_prefill_sweep` spells it in XLA): a block of cached
rows [c; k_r] is multiplied out to K and V of every head (K_h = c W_uk,h with
the one rotated key appended, V_h = c W_uv,h), the chunk's T rows score it,
and an online softmax sums the values. As an XLA
loop that is HBM-bound on its own temporaries: the (H, T, block) float32 scores
are written, read twice and written again as probabilities, ~0.7 GB a block
of 1,024 keys at 128 heads beside 77 GFLOP (PERF.md section 6 PR 60). Here a
block is expanded, scored, masked and summed IN VMEM: no score, probability or
expanded K / V tile reaches HBM.

  grid (head groups, key blocks), key blocks innermost. A step holds one block
  of `key_block` cached rows (an ordinary BlockSpec over ONE contiguous copy of
  the slot's rows in position order, which the caller gathers through the page
  table before the call: `slot_rows`; the pool itself is never copied or relaid
  out), the group's `head_group` slices of W_kvb and of the chunk's queries,
  and per head: kv = c W_kvb,h^T (block, nope + v), s = q_h [k_n; k_r]^T (T,
  block) float32, the mask, ops/online_softmax.online_block, p v. The running
  (m, l, acc) of the group's heads live in VMEM scratch across its sweep and
  are finalised in the kernel; the output is (T, H * v), a head's columns side
  by side as the XLA sweep gives them.

  WHICH KEYS A ROW SEES arrives as an operand, `keep` (T, S) int8: the caller
  folds whatever decides it (a learned selection, the causal bound, pad rows)
  into one byte a pair. The kernel takes NO per-row scalar: 512 stacked scalar
  counts are refused by Mosaic ("Input offsets outside of the first tile",
  PERF.md section 6 PR 59). Without `keep` the kernel forms a causal chunk's
  visibility itself from two prefetched scalars: row t of a chunk that begins
  at `start` with `n_valid` real rows keeps col <= min(start + t, start +
  n_valid - 1), an iota compare (pad rows see what the last valid row sees).

  Only the LIVE BLOCK COUNT bounds the sweep: ceil(n_keys / key_block), n_keys
  the keys the last row sees, rides scalar prefetch. A step past it computes
  nothing, and its index maps clamp to the last live block, so nothing is
  fetched for it either (it still costs its grid step, ~0.35 us).

  VMEM: inside the DEFAULT scoped limit, on purpose. A larger limit in one
  Mosaic call moves every other fusion's scoped region in the program that
  holds it (PERF.md section 6 PR 50: -4 % in a cell whose time was elsewhere).
  At T 512, 2 heads a group, 1,024 keys a block, bf16: queries 2 x 0.5 MiB,
  weights 2 x 0.5 MiB, rows 2 x 1.25 MiB, mask 2 x 0.5 MiB, output 2 x 0.25
  MiB, accumulator 0.5 MiB, statistics 1 MiB, and the body's one live score
  tile (2 MiB float32) with its probabilities and the block's expanded K and V
  of one head. Measured on the v5e (PERF.md section 6 PR 60): 0.55 ms a block
  of 1,024 keys at 128 heads; 512 keys and 4 heads read 0.65 (the products'
  streamed side is half as long), 512 and 2 0.79, 256 and 8 1.08; 1,024 and 4
  or 512 and 8 do not fit.

No `pallas_call(name=...)` and no named scope around the call: the benchmark
finds the kernel by the name its caller's scope gives it. Not a spec of
kernels/attention_template.py: that body scores pool rows AS STORED against rows
folded from heads and copies pages by hand; this one expands a block per head
and takes a query-row tile and a mask tile. A sibling file leaves the text of
the decode kernel that every serving cell runs as it is.
"""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from midgpt_tpu.kernels.flash_attention import _STATS_LANES, _interpret
from midgpt_tpu.ops.online_softmax import M_INIT, MASK, finalize, online_block

Array = jax.Array

KEY_BLOCK = 1024  # cached rows a grid step (module docstring, "VMEM")
HEAD_GROUP = 2  # heads a grid step


def slot_rows(pool: Array, li: int, table_row: Array, n_keys: Array) -> Array:
    """ONE contiguous copy of a slot's cached rows in position order, (MP * ps,
    lanes): layer `li` of `pool` (L, 1, P, ps, lanes) through the slot's page
    table `table_row` (MP,). NEVER-DEREFERENCE RULE: an entry is read only for
    a page that holds one of the `n_keys` visible keys; past them an entry may
    hold anything and page 0 (the sink, finite) is read in its place."""
    MP, ps = table_row.shape[0], pool.shape[3]
    live = jnp.arange(MP, dtype=jnp.int32) * ps < n_keys
    return pool[li, 0, jnp.where(live, table_row, 0)].reshape(MP * ps, pool.shape[-1])


def _kernel(
    sc_ref,  # (3,) int32 scalar prefetch: [live key blocks, start, n_valid]
    q_ref,  # (G, T, nope + rope lanes)
    lat_ref,  # (blk, r + rope lanes)
    w_ref,  # (G, nope + v, r): a head's rows [W_uk; W_uv]
    *rest,  # keep_ref (T, blk) int8 where the caller masks; o_ref (T, G * v); acc_sc (G, T, v), m_sc, l_sc (G, T, 8) f32
    scale: float,
    nope: int,
    r: int,
    masked: bool,
):
    keep_ref = rest[0] if masked else None
    o_ref, acc_sc, m_sc, l_sc = rest[-4:]
    kb = pl.program_id(1)
    G, T, v = acc_sc.shape
    blk = lat_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        acc_sc[:] = jnp.zeros(acc_sc.shape, acc_sc.dtype)
        m_sc[:] = jnp.full(m_sc.shape, M_INIT, m_sc.dtype)
        l_sc[:] = jnp.zeros(l_sc.shape, l_sc.dtype)

    @pl.when(kb < sc_ref[0])
    def _compute():
        if masked:
            keep = keep_ref[...].astype(jnp.int32) != 0
        else:
            col = kb * blk + jax.lax.broadcasted_iota(jnp.int32, (T, blk), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (T, blk), 0)
            keep = col <= jnp.minimum(sc_ref[1] + row, sc_ref[1] + sc_ref[2] - 1)
        for h in range(G):  # operands are read from their refs where a product uses them (flash_attention.py, PR 40)
            kv = jax.lax.dot_general(
                lat_ref[:, :r], w_ref[h], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ).astype(q_ref.dtype)  # (blk, nope + v): the block's K_n and V of head h
            k = jnp.concatenate([kv[:, :nope], lat_ref[:, r:]], axis=1)  # the ONE rotated key appended
            s = jax.lax.dot_general(
                q_ref[h], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (T, blk) f32
            s = jnp.where(keep, s, MASK)
            m_new, alpha, p, l_new = online_block(m_sc[h][:, 0], l_sc[h][:, 0], s)
            pv = jax.lax.dot_general(
                p.astype(kv.dtype), kv[:, nope:], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            acc_sc[h] = acc_sc[h] * alpha[:, None] + pv
            m_sc[h] = jnp.broadcast_to(m_new[:, None], m_sc.shape[1:])
            l_sc[h] = jnp.broadcast_to(l_new[:, None], l_sc.shape[1:])

    @pl.when(kb == pl.num_programs(1) - 1)
    def _emit():
        for h in range(G):
            out, _ = finalize(m_sc[h][:, 0], l_sc[h][:, 0], acc_sc[h], dtype=o_ref.dtype)
            o_ref[:, h * v:(h + 1) * v] = out


def latent_prefill_attention(
    q: Array,  # (T, H, nope + rope): a chunk's query rows
    lat: Array,  # (S, >= r + rope): the slot's cached rows in position order (`slot_rows`), lanes past r + rope zero
    w_kvb: Array,  # (H, nope + v, r): a head's rows [W_uk; W_uv]
    n_keys: Array,  # () int: keys the LAST row sees; blocks past them are not swept
    keep: tp.Optional[Array] = None,  # (T, S) bool / int8: which keys each row attends to
    start: tp.Optional[Array] = None,  # without `keep`: () int, the chunk's first position ...
    n_valid: tp.Optional[Array] = None,  # ... and its real rows: the causal chunk's visibility, formed in the kernel
    *,
    nope: int,
    scale: float,
) -> Array:
    """softmax over the kept keys of (q_h [k_n,h; k_r]^T * scale) times V_h,
    K and V expanded from `lat` a block at a time in VMEM (module docstring).
    -> (T, H, v) in q.dtype. A row that keeps no key gives 0."""
    if keep is None and (start is None or n_valid is None):
        raise ValueError("latent_prefill_attention: without `keep` the chunk's `start` and `n_valid` decide what a row sees")
    return _call(q, lat, w_kvb, n_keys, keep, start, n_valid, nope=nope, scale=scale,
                 key_block=KEY_BLOCK, head_group=HEAD_GROUP)


# jit(inline=True) for what it is in attention_template.py: a program's full
# layers trace the wrapper and the kernel body once, and the call adds no
# equation and no scope of its own.
@functools.partial(jax.jit, static_argnames=("nope", "scale", "key_block", "head_group"), inline=True)
def _call(q, lat, w_kvb, n_keys, keep, start, n_valid, *, nope, scale, key_block, head_group):
    T, H, qk = q.shape
    r, rope = w_kvb.shape[2], qk - nope
    v = w_kvb.shape[1] - nope
    rope_l = -(-rope // 128) * 128  # the rotary part as whole 128-lane rows on both sides of its product
    S = lat.shape[0]
    blk = min(key_block, S)
    G = max(g for g in range(1, min(head_group, H) + 1) if H % g == 0)
    if lat.shape[1] != r + rope_l:  # a pool off the kernel layout (tests): the gathered copy is cut and padded, not the pool
        lat = jnp.pad(lat[:, :r + rope], ((0, 0), (0, rope_l - rope)))
    lat = jnp.pad(lat.astype(q.dtype), ((0, -S % blk), (0, 0)))
    q = jnp.pad(jnp.transpose(q, (1, 0, 2)), ((0, 0), (0, 0), (0, rope_l - rope)))  # (H, T, nope + rope_l)
    nb = lat.shape[0] // blk
    live = jnp.clip((n_keys + blk - 1) // blk, 1, nb).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    scalars = jnp.stack([live, zero if start is None else start.astype(jnp.int32),
                         zero if n_valid is None else n_valid.astype(jnp.int32)])
    block = lambda kb, sc: jnp.minimum(kb, sc[0] - 1)  # a dead step names the last live block: nothing is fetched
    in_specs = [
        pl.BlockSpec((G, T, nope + rope_l), lambda g, kb, sc: (g, 0, 0)),
        pl.BlockSpec((blk, r + rope_l), lambda g, kb, sc: (block(kb, sc), 0)),
        pl.BlockSpec((G, nope + v, r), lambda g, kb, sc: (g, 0, 0)),
    ]
    operands = [q, lat, w_kvb.astype(q.dtype)]
    if keep is not None:
        in_specs.append(pl.BlockSpec((T, blk), lambda g, kb, sc: (0, block(kb, sc))))
        operands.append(jnp.pad(keep.astype(jnp.int8), ((0, 0), (0, -S % blk))))  # graftcheck: disable=GC008 — a mask of 0 / 1, integral already
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nope=nope, r=r, masked=keep is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // G, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((T, G * v), lambda g, kb, sc: (0, g)),
            scratch_shapes=[
                pltpu.VMEM((G, T, v), jnp.float32),
                pltpu.VMEM((G, T, _STATS_LANES), jnp.float32),
                pltpu.VMEM((G, T, _STATS_LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H * v), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(scalars, *operands)
    return out.reshape(T, H, v)
