"""All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

The second context-parallel schedule next to the ring
(parallel/ring_attention.py), selectable per config (attn_impl='ulysses').
Where the ring keeps queries local and rotates K/V shards n-1 hops around
the 'sp' axis, Ulysses re-shards ONCE: an all-to-all trades the sequence
sharding for a head sharding, every device then runs ordinary dense causal
attention over the FULL sequence for its H/n heads (the same Pallas flash
kernel as the single-device path — no per-pair decomposition at all), and a
second all-to-all restores the sequence sharding.

Trade-offs vs the ring (why both exist):
  * collectives: 2 all-to-alls of the local shard vs 2(n-1) neighbor
    ppermutes — Ulysses wins on latency for moderate n on all-to-all-capable
    interconnects (TPU ICI is), the ring wins on very large n where its
    traffic stays neighbor-only and overlaps with per-pair compute.
  * memory: Ulysses materializes full-T attention inputs for H/n heads
    (activation O(T·H/n·C) = same total as the ring's O(T/n·H·C)); but its
    attention is one dense kernel call, so the kernel's own O(T) statistics
    apply, not O(T/n).
  * constraint: needs n_head divisible by the sp size (whole heads per
    device); the ring has no head constraint.

Differentiation needs no custom VJP: `all_to_all` is its own transpose, and
the inner attention is the already-differentiable dispatcher (custom-VJP
flash kernel on TPU, blockwise jnp elsewhere).

Use `ulysses_attention` inside shard_map; `ulysses_attention_sharded`
applies the shard_map given a mesh (same contract as the ring wrapper,
including `head_axis='tp'` composition — heads then shard over tp x sp).
"""

from __future__ import annotations

import typing as tp

import jax
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from midgpt_tpu.ops.attention import flash_or_blockwise, multihead_attention

Array = jax.Array


def ulysses_attention(
    q: Array,  # (B, H, Tl, C) local sequence shard
    k: Array,
    v: Array,
    axis_name: str,
    block_size: int = 512,
    impl: str = "flash",
) -> Array:
    """Causal attention across the `axis_name` group. Call inside shard_map.

    Shards are contiguous sequence chunks in axis order (what sharding the
    T axis over `axis_name` produces); heads must divide the axis size."""
    n = axis_size(axis_name)
    if n > 1:
        if q.shape[1] % n != 0:
            # ValueError (not assert): direct callers bypass the
            # ExperimentConfig validation, and `python -O` strips asserts —
            # the failure would otherwise surface as an opaque all_to_all
            # shape error.
            raise ValueError(
                f"n_head={q.shape[1]} not divisible by {axis_name} size {n}"
            )
        # trade sequence sharding for head sharding: (B, H/n, T, C)
        q, k, v = (
            jax.lax.all_to_all(a, axis_name, split_axis=1, concat_axis=2, tiled=True)
            for a in (q, k, v)
        )
    # inference=True here only disables dropout inside the dispatcher — and
    # no dropout can ever reach this path: the fused impls define none
    # (ops/attention.py raises NotImplementedError), GPT._attention refuses
    # to inject an attn_fn when training with dropout>0, and config
    # validation rejects attn_impl='ulysses' + dropout up front. Three
    # guards, so this flag is not load-bearing for train/eval semantics.
    out = multihead_attention(
        q, k, v,
        impl=flash_or_blockwise(impl, q.shape[2], block_size),
        inference=True, block_size=block_size, layout="bhtc",
    )
    if n > 1:
        # restore the sequence sharding: (B, H, Tl, C)
        out = jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1, tiled=True)
    return out


def ulysses_attention_sharded(
    q: Array,  # (B, H, T, C) global arrays, T sharded (or shardable) over sp
    k: Array,
    v: Array,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axes: tp.Tuple[str, ...] = ("data", "fsdp"),
    block_size: int = 512,
    head_axis: tp.Optional[str] = None,
    impl: str = "flash",
) -> Array:
    """shard_map wrapper, same contract as ring_attention_sharded: shards T
    over `axis_name` (and heads over `head_axis`, e.g. 'tp'), returns the
    (B, H, T, C) result with the same layout. `impl` selects the inner dense
    attention ('flash' kernel-dispatched; 'blockwise'/'naive' for debug)."""
    spec = P(batch_axes, head_axis, axis_name, None)
    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name, block_size, impl),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
