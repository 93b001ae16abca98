"""Ring attention: causal self-attention over a sequence sharded on a mesh axis.

Makes the mesh's `sp` (sequence-parallel) axis real: each device holds a
contiguous (B, H, T/n, C) shard of Q/K/V; K/V shards rotate around the ring
with `jax.lax.ppermute` while every device merges online-softmax statistics
of its local queries against each visiting K/V shard. After n steps every
query has seen every key once — attention over the full sequence with O(T/n)
activation memory per device and only neighbor-to-neighbor ICI traffic.

This is the long-context scaling story the reference lacks entirely (its
attention materializes the full T x T scores on every device, reference
model.py:71-73, and its sequence axis is never sharded, reference
train.py:105). Design follows the blockwise/ring formulation of Liu et al.
(Ring Attention with Blockwise Transformers), structured TPU-first:

  * The causal structure is decided PER PAIR of shards, not per element:
    with contiguous sequence chunks in ring order, the local (diagonal)
    pair is ordinary causal attention, a visiting shard j < mine is fully
    valid (NO mask — full-attention kernel), and j > mine contributes
    nothing (its statistics are multiplied out at merge time; the compute
    still runs because shapes under `lax.scan` are static). So the per-pair
    compute is served by the SAME Pallas flash kernels as the dense path
    (kernels/flash_attention.py with causal=True/False) — on a real sp>1
    slice the per-pair attention runs at kernel speed, not jnp speed.
  * The whole ring is one `jax.custom_vjp`: forward saves only
    (q, k, v, out, lse) — O(T/n · C) per device. The backward pass is a
    second authored ring pass: dK/dV accumulators rotate WITH the visiting
    K/V shards (n rotations total brings them home), per-pair grads come
    from the flash backward kernels reconstructing p = exp(s − lse_global),
    and dQ accumulates locally. No AD through the scan, so nothing is
    stacked — this is the blockwise-backward of the paper, written down.
  * Per-pair partials merge through log-sum-exp statistics in f32:
    out = Σ_j out_j · exp(lse_j − lse_total), with lse_j = MASK for invalid
    pairs (the same finite-mask trick as the kernels: exp underflows to
    exactly 0, no NaN-scrubbing selects).

Off-TPU the per-pair compute falls back to the equivalent blockwise jnp
online-softmax (`use_kernel=False`, auto-selected; tests force the kernel
path in interpret mode for parity coverage).

Use `ring_attention` inside `shard_map` (it needs a named axis); the
`ring_attention_sharded` wrapper applies the shard_map given a mesh and spec.
Numerics: scores/statistics in float32, matmuls in the input dtype — same
contract as ops/attention.py.
"""

from __future__ import annotations

import functools
import math
import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

import importlib

# the real module (the kernels package re-exports a same-named function)
fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
from midgpt_tpu.ops.attention import flash_block_sizes
from midgpt_tpu.ops.online_softmax import (
    MASK,
    M_INIT,
    finalize,
    merge_normalized,
    online_block,
)

Array = jax.Array


def _auto_use_kernel() -> bool:
    """Kernel per-pair compute on TPU (or when tests force interpret mode)."""
    return jax.default_backend() == "tpu" or fa.RUN_INTERPRET_OFF_TPU


def _kernel_serves(Tl: int, block_size: int) -> bool:
    """True when the flash kernels tile this shard length cleanly. Shard
    lengths the dispatcher's blocks don't divide (e.g. Tl=2560 at the
    default 1024 KV block) fall back to the jnp pair path instead of
    tripping _block_sizes' VMEM bound; the same predicate gates forward and
    backward, so the custom VJP stays consistent."""
    bq, bk = flash_block_sizes(Tl, block_size)
    return Tl % bq == 0 and Tl % bk == 0


def _divisor_block(Tl: int, block_size: int) -> int:
    blk = min(block_size, Tl)
    if Tl % blk:
        blk = max(d for d in range(1, blk + 1) if Tl % d == 0)
    return blk


# (Tl, block_size) pairs already warned about — the fallback is a large,
# silent-by-default perf cliff, so it gets exactly one loud line per shape.
_WARNED: tp.Set[tp.Tuple[int, int]] = set()


def _resolve_pair_plan(
    Tl: int, block_size: int, use_kernel: tp.Optional[bool]
) -> tp.Tuple[bool, int]:
    """Decide (use_kernel, block_size) for this shard length, at trace time.

    When the configured block does not tile Tl, prefer AUTO-ADJUSTING to the
    largest divisor of Tl in [128, block_size] (8-aligned for the kernel's
    sublane tiling) so the per-pair compute stays on the Pallas kernels —
    e.g. Tl=1280 at block 1024 runs at block 640 instead of dropping to jnp.
    Only when no such divisor exists fall back to the jnp pair path, and say
    so ONCE per shape: the fallback preserves correctness but costs kernel
    speed (the whole point of ring v2), which silently looks like 'ring
    attention is slow'. Pure function of its arguments, so the forward and
    backward rings always agree on the plan."""
    if use_kernel is None:
        use_kernel = _auto_use_kernel()
    if not use_kernel:
        return False, block_size
    if _kernel_serves(Tl, block_size):
        return True, block_size
    for d in range(min(block_size, Tl), 127, -1):
        if Tl % d == 0 and d % 8 == 0 and _kernel_serves(Tl, d):
            return True, d
    if (Tl, block_size) not in _WARNED:
        _WARNED.add((Tl, block_size))
        import warnings

        divisors = [d for d in range(8, Tl + 1) if Tl % d == 0 and d % 8 == 0]
        hint = (
            f"e.g. attn_block_size={max(divisors)}"
            if divisors
            else "no 8-aligned divisor exists; change the sequence shard length"
        )
        warnings.warn(
            f"ring attention: shard length {Tl} is not tileable by "
            f"attn_block_size={block_size} and has no kernel-servable "
            f"divisor >= 128 — per-pair compute falls back to the jnp path "
            f"(correct but far slower than the Pallas kernels). Pick a "
            f"block that divides the shard ({hint}).",
            RuntimeWarning,
            stacklevel=3,
        )
    return False, block_size


# ----------------------------------------------------------------------
# per-pair attention: local q against one visiting K/V shard
# ----------------------------------------------------------------------


def _pair_fwd_jnp(
    q: Array, k: Array, v: Array, causal: bool, block_size: int
) -> tp.Tuple[Array, Array]:
    """Blockwise online-softmax pair attention -> (out, lse (B,H,Tl) f32)."""
    B, H, Tl, C = q.shape
    scale = 1.0 / math.sqrt(C)
    blk = _divisor_block(Tl, block_size)
    n_blk = Tl // blk
    rows = jnp.arange(Tl)[:, None]
    cols = jnp.arange(blk)[None, :]

    def kv_block_step(carry, kv_and_col0):
        m, l, acc = carry
        k_blk, v_blk, col0 = kv_and_col0
        s = (
            jnp.einsum("bhqc,bhkc->bhqk", q, k_blk).astype(jnp.float32) * scale
        )
        if causal:
            s = jnp.where(rows >= (col0 + cols), s, MASK)
        m_new, alpha, p, l_new = online_block(m, l, s)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkc->bhqc", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    kb = k.reshape(B, H, n_blk, blk, C).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blk, blk, C).transpose(2, 0, 1, 3, 4)
    col0 = blk * jnp.arange(n_blk)
    # init derived from q (not fresh constants) so the carry's device-varying
    # axes match the body output under shard_map's vma tracking
    zero_q = q.astype(jnp.float32) * 0
    init = (zero_q[..., 0] + M_INIT, zero_q[..., 0], zero_q)
    (m, l, acc), _ = jax.lax.scan(kv_block_step, init, (kb, vb, col0))
    # every row has >= 1 valid key in both pair cases (diagonal: itself)
    out, lse = finalize(m, l, acc, dtype=q.dtype)
    return out, lse


def _pair_bwd_jnp(
    q, k, v, out, do, lse, delta, causal: bool, block_size: int
) -> tp.Tuple[Array, Array, Array]:
    """Pair backward from global statistics: p = exp(s - lse), delta global.

    Blockwise over the visiting shard's KV blocks (bounds scores memory to
    (Tl, blk), matching the forward)."""
    B, H, Tl, C = q.shape
    scale = 1.0 / math.sqrt(C)
    blk = _divisor_block(Tl, block_size)
    n_blk = Tl // blk
    rows = jnp.arange(Tl)[:, None]
    cols = jnp.arange(blk)[None, :]

    def kv_block_step(dq_acc, kv_and_col0):
        k_blk, v_blk, col0 = kv_and_col0
        s = (
            jnp.einsum("bhqc,bhkc->bhqk", q, k_blk).astype(jnp.float32) * scale
        )
        if causal:
            s = jnp.where(rows >= (col0 + cols), s, MASK)
        p = jnp.exp(s - lse[..., None])  # masked entries underflow to 0
        dv_blk = jnp.einsum("bhqk,bhqc->bhkc", p.astype(do.dtype), do)
        dp = jnp.einsum("bhqc,bhkc->bhqk", do, v_blk).astype(jnp.float32)
        ds = (p * (dp - delta[..., None]) * scale).astype(q.dtype)
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkc->bhqc", ds, k_blk).astype(
            jnp.float32
        )
        dk_blk = jnp.einsum("bhqk,bhqc->bhkc", ds, q)
        return dq_acc, (dk_blk, dv_blk)

    kb = k.reshape(B, H, n_blk, blk, C).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blk, blk, C).transpose(2, 0, 1, 3, 4)
    col0 = blk * jnp.arange(n_blk)
    dq, (dkb, dvb) = jax.lax.scan(
        kv_block_step, q.astype(jnp.float32) * 0, (kb, vb, col0)
    )
    dk = dkb.transpose(1, 2, 0, 3, 4).reshape(B, H, Tl, C)
    dv = dvb.transpose(1, 2, 0, 3, 4).reshape(B, H, Tl, C)
    return dq, dk.astype(jnp.float32), dv.astype(jnp.float32)


def _pair_fwd(q, k, v, causal: bool, block_size: int, use_kernel: bool):
    Tl = q.shape[2]
    if use_kernel and _kernel_serves(Tl, block_size):
        bq, bk = flash_block_sizes(Tl, block_size)
        out, lse8 = fa._flash_forward(q, k, v, bq, bk, causal=causal)
        return out, lse8[..., 0]
    return _pair_fwd_jnp(q, k, v, causal, block_size)


def _pair_bwd(q, k, v, out, do, lse, delta, causal: bool, block_size: int, use_kernel: bool):
    Tl = q.shape[2]
    if use_kernel and _kernel_serves(Tl, block_size):
        bq, bk = flash_block_sizes(Tl, block_size)
        lse8 = jnp.broadcast_to(lse[..., None], (*lse.shape, fa._STATS_LANES))
        dq, dk, dv = fa._flash_backward(
            bq, bk, (q, k, v, out, lse8), do, causal=causal
        )
        return dq.astype(jnp.float32), dk.astype(jnp.float32), dv.astype(jnp.float32)
    return _pair_bwd_jnp(q, k, v, out, do, lse, delta, causal, block_size)


# ----------------------------------------------------------------------
# the ring
# ----------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(
    q: Array,  # (B, H, Tl, C) local query shard
    k: Array,  # (B, H, Tl, C) local key shard
    v: Array,  # (B, H, Tl, C) local value shard
    axis_name: str,
    block_size: int = 1024,
    use_kernel: tp.Optional[bool] = None,
) -> Array:
    """Causal attention across the `axis_name` ring. Call inside shard_map.

    Returns the local (B, H, Tl, C) output shard. Shards are assumed to be
    contiguous sequence chunks in axis order (chunk g holds global positions
    [g*Tl, (g+1)*Tl) — exactly what sharding the T axis of a (B, H, T, C)
    array over `axis_name` produces)."""
    out, _ = _ring_fwd(q, k, v, axis_name, block_size, use_kernel)
    return out


def _ring_fwd(q, k, v, axis_name, block_size, use_kernel):
    use_kernel, block_size = _resolve_pair_plan(q.shape[2], block_size, use_kernel)
    n = axis_size(axis_name)
    g = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Diagonal pair: ordinary causal attention on the local shard (static
    # case — ring step s=0 always visits the local shard).
    out_d, lse_d = _pair_fwd(q, k, v, True, block_size, use_kernel)
    if n == 1:
        return out_d, (q, k, v, out_d, lse_d)

    def ring_step(carry, s):
        k_c, v_c, m, l, acc = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        j = (g - s) % n  # global chunk index of the visiting K/V shard
        # Off-diagonal pairs are never diagonal-straddling: j < g is fully
        # valid (full attention, no mask), j > g contributes nothing — its
        # lse is forced to MASK so its weight underflows to exactly 0 at
        # merge (compute still runs: static shapes under scan).
        o_s, lse_s = _pair_fwd(q, k_c, v_c, False, block_size, use_kernel)
        lse_s = jnp.where(j < g, lse_s, MASK)
        m_new, l, acc = merge_normalized(m, l, acc, o_s, lse_s)
        return (k_c, v_c, m_new, l, acc), None

    init = (k, v, lse_d, lse_d * 0 + 1.0, out_d.astype(jnp.float32))
    (_, _, m, l, acc), _ = jax.lax.scan(ring_step, init, jnp.arange(1, n))
    # l >= exp(lse_d - m) > 0 always (the local diagonal softmax seeds the
    # running sum), so the shared finalize is a bitwise no-op guard here.
    out, lse = finalize(m, l, acc, dtype=q.dtype)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, block_size, use_kernel, residuals, do):
    q, k, v, out, lse = residuals
    use_kernel, block_size = _resolve_pair_plan(q.shape[2], block_size, use_kernel)
    n = axis_size(axis_name)
    g = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Global softmax-jacobian correction, one pass (the kernels recompute it
    # in-VMEM from the same o/do tiles; the jnp path takes it as input).
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)

    dq, dk_c, dv_c = _pair_bwd(
        q, k, v, out, do, lse, delta, True, block_size, use_kernel
    )
    if n == 1:
        return dq.astype(q.dtype), dk_c.astype(k.dtype), dv_c.astype(v.dtype)

    def ring_step(carry, s):
        k_c, v_c, dk_c, dv_c, dq_acc = carry
        # dK/dV accumulators ride the ring WITH their K/V shard: after the
        # final rotation below they have made n hops and are home, carrying
        # every device's contribution.
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        dk_c = jax.lax.ppermute(dk_c, axis_name, perm)
        dv_c = jax.lax.ppermute(dv_c, axis_name, perm)
        j = (g - s) % n
        dq_s, dk_s, dv_s = _pair_bwd(
            q, k_c, v_c, out, do, lse, delta, False, block_size, use_kernel
        )
        valid = j < g
        dq_acc = dq_acc + jnp.where(valid, dq_s, 0.0)
        dk_c = dk_c + jnp.where(valid, dk_s, 0.0)
        dv_c = dv_c + jnp.where(valid, dv_s, 0.0)
        return (k_c, v_c, dk_c, dv_c, dq_acc), None

    (k_c, v_c, dk_c, dv_c, dq), _ = jax.lax.scan(
        ring_step, (k, v, dk_c, dv_c, dq), jnp.arange(1, n)
    )
    dk = jax.lax.ppermute(dk_c, axis_name, perm)  # n-th hop: home
    dv = jax.lax.ppermute(dv_c, axis_name, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _ring_fwd_rule(q, k, v, axis_name, block_size, use_kernel):
    return _ring_fwd(q, k, v, axis_name, block_size, use_kernel)


ring_attention.defvjp(_ring_fwd_rule, _ring_bwd)


def ring_attention_sharded(
    q: Array,  # (B, H, T, C) global arrays, T sharded (or shardable) over sp
    k: Array,
    v: Array,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axes: tp.Tuple[str, ...] = ("data", "fsdp"),
    block_size: int = 1024,
    use_kernel: tp.Optional[bool] = None,
    head_axis: tp.Optional[str] = None,
) -> Array:
    """shard_map wrapper: shards T over `axis_name`, batch over `batch_axes`,
    runs the ring, returns the (B, H, T, C) result with the same layout.

    `head_axis` (e.g. 'tp') additionally shards the head axis — the ring is
    head-independent, so Megatron tensor parallelism and sequence parallelism
    compose here with no extra collectives: each (tp, sp) device runs the
    ring over its own H/tp heads' T/sp shard."""
    spec = P(batch_axes, head_axis, axis_name, None)
    # nondiff_argnums of a custom_vjp function must be passed positionally
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name, block_size, use_kernel),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
