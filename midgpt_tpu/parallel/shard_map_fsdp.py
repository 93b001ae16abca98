"""Explicit shard_map FSDP: authored per-layer all-gather / grad reduce-scatter.

The GSPMD path (parallel/fsdp.py) matches the reference's approach — sharding
constraints in, compiler-chosen collectives out (reference model.py:167-178,
train.py:87). This module is the TPU-first redesign: the FSDP schedule is
*written down* instead of inferred. It is the schedule a GPT takes on every
multi-device mesh it composes with (ExperimentConfig.fsdp_schedule derives
it; the compiler's is the fall-back), because on the chip the compiler's
lowering of the same algorithm all-reduces whole gradients, moves
activations through all-to-alls and leaves 39 % of the XL step to exposed
collectives (PERF.md section 6, PR 29).

  * Params enter `jax.shard_map` still sharded (in_specs = their FSDP specs).
  * The embedding and lm_head are all-gathered once per step.
  * Each block's weights are all-gathered INSIDE the layer scan
    (`layer_transform` hook in GPT.hidden) — classic ZeRO-3 streaming: at any
    moment only one layer's full weights exist per device. Under the
    per-block `jax.checkpoint` the gather replays in the backward pass
    (re-gather instead of keeping gathered weights alive).
  * Gradients need no hand-written collective at all: the transpose rule of
    `all_gather(axis='fsdp', tiled=True)` IS `psum_scatter` over 'fsdp', so
    AD emits exactly the per-layer grad reduce-scatter ZeRO-3 prescribes,
    and shard_map's replication tracking inserts the `psum` over 'data' for
    the data-parallel grad reduction.
  * The loss is a `pmean` over ('data', 'fsdp') — the only explicit
    collective in the module besides the gathers.

Gather/compute overlap is pinned, not assumed (r5):
  * tests/test_shard_map_fsdp.py::test_zero3_gathers_schedulable_ahead_of_compute
    asserts the dataflow precondition on the compiled step — at
    scan_unroll=2 no weight gather in the scan body depends on the body's
    compute, so the scheduler is free to issue layer l+1's gathers during
    layer l.
  * tests/test_chip_compile.py AOT-compiles this step for a described v5e
    2x2 and asserts the TPU compiler actually exploits that freedom: in
    every gather-bearing scan body, forward and backward, weight gathers
    are async (annotated async_collective_name="all-gather-start") or
    continuation-FUSED into the block matmul kernels (gather windows
    streamed inside the dots). On jax 0.9.0 / libtpu 0.0.34 it holds with
    no compiler option set (docs/PARALLELISM.md "Overlap", with what the
    four-chip cell measures: the gathers are hidden, the synchronous
    reduce-scatters are what stays exposed).

Precision of the cross-chip gradient sum: the transpose of a tiled bf16
`all_gather` is a bf16 `psum_scatter` over bf16-rounded per-chip partials.
That is the dtype the compiler's schedule carries its own weight-sized
gradient all-reduces in at the four-chip cell's shapes (bf16, on bf16
partials: tests/test_chip_compile.py pins both programs' text), so the two
lowerings sum the same partials in the same precision; the G microsteps'
gradients are accumulated in float32 after it, as before.

Numerical parity with the GSPMD path is asserted in
tests/test_shard_map_fsdp.py (same loss and same grads to fp32 tolerance on
the 8-device CPU mesh).
"""

from __future__ import annotations

import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from midgpt_tpu.models.gpt import GPT, GPTParams
from midgpt_tpu.ops.loss import fused_linear_cross_entropy
from midgpt_tpu.parallel.mesh import BATCH_AXES

Array = jax.Array


def _sharded_axis(spec: P) -> tp.Optional[int]:
    """Index of the axis a spec shards over 'fsdp', or None if replicated."""
    for ax, names in enumerate(spec):
        if names == "fsdp" or (isinstance(names, tuple) and "fsdp" in names):
            return ax
    return None


def _gather_leaf(x: Array, spec: P) -> Array:
    ax = _sharded_axis(spec)
    if ax is None:
        return x
    return jax.lax.all_gather(x, "fsdp", axis=ax, tiled=True)


def _drop_leading(spec: P) -> P:
    """Spec for one layer's slice of a stacked (n_layer, ...) leaf."""
    return P(*spec[1:]) if len(spec) else spec


def make_shard_map_loss(
    model_cfg,
    mesh: Mesh,
    param_specs,
    loss_chunk_tokens: int,
    loss_remat_chunks: tp.Optional[bool] = None,
    sequence_parallel: tp.Optional[str] = None,
) -> tp.Callable:
    """Build loss_fn(params, x, y, key) -> scalar with authored collectives.

    Drop-in replacement for the GSPMD loss in make_train_step: takes GLOBAL
    arrays, returns the global-mean loss; differentiable (grads come back in
    the params' sharded layout).

    `sequence_parallel` ('ring' | 'ulysses' | None) additionally shards the
    batch's T axis over the mesh's 'sp' axis and runs the named
    context-parallel attention schedule — ZeRO-3 and SP compose inside ONE
    shard_map body: per-layer weight all-gathers ride the 'fsdp' axis while
    the attention collectives ride 'sp' (K/V ppermute rotation for the ring,
    head<->sequence all_to_all for Ulysses), with no nesting. Everything
    else in the backbone is token-pointwise, needing only shard-aware RoPE
    positions (GPT.hidden positions/rope_len)."""
    if sequence_parallel not in (None, "ring", "ulysses"):
        raise ValueError(f"unknown sequence_parallel {sequence_parallel!r}")
    block_specs = jax.tree.map(_drop_leading, param_specs.blocks)

    def gather_block(block):
        return jax.tree.map(_gather_leaf, block, block_specs)

    loss_axes = BATCH_AXES + ("sp",) if sequence_parallel else BATCH_AXES

    def local_loss(params: GPTParams, x: Array, y: Array, key) -> Array:
        if key is not None:
            # decorrelate dropout masks across batch (and sequence) shards
            key = jax.random.fold_in(key, jax.lax.axis_index(loss_axes))
        full_wte = _gather_leaf(params.wte, param_specs.wte)
        full_head = _gather_leaf(params.lm_head, param_specs.lm_head)
        gathered = GPTParams(
            wte=full_wte, blocks=params.blocks, lm_head=full_head
        )
        positions = rope_len = attn_fn = None
        if sequence_parallel:
            Tl = x.shape[1]
            rope_len = Tl * axis_size("sp")
            positions = jax.lax.axis_index("sp") * Tl + jnp.arange(Tl)
            if sequence_parallel == "ring":
                from midgpt_tpu.parallel.ring_attention import ring_attention

                attn_fn = lambda q, k, v: ring_attention(q, k, v, "sp")
            else:
                from midgpt_tpu.parallel.ulysses import ulysses_attention

                attn_fn = lambda q, k, v: ulysses_attention(
                    q, k, v, "sp",
                    block_size=model_cfg.attn_block_size,
                    impl="flash",
                )
        h = GPT.hidden(
            model_cfg,
            gathered,
            x,
            key=key,
            inference=key is None,
            layer_transform=gather_block,
            attn_fn=attn_fn,
            positions=positions,
            rope_len=rope_len,
        )
        # local mean over an equal-size token shard -> pmean is the global
        # mean (batch shards over data/fsdp, sequence shards over sp)
        loss = fused_linear_cross_entropy(h, full_head, y, loss_chunk_tokens, loss_remat_chunks)
        return jax.lax.pmean(loss, loss_axes)

    batch_spec = P(BATCH_AXES, "sp" if sequence_parallel else None)
    # tp composition (r5): same split as the pipeline's pp×tp — 'tp' stays
    # a GSPMD auto axis, so the authored ZeRO-3 gathers/reduce-scatters
    # keep riding 'fsdp' while the Megatron column/row schedule (specs from
    # parallel/tp.py, split3 QKV lowering auto-selected by the runtime) is
    # inserted by GSPMD inside the body. The kwargs builder
    # (parallel/pipeline.py auto_tp_shard_map_kwargs, shared) strips 'tp'
    # from in_specs and the manual axis set only when tp>1 — the tp=1 path
    # stays byte-identical (the partial-manual form also trips an XLA CPU
    # AllReducePromotion crash on bf16; config validation keeps
    # ring/ulysses out of the tp combination for now).
    from midgpt_tpu.parallel.pipeline import auto_tp_shard_map_kwargs

    in_specs, extra = auto_tp_shard_map_kwargs(mesh, param_specs)
    return shard_map(
        local_loss,
        mesh=mesh,
        in_specs=(in_specs, batch_spec, batch_spec, P()),
        out_specs=P(),
        **extra,
    )
