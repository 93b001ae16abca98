"""Explicit shard_map FSDP: authored per-layer all-gather AND gradient sum.

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
  * The embedding is all-gathered once per step; its gradient's sum is the
    transpose AD gives that gather, a `psum_scatter` (the last gradient a
    microstep has: nothing is left to run beside it).
  * Each block's weights are all-gathered INSIDE the layer loop
    (`exchange_behind_scan`, GPT.hidden's `layer_scan` hook) — classic
    ZeRO-3 streaming: at any moment only one layer's full weights exist per
    device, and the gather replays in the backward pass (re-gather instead
    of keeping gathered weights alive).
  * The cross-chip gradient sum of the blocks and of lm_head is written down
    too (PR 44). The transpose of `all_gather(axis='fsdp', tiled=True)` is a
    `psum_scatter` this compiler lowers to a SYNCHRONOUS reduce-scatter: six
    of them held 17.7 % of the XL step with nothing else running. In their
    place: n-1 `ppermute`s a layer (`GradExchange`: chip i sends chip i+k
    its partials of the slices chip i+k keeps, every leaf of the layer
    packed into one buffer, and adds what it receives to its own in
    float32), asynchronous collective-permute start/done pairs, run ONE
    LAYER BEHIND the backward loop: the loop over the layers is this
    module's own (`jax.custom_vjp` around the stack), iteration l computes
    layer l's partials and carries them, cut up, into iteration l-1, whose
    compute the transfers run beside. Not a ring of dependent hops: this
    compiler's scheduler starts a transfer that waits for another only when
    the first is done, and gives a start/done pair all the compute it finds,
    so of a chain only the last hop is hidden (AOT compiles, PR 44:
    docs/PARALLELISM.md "Overlap"). lm_head's exchange rides beside the top
    layer's backward; layer 0's is the one left after the loop.
  * shard_map's replication tracking inserts the `psum` over 'data' (and
    'sp') for the data-parallel grad reduction inside the layer's backward;
    what a sum still varies over and its parameter does not is psummed after
    the exchange (`GradExchange.finish`).
  * The loss is a `pmean` over ('data', 'fsdp').

An axis 'fsdp' of size 1, or a leaf no spec shards over it, has nothing to
exchange and is passed through: one algorithm, the axis size its parameter.

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
    streamed inside the dots); and that at XL widths every gradient
    permute of the backward loop's body has the block's matmuls and flash
    kernels between its start and its done (`utils/hlo.py
    permute_overlap_census`). On jax 0.9.0 / libtpu 0.0.34 both hold with
    no compiler option set (docs/PARALLELISM.md "Overlap", with what the
    four-chip cell measures).

Precision of the cross-chip gradient sum: bf16-rounded per-chip partials go
on the wire as bf16, are added in float32 and rounded to bf16 ONCE (the
reduce-scatter this replaces, and the compiler schedule's own weight-sized
gradient all-reduces at the four-chip cell's shapes, add in bf16:
tests/test_chip_compile.py pins both programs' text); the G microsteps'
gradients are accumulated in float32 after it, as before.

Numerical parity with the GSPMD path is asserted in
tests/test_shard_map_fsdp.py (same loss and same grads to fp32 tolerance on
the 8-device CPU mesh; the exchange against `psum_scatter` exactly).
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


class Staged(tp.NamedTuple):
    """Per-chip partial gradients cut up for the exchange: `own`, this chip's
    partials of the slices it keeps, and `sends[k-1]`, its partials of the
    slices chip i+k keeps, each ONE packed (rows, width) buffer holding every
    leaf sharded over the axis (None / empty where there is none); `whole`,
    the partials of the leaves that are not (nothing to exchange), as they
    came."""

    own: tp.Optional[Array]
    sends: tp.List[Array]
    whole: tp.List[Array]


def _slice_shape(shape: tp.Sequence[int], ax: int, n: int) -> tp.List[int]:
    sizes = list(shape)
    sizes[ax] //= n
    return sizes


def stage_exchange(
    partials: tp.Sequence[Array], axes: tp.Sequence[tp.Optional[int]], axis_name: str
) -> Staged:
    """Cut full-shape partials (leaf i sharded along `axes[i]`, or not at all
    where that is None) into what each chip of `axis_name` is owed.

    A buffer's width is the narrowest slice's last dimension (their gcd); a
    wider slice goes in as its column blocks of that width, one under the
    other, each cut straight out of the partial and written once: where the
    width is a multiple of the lane count these are tile-aligned copies, one
    pass over the partials in all."""
    n = axis_size(axis_name)
    cut = [(g, ax) for g, ax in zip(partials, axes) if ax is not None and n > 1]
    whole = [g for g, ax in zip(partials, axes) if ax is None or n == 1]
    if not cut:
        return Staged(None, [], whole)
    (dtype,) = {g.dtype for g, _ in cut}
    me = jax.lax.axis_index(axis_name).astype(jnp.uint32)
    shapes = [_slice_shape(g.shape, ax, n) for g, ax in cut]
    width = math.gcd(*(shape[-1] for shape in shapes))
    rows = sum(math.prod(shape) for shape in shapes) // width
    varying = tuple(sorted(set().union(*(jax.typeof(g).vma for g, _ in cut))))

    def packed(k):
        # every leaf's slice number me + k
        buffer = jnp.zeros((rows, width), dtype)
        if varying:
            buffer = jax.lax.pcast(buffer, varying, to="varying")
        at = 0
        for (g, ax), shape in zip(cut, shapes):
            start = [jnp.uint32(0)] * g.ndim
            start[ax] = jax.lax.rem(me + jnp.uint32(k), jnp.uint32(n)) * jnp.uint32(shape[ax])
            for j in range(shape[-1] // width):
                corner = list(start)
                corner[-1] = corner[-1] + jnp.uint32(j * width)
                block = jax.lax.dynamic_slice(g, corner, shape[:-1] + [width]).reshape(-1, width)
                buffer = jax.lax.dynamic_update_slice(buffer, block, (at, 0))
                at += block.shape[0]
        return buffer

    return Staged(packed(0), [packed(k) for k in range(1, n)], whole)


def finish_exchange(
    staged: Staged, shards: tp.Sequence[tp.Any], axes: tp.Sequence[tp.Optional[int]], axis_name: str
) -> tp.List[Array]:
    """The n-1 `ppermute`s and the sum: what `[jax.lax.psum_scatter(g,
    axis_name, scatter_dimension=ax, tiled=True) for g, ax in zip(partials,
    axes)]` gives for the partials `stage_exchange` cut up (`shards[i]`: an
    array of the shape of leaf i's shard). Chip i sends chip i+k its packed
    partials (k = 1 .. n-1) and adds the n-1 buffers it receives to its own
    in float32, rounding once.

    The same (n-1)/n of the bytes leave a chip as in the reduce-scatters this
    replaces. What differs is what the TPU's scheduler does with it: its
    reduce-scatter is synchronous, a collective-permute is an asynchronous
    start/done pair that runs beside compute; it keeps at most five of those
    in flight, so a layer's leaves travel as ONE buffer a destination; and
    a transfer that waits for another (a ring's hop) it starts only when the
    first is done, so no transfer here waits for any other
    (docs/PARALLELISM.md "Overlap")."""
    n = axis_size(axis_name)
    whole = iter(staged.whole)
    if staged.own is None:
        return list(whole)
    total = staged.own.astype(jnp.float32)
    for k, send in enumerate(staged.sends, start=1):
        got = jax.lax.ppermute(send, axis_name, [(j, (j + k) % n) for j in range(n)])
        total = total + got.astype(jnp.float32)
    total = total.astype(staged.own.dtype)
    width = total.shape[-1]
    out, at = [], 0
    for shard, ax in zip(shards, axes):
        if ax is None or n == 1:
            out.append(next(whole))
            continue
        rows = math.prod(shard.shape[:-1])
        blocks = []  # the shard's column blocks, as stage_exchange laid them down
        for _ in range(shard.shape[-1] // width):
            blocks.append(total[at : at + rows].reshape(*shard.shape[:-1], width))
            at += rows
        out.append(blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=-1))
    return out


class GradExchange:
    """The cross-chip gradient sum of one tree of parameters sharded as
    `specs` say, in its two halves: `stage(partials)` cuts the per-chip
    partial gradients (full shapes) up, `finish(staged, like)` moves them
    and returns the sums the sharded parameters `like` are owed: the
    exchange over 'fsdp', then a psum over whatever other axes a sum still
    varies over and its parameter does not ('data', 'sp': what shard_map's
    own transpose would insert; nothing when check_vma is off and the
    boundary does it)."""

    def __init__(self, specs):
        leaves, self.treedef = jax.tree.flatten(specs, is_leaf=lambda s: isinstance(s, P))
        self.axes = [_sharded_axis(spec) for spec in leaves]

    def stage(self, partials) -> Staged:
        with jax.named_scope("grad_exchange"):
            return stage_exchange(self.treedef.flatten_up_to(partials), self.axes, "fsdp")

    def finish(self, staged: Staged, like):
        like = self.treedef.flatten_up_to(like)
        with jax.named_scope("grad_exchange"):
            sums = finish_exchange(staged, like, self.axes, "fsdp")
        for i, (g, p) in enumerate(zip(sums, like)):
            extra = tuple(sorted(jax.typeof(g).vma - jax.typeof(p).vma))
            if extra:
                sums[i] = jax.lax.psum(g, extra)
        return self.treedef.unflatten(sums)


@jax.tree_util.register_pytree_node_class
class _Pullback:
    """`jax.vjp`'s pullback without the residuals that ARE `known` arrays (a
    layer's gathered weights): those are not stored, and `__call__` takes
    them again, re-gathered. The leaves are what the remat policy saved."""

    def __init__(self, saved, treedef, where):
        self.saved, self.treedef, self.where = saved, treedef, where

    @classmethod
    def of(cls, pull, known):
        leaves, treedef = jax.tree.flatten(pull)
        where = tuple(
            next((k for k, v in enumerate(known) if v is leaf), None) for leaf in leaves
        )
        return cls([l for l, k in zip(leaves, where) if k is None], treedef, where)

    def tree_flatten(self):
        return self.saved, (self.treedef, self.where)

    @classmethod
    def tree_unflatten(cls, aux, saved):
        return cls(list(saved), *aux)

    def __call__(self, known, ct):
        saved = iter(self.saved)
        leaves = [next(saved) if k is None else known[k] for k in self.where]
        return self.treedef.unflatten(leaves)(ct)


def _tied(a, b):
    """(a, b) behind one `optimization_barrier`: whatever uses `a` waits for
    `b` too. The barrier gives its operands the union of their varying axes,
    so only the leaves of `a` that vary as `b` does go through it."""
    leaves, treedef = jax.tree.flatten(a)
    same = [i for i, l in enumerate(leaves) if jax.typeof(l).vma == jax.typeof(b).vma]
    tied, b = jax.lax.optimization_barrier(([leaves[i] for i in same], b))
    for i, l in zip(same, tied):
        leaves[i] = l
    return treedef.unflatten(leaves), b


def exchange_behind_scan(
    block_fn, x, blocks, keys, rider, *, block_specs, rider_spec: P, unroll: int
):
    """(the layer stack applied to x, `rider` gathered): the scan of
    GPT.hidden's `layer_scan` hook with its own backward, in which a layer's
    gradient exchange runs ONE LAYER BEHIND the layer's backward and a
    layer's weight gathers ONE LAYER AHEAD of it.

    Iteration l of the backward loop computes layer l's per-chip partial
    gradients, cuts them up (`GradExchange.stage`) and CARRIES them into
    iteration l-1, which moves and sums them beside its own replayed forward
    and backward and writes the sums into layer l's row. AD of `lax.scan`
    cannot say that (a layer's cotangent has to leave its own iteration), and
    a transfer cannot straddle a loop iteration, so it is the data that is
    carried. The same iteration gathers layer l-1's weights and carries THEM:
    a gather started beside the permutes queues behind them on the links and
    the matmul that streams it waits (0.72 ms an iteration on the 2x2 v5e,
    more than the exchange hides: PERF.md section 6, PR 44); gathered an
    iteration ahead, nothing waits for it (the forward loop does the same
    with layer l+1's: its first gather showed 0.3 ms an iteration). The top
    layer's backward runs before the loop, beside the exchange of `rider`'s
    gradient (lm_head: the first gradient a microstep's backward has,
    gathered here so that its exchange can be tied in before the loop);
    layer 0's exchange runs after it.

    `block_fn` (from GPT.hidden: the block under the config's
    `jax.checkpoint` policy) is differentiated by `jax.vjp` in the forward
    loop, so what is stored per layer is what the policy saves; the
    gathered weights are not: the backward gathers them again (ZeRO-3), two
    layers' worth alive at a time."""
    exchange, rider_exchange = GradExchange(block_specs), GradExchange(rider_spec)
    n_layer = jax.tree.leaves(blocks)[0].shape[0]

    def row(stacked, l):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, keepdims=False), stacked
        )

    def gathered(blocks, l):
        return jax.tree.map(_gather_leaf, row(blocks, l), block_specs)

    def layer(x, full, key):
        out, aux = block_fn(x, (full, key))
        assert aux is None, "the authored schedule's stack emits no per-layer output"
        return out

    def forward(apply, x, blocks, keys):
        """The stack, each iteration gathering the NEXT layer's weights
        beside its own compute; `apply(x, full, key) -> (x, what to stack)`."""

        def body(carry, l_and_key):
            x, full = carry
            l, key = l_and_key
            ahead = gathered(blocks, jnp.minimum(l + 1, n_layer - 1))
            x, stacked = apply(x, full, key)
            return (x, ahead), stacked

        (x, _), stacked = jax.lax.scan(
            body, (x, gathered(blocks, 0)), (jnp.arange(n_layer), keys), unroll=unroll
        )
        return x, stacked

    @jax.custom_vjp
    def stack(x, blocks, keys, rider):
        out, _ = forward(lambda x, full, key: (layer(x, full, key), None), x, blocks, keys)
        return out, _gather_leaf(rider, rider_spec)

    def stack_fwd(x, blocks, keys, rider):
        def apply(x, full, key):
            out, pull = jax.vjp(lambda x, full: layer(x, full, key), x, full)
            return out, _Pullback.of(pull, jax.tree.leaves(full))

        out, pulls = forward(apply, x, blocks, keys)
        return (out, _gather_leaf(rider, rider_spec)), (pulls, blocks, rider)

    def stack_bwd(res, cts):
        pulls, blocks, rider = res
        ct, rider_partial = cts
        shard = row(blocks, 0)  # what a layer's sums are shaped and typed like

        def backward(full, l, ct):
            return row(pulls, l)(jax.tree.leaves(full), ct)

        def into_row(grads, sums, l):
            return jax.tree.map(
                lambda g, s: jax.lax.dynamic_update_index_in_dim(g, s, l, 0), grads, sums
            )

        def body(carry, l):
            ct, above, grads, full = carry
            ahead = gathered(blocks, jnp.maximum(l - 1, 0))
            ct, partials = backward(full, l, ct)
            # layer l+1's sums travel while layer l computes, and land in
            # layer l+1's row
            sums = exchange.finish(above, shard)
            # layer l's partials are cut up when those transfers are done,
            # into the buffers they leave (the carry's own, no copy)
            sums, treedef = jax.tree.flatten(sums)
            partials, sums[-1] = _tied(partials, sums[-1])
            grads = into_row(grads, treedef.unflatten(sums), l + 1)
            return (ct, exchange.stage(partials), grads, ahead), None

        rider_staged = rider_exchange.stage(rider_partial)
        ahead = gathered(blocks, max(n_layer - 2, 0))
        ct, partials = backward(gathered(blocks, n_layer - 1), n_layer - 1, ct)
        # the loop waits for the rider's sum: its transfers end before the
        # first iteration starts its own, with the top layer to run beside
        top, rider_sum = _tied(
            exchange.stage(partials), rider_exchange.finish(rider_staged, rider)
        )
        (ct, first, grads, _), _ = jax.lax.scan(
            body,
            (ct, top, jax.tree.map(jnp.zeros_like, blocks), ahead),
            jnp.arange(n_layer - 1),
            reverse=True,
            unroll=unroll,
        )
        return ct, into_row(grads, exchange.finish(first, shard), 0), None, rider_sum

    stack.defvjp(stack_fwd, stack_bwd)
    return stack(x, blocks, keys, rider)


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

    loss_axes = BATCH_AXES + ("sp",) if sequence_parallel else BATCH_AXES

    def local_loss(params: GPTParams, x: Array, y: Array, key) -> Array:
        if key is not None:
            # decorrelate dropout masks across batch (and sequence) shards
            key = jax.random.fold_in(key, jax.lax.axis_index(loss_axes))
        full_wte = _gather_leaf(params.wte, param_specs.wte)
        gathered = {}

        def layer_scan(block_fn, x, xs):
            # lm_head is gathered by the stack: its gradient's exchange rides
            # beside the top layer's backward (exchange_behind_scan)
            x, gathered["lm_head"] = exchange_behind_scan(
                block_fn, x, *xs, params.lm_head, block_specs=block_specs,
                rider_spec=param_specs.lm_head, unroll=model_cfg.scan_unroll,
            )
            return x, None

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
            GPTParams(wte=full_wte, blocks=params.blocks, lm_head=None),
            x,
            key=key,
            inference=key is None,
            layer_scan=layer_scan,
            attn_fn=attn_fn,
            positions=positions,
            rope_len=rope_len,
        )
        # local mean over an equal-size token shard -> pmean is the global
        # mean (batch shards over data/fsdp, sequence shards over sp)
        loss = fused_linear_cross_entropy(
            h, gathered["lm_head"], y, loss_chunk_tokens, loss_remat_chunks
        )
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
