"""GPipe pipeline parallelism over the mesh 'pp' axis.

Beyond the reference's capability set (its only model sharding is FSDP,
reference model.py:167-178). The design falls out of this framework's
model representation: block parameters are already STACKED along a leading
layer axis (models/gpt.py), so a pipeline stage is nothing more than that
axis sharded over 'pp' — stage s holds the (L/pp, ...) slice of every block
leaf, and shard_map hands it each stage's slice with zero data movement.

Schedule (classic GPipe, SPMD-expressed — every stage runs the SAME
program every tick; there is no per-stage control flow to trace):

  * the step's local batch is split into M microbatches; the embedded
    activations (M, Bm, T, D) are visible to every stage (the 'pp' axis is
    replicated for activations — only stage 0's use of them is real);
  * one `lax.scan` runs M + pp - 1 ticks. Each tick, every stage runs its
    layer slice on one activation: stage 0 reads microbatch t from the
    input stream, stage s>0 reads what stage s-1 ppermuted to it last tick.
    Tick outputs ride a single neighbor `ppermute`; the last stage collects
    its finished microbatches into an output buffer by a masked
    dynamic-index update (bubble ticks compute on garbage that is never
    collected — static shapes, no `lax.cond`);
  * loss (v2): the collected outputs are `psum_scatter`ed over 'pp' — only
    the last stage's buffer is nonzero, so the scatter-sum is a
    broadcast-slice handing stage s tokens [s·B/pp, (s+1)·B/pp) — and EVERY
    stage runs final-norm + fused CE on its 1/pp slice; a `pmean` over 'pp'
    recombines the mean. Total lm_head/CE matmul volume is 1×, not the v1
    pp× (where each stage ran the full-batch CE on mostly-zero outputs).
    Reverse-mode AD through the tick scan + ppermute IS the GPipe backward
    schedule (ppermute transposes to the reverse permutation; the scan's
    saved residuals are the activation stash; psum_scatter transposes to
    all_gather), and shard_map's transpose of the pp-replicated wte/lm_head
    inputs inserts the psum that combines stage 0's embedding grad and the
    per-stage head grads.
  * fsdp composition (v2): with a real 'fsdp' axis the batch additionally
    shards over it (BATCH_AXES) and each stage's block leaves shard a
    non-layer axis over 'fsdp' (pipeline_param_specs); the body all-gathers
    each layer's weights inside the stage scan (ZeRO-3 streaming, same
    authored collective as parallel/shard_map_fsdp.py — AD emits the
    per-layer grad reduce-scatter as the gather's transpose).

The pipeline bubble is the standard (pp-1)/(M+pp-1) fraction of ticks;
`pipeline_microbatches` trades bubble against per-tick matmul size.

**1F1B** (`pipeline_schedule='1f1b'`, r5 — make_pipeline_loss_and_grad):
GPipe's activation stash grows with M (reverse AD of the tick scan saves
every tick's stage input). The 1F1B schedule bounds it at 2·pp slots,
M-INDEPENDENT, by running forward and backward in ONE loop — which reverse
AD cannot express, so the backward is written out: each tick every stage
does one forward (GPipe timing: F of microbatch m at stage s on tick m+s)
AND one backward (B of m at stage s on tick m+2·pp-1-s: recompute the
stage from its stashed INPUT via jax.vjp and pull the incoming cotangent
through), with bubble ticks masked. The loss stage runs the same
pp-scattered CE as GPipe per fresh microbatch and seeds the cotangent
stream; grads accumulate in-loop (blocks per-stage, wte by scatter-add,
lm_head from the CE pull), so nothing M-sized is ever stored. Memory bound
and loss/grad parity with GPipe are test-pinned (tests/test_pipeline.py).

Composes with 'data' and 'fsdp' (same per-layer gather streaming; the
gather's vjp IS the grad reduce-scatter). tp under 1F1B and sp under any
pipeline schedule are future work (config validation enforces this).
"""

from __future__ import annotations

import functools
import typing as tp

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from midgpt_tpu.models.gpt import GPT, GPTConfig, GPTParams, _remat_policy
from midgpt_tpu.ops.norms import rms_norm
from midgpt_tpu.ops.rope import rope_table
from midgpt_tpu.ops.loss import fused_linear_cross_entropy
from midgpt_tpu.parallel.mesh import BATCH_AXES

Array = jax.Array


def pipeline_param_specs(
    params: tp.Any,
    mesh: tp.Optional[Mesh] = None,
    shard_model: bool = True,
    min_size: int = 2**18,
) -> tp.Any:
    """Specs for the GPipe schedule: block leaves shard their leading LAYER
    axis over 'pp'; with a real 'fsdp' mesh axis (and shard_model), large
    leaves additionally shard a non-layer axis over 'fsdp' (the same
    axis-choice rule as parallel/fsdp.py — exact divisibility required,
    since shard_map hands the body literal shards). With a real 'tp' axis
    the four block projections additionally shard their Megatron axis over
    'tp' (same name->axis table as parallel/tp.py, which the stacked leaves
    share since both carry the leading L) and fsdp moves to the OTHER
    feature axis; the embedding/lm_head stay tp-replicated (no
    vocab-parallel under pp — the pipeline CE runs on gathered heads).
    Works for params AND optimizer-state trees (path-keyed on 'blocks')."""
    from midgpt_tpu.parallel.fsdp import fsdp_leaf_spec
    from midgpt_tpu.parallel.tp import _leaf_name, megatron_leaf_axes

    n_fsdp = mesh.shape["fsdp"] if mesh is not None else 1
    n_tp = mesh.shape["tp"] if mesh is not None else 1

    def rule(path, x) -> P:
        names = [getattr(e, "name", None) or getattr(e, "key", None) for e in path]
        if "blocks" in names:
            if n_tp > 1:
                axes = megatron_leaf_axes(_leaf_name(path), x.shape, n_tp)
                # Stacked block leaves carry the leading layer axis, so the
                # Megatron axes (trailing) can never collide with slot 0 —
                # guarded anyway: fall through to the plain pp+fsdp rule.
                if axes is not None and 0 not in axes:
                    tp_ax, fsdp_ax = axes
                    spec: tp.List[tp.Any] = [None] * x.ndim
                    spec[0] = "pp"
                    spec[tp_ax] = "tp"
                    if (
                        shard_model
                        and n_fsdp > 1
                        and x.size > min_size
                        and x.shape[fsdp_ax] % n_fsdp == 0
                    ):
                        spec[fsdp_ax] = "fsdp"
                    return P(*spec)
            # layer axis reserved for 'pp'; fsdp picks among the rest
            spec = fsdp_leaf_spec(x, n_fsdp, shard_model, min_size, reserved_leading=1)
            spec[0] = "pp"
            return P(*spec)
        spec = fsdp_leaf_spec(x, n_fsdp, shard_model, min_size)
        return P(*spec) if any(e is not None for e in spec) else P()

    return jax.tree_util.tree_map_with_path(rule, params)


def _strip_tp(spec: P) -> P:
    """in_specs for the pipeline shard_map mention MANUAL axes only: 'tp'
    stays a GSPMD ('auto') axis inside the body, its sharding carried by the
    arrays themselves (make_pipeline_loss)."""
    def strip(entry):
        if entry == "tp":
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e != "tp")
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return entry

    return P(*(strip(e) for e in spec))


def auto_tp_shard_map_kwargs(mesh: Mesh, param_specs):
    """(param_in_specs, extra_shard_map_kwargs) for the tp-as-auto-axis
    composition — ONE definition of the rule, used by the pipeline losses
    here and the explicit ZeRO-3 body (parallel/shard_map_fsdp.py): with a
    real 'tp' axis, strip it from in_specs (auto axes may not appear there)
    and exclude it from the manual axis_names so GSPMD authors the Megatron
    collectives inside the body; at tp=1 return the specs untouched and no
    extra kwargs, keeping that path byte-identical to the full-manual form
    (which also sidesteps an XLA CPU AllReducePromotion CHECK-crash on the
    partial-manual + bf16 combination)."""
    if mesh.shape["tp"] > 1:
        return (
            jax.tree.map(_strip_tp, param_specs),
            dict(
                axis_names=frozenset(mesh.axis_names) - {"tp"},
                check_vma=False,
            ),
        )
    return param_specs, {}


def gpipe_stage_apply(
    config: GPTConfig, stage_blocks, x: Array, rope, layer_transform=None
) -> Array:
    """Run this stage's (L/pp)-layer slice on one microbatch (Bm, T, D).

    `layer_transform` (optional) maps a layer's sharded block leaves to full
    ones — the fsdp all-gather hook; under remat the gather replays in the
    backward instead of keeping gathered weights alive (ZeRO-3)."""

    def block_fn(h, block):
        if layer_transform is not None:
            block = layer_transform(block)
        return (
            GPT.block_apply(config, block, h, key=None, inference=True, rope=rope),
            None,
        )

    if config.remat:
        block_fn = jax.checkpoint(block_fn, policy=_remat_policy(config.remat_policy))
    h, _ = jax.lax.scan(block_fn, x, stage_blocks, unroll=config.scan_unroll)
    return h


def make_pipeline_loss(
    model_cfg: GPTConfig,
    mesh: Mesh,
    param_specs,
    loss_chunk_tokens: int,
    loss_remat_chunks: tp.Optional[bool] = None,
    microbatches: int = 0,
) -> tp.Callable:
    """Build loss_fn(params, x, y, key) -> scalar running the GPipe schedule.

    Drop-in replacement for the GSPMD loss in make_train_step (same contract
    as make_shard_map_loss): GLOBAL (B, T) arrays in, global-mean scalar
    out, differentiable. `key` is accepted for interface compatibility but
    unused (pp requires dropout 0, enforced at config construction)."""
    pp = mesh.shape["pp"]
    M = microbatches or pp

    # fsdp gather plumbing (shared helpers with the explicit ZeRO-3 module):
    # per-layer block specs are the stacked specs minus the leading 'pp' axis.
    from midgpt_tpu.parallel.shard_map_fsdp import _drop_leading, _gather_leaf

    block_layer_specs = jax.tree.map(_drop_leading, param_specs.blocks)

    def gather_block(block):
        return jax.tree.map(_gather_leaf, block, block_layer_specs)

    def local_loss(params: GPTParams, x: Array, y: Array, key) -> Array:
        del key  # dropout 0 under pp (config validation)
        B, T = x.shape
        if B % M != 0 or B % pp != 0:
            raise ValueError(
                f"per-data-shard batch {B} must be divisible by both "
                f"pipeline_microbatches={M} and pp={pp} — lower them or "
                "raise batch_size (config-time validation can only check the "
                "global batch; this is the per-shard constraint)"
            )
        Bm = B // M
        s = jax.lax.axis_index("pp")
        rope = rope_table(model_cfg.head_dim, T)

        # Embedding on every stage (replicated compute — a cheap gather);
        # only stage 0's result enters the pipeline, so only stage 0
        # contributes wte grad (shard_map's pp-replicated-input transpose
        # psums over 'pp'; the fsdp gather transposes to reduce-scatter).
        full_wte = _gather_leaf(params.wte, param_specs.wte)
        full_head = _gather_leaf(params.lm_head, param_specs.lm_head)
        h = jnp.take(full_wte, x, axis=0)  # (B, T, D)
        x_mb = h.reshape(M, Bm, T, model_cfg.n_embd)

        n_ticks = M + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        stage_fn = functools.partial(
            gpipe_stage_apply, model_cfg, params.blocks, rope=rope,
            layer_transform=gather_block,
        )

        def tick(carry, t):
            recv, outs = carry
            mb = t - s  # microbatch index this stage serves at tick t
            inp = jnp.where(
                s == 0,
                jax.lax.dynamic_index_in_dim(
                    x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
                ),
                recv,
            )
            out = stage_fn(inp)
            collect = (s == pp - 1) & (mb >= 0) & (mb < M)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, out.astype(outs.dtype), jnp.clip(mb, 0, M - 1), 0
            )
            outs = jnp.where(collect, upd, outs)
            send = jax.lax.ppermute(out, "pp", perm)
            return (send, outs), None

        init = (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb))
        (_, outs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))

        # v2 loss: scatter the collected outputs over 'pp' so the final-norm
        # + fused-CE matmul volume is 1× the batch, not pp×. Only the last
        # stage's buffer is nonzero, so the scatter-SUM is a broadcast-slice:
        # stage s receives rows [s·B/pp, (s+1)·B/pp). Each stage's CE is the
        # mean over its equal-size token slice; pmean over 'pp' recombines
        # the global mean. (Transpose: psum_scatter -> all_gather, so the
        # backward hands the full outs-cotangent to the last stage's stash.)
        shard = jax.lax.psum_scatter(
            outs.reshape(B, T, model_cfg.n_embd), "pp",
            scatter_dimension=0, tiled=True,
        )  # (B/pp, T, D)
        Bp = B // pp
        y_s = jax.lax.dynamic_slice_in_dim(y, s * Bp, Bp, axis=0)
        hidden = rms_norm(shard, eps=1e-5)
        loss = fused_linear_cross_entropy(
            hidden, full_head, y_s, loss_chunk_tokens, loss_remat_chunks
        )
        loss = jax.lax.pmean(loss, "pp")
        # global mean over the batch axes
        return jax.lax.pmean(loss, BATCH_AXES)

    batch_spec = P(BATCH_AXES, None)
    # tp composition (r5): 'tp' is deliberately NOT a manual axis — the
    # tick body stays written in pp/fsdp collectives only, while the
    # Megatron tp schedule rides GSPMD inside it (auto axis) — see
    # auto_tp_shard_map_kwargs.
    in_param_specs, extra = auto_tp_shard_map_kwargs(mesh, param_specs)
    return shard_map(
        local_loss,
        mesh=mesh,
        in_specs=(in_param_specs, batch_spec, batch_spec, P()),
        out_specs=P(),
        **dict({"check_vma": False}, **extra),
    )


def make_pipeline_loss_and_grad(
    model_cfg: GPTConfig,
    mesh: Mesh,
    param_specs,
    loss_chunk_tokens: int,
    loss_remat_chunks: tp.Optional[bool] = None,
    microbatches: int = 0,
) -> tp.Callable:
    """1F1B schedule: loss_and_grad(params, x, y, key) -> (loss, grads).

    Reverse AD of the GPipe tick scan stashes EVERY tick's stage input —
    O(M) activations per stage. 1F1B interleaves forward and backward in
    one loop, which AD cannot express, so this function computes loss AND
    grads directly (the train step calls it instead of value_and_grad;
    module docstring has the schedule). Tick timing:

      F of microbatch m at stage s:  tick  m + s            (GPipe timing)
      CE + cotangent seed for m:     tick  m + pp - 1       (its last-stage F)
      B of microbatch m at stage s:  tick  m + 2*pp - 1 - s

    F at stage s lands on ticks == s (mod 1... both streams run every tick,
    masked); the stash slot for m is m % (2*pp): F_m is written at tick m+s
    and read back at tick m+2*pp-1-s, before F_{m+2*pp} rewrites the slot at
    tick m+2*pp+s — a 2*pp ring buffer regardless of M. B recomputes the
    stage from the stashed INPUT (jax.vjp), so activation memory is the
    stash + one in-flight vjp, and the per-layer fsdp gather's vjp emits the
    grad reduce-scatter exactly as in the GPipe path.

    Gradient bookkeeping (all in-loop, nothing M-sized): block grads
    accumulate per stage in f32; wte grads scatter-add token rows at stage
    0's B; lm_head grads accumulate from the CE pull. Final reductions match
    what shard_map AD inserts for the GPipe path: psum over 'data' (+ the
    fsdp batch contribution via reduce-scatter), psum over 'pp' for the
    replicated wte/lm_head, and a 1/(M * n_data * n_fsdp) scale pairing the
    per-tick cotangent seed (1/pp for the pp-scattered CE slices) with the
    loss's batch pmean."""
    pp = mesh.shape["pp"]
    M = microbatches or pp
    S = 2 * pp  # stash slots
    n_batch = mesh.shape["data"] * mesh.shape["fsdp"]

    from midgpt_tpu.parallel.shard_map_fsdp import (
        _drop_leading,
        _gather_leaf,
        _sharded_axis,
    )

    block_layer_specs = jax.tree.map(_drop_leading, param_specs.blocks)

    def gather_block(block):
        return jax.tree.map(_gather_leaf, block, block_layer_specs)

    def _reduce_to_spec(g: Array, spec: P) -> Array:
        """Full (gathered-layout) grad -> sharded layout: sum the fsdp batch
        shards' contributions and scatter per the param's fsdp axis."""
        ax = _sharded_axis(spec)
        if ax is None:
            return jax.lax.psum(g, "fsdp") if mesh.shape["fsdp"] > 1 else g
        return jax.lax.psum_scatter(g, "fsdp", scatter_dimension=ax, tiled=True)

    def local_loss_and_grad(params: GPTParams, x: Array, y: Array, key):
        del key  # dropout 0 under pp (config validation)
        B, T = x.shape
        if B % M != 0 or B % pp != 0 or (B // M) % pp != 0:
            raise ValueError(
                f"per-data-shard batch {B} must be divisible by "
                f"pipeline_microbatches={M} (and each microbatch by pp={pp} "
                "for the scattered CE) — lower them or raise batch_size"
            )
        Bm = B // M
        Bmp = Bm // pp
        s = jax.lax.axis_index("pp")
        rope = rope_table(model_cfg.head_dim, T)
        f32 = jnp.float32

        full_wte = _gather_leaf(params.wte, param_specs.wte)
        full_head = _gather_leaf(params.lm_head, param_specs.lm_head)
        x_tok = x.reshape(M, Bm, T)
        y_mb = y.reshape(M, Bm, T)
        # NO up-front (M, Bm, T, D) embedding buffer (GPipe embeds the whole
        # batch before its scan): stage 0 embeds ONE microbatch per tick
        # inside the loop, keeping the schedule's memory M-independent —
        # only the int32 token ids are M-sized.

        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]
        stage_fn = functools.partial(
            gpipe_stage_apply, model_cfg, rope=rope, layer_transform=gather_block
        )

        def ce_fn(shard, head, y_slice):
            hidden = rms_norm(shard, eps=1e-5)
            return fused_linear_cross_entropy(
                hidden, head, y_slice, loss_chunk_tokens, loss_remat_chunks
            )

        act_shape = (Bm, T, model_cfg.n_embd)
        act_dtype = full_wte.dtype
        gblocks0 = jax.tree.map(lambda p: jnp.zeros(p.shape, f32), params.blocks)
        carry0 = dict(
            stash=jnp.zeros((S,) + act_shape, act_dtype),
            fwd_recv=jnp.zeros(act_shape, act_dtype),
            bwd_recv=jnp.zeros(act_shape, f32),
            dh_pend=jnp.zeros(act_shape, f32),
            gblocks=gblocks0,
            dwte=jnp.zeros(full_wte.shape, f32),
            dhead=jnp.zeros(full_head.shape, f32),
            loss=jnp.zeros((), f32),
        )
        n_ticks = M + 2 * pp - 1

        def tick(c, t):
            # ---- forward stream: F of mf = t - s at this stage
            mf = t - s
            f_valid = (mf >= 0) & (mf < M)
            mf_c = jnp.clip(mf, 0, M - 1)
            tok_f = jax.lax.dynamic_index_in_dim(x_tok, mf_c, 0, keepdims=False)
            inp = jnp.where(
                s == 0,
                jnp.take(full_wte, tok_f, axis=0).astype(act_dtype),
                c["fwd_recv"],
            )
            out = stage_fn(params.blocks, inp)
            slot_f = mf_c % S
            stash = jax.lax.dynamic_update_index_in_dim(
                c["stash"],
                jnp.where(f_valid, inp, c["stash"][slot_f]),
                slot_f,
                0,
            )

            # ---- CE + cotangent seed for the microbatch finishing this tick
            mf_last = t - (pp - 1)  # uniform scalar across stages
            ce_valid = (mf_last >= 0) & (mf_last < M)
            mf_last_c = jnp.clip(mf_last, 0, M - 1)
            o_ce = jnp.where(s == pp - 1, out, jnp.zeros_like(out))
            shard = jax.lax.psum_scatter(
                o_ce, "pp", scatter_dimension=0, tiled=True
            )  # (Bm/pp, T, D)
            y_m = jax.lax.dynamic_index_in_dim(y_mb, mf_last_c, 0, keepdims=False)
            y_slice = jax.lax.dynamic_slice_in_dim(y_m, s * Bmp, Bmp, axis=0)
            lm, pull_ce = jax.vjp(lambda sh, hd: ce_fn(sh, hd, y_slice), shard, full_head)
            lm = jax.lax.pmean(lm, "pp")
            dshard, dhead_m = pull_ce(jnp.asarray(1.0 / pp, lm.dtype))
            dh_full = jax.lax.all_gather(
                dshard.astype(f32), "pp", axis=0, tiled=True
            )  # (Bm, T, D)
            loss = c["loss"] + jnp.where(ce_valid, lm.astype(f32), 0.0)
            dhead = c["dhead"] + jnp.where(ce_valid, dhead_m.astype(f32), 0.0)

            # ---- backward stream: B of mb = t - 2*pp + 1 + s at this stage
            mb = t - 2 * pp + 1 + s
            b_valid = (mb >= 0) & (mb < M)
            mb_c = jnp.clip(mb, 0, M - 1)
            inp_b = c["stash"][mb_c % S]
            cot = jnp.where(s == pp - 1, c["dh_pend"], c["bwd_recv"])
            _, pull_stage = jax.vjp(
                lambda bl, ii: stage_fn(bl, ii), params.blocks, inp_b
            )
            dbl, dinp = pull_stage(cot.astype(out.dtype))
            bm = b_valid.astype(f32)
            gblocks = jax.tree.map(
                lambda g, d: g + d.astype(f32) * bm, c["gblocks"], dbl
            )
            tok_b = jax.lax.dynamic_index_in_dim(x_tok, mb_c, 0, keepdims=False)
            dinp32 = dinp.astype(f32) * (bm * (s == 0).astype(f32))
            dwte = c["dwte"].at[tok_b.reshape(-1)].add(
                dinp32.reshape(-1, dinp32.shape[-1])
            )

            # ---- sends
            new_c = dict(
                stash=stash,
                fwd_recv=jax.lax.ppermute(out, "pp", perm_fwd),
                bwd_recv=jax.lax.ppermute(dinp.astype(f32), "pp", perm_bwd),
                dh_pend=jnp.where(ce_valid, dh_full, jnp.zeros_like(dh_full)),
                gblocks=gblocks,
                dwte=dwte,
                dhead=dhead,
                loss=loss,
            )
            return new_c, None

        c, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))

        scale = 1.0 / (M * n_batch)
        loss = jax.lax.pmean(c["loss"] / M, BATCH_AXES)

        # blocks: the batch shards over BOTH 'data' and 'fsdp'. For
        # fsdp-SHARDED leaves the gather's vjp already reduce-scattered the
        # fsdp contributions; fsdp-REPLICATED leaves (below fsdp_min_size,
        # shard_model=False, or no divisible axis — e.g. q/k scales) still
        # hold only this rank's batch contribution and need the psum that
        # shard_map AD inserts for the GPipe path. Then sum the data shards
        # and apply the loss-mean scale.
        def block_reduce(g, spec):
            if mesh.shape["fsdp"] > 1 and _sharded_axis(spec) is None:
                g = jax.lax.psum(g, "fsdp")
            if mesh.shape["data"] > 1:
                g = jax.lax.psum(g, "data")
            return g * scale

        gblocks = jax.tree.map(block_reduce, c["gblocks"], param_specs.blocks)
        # wte / lm_head: only stage 0 / the CE contribute (masked), so the
        # pp-psum collects them; data-psum + fsdp reduce-scatter as above.
        def emb_reduce(g, spec):
            g = jax.lax.psum(g, "pp")
            if mesh.shape["data"] > 1:
                g = jax.lax.psum(g, "data")
            return _reduce_to_spec(g, spec) * scale

        grads = GPTParams(
            wte=emb_reduce(c["dwte"], param_specs.wte),
            blocks=gblocks,
            lm_head=emb_reduce(c["dhead"], param_specs.lm_head),
        )
        return loss, grads

    batch_spec = P(BATCH_AXES, None)
    return shard_map(
        local_loss_and_grad,
        mesh=mesh,
        in_specs=(param_specs, batch_spec, batch_spec, P()),
        out_specs=(P(), param_specs),
        check_vma=False,
    )
