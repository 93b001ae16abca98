"""Training runtime: one compiled SPMD step + the host-side experiment loop.

Step semantics match the reference hot path (reference train.py:69-97) for
val-loss parity:
  * fp32 master params, cast to the compute dtype (bf16) once per step;
  * `lax.scan` over `g_accum_iters` microbatches, each microgradient
    re-constrained to the FSDP layout (so accumulation happens *sharded* —
    GSPMD reduce-scatters each microstep, reference train.py:87) and
    accumulated in fp32 pre-scaled by 1/G (no epilogue divide); losses
    averaged on the scalar;
  * optax update + apply, params re-constrained, buffers donated.

The whole step — microbatching, collectives, optimizer — is ONE XLA program
(jit with donate_argnums), executing identically on every device of every
host. Eval runs `eval_steps` fresh seeded batches at compute dtype with
dropout off (reference train.py:99-117).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np
import optax

from midgpt_tpu.config import ExperimentConfig
from midgpt_tpu.data.dataset import TokenDataset
from midgpt_tpu.models.gpt import GPTParams
from midgpt_tpu.obs import STEP_SCOPES, dump_flight_recorder, flight_recorder
from midgpt_tpu.ops.loss import fused_linear_cross_entropy
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.fsdp import constrain, named_shardings
from midgpt_tpu.parallel.mesh import batch_spec, fit_mesh_config, make_mesh
from midgpt_tpu.robustness import faults, preempt
from midgpt_tpu.robustness.errors import DivergenceError
from midgpt_tpu.robustness.watchdog import StepWatchdog
from midgpt_tpu.training.checkpoint import CheckpointManager, _abstract_like
from midgpt_tpu.training.metrics import MetricLogger, Profiler, Progress, mfu
from midgpt_tpu.training.optim import make_optimizer, make_schedule

Array = jax.Array


def health_flag(grad, loss: Array, prev_loss: Array) -> Array:
    """Sticky post-update health, folded into the reported loss.

    Returns the loss to report: `loss` when this step AND every earlier step
    were healthy, else NaN. Three properties (each pinned by
    tests/test_train.py):

    * **Leaf-wise finiteness, not global-norm finiteness.** isfinite of
      `optax.global_norm(grad)` squares in fp32, so large-but-finite grads
      (|g| ~ 1e20) overflow the squared sum to inf and would flag a step
      that clip_by_global_norm(1.0) handles fine (scale -> ~0, training
      recovers) — a spurious hard stop (ADVICE r4). The per-leaf
      `all(isfinite)` reductions read the same grad leaves the optimizer's
      clip reads; measured free on the v5e G=1 124M bench (48.5/48.9% MFU
      vs the 48.8% r3/r4 baseline, within the ±0.3 noise band — unlike the
      non-CSE'd global_norm(updates) variant, which cost −1.4 MFU).
    * **Sticky via the reported loss.** A non-finite step at an iteration
      that is neither a log nor a save step could otherwise leave NaN only
      in optimizer state (e.g. Adam mu of a rare embedding row whose later
      grads are 0) while every later loss/grad is finite — and a later save
      would persist it (ADVICE r4). Threading the previous REPORTED loss in
      and NaN-poisoning on `~isfinite(prev_loss)` makes badness sticky by
      induction, with no extra carry in the step signature: every later
      log raise / pre-save gate / final force-save sees NaN.
    * **Soundness by induction** (unchanged): state_t finite ∧ grad_t finite
      ⇒ clip/adam/wd/schedule all finite ⇒ state_{t+1} finite; so a NaN/Inf
      anywhere first shows in some step's grad leaves or loss. The base case
      for restored checkpoints is the resume-time sweep below. The induction
      is a property of THIS chain (training/optim.py: clip(1.0) is
      0-norm-safe, adam bias correction needs beta2<1 — enforced by config
      validation, eps>0); revisit if the chain changes."""
    grads_ok = jnp.all(
        jnp.stack([jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grad)])
    )
    healthy = grads_ok & jnp.isfinite(loss) & jnp.isfinite(prev_loss)
    return jnp.where(healthy, loss, jnp.nan)


_CAST_PARAMS, _, _GRAD_ACCUM, _OPTIMIZER, _HEALTH = STEP_SCOPES


def make_train_step(
    config: ExperimentConfig,
    optimizer: optax.GradientTransformation,
    mesh,
    param_specs,
) -> tp.Tuple[tp.Callable, tp.Callable, tp.Callable]:
    """Build (step, eval_loss, eval_loss_many) jitted functions."""
    model_cfg = config.model_config
    # The model is reached through what every family's config and namespace
    # provide (models/__init__.py), never by name.
    model = model_cfg.model()
    model_cfg.check_training("make_train_step")  # a family with no backward stops here, by name
    if mesh.shape["tp"] > 1 and model_cfg.qkv_proj == "fused":  # tp: the GPT only (check_experiment)
        # The fused lowering reshapes the tp-sharded feature axis into the
        # merged 3D axis (a reshard); the batched per-third form keeps each
        # of q/k/v independently column-sharded (models/gpt.py _project_qkv).
        import dataclasses

        model_cfg = dataclasses.replace(model_cfg, qkv_proj="split3")
    compute_dtype = jnp.dtype(config.compute_dtype)
    G = config.g_accum_iters

    # Sequence parallelism: ring attention is bound to the mesh here (the
    # model is mesh-agnostic; attention is its only cross-token op). The
    # GSPMD-sharded wrapper serves the implicit-FSDP train loss and all
    # eval paths; the explicit shard_map path calls the ring directly
    # inside its own body (no nesting — see make_shard_map_loss).
    attn_fn = None
    if model_cfg.attn_impl == "ring":
        from midgpt_tpu.parallel.ring_attention import ring_attention_sharded

        attn_fn = functools.partial(
            ring_attention_sharded,
            mesh=mesh,
            block_size=model_cfg.attn_block_size,
            # tp x sp composition: the ring is head-independent, so with a
            # real 'tp' axis each device runs the ring over its head shard.
            head_axis="tp" if mesh.shape["tp"] > 1 else None,
        )
    elif model_cfg.attn_impl == "ulysses":
        from midgpt_tpu.parallel.ulysses import ulysses_attention_sharded

        attn_fn = functools.partial(
            ulysses_attention_sharded,
            mesh=mesh,
            block_size=model_cfg.attn_block_size,
            head_axis="tp" if mesh.shape["tp"] > 1 else None,
        )

    elif (
        model_cfg.attn_impl == "flash"
        and mesh.devices.size > 1
        and mesh.shape["pp"] == 1
    ):
        # The implicit-GSPMD forward on more than one device — the train loss
        # under the compiler's schedule, and the EVAL path under either
        # schedule: the compiler cannot partition a Mosaic kernel, so the
        # flash call is mapped over the batch axes by hand (the authored
        # shard_map loss and the pipeline already run the kernel inside
        # their own per-device bodies and never read this attn_fn).
        from midgpt_tpu.ops.attention import flash_attention_sharded

        attn_fn = functools.partial(
            flash_attention_sharded,
            mesh=mesh,
            block_size=model_cfg.attn_block_size,
            head_axis="tp" if mesh.shape["tp"] > 1 else None,
        )

    loss_and_grad_fn = None  # set only by the 1F1B pipeline schedule
    if mesh.shape["pp"] > 1:
        from midgpt_tpu.parallel.pipeline import (
            make_pipeline_loss,
            make_pipeline_loss_and_grad,
        )

        # The GPipe loss serves eval under BOTH schedules (same math,
        # dropout-free); 1F1B replaces only the value_and_grad of training.
        _pp_loss = make_pipeline_loss(
            model_cfg, mesh, param_specs, config.loss_chunk_tokens,
            config.loss_remat_chunks,
            microbatches=config.pipeline_microbatches,
        )
        if config.pipeline_schedule == "1f1b":
            loss_and_grad_fn = make_pipeline_loss_and_grad(
                model_cfg, mesh, param_specs, config.loss_chunk_tokens,
                config.loss_remat_chunks,
                microbatches=config.pipeline_microbatches,
            )

        def loss_fn(params_c: GPTParams, x: Array, y: Array, key) -> Array:
            return _pp_loss(params_c, x, y, key)

    elif config.fsdp_schedule(mesh.shape) == "authored":
        # Derived from the mesh and the model, not asked of the user
        # (ExperimentConfig.fsdp_schedule): never on a one-device mesh.
        from midgpt_tpu.parallel.shard_map_fsdp import make_shard_map_loss

        _sm_loss = make_shard_map_loss(
            model_cfg, mesh, param_specs, config.loss_chunk_tokens,
            config.loss_remat_chunks,
            sequence_parallel=(
                model_cfg.attn_impl
                if model_cfg.attn_impl in ("ring", "ulysses")
                else None
            ),
        )

        def loss_fn(params_c: GPTParams, x: Array, y: Array, key) -> Array:
            return _sm_loss(params_c, x, y, key)

    else:
        # Router load-balance pressure (config.moe_aux_coef): CE +
        # coef * aux. Gated at trace time — with the default coef of 0.0
        # the aux term is never even requested, so this path's compiled
        # program is byte-identical to the pre-knob loss (zero-impact pin
        # in tests/test_moe.py).
        use_moe_aux = (
            config.moe_aux_coef != 0.0 and model_cfg.n_experts > 0
        )

        aux_kw = {"return_moe_aux": True} if use_moe_aux else {}

        def loss_fn(params_c: GPTParams, x: Array, y: Array, key) -> Array:
            h = model.hidden(
                model_cfg, params_c, x, key=key, inference=False, attn_fn=attn_fn,
                **aux_kw,
            )
            if use_moe_aux:
                h, aux = h
            ce = fused_linear_cross_entropy(
                h, params_c.lm_head, y, config.loss_chunk_tokens,
                config.loss_remat_chunks,
            )
            return ce + config.moe_aux_coef * aux if use_moe_aux else ce

    def cast_compute(params: GPTParams) -> GPTParams:
        return model.cast_params(params, compute_dtype)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params: GPTParams, opt_state, x_GBT: Array, y_GBT: Array, key,
             prev_loss=0.0):
        # The step's phases carry jax.named_scopes (obs.STEP_SCOPES; the
        # model opens embed / block / attn / mlp / final_norm, ops/loss.py
        # lm_head_loss): metadata that names the ops in a device trace for
        # benchmarks/metrics/step_phases.py. They change no instruction
        # (tests/test_tracing.py pins the count). None may be opened around a
        # Pallas kernel call: the TPU compiler names a custom call after its
        # innermost scope, and the kernel readers match `attn.<n>`.
        with jax.named_scope(_CAST_PARAMS):
            params_c = cast_compute(params)
        keys = jax.random.split(key, G)

        value_and_grad = (
            loss_and_grad_fn
            if loss_and_grad_fn is not None
            else jax.value_and_grad(loss_fn)
        )
        if G == 1:
            # No accumulation machinery: skip the zeros-init + add + divide
            # passes over a full parameter-sized buffer (~3 HBM sweeps).
            loss, grad = value_and_grad(params_c, x_GBT[0], y_GBT[0], keys[0])
            with jax.named_scope(_GRAD_ACCUM):
                grad = constrain(grad, param_specs, mesh)
                grad = jax.tree.map(lambda g, p: g.astype(p.dtype), grad, params)
        else:

            # The /G rides each accumulate as a fused elementwise scale, so
            # the epilogue divide's parameter-sized read+write sweep
            # disappears. (Measured: the whole accumulation machinery is
            # ~3 ms of a 2.2 s G=16 step at 124M (measured on an earlier
            # toolchain, not re-measured) — so no
            # first-microstep peel: it would double the compiled graph for
            # a win within noise.) Math is the reference's sharded-fp32
            # accumulation (reference train.py:85-94) up to f32
            # reassociation of the mean.
            inv_G = 1.0 / G

            def microstep(grad_acc, xyk):
                x, y, k = xyk
                loss, grad = value_and_grad(params_c, x, y, k)
                with jax.named_scope(_GRAD_ACCUM):
                    grad = constrain(grad, param_specs, mesh)
                    grad_acc = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype) * inv_G, grad_acc, grad
                    )
                return grad_acc, loss

            with jax.named_scope(_GRAD_ACCUM):
                grad_init = jax.tree.map(jnp.zeros_like, params)
            grad, losses = jax.lax.scan(microstep, grad_init, (x_GBT, y_GBT, keys))
            loss = jnp.mean(losses)
        with jax.named_scope(_OPTIMIZER):
            updates, opt_state = optimizer.update(grad, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = constrain(params, param_specs, mesh)
        # Post-UPDATE health, folded into the reported loss: the scalar loss
        # is computed from the PRE-update params, so on its own it shows
        # divergence one step after the poisoned state could already have
        # been checkpointed. Semantics + cost rationale: health_flag above.
        # Callers that thread the previous reported loss back in (the train
        # loop) get sticky poisoning; one-shot callers (benches, parity
        # tests) pass nothing and get the per-step check.
        with jax.named_scope(_HEALTH):
            loss = health_flag(grad, loss, prev_loss)
        return params, opt_state, loss

    def _eval_loss_one(params_c: GPTParams, x: Array, y: Array) -> Array:
        if mesh.shape["pp"] > 1:
            # GSPMD cannot shard a scan over its length axis, so the dense
            # backbone would all-gather the stage-sharded blocks; evaluate
            # through the same GPipe schedule instead (dropout-free, so the
            # train-mode loss IS the eval loss).
            return loss_fn(params_c, x, y, None)
        h = model.hidden(model_cfg, params_c, x, inference=True, attn_fn=attn_fn)
        return fused_linear_cross_entropy(
            h, params_c.lm_head, y, config.loss_chunk_tokens,
            config.loss_remat_chunks,
        )

    @jax.jit
    def eval_loss(params: GPTParams, x: Array, y: Array) -> Array:
        return _eval_loss_one(cast_compute(params), x, y)

    @jax.jit
    def eval_loss_many(params: GPTParams, x_NBT: Array, y_NBT: Array) -> Array:
        """SUMMED loss over a stacked (N, B, T) eval set in one device-side
        scan. Returning the sum (not the mean) lets `evaluate` chunk the
        eval set to a fixed host-memory budget over the same windows, with
        one division at the end (equal to the monolithic mean up to f32
        re-association of the chunk subtotals). Still asynchronous — the
        caller syncs once per eval, vs the reference's 200 sequential jit
        calls + float() round-trips (reference train.py:107-117)."""
        params_c = cast_compute(params)

        def body(total, xy):
            x, y = xy
            return total + _eval_loss_one(params_c, x, y), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (x_NBT, y_NBT))
        return total

    return step, eval_loss, eval_loss_many


def init_state(config: ExperimentConfig, mesh) -> tp.Tuple[GPTParams, tp.Any, tp.Any, tp.Any]:
    """Sharded-at-birth params + optimizer state (never materialized dense).

    Returns (params, opt_state, param_specs, optimizer)."""
    optimizer, _ = make_optimizer(config)
    model = config.model_config.model()
    abstract_params = jax.eval_shape(
        lambda k: model.init(config.model_config, k), jax.random.PRNGKey(0)
    )
    param_specs = model.param_specs(config, abstract_params, mesh)

    def init_fn(key):
        params = model.init(config.model_config, key)
        params = jax.tree.map(lambda p: p.astype(jnp.dtype(config.param_dtype)), params)
        return constrain(params, param_specs, mesh)

    params = jax.jit(init_fn)(jax.random.PRNGKey(config.seed))

    abstract_opt = jax.eval_shape(optimizer.init, abstract_params)
    opt_specs = model.param_specs(config, abstract_opt, mesh)  # the same rule over the optimizer's tree
    opt_state = jax.jit(
        optimizer.init, out_shardings=named_shardings(opt_specs, mesh)
    )(params)
    return params, opt_state, param_specs, optimizer


def evaluate(
    config: ExperimentConfig,
    eval_loss_many: tp.Callable,
    params: GPTParams,
    dataset: TokenDataset,
    split: str,
    mesh,
    step_idx: int,
) -> float:
    """Stream the eval set through fixed-size device programs, one sync.

    Host memory is bounded to `eval_host_chunk` batches at a time (at
    openwebtext_mh scale the whole 200-batch eval set is ~1.7 GB of int32
    per host — an avoidable cliff). Each chunk is dispatched asynchronously
    and only the final total is pulled to host, so the single-sync property
    of the batched eval is preserved; the chunked result sums the same
    windows (accum_slice) and differs from the monolithic one only by f32
    re-association of chunk subtotals."""
    # leading N axis ~ the accum axis; sequence shards over 'sp' when on
    spec = batch_spec(with_accum=True, shard_seq=mesh.shape["sp"] > 1)
    n = 1 if config.debug else config.eval_steps
    chunk = max(1, min(n, config.eval_host_chunk))
    total = None
    for lo in range(0, n, chunk):
        m = min(chunk, n - lo)
        x, y = dataset.batch(
            split,
            # decorrelate eval batches from train batches and across evals
            1_000_000_000 + step_idx,
            config.model_config.block_size,
            config.batch_size // jax.process_count(),
            g_accum_iters=n,
            accum_slice=(lo, m),
        )
        xg = make_global_batch(x, mesh, spec)
        yg = make_global_batch(y, mesh, spec)
        part = eval_loss_many(params, xg, yg)  # async device scalar (sum)
        total = part if total is None else total + part
    return float(total) / n


def _all_finite(tree) -> Array:
    """Device-side finiteness sweep over every floating leaf of `tree`."""
    return jnp.all(
        jnp.array(
            [
                jnp.all(jnp.isfinite(l))
                for l in jax.tree.leaves(tree)
                if jnp.issubdtype(l.dtype, jnp.floating)
            ]
        )
    )


def describe_param_placement(params) -> str:
    """One line: the parameter bytes each local device holds, next to the
    global total. Under FSDP every device should hold about total/fsdp; a
    mesh that quietly put everything on the first chip shows here at once."""
    per_device: tp.Dict[int, int] = {}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    held = ", ".join(f"{d}: {n}" for d, n in sorted(per_device.items()))
    return f"param bytes per device: {{{held}}} of {total} total"


def describe_step_program(step: tp.Callable, args: tp.Tuple, attn_impl: str) -> str:
    """One line saying what the COMPILED train step is made of: how many
    Mosaic (Pallas) kernel calls its optimized HLO holds, on which backend.
    With attn_impl='flash' on a TPU that count is the flash forward and
    backward kernels; zero there would mean the kernels ran interpreted or
    not at all. Costs no second compile: the jit call that follows reuses
    the executable this lowering compiled (same avals, same cache entry —
    pinned by tests/test_recompile_pins.py)."""
    hlo = step.lower(*args).compile().as_text()
    return (
        f"train step program: {hlo.count('tpu_custom_call')} Mosaic kernel "
        f"call(s) (attn_impl={attn_impl!r}, backend {jax.default_backend()!r})"
    )


@dataclasses.dataclass
class TrainRuntime:
    """Everything about a run that survives a restart attempt.

    The supervisor's rollback (robustness/supervisor.py) re-enters `train`
    after restoring a checkpoint; rebuilding the jitted step there would
    recompile the entire program per attempt (minutes at scale — and pinned
    against by tests/test_robustness.py with the test_recompile_pins.py
    methodology). A TrainRuntime carries the mesh, dataset, and every jitted
    callable across attempts; only host-side config fields (e.g.
    `data_step_offset`) may differ between the attempts that share one.
    """

    mesh: tp.Any
    dataset: TokenDataset
    optimizer: tp.Any
    schedule: tp.Callable
    param_specs: tp.Any
    step: tp.Callable
    eval_loss: tp.Callable
    eval_loss_many: tp.Callable
    # Abstract {"params", "opt_state"} with shardings — the restore template,
    # so a rollback attempt never needs live donated buffers from a previous
    # attempt.
    abstract_state: tp.Dict[str, tp.Any]
    finite_check: tp.Callable
    n_params: int
    _initial: tp.Optional[tp.Tuple[tp.Any, tp.Any]] = None
    # The step's arguments as the loop passes them, abstract (shapes and
    # shardings; the key and the loss carrier concrete): what
    # `step_program_text` lowers with.
    step_avals: tp.Tuple = ()
    # The collective schedule the step program took on this mesh: 'authored'
    # (parallel/shard_map_fsdp.py) or 'compiler' (implicit GSPMD) —
    # ExperimentConfig.fsdp_schedule(mesh.shape), the branch make_train_step
    # picked the loss by. Logged by make_runtime; the flight recorder's gauge
    # `fsdp.schedule_authored` is 1 / 0 for it.
    fsdp_schedule: str = "compiler"
    # Share of the (T, T) causal score matrix the flash kernels form a call
    # at this model's (T, attn_block_size): 1.0 = all of it, 0.5 the limit
    # (kernels/flash_attention.py score_tile_share); None where attn_impl is
    # not 'flash'. Logged by make_runtime; gauge `attn.score_tile_share`.
    attn_score_tile_share: tp.Optional[float] = None
    # Jitted (params, x (B, T)) -> {counter name: scalar} of the model's own
    # counters (models/kimi_linear.py route_stats: moe.*), or None for a model
    # that has none. Forward only, one microbatch, off the step program.
    model_stats: tp.Optional[tp.Callable] = None
    _step_text: tp.Optional[str] = None

    def step_program_text(self) -> str:
        """Optimized HLO of the compiled step program, with each
        instruction's `metadata={op_name="jit(step)/.../<scope>/<op>"}`: the
        named scopes of obs.STEP_SCOPES and models/gpt.py by instruction
        name. A device trace names its ops by instruction (`fusion.2826`)
        and, on the v5e, carries no scope path of its own, so this text is
        what maps a traced op to its phase
        (benchmarks/metrics/step_phases.py). Same avals as the loop's call,
        so the compile is the persistent cache's entry of the running
        program (a load, not a compile); kept, since more than one reader
        asks (step_phases.py, hybrid_step_phases.py)."""
        if self._step_text is None:
            self._step_text = self.step.lower(*self.step_avals).compile().as_text()
        return self._step_text

    def take_initial(self, config: ExperimentConfig) -> tp.Tuple[tp.Any, tp.Any]:
        """Hand out the freshly initialized state (once); re-init if a later
        attempt starts from scratch (the first attempt donated the buffers)."""
        if self._initial is None:
            params, opt_state, _, _ = init_state(config, self.mesh)
            return params, opt_state
        state, self._initial = self._initial, None
        return state

    def rebuild(
        self,
        config: ExperimentConfig,
        *,
        devices: tp.Optional[tp.Sequence[tp.Any]] = None,
    ) -> "TrainRuntime":
        """A fresh runtime on a DIFFERENT topology (elastic resume).

        `devices` is the new slice (default: every visible device); the
        mesh's data and fsdp axes are re-derived for the new count
        (fit_mesh_config), so the same config resumes on whatever the
        scheduler gives back. The dataset is shared — the positional
        sampler is device-count-independent, which is what keeps the global
        batch order (and so the loss trajectory) continuous across the
        move. The step program necessarily recompiles ONCE for the new
        mesh; the warm-then-count pin in tests/test_robustness.py holds it
        to exactly one."""
        return make_runtime(config, devices=devices, dataset=self.dataset)


def _report_score_tile_share(model_cfg) -> tp.Optional[float]:
    """How far the flash kernels' causal tile skipping engages at this
    model's sequence length, said once at start-up and set as the gauge
    `attn.score_tile_share`; None (and silent) for any other attn_impl."""
    if model_cfg.attn_impl != "flash":
        return None
    import importlib

    from midgpt_tpu.ops.attention import flash_block_sizes

    # by module path: the kernels package re-exports a same-named function
    fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
    T = model_cfg.block_size
    bq, bk = flash_block_sizes(T, model_cfg.attn_block_size)
    share = fa.score_tile_share(T, bq, bk)
    flight_recorder().metrics.gauge("attn.score_tile_share").set(share)
    if jax.process_index() == 0:
        print(
            f"flash attention: T={T} in blocks ({bq}, {bk}) forms {share:.4f} "
            "of the causal score matrix (score_tile_share; 0.5 is the limit)"
        )
    return share


def make_runtime(
    config: ExperimentConfig,
    *,
    devices: tp.Optional[tp.Sequence[tp.Any]] = None,
    dataset: tp.Optional[TokenDataset] = None,
) -> TrainRuntime:
    """Build the mesh/dataset/compiled-step bundle `train` runs on.

    `devices` pins the mesh to an explicit slice (elastic resume,
    TrainRuntime.rebuild): the data and fsdp axes are re-derived for the
    new count (parallel/mesh.py fit_mesh_config), so ONE config builds a
    valid mesh on whatever topology the run lands on. Without `devices`
    the configured mesh is taken literally and must fit. `dataset` reuses
    an already-open TokenDataset — the positional sampler is
    device-count-independent, which is the property that keeps the global
    batch order continuous across a mesh change."""
    mesh_cfg = config.mesh
    if devices is not None:
        mesh_cfg = fit_mesh_config(mesh_cfg, len(devices))
    mesh = make_mesh(mesh_cfg, devices=devices)
    n_proc = jax.process_count()
    assert config.batch_size % n_proc == 0, "global batch must divide process count"
    if dataset is None:
        dataset = TokenDataset(
            config.data_dir, seed=config.data_seed, shard_by_process=n_proc > 1
        )
    params, opt_state, param_specs, optimizer = init_state(config, mesh)
    schedule = make_schedule(config)
    step, eval_loss, eval_loss_many = make_train_step(
        config, optimizer, mesh, param_specs
    )
    fsdp_schedule = config.fsdp_schedule(mesh.shape)
    flight_recorder().metrics.gauge("fsdp.schedule_authored").set(
        float(fsdp_schedule == "authored")
    )
    # the authored schedule sums a layer's gradients across 'fsdp' with its
    # own ppermutes (parallel/shard_map_fsdp.py), n-1 a layer; the
    # compiler's schedule has whatever collective the compiler chose
    grad_hops = mesh.shape["fsdp"] - 1 if fsdp_schedule == "authored" else 0
    flight_recorder().metrics.gauge("fsdp.grad_ring_hops").set(float(grad_hops))
    if jax.process_index() == 0:
        if grad_hops:
            grad_sum = f"{grad_hops} authored ppermute(s) a layer, one layer behind the backward"
        else:
            grad_sum = "the compiler's" if fsdp_schedule == "compiler" else "none across fsdp=1"
        print(
            f"fsdp schedule: {fsdp_schedule} (fsdp_mode={config.fsdp_mode!r}) "
            f"on mesh {dict(mesh.shape)}; gradient sum over fsdp: {grad_sum}"
        )
    score_tile_share = _report_score_tile_share(config.model_config)
    global _LAST_RUNTIME
    abstract_params, abstract_opt = _abstract_like(params), _abstract_like(opt_state)
    model = config.model_config.model()
    model_stats = None
    if model.route_stats is not None:
        # forward-only counters of ONE microbatch (B, T), for the loop's logged
        # steps: the step program's own outputs stay (params, opt_state, loss)
        compute_dtype = jnp.dtype(config.compute_dtype)
        model_stats = jax.jit(
            lambda p, x: model.route_stats(config.model_config, model.cast_params(p, compute_dtype), x)
        )
    batch = jax.ShapeDtypeStruct(
        (config.g_accum_iters, config.batch_size, config.model_config.block_size),
        jnp.int32,
        sharding=jax.sharding.NamedSharding(
            mesh, batch_spec(with_accum=True, shard_seq=mesh.shape["sp"] > 1)
        ),
    )
    loss_carrier = jax.ShapeDtypeStruct(
        (), jnp.float32,
        sharding=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
    )
    _LAST_RUNTIME = TrainRuntime(
        mesh=mesh,
        dataset=dataset,
        optimizer=optimizer,
        schedule=schedule,
        param_specs=param_specs,
        step=step,
        eval_loss=eval_loss,
        eval_loss_many=eval_loss_many,
        abstract_state={"params": abstract_params, "opt_state": abstract_opt},
        finite_check=jax.jit(_all_finite),
        n_params=model.count_params(params),
        fsdp_schedule=fsdp_schedule,
        attn_score_tile_share=score_tile_share,
        model_stats=model_stats,
        _initial=(params, opt_state),
        step_avals=(
            abstract_params, abstract_opt, batch, batch,
            jax.random.fold_in(jax.random.PRNGKey(config.seed), 0), loss_carrier,
        ),
    )
    return _LAST_RUNTIME


# TEMPORARY seam, like obs.live(): benchmarks/metrics/*.py readers get no
# handle on the runtime the cell built, so the newest one stays reachable
# here until a `benchmark` issue lets the cells hand it to the readers.
_LAST_RUNTIME: tp.Optional[TrainRuntime] = None


def last_runtime() -> tp.Optional[TrainRuntime]:
    """The TrainRuntime `make_runtime` last built in this process."""
    return _LAST_RUNTIME


def train(
    config: ExperimentConfig, *, runtime: tp.Optional[TrainRuntime] = None
) -> dict:
    """Run the experiment; returns final metrics (for tests/benches).

    `runtime` lets a supervisor re-enter after a rollback without
    recompiling anything (TrainRuntime docstring). Resume picks the newest
    *verified* checkpoint (training/checkpoint.py manifests), so a save
    truncated by a preemption is skipped, not restored."""
    rt = runtime if runtime is not None else make_runtime(config)
    mesh, dataset, schedule = rt.mesh, rt.dataset, rt.schedule
    step, eval_loss_many = rt.step, rt.eval_loss_many
    local_bs = config.batch_size // jax.process_count()
    if jax.process_index() == 0:
        print(f"Model has {rt.n_params:,} parameters.")

    mngr = None
    first_step = 0
    params = opt_state = None
    if not config.debug and config.rundir:
        mngr = CheckpointManager(
            config.rundir,
            max_to_keep=config.ckpt_max_to_keep,
            save_interval_steps=config.eval_interval,
            write_retries=config.ckpt_write_retries,
            retry_backoff_sec=config.ckpt_retry_backoff_sec,
        )
        resume_step = mngr.latest_verified_step()
        if resume_step is not None:
            state = mngr.restore(resume_step, rt.abstract_state)
            params, opt_state = state["params"], state["opt_state"]
            first_step = resume_step + 1
            # Base case of the per-step health induction (the in-step check
            # watches grads, which cannot see a corrupted RESTORED state):
            # one device-side finiteness sweep of params + opt_state at
            # resume, one sync, never again. The manifest guards the bytes;
            # this guards the VALUES (a v2->v3 migration bug, a save of
            # NaN state by older code).
            if not bool(rt.finite_check((params, opt_state))):
                raise FloatingPointError(
                    f"checkpoint step {resume_step} in {config.rundir} "
                    "restored non-finite values — it is corrupt; do not "
                    "resume from it."
                )
    if params is None:
        params, opt_state = rt.take_initial(config)
    if jax.process_index() == 0:
        print(describe_param_placement(params))

    logger = MetricLogger(config)
    profiler = Profiler(config.rundir, enabled=config.debug)
    progress = Progress(config.max_steps, first_step, enabled=not config.debug)
    if os.environ.get("MIDGPT_VIZ_SHARDING") and jax.process_index() == 0:
        # Startup sharding diagnostic (reference sample.py:181-182): how the
        # largest weight and one batch land on the mesh.
        try:
            jax.debug.visualize_array_sharding(params.blocks.attn.wqkv[0])
        except Exception as e:  # diagnostic only — never block training
            print(f"visualize_array_sharding unavailable: {e}")
    data_sp = batch_spec(with_accum=True, shard_seq=mesh.shape["sp"] > 1)
    # Positional key stream: fold the DATA step index into the base key so
    # resumed runs continue the exact dropout-key sequence (the data sampler
    # is already positional; this makes the whole step a function of the
    # data index). `data_step_offset` shifts both streams together: after a
    # divergence rollback the supervisor advances it so the replayed steps
    # sample PAST the poisoned window — deterministically, since the offset
    # is plain config.
    base_key = jax.random.PRNGKey(config.seed)
    T = config.model_config.block_size
    metrics: tp.Dict[str, float] = {}
    import time as _time

    t_last, tokens_since = _time.time(), 0
    # Sticky health carrier (health_flag): the previous reported loss feeds
    # the next step; once NaN, always NaN, so no later save can persist a
    # state poisoned at an un-inspected step. Committed mesh-replicated
    # placement, matching the step's own loss output: an uncommitted
    # jnp.zeros here gives iteration 1 a different input-sharding aval than
    # every later iteration, silently compiling the whole step TWICE (found
    # by the pass-2 compile counter; pinned in tests/test_recompile_pins.py).
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    loss = jax.device_put(jnp.zeros((), jnp.float32), replicated)
    from midgpt_tpu.analysis.hlo_audit import jit_cache_size

    step_cache_size = functools.partial(jit_cache_size, step)
    warned_recompile = False
    preempted = False
    # Training-side flight recorder (midgpt_tpu/obs/): per-step spans and
    # lifecycle instants land in the process-global ring; crash paths
    # (DivergenceError in the supervisor, the preempt branch below) dump it
    # to the rundir as a Chrome trace for postmortems. Host-side only —
    # spans never cross the jit boundary, so the step program is untouched.
    _tr = flight_recorder().tracer
    # Hung-step watchdog (robustness/watchdog.py): the loop's host<->device
    # sync points go through `_sync` so a wedged dispatch (device hung,
    # collective stuck) is bounded by `watchdog_deadline_s` instead of blocking
    # the process forever. Off by default: `_sync` is then a plain float()
    # — no thread, no event, zero machinery (pinned by the watchdog-off
    # zero-extra-programs test in tests/test_robustness.py).
    wd = (
        StepWatchdog(
            config.watchdog_deadline_s,
            escalate=config.watchdog_escalate,
            rundir=config.rundir,
        )
        if config.watchdog_deadline_s > 0
        else None
    )

    def _sync(arr, itr: int, data_itr: int) -> float:
        # The `hang_step` fault wedges the force ITSELF (a never-set
        # event), modeling the failure where float() never returns — so
        # only the watchdog's worker-thread inversion can end the wait.
        hang = faults.should_fire("hang_step", step=data_itr)

        def force() -> float:
            if hang:
                threading.Event().wait()
            return float(arr)

        with _tr.span("train.loss_sync", "train", "train"):
            if wd is None:
                return force()
            return wd.sync(force, step=itr, label="train.loss_sync")

    try:
        for itr in range(first_step, config.max_steps):
            if itr % config.eval_interval == 0:
                with _tr.span("train.eval", "train", "train"):
                    metrics["loss/train"] = evaluate(
                        config, eval_loss_many, params, dataset, "train", mesh, itr
                    )
                    metrics["loss/val"] = evaluate(
                        config, eval_loss_many, params, dataset, "val", mesh, itr
                    )
                logger.log(itr, {k: metrics[k] for k in ("loss/train", "loss/val")})
                t_last, tokens_since = _time.time(), 0  # eval pauses don't count

            data_itr = itr + config.data_step_offset
            x, y = dataset.batch("train", data_itr, T, local_bs, config.g_accum_iters)
            xg = make_global_batch(x, mesh, data_sp)
            yg = make_global_batch(y, mesh, data_sp)
            step_key = jax.random.fold_in(base_key, data_itr)
            if itr == first_step and jax.process_index() == 0:
                print(
                    describe_step_program(
                        step, (params, opt_state, xg, yg, step_key, loss),
                        config.model_config.attn_impl,
                    )
                )
            profiler.maybe_start(itr, at_step=first_step + 1)
            # Span covers the async ENQUEUE of the one step program — the
            # feed has its own spans (`data.batch` in Dataset.batch,
            # `data.put` in make_global_batch) and device time shows up at
            # the log-interval sync (`train.loss_sync`), not here.
            with _tr.span("train.step", "train", "train"):
                params, opt_state, loss = step(params, opt_state, xg, yg, step_key, loss)
            profiler.maybe_stop(wait_for=loss)

            if faults.should_fire("nan_grad", step=data_itr):
                # Poison the sticky carrier exactly as a NaN gradient would
                # (health_flag folds grad badness into the reported loss).
                # Same committed replicated aval as the real carrier, so the
                # injection cannot recompile the step.
                loss = jax.device_put(jnp.full((), jnp.nan, jnp.float32), replicated)
            if faults.should_fire("preempt", step=data_itr):
                preempt.request()
            if faults.should_fire("resume_reshard", step=data_itr):
                # Same exit mechanics as a preemption; the DRIVER
                # (tools/chaos_run.py) restarts on a different device count,
                # exercising the cross-mesh resharding resume path
                # (TrainRuntime.rebuild + on_resume_mesh in the supervisor).
                preempt.request()

            tokens_since += config.batch_size * config.g_accum_iters * T
            if itr % config.log_interval == 0:
                loss_f = _sync(loss, itr, data_itr)
                if not np.isfinite(loss_f):
                    # Divergence guard (no reference counterpart — its NaN
                    # runs burn wall-clock until someone looks at wandb):
                    # stop loudly at the already-paid log sync, WITHOUT
                    # saving the poisoned params over the rolling
                    # checkpoint, and say where the last good state is. The
                    # supervisor catches this, rolls back, and skips the
                    # window (robustness/supervisor.py).
                    last_good = (
                        mngr.latest_verified_step() if mngr is not None else None
                    )
                    _tr.instant(
                        "train.divergence", "train", "train",
                        args={"step": itr, "last_good": last_good},
                    )
                    raise DivergenceError(
                        f"non-finite loss ({loss_f}) at step {itr} — training "
                        "has diverged. Last good checkpoint: "
                        + (f"step {last_good} in {config.rundir}"
                           if last_good is not None else "none was saved")
                        + ". Lower learning_rate or raise warmup_steps and "
                        "resume.",
                        step=itr,
                        last_good_step=last_good,
                        rundir=config.rundir,
                    )
                with _tr.span("train.log", "train", "train"):
                    dt = _time.time() - t_last
                    tok_s = tokens_since / dt if dt > 0 else 0.0
                    t_last, tokens_since = _time.time(), 0
                    # Recompile watch (graftcheck pass-2 hook): the whole step is
                    # ONE XLA program, so its jit cache must stay at exactly one
                    # entry. Growth means some input's shape/dtype is unstable
                    # across steps — the silent per-step-recompile failure mode
                    # CLAUDE.md warns about, easily >10x wall-clock, invisible in
                    # the loss. Warn at the already-paid log sync; pinned in
                    # tests/test_recompile_pins.py.
                    n_programs = step_cache_size()
                    if n_programs is not None and n_programs > 1 and not warned_recompile:
                        warned_recompile = True
                        if jax.process_index() == 0:
                            print(
                                f"WARNING: train step has compiled {n_programs} distinct "
                                "programs — input shapes/dtypes are unstable across "
                                "steps and every recompile stalls the device "
                                "(run graftcheck --audit / check batch shapes)"
                            )
                    metrics.update(
                        {
                            "loss/optimized": loss_f,
                            "lr": float(schedule(itr)),
                            "throughput/tokens_per_sec": tok_s,
                        }
                    )
                    stats = None
                    if rt.model_stats is not None:
                        # the model's own counters of this step's first
                        # microbatch: into the log line and the flight
                        # recorder's gauges (docs/OBSERVABILITY.md)
                        stats = {k: float(v) for k, v in rt.model_stats(params, xg[0]).items()}
                        metrics.update(stats)
                        for k, v in stats.items():
                            flight_recorder().metrics.gauge(k).set(v)
                    m = mfu(tok_s, config.model_config, jax.device_count(), stats)
                    if m is not None:
                        metrics["throughput/mfu"] = m
                    logger.log(itr, dict(metrics))
                    if progress.active:
                        progress.update(
                            0, loss=f"{loss_f:.4f}", lr=f"{metrics['lr']:.2e}",
                            tok_s=f"{tok_s:,.0f}",
                        )
                    elif jax.process_index() == 0:
                        print(
                            f"step {itr}: loss {loss_f:.4f} lr {metrics['lr']:.2e} "
                            f"tok/s {tok_s:,.0f}"
                        )
            progress.update(1)
            if mngr is not None and mngr.should_save(itr):
                # One device sync per SAVE interval (not per step): never let
                # a poisoned state overwrite the rolling checkpoints.
                if not np.isfinite(_sync(loss, itr, data_itr)):
                    last_good = mngr.latest_verified_step()
                    _tr.instant(
                        "train.divergence", "train", "train",
                        args={"step": itr, "last_good": last_good},
                    )
                    raise DivergenceError(
                        f"non-finite training state at step {itr} — refusing "
                        "to overwrite the rolling checkpoint. Last good "
                        f"checkpoint: step {last_good} in {config.rundir}. "
                        "Lower learning_rate or raise warmup_steps and resume.",
                        step=itr,
                        last_good_step=last_good,
                        rundir=config.rundir,
                    )
                mngr.save(itr, {"params": params, "opt_state": opt_state})
            if itr % config.preempt_check_interval == 0 and preempt.any_host_requested():
                # Preemption (SIGTERM/SIGINT or the `preempt` fault): one
                # SYNCHRONOUS emergency save at this step boundary, then a
                # clean exit. The flag is replicated across hosts
                # (robustness/preempt.py), so every host takes this branch
                # at the same itr — no host-divergent control flow around
                # the collectives inside `step`.
                grace = config.preempt_grace_s
                req_at = preempt.requested_at()
                save_late = bool(
                    grace > 0
                    and req_at is not None
                    and _time.monotonic() - req_at > grace
                )
                if save_late:
                    # The grace budget was spent before the save could even
                    # START (a long step or eval sat between the signal and
                    # this boundary): beginning a multi-second checkpoint
                    # write now risks a SIGKILL mid-write. Skip it LOUDLY —
                    # ledger note + flight-recorder dump below — and let
                    # resume fall back to the last verified checkpoint.
                    _tr.instant(
                        "train.preempt_save_skipped", "train", "train",
                        args={"step": itr, "grace_s": grace},
                    )
                    if config.rundir and not config.rundir.startswith("gs://"):
                        from midgpt_tpu.robustness import supervisor as _sup

                        _sup.append_note(
                            config.rundir,
                            {"event": "preempt_save_skipped", "step": itr,
                             "grace_s": grace},
                        )
                    if jax.process_index() == 0:
                        print(
                            f"preemption: grace budget ({grace:g}s) already "
                            f"spent at step {itr} — skipping the emergency "
                            "save; resume falls back to the last verified "
                            "checkpoint"
                        )
                elif (
                    mngr is not None
                    and mngr.latest_step() != itr  # interval save just landed?
                    and np.isfinite(_sync(loss, itr, data_itr))  # not poisoned
                ):
                    mngr.save(itr, {"params": params, "opt_state": opt_state},
                              force=True)
                    mngr.wait()  # barrier + manifest: verified before we exit
                metrics["preempted"] = True
                preempted = True
                _tr.instant(
                    "train.preempt", "train", "train", args={"step": itr}
                )
                if config.rundir and jax.process_index() == 0:
                    # SIGTERM postmortem artifact: the flight recorder's
                    # crash-adjacent tail as a loadable Chrome trace
                    # (docs/OBSERVABILITY.md "Crash dumps").
                    dump_flight_recorder(config.rundir)
                if jax.process_index() == 0 and not save_late:
                    print(
                        f"preemption: emergency checkpoint at step {itr} in "
                        f"{config.rundir or '(no rundir)'}; exiting"
                    )
                break

        if not preempted:
            metrics["loss/final"] = float(
                evaluate(
                    config, eval_loss_many, params, dataset, "val", mesh,
                    config.max_steps,
                )
            )
            logger.log(config.max_steps, {"loss/val_final": metrics["loss/final"]})
            if mngr is not None:
                # Force-persist the final state unless the in-loop save
                # already did (orbax raises StepAlreadyExists on a forced
                # duplicate).
                mngr.wait()
                # Gate on the sticky loss too: a transient mid-run poisoning
                # that left NaN only in optimizer state would pass the
                # val-loss check.
                if mngr.latest_step() != config.max_steps - 1 and np.isfinite(
                    metrics["loss/final"]
                ) and np.isfinite(float(loss)):
                    mngr.save(
                        config.max_steps - 1,
                        {"params": params, "opt_state": opt_state},
                        force=True,
                    )
    finally:
        # Never abandon an in-flight async save: a raised divergence guard
        # (or any other exception) must not leave a half-written TensorStore
        # step behind — close() barriers, manifests, and GCs.
        progress.close()
        logger.close()
        if mngr is not None:
            mngr.close()
    return {"params": params, "opt_state": opt_state, "metrics": metrics}
