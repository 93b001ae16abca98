"""Training cells (traffic kind "train"): the repo's own train step, driven as
`training/train.py` drives it between log syncs.

The system under test is `make_runtime(config)`: mesh, dataset/batcher, the one
jitted step (scan over G microbatches + optimizer). The benchmark only feeds
it and reads the clock:

    set-up   seeded token ids -> <tmp>/train.bin; make_runtime (weights on the
             device from --seed); correctness check (below); two whole steps.
    window   step i+1 is enqueued BEFORE the host blocks on step i's loss, so
             one step is always in flight and the device never waits for the
             host. The host stamps the clock when each loss is ready; a step
             duration is the difference of two consecutive stamps. The window
             opens at the stamp of the last warm-up step and ends at the first
             stamp past --seconds. Fewer than `min_durations` durations is a
             failed run.

`train_tokens_per_s` = tokens of all the whole steps of the window / the
window's length, stamp to stamp: every stall inside the window counts. With a
step always in flight a host stall shorter than a step does not idle the chip,
so the figure is steady (PERF.md, PR 23); the median and the longest step are
per-layer metrics beside it.

Correct: every loss finite, and on `check_sequences` seeded sequences of the
initial weights (a) the system's own eval loss (the forward the step uses:
flash attention, bf16 compute, fused cross-entropy) agrees with the mean of
reference.py's float32 per-token losses, and (b) per-token losses of the
system's forward agree with the reference's token by token.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import tempfile
import time

import numpy as np

# (a) |system eval loss - mean reference loss| at initialisation, over
# check_sequences x T tokens. bf16 compute against float32 moved it by 3e-5 to
# 3.4e-4 in 24 chip runs (124M and 1.5B; PERF.md, PR 23): the per-token errors
# below average out. Three times the largest seen.
MEAN_TOLERANCE = 1e-3
# (b) RMS over the tokens of (system - reference) per-token loss, as a share of
# the reference per-token losses' standard deviation. A mean hides what this
# shows: an error of random sign in every token moves the mean by little and
# this by its whole size. Read 1.0e-2 on the chip (124M, three seeds; largest
# single token 4.2e-2); four times that. On the CPU at a small size a
# non-causal mask read 8e-1, a mask without the diagonal 1.3e-1 and
# 8-bit floating point weights 8e-2 (PERF.md, PR 23).
TOKEN_RMS_TOLERANCE = 4e-2


def system_token_losses(config, mesh):
    """Jitted (params, x, y) -> (B, T) float32 per-token cross-entropy of the
    system's forward, bound as training/train.py make_train_step binds it for
    the gspmd loss and every eval path: parameters cast to the compute dtype,
    GPT.hidden, flash attention mapped over the batch axes by hand on more
    than one device, lm_head in the compute dtype, cross-entropy in float32.
    (`rt.eval_loss` gives the mean only; PERF.md lists a per-token eval in the
    program as an open question.)"""
    import functools

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.utils.precision import cast_floating

    mc, dtype = config.model_config, jnp.dtype(config.compute_dtype)
    attn_fn = None
    if mc.attn_impl == "flash" and mesh.devices.size > 1:
        from midgpt_tpu.ops.attention import flash_attention_sharded

        attn_fn = functools.partial(flash_attention_sharded, mesh=mesh, block_size=mc.attn_block_size)

    def token_losses(params, x, y):
        pc = cast_floating(params, dtype)
        h = GPT.hidden(mc, pc, x, inference=True, attn_fn=attn_fn)
        lg = jnp.einsum("btd,vd->btv", h, pc.lm_head).astype(jnp.float32)
        picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    return jax.jit(token_losses)


def resolve_config(ctx, data_dir: str):
    """ctx.repo_config() + the traffic file's batch schedule and mesh."""
    import dataclasses

    tr = ctx.traffic
    config = ctx.repo_config()
    return config.replace(
        batch_size=int(tr["microbatch_per_chip"]) * ctx.chips,
        g_accum_iters=int(tr["g_accum_iters"]),
        mesh=dataclasses.replace(config.mesh, **tr["mesh"]), shard_model=bool(tr["shard_model"]),
        seed=ctx.seed32, data_seed=ctx.seed32, data_dir=data_dir, rundir="", debug=True,
    )


def write_tokens(path: str, n: int, vocab: int, seed: int) -> None:
    rng = np.random.default_rng([seed, 7])
    rng.integers(0, vocab, n, dtype=np.uint16).tofile(path)


def run(ctx) -> dict:
    import jax

    from midgpt_tpu.training.train import make_runtime

    tr = ctx.traffic
    ctx.phases.mark("program_imports")
    with tempfile.TemporaryDirectory(prefix="bench_data_") as data_dir:
        config = resolve_config(ctx, data_dir)
        mc = config.model_config
        if mc.vocab_size > 65536:
            raise SystemExit("the dataset format is uint16: vocab_size > 65536")
        for split, n in (("train", int(tr["data_tokens"])), ("val", 4 * mc.block_size + 1)):
            write_tokens(os.path.join(data_dir, f"{split}.bin"), n, mc.vocab_size, ctx.seed32)
        ctx.phases.mark("data")
        rt = make_runtime(config)
        params, opt_state = rt.take_initial(config)
        jax.block_until_ready(params)
        ctx.phases.mark("weights")
        return _measure(ctx, config, rt, params, opt_state)


def _measure(ctx, config, rt, params, opt_state) -> dict:
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec

    tr, mc, mesh = ctx.traffic, config.model_config, rt.mesh
    T, G = mc.block_size, config.g_accum_iters
    local_bs = config.batch_size // jax.process_count()
    tokens_per_step = config.batch_size * G * T
    ctx.log(f"train: {rt.n_params:,} parameters; step = {config.batch_size} x G={G} x T={T} = "
            f"{tokens_per_step:,} tokens; mesh {dict(mesh.shape)}; fsdp_mode={config.fsdp_mode}; "
            f"attn_impl={mc.attn_impl}; remat={mc.remat}")

    # ---- correctness: system eval loss vs the plain float32 reference ----
    reference = ctx.load("reference.py")
    n_chk = max(int(tr["check_sequences"]), ctx.chips)
    xc, yc = rt.dataset.batch("val", 0, T, n_chk)
    sp = batch_spec(with_accum=False)
    xg, yg = make_global_batch(xc, mesh, sp), make_global_batch(yc, mesh, sp)
    sys_loss = float(rt.eval_loss(params, xg, yg))
    ref_tok = np.asarray(jax.jit(reference.token_losses, static_argnums=3)(params, xg, yg, mc.n_head))
    sys_tok = np.asarray(system_token_losses(config, mesh)(params, xg, yg))
    mean_err = abs(sys_loss - float(ref_tok.mean()))
    diff = sys_tok - ref_tok
    tok_rms, tok_max = float(np.sqrt(np.mean(diff ** 2)) / ref_tok.std()), float(np.abs(diff).max() / ref_tok.std())
    correct = bool(np.isfinite(sys_loss) and mean_err <= MEAN_TOLERANCE
                   and np.isfinite(tok_rms) and tok_rms <= TOKEN_RMS_TOLERANCE)
    ctx.log(f"correctness: {n_chk} sequences x {T} tokens of the initial weights against the float32 "
            f"reference: system eval loss {sys_loss:.6f} vs {ref_tok.mean():.6f}, |diff| {mean_err:.2e} "
            f"(tolerance {MEAN_TOLERANCE:.0e}); per-token loss error/std rms {tok_rms:.3e} (tolerance "
            f"{TOKEN_RMS_TOLERANCE:.0e}), max {tok_max:.3e} -> {'ok' if correct else 'NOT CORRECT'}")
    ctx.phases.mark("correctness_check")

    # ---- the step loop ----
    data_sp = batch_spec(with_accum=True)
    base_key = jax.random.PRNGKey(config.seed)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    spans = []  # (name, start, duration) on time.perf_counter
    state = {"params": params, "opt": opt_state, "i": 0,
             "loss": jax.device_put(jnp.zeros((), jnp.float32), replicated)}
    clock = time.perf_counter

    def enqueue():
        """Assemble batch i, put it on the device, enqueue step i; returns its loss."""
        i = state["i"]
        t0 = clock()
        x, y = rt.dataset.batch("train", i, T, local_bs, G)
        t1 = clock()
        xg, yg = make_global_batch(x, mesh, data_sp), make_global_batch(y, mesh, data_sp)
        t2 = clock()
        key = jax.random.fold_in(base_key, i)
        state["params"], state["opt"], state["loss"] = rt.step(
            state["params"], state["opt"], xg, yg, key, state["loss"])
        t3 = clock()
        spans.extend([("bench.data", t0, t1 - t0), ("bench.put", t1, t2 - t1),
                      ("bench.step_enqueue", t2, t3 - t2)])
        state["i"] = i + 1
        return state["loss"]

    def ready(loss):
        """Block until `loss` is on the host; returns (stamp, value)."""
        t0 = clock()
        v = float(loss)
        t1 = clock()
        spans.append(("bench.loss_sync", t0, t1 - t0))
        return t1, v

    losses = []
    t, v = ready(enqueue())  # first step: compiles, or loads from the cache
    losses.append(v)
    ctx.phases.mark("step_compile_or_cache_load")
    pending = enqueue()
    nxt = enqueue()
    t_open, v = ready(pending)  # second whole step done, third in flight
    losses.append(v)
    pending = nxt
    ctx.phases.mark("warmup_steps")
    setup_s = t_open - ctx.t_process
    compiles_before = ctx.compiles.count

    # ---- measured window ----
    stamps = [t_open]
    while True:
        nxt = enqueue()
        t, v = ready(pending)
        pending = nxt
        stamps.append(t)
        losses.append(v)
        if t - t_open >= ctx.seconds:
            break
    window_compiles = ctx.compiles.count - compiles_before
    durations = [b - a for a, b in zip(stamps, stamps[1:])]
    window_s = stamps[-1] - stamps[0]
    window_spans = [s for s in spans if s[1] >= t_open]

    # ---- traced extension (per-layer run only) ----
    trace_summary, traced_steps = None, 0
    if ctx.trace:
        med = statistics.median(durations)
        k = max(3, int(-(-ctx.trace_seconds // med)))
        t_sync = ctx.start_trace()
        nxt = enqueue()
        t, v = ready(pending)  # pipeline refilled after the profiler's start-up
        pending = nxt
        losses.append(v)
        compiles_before = ctx.compiles.count
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(k):
                nxt = enqueue()
                t, v = ready(pending)
                pending = nxt
                losses.append(v)
        window_compiles += ctx.compiles.count - compiles_before
        traced_steps = k
        trace_summary = ctx.stop_trace(t_sync, spans)
    _, v = ready(pending)
    losses.append(v)

    bad = [i for i, v in enumerate(losses) if not np.isfinite(v)]
    n = len(durations)
    ctx.log(f"window: {n} step durations in {window_s:.3f} s; ms: "
            + " ".join(f"{1e3 * d:.1f}" for d in durations))
    ctx.log(f"losses first/last: {losses[0]:.4f} {losses[-1]:.4f}; non-finite: {len(bad)}")
    if n < int(tr["min_durations"]):
        raise SystemExit(f"only {n} step durations fit in {ctx.seconds} s; the cell needs "
                         f"{tr['min_durations']} (run_seconds is too short for this step)")
    return {
        "kind": "train", "correct": correct and not bad,
        "attempted": len(losses), "failed": len(bad),
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s": tokens_per_step * n / window_s},
        "samples": {"step_s": durations},
        "counters": {"window.compiles": window_compiles, "tokens_per_step": tokens_per_step,
                     "traced_steps": traced_steps, "n_sequences_per_step": config.batch_size * G},
        "window_s": window_s, "spans": window_spans, "trace_summary": trace_summary,
        "model": dataclasses.asdict(mc),
    }
