"""Training cells of a model family `reference.py` does not cover (traffic kind
"train_hybrid"): the same runtime, loop, window and rate as train_cell.py, with
the correctness check against the plain reference that the configuration
brings beside its file (`configs/<config>_reference.py`).

The system under test is `make_runtime(config)`, as in train_cell.py, and the
loop below is train_cell.py's, step for step: step i+1 is enqueued before the
host blocks on step i's loss, a step duration is the difference of two
consecutive loss stamps, the window opens at the stamp of the last warm-up step
and ends at the first stamp past --seconds, `train_tokens_per_s` = tokens of
the window's whole steps / its length. It is a copy because train_cell.py's
loop and its GPT-only check are one function; the helpers that are apart
(`resolve_config`, `write_tokens`) are called through `ctx.load`. The result
says `"kind": "train"`, so every reader that gates on that applies.

Correct: every loss finite, `moe.dropped` 0, and four comparisons. On
`check_sequences` seeded sequences of the INITIAL weights, at the timed sizes
(T = 8,192, published widths): (a) the system's own eval loss (`rt.eval_loss`:
the forward the step differentiates: bf16 compute, chunked KDA, flash MLA,
dispatched experts, fused cross-entropy) against the mean of the reference's
float32 per-token losses, and (b) the per-token losses of that forward against
the reference's, token by token. Of the TIMED program, `rt.step`: (c) the loss
step 0 returns (the whole step program on the initial weights: the scan over G,
the loss the backward starts from) against the mean of the reference's losses
on step 0's batch, and (d) the change of every parameter over step 1, the first
update at a learning rate above 0, against the optimizer as the configuration
states it (clip, Adam's bias-corrected moments, decoupled decay on the
matrices only, warm-up), applied to the moments the step itself left in its
state. What (d) sees is the update's form, rate, sign and decay mask on every
leaf; it does not see a wrong GRADIENT (the moments are the step's own): the
gradients are compared with the reference's at a small size in
tests/test_kimi_linear.py, not at this one (PERF.md §7 row 14).
"""

from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import tempfile
import time

import numpy as np

# (a), (c) |system loss - mean reference loss| over check_sequences x T tokens
# (a) and over step 0's tokens (c).
# (b) RMS over the tokens of (system - reference) per-token loss, as a share of
# the reference per-token losses' standard deviation. Each limit stands against
# two readings on the chip at the published widths and T = 8,192 (my chip
# runs, PR 26; PERF.md §6): the largest this program gave over its seeds (bf16
# compute: a 8.9e-4, c 1.13e-3, b 4.9e-2; the largest single token 0.60 of a standard
# deviation: an expert selected the other way at a near tie) and what the
# float32 reference gives against itself with its matrices rounded to 8-bit
# floating point (e4m3), the nearest precision below the bf16 the
# configuration states (two seeds: a 1.1e-3 and 6.0e-3, b 3.3e-1 both). That
# must come out as not correct and does, by (b), which lies between its two
# readings with room on both sides. The MEAN does not separate the two
# precisions (the per-token errors average out: 8.9e-4 against 1.1e-3), so its
# limit is a training loss's: about three times the largest sound reading
# alone. What the comparison is there to catch sits far above (b): a missing
# renormalisation or scale, or another chip's experts, move the logits by over
# 50 tolerances at the tiny size (tests/test_kimi_linear.py).
MEAN_TOLERANCE = 3e-3
TOKEN_RMS_TOLERANCE = 1.3e-1
# (d) the update of step 1 = -rate x u, u the stated direction (Adam's
# bias-corrected moments + decay on the leaves that decay). RATE: the rate
# fitted over all 602 M parameters, <dp, u> / <u, u>, against the warm-up's
# stated one; the schedule computes 1.5e-7 in float32 as 3e-4 - 3e-4 x
# (1 - 1/2000), which comes out as 1.50007e-7. UPDATE: |p_after -
# (p_before - rate u)| / |rate u|, 2-norms, the sum made in float32 as the
# step makes it, so that the rounding of p + dp (dp ~ 1.5e-7 beside p ~ 2e-2:
# 4e-3 of dp) cancels and what is left is where a last-bit difference in dp
# crossed a rounding boundary (the chip's divide and square root need not
# round alike in two fusions). About three times the largest reading over
# fourteen seeds (my chip runs, PR 26, PERF.md §6: RATE 4.2e-5 to 4.9e-5;
# UPDATE 6.4e-4 to 7.1e-4). Decay put on the leaves that take none would read
# 1.2e-2 at the preset's decay of 1e-4 (dt_bias and the norm weights are
# large), decay left off the matrices 7e-3.
RATE_TOLERANCE = 2e-4
UPDATE_TOLERANCE = 2e-3
# Leaves that take no weight decay, by name, as ISSUE 26 lists them: norm
# weights, A_log, dt_bias, the router's correction bias, convolution taps.
NO_DECAY = ("norm1", "norm2", "final_norm", "kv_norm", "o_norm", "A_log", "dt_bias", "router_bias", "conv")


def load_reference(ctx):
    """configs/<config>_reference.py: the plain float32 forward of this family."""
    return ctx.load(os.path.join("configs", ctx.cell["config"] + "_reference.py"))


def system_token_losses(config):
    """Jitted (params, x, y) -> (B, T) float32 per-token cross-entropy of the
    forward the train step differentiates: the family's compute copy of the
    parameters, its `hidden`, the head in the compute dtype, cross-entropy in
    float32 (training/train.py make_train_step binds the same three)."""
    import jax
    import jax.numpy as jnp

    mc, dtype = config.model_config, jnp.dtype(config.compute_dtype)
    model = mc.model()

    def token_losses(params, x, y):
        pc = model.cast_params(params, dtype)
        h = model.hidden(mc, pc, x, inference=True)
        lg = jnp.einsum("btd,vd->btv", h, pc.lm_head).astype(jnp.float32)
        picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    return jax.jit(token_losses)


def compare(sys_loss: float, sys_tok: np.ndarray, ref_tok: np.ndarray) -> dict:
    """The two errors of the check and whether they pass."""
    mean_err = abs(sys_loss - float(ref_tok.mean()))
    diff = sys_tok - ref_tok
    std = float(ref_tok.std())
    tok_rms, tok_max = float(np.sqrt(np.mean(diff**2)) / std), float(np.abs(diff).max() / std)
    ok = bool(np.isfinite(sys_loss) and mean_err <= MEAN_TOLERANCE
              and np.isfinite(tok_rms) and tok_rms <= TOKEN_RMS_TOLERANCE)
    return {"mean_err": mean_err, "tok_rms": tok_rms, "tok_max": tok_max, "ref_mean": float(ref_tok.mean()), "ok": ok}


def stated_update(config):
    """Two jitted passes over the whole tree for update number `t` (1-based),
    with the optimizer written out from the configuration's numbers: the
    direction u = m_hat / (sqrt(v_hat) + 1e-8), Adam's bias corrections (b1
    0.9, b2 `beta2`), plus `weight_decay / learning_rate` x p on the leaves
    that decay. (The clip acts on the gradient before the moments: it is
    inside mu, nu.)
    `fit(p0, p1, mu, nu, t)` -> (<p1 - p0, u>, <u, u>): the rate the step
    applied is minus their ratio. `residual(p0, p1, mu, nu, t, rate)` ->
    (|p1 - (p0 - rate u)|^2, |rate u|^2), the sum made in float32 as the step
    makes it."""
    import jax
    import jax.numpy as jnp

    b1, b2 = 0.9, config.beta2
    decay = config.weight_decay / config.learning_rate

    def over_leaves(fn):
        def run(p0, p1, mu, nu, t, *rest):
            def leaf(path, a, b, m, v):
                name = str(getattr(path[-1], "name", getattr(path[-1], "key", path[-1])))
                u = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + 1e-8) + (0.0 if name in NO_DECAY else decay) * a
                return jnp.stack(fn(a, b, u, *rest))
            return sum(jax.tree.leaves(jax.tree_util.tree_map_with_path(leaf, p0, p1, mu, nu)))
        return jax.jit(run)

    fit = over_leaves(lambda a, b, u: [jnp.sum((b - a) * u), jnp.sum(u * u)])
    residual = over_leaves(lambda a, b, u, rate: [jnp.sum((b - (a - rate * u)) ** 2), jnp.sum((rate * u) ** 2)])
    return fit, residual


def pieces(tree) -> list:
    """The parameter tree (or one shaped like it) as {field name: subtree}
    pieces of at most one layer: what the update check puts on the device at a
    time beside the training state (the whole host copy would be 2.4 GB more)."""
    import jax

    out = []
    for path, child in jax.tree_util.tree_flatten_with_path(tree, is_leaf=lambda x: x is not tree)[0]:
        name = str(getattr(path[-1], "name", path[-1]))
        out.extend({name: c} for c in (child if isinstance(child, tuple) else (child,)))
    return out


def run(ctx) -> dict:
    # The family's model FIRST: a checkout that lacks it (the parent of the PR
    # that brought this cell) stops here with an ImportError, in seconds.
    from midgpt_tpu.models import kimi_linear  # noqa: F401

    import jax

    from midgpt_tpu.training.train import make_runtime

    tc = ctx.load("train_cell.py")
    tr = ctx.traffic
    ctx.phases.mark("program_imports")
    with tempfile.TemporaryDirectory(prefix="bench_data_") as data_dir:
        config = tc.resolve_config(ctx, data_dir)
        mc = config.model_config
        if mc.vocab_size > 65536:
            raise SystemExit("the dataset format is uint16: vocab_size > 65536")
        for split, n in (("train", int(tr["data_tokens"])), ("val", 4 * mc.block_size + 1)):
            tc.write_tokens(os.path.join(data_dir, f"{split}.bin"), n, mc.vocab_size, ctx.seed32)
        ctx.phases.mark("data")
        rt = make_runtime(config)
        params, opt_state = rt.take_initial(config)
        jax.block_until_ready(params)
        ctx.phases.mark("weights")
        return _measure(ctx, config, rt, params, opt_state)


def _measure(ctx, config, rt, params, opt_state) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec

    tr, mc, mesh = ctx.traffic, config.model_config, rt.mesh
    T, G = mc.block_size, config.g_accum_iters
    local_bs = config.batch_size // jax.process_count()
    tokens_per_step = config.batch_size * G * T
    model_dict = dataclasses.asdict(mc)
    ctx.log(f"train: {rt.n_params:,} parameters ({16 * rt.n_params / 1e9:.2f} GB of training state at 16 B); "
            f"step = {config.batch_size} x G={G} x T={T} = {tokens_per_step:,} tokens; mesh {dict(mesh.shape)}; "
            f"attn_impl={mc.attn_impl}; experts held {mc.n_experts_held} of {mc.n_experts} "
            f"from {mc.expert_offset}")

    # ---- correctness: the system's forward vs the plain float32 reference ----
    reference = load_reference(ctx)
    n_chk = max(int(tr["check_sequences"]), ctx.chips)
    xc, yc = rt.dataset.batch("val", 0, T, n_chk)
    sp = batch_spec(with_accum=False)
    xg, yg = make_global_batch(xc, mesh, sp), make_global_batch(yc, mesh, sp)
    sys_loss = float(rt.eval_loss(params, xg, yg))
    ref_fn = jax.jit(lambda p, x, y: reference.token_losses(p, x, y, model_dict))
    ref_tok = np.asarray(ref_fn(params, xg, yg))
    sys_tok = np.asarray(system_token_losses(config)(params, xg, yg))
    chk = compare(sys_loss, sys_tok, ref_tok)
    ctx.log(f"correctness: {n_chk} sequence(s) x {T} tokens of the initial weights against the float32 "
            f"reference: system eval loss {sys_loss:.6f} vs {chk['ref_mean']:.6f}, |diff| {chk['mean_err']:.2e} "
            f"(tolerance {MEAN_TOLERANCE:.0e}); per-token loss error/std rms {chk['tok_rms']:.3e} (tolerance "
            f"{TOKEN_RMS_TOLERANCE:.1e}), max {chk['tok_max']:.3e} -> {'ok' if chk['ok'] else 'NOT CORRECT'}")
    # (c)'s reference: the mean float32 loss of step 0's batch (batch i is a
    # function of i and the seed: `enqueue` below assembles the same one)
    x0, y0 = rt.dataset.batch("train", 0, T, local_bs, G)
    ref_step0 = float(np.mean([np.asarray(ref_fn(params, make_global_batch(x0[g], mesh, sp),
                                                 make_global_batch(y0[g], mesh, sp))).mean() for g in range(G)]))
    ctx.phases.mark("correctness_check")

    # ---- the step loop (train_cell.py's) ----
    data_sp = batch_spec(with_accum=True)
    base_key = jax.random.PRNGKey(config.seed)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    spans = []  # (name, start, duration) on time.perf_counter
    state = {"params": params, "opt": opt_state, "i": 0, "x": None,
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
        state["i"], state["x"] = i + 1, xg
        return state["loss"]

    def ready(loss):
        """Block until `loss` is on the host; returns (stamp, value)."""
        t0 = clock()
        v = float(loss)
        t1 = clock()
        spans.append(("bench.loss_sync", t0, t1 - t0))
        return t1, v

    def on_gc(phase, info, t0=[0.0]):  # the interpreter's collections, as host spans beside the loop's
        if phase == "start":
            t0[0] = clock()
        else:
            spans.append((f"bench.gc{info['generation']}", t0[0], clock() - t0[0]))

    gc.callbacks.append(on_gc)
    losses = []
    t, v = ready(enqueue())  # first step: compiles, or loads from the cache
    losses.append(v)
    ctx.phases.mark("step_compile_or_cache_load")

    # ---- correctness of the timed program: (c) step 0's loss, (d) step 1's update ----
    step_err = abs(losses[0] - ref_step0)
    p_before = jax.tree.map(np.asarray, state["params"])  # host copy: the step donates its parameters
    t, v = ready(enqueue())  # step 1: the first update at a rate above 0 (the warm-up starts at 0)
    losses.append(v)
    mu, nu = (optax.tree_utils.tree_get(state["opt"], k) for k in ("mu", "nu"))
    fit, residual = stated_update(config)
    t_upd = float(state["i"])
    parts = list(zip(pieces(p_before), pieces(state["params"]), pieces(mu), pieces(nu)))
    del p_before
    du, uu = np.sum([np.asarray(fit(*part, t_upd), np.float64) for part in parts], axis=0)
    rate, rate_stated = -du / uu, config.learning_rate * (t_upd - 1) / config.warmup_steps
    d2, w2 = np.sum([np.asarray(residual(*part, t_upd, rate), np.float64) for part in parts], axis=0)
    del parts, mu, nu
    rate_err, update_err = abs(rate / rate_stated - 1.0), (d2 / w2) ** 0.5
    step_ok = bool(step_err <= MEAN_TOLERANCE and rate_err <= RATE_TOLERANCE and update_err <= UPDATE_TOLERANCE)
    ctx.log(f"correctness of rt.step: step 0's loss {losses[0]:.6f} vs the reference's {ref_step0:.6f} on its "
            f"batch, |diff| {step_err:.2e} (tolerance {MEAN_TOLERANCE:.0e}); step 1's update: rate {rate:.6e} vs "
            f"{rate_stated:.6e} stated, off by {rate_err:.2e} (tolerance {RATE_TOLERANCE:.0e}); parameter change "
            f"{w2 ** 0.5:.4e}, error {update_err:.3e} of it (tolerance {UPDATE_TOLERANCE:.0e}) -> "
            f"{'ok' if step_ok else 'NOT CORRECT'}")
    ctx.phases.mark("step_check")
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
    gc.callbacks.remove(on_gc)

    # ---- the model's own counters, after the window: one microbatch of the
    # last step's batch through the weights as they are now ----
    stats = {k: float(v) for k, v in rt.model_stats(state["params"], state["x"][0]).items()}
    ctx.log("model counters (one microbatch, the trained weights): "
            + " ".join(f"{k} {v:g}" for k, v in sorted(stats.items())))

    bad = [i for i, v in enumerate(losses) if not np.isfinite(v)]
    n = len(durations)
    ctx.log(f"window: {n} step durations in {window_s:.3f} s; ms: "
            + " ".join(f"{1e3 * d:.1f}" for d in durations))
    ctx.log(f"losses first/last: {losses[0]:.4f} {losses[-1]:.4f}; non-finite: {len(bad)}")
    syncs = sorted((s for s in window_spans if s[0] == "bench.loss_sync"), key=lambda s: -s[2])[:1]
    gcs = sorted((s for s in window_spans if s[0].startswith("bench.gc")), key=lambda s: -s[2])[:3]
    ctx.log("host, in the window: longest loss sync " + " ".join(f"{1e3 * s[2]:.1f} ms at +{s[1] - t_open:.2f} s" for s in syncs)
            + "; longest collections " + (" ".join(f"{s[0][6:]} {1e3 * s[2]:.1f} ms at +{s[1] - t_open:.2f} s" for s in gcs) or "none"))
    if n < int(tr["min_durations"]):
        raise SystemExit(f"only {n} step durations fit in {ctx.seconds} s; the cell needs "
                         f"{tr['min_durations']} (run_seconds is too short for this step)")
    if stats["moe.dropped"]:
        ctx.log(f"NOT CORRECT: {stats['moe.dropped']:g} token-expert pairs were assigned here and not computed")
    counters = {"window.compiles": window_compiles, "tokens_per_step": tokens_per_step,
                "traced_steps": traced_steps, "n_sequences_per_step": config.batch_size * G}
    counters.update(stats)
    return {
        "kind": "train", "correct": chk["ok"] and step_ok and not bad and not stats["moe.dropped"],
        "attempted": len(losses), "failed": len(bad),
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s": tokens_per_step * n / window_s},
        "samples": {"step_s": durations},
        "counters": counters,
        "window_s": window_s, "spans": window_spans, "trace_summary": trace_summary,
        "model": model_dict,
    }
