"""Serving cells of a model FAMILY other than the GPT (traffic kind
"serve_family"): `ServeEngine` under generated load, reached through the family
namespace (`midgpt_tpu/models/__init__.py`: `init`, `cast_params`, `init_cache`,
`prefill_paged_chunk`, `decode_step_paged`, `cache_kinds`).

The loop, the window, the ramp, the client-side latencies and the token count
are `serve_cell.py`'s, step for step (its `warmup_plan`, `page_bucket` and
`Rec` are called through `ctx.load`); this file brings what that file names the
GPT for: the weights (`model().init` + the family's `cast_params`, so what the
family keeps in float32 stays so), the correctness check against the plain
float32 reference that lies beside the configuration file
(`configs/<config>_reference.py`), and the counters of a cache of several kinds
and of the family's own layers (`ServeEngine.serve_counters`). Its result says
`"kind": "serve"`: every reader that gates on that applies.

Correct: no request failed or was preempted, no routed pair assigned here went
uncomputed (`moe.dropped` 0), nothing compiled inside the window (run.py), and
the check below.

THE CHECK GOES THROUGH THE ENGINE. A `ServeEngine` built by the same call as
the timed one (the served dtype, the pools' sizes and dtype, page size, chunk,
slot count, the attention lowering the backend resolves) serves the traffic
file's `check.prompts` (seeded token ids; 704, 2,600, 1,300 and 200 tokens) in
ONE queue, all live at once, each for 1 + `check.decode_rounds` x decode_chunk
tokens, sampled as the timed requests are. So the compared numbers come out of
the engine's own admission, per-kind allocators, window reclaim
(`kv.window_pages_reclaimed` must be over 0 when the check ends), device page
tables, chunked prefill programs (the 2,600-token prompt's later chunks sweep
three key blocks) and decode programs (split-K 8 once that prompt decodes).
Compared, per request: the prefill program's logits at the prompt's last
position (`on_first_logits`: what the engine samples the first token from),
and before every decode round after a request's first (which follows its last
prefill chunk inside one `step`) the logits of the step that round starts with
(`ServeEngine.next_logits`: one step of `decode_step_paged` on the round's own
cache, tables, lengths, page bucket and split-K factor; it reads K/V that the
engine's `_serve_decode_chunk` wrote in the rounds before): 1 + 7 rows a
request, 32 in all. The engine is
then dropped (its pools leave the device) and the reference runs its full
forward of each request's tokens as the engine produced them, padded to one
length (causal: the padding changes no compared row).

The two limits, as shares of the reference logits' standard deviation over all
compared rows, each between two readings on the chip (PERF.md §6 PR 30): the
largest this program gave over the seeds run, and what the reference gives
against ITSELF with its matrices rounded to 8-bit floating point
(`reference.logits(..., round_to=float8_e4m3fn)`), which must fail. That
control is this file's own entry point, run apart from the timed set-up (it
would put a dozen more compiled programs in front of the engine's):

    python3 benchmarks/serve_family_cell.py --workload <cell> --seed <n>

puts the 8-bit reference's logits in the program's place through the same
`judge` and exits 0 only if the program is correct AND the control is not.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import statistics
import time

import numpy as np

# error / std of the reference logits over the 32 compared rows: RMS, and the
# largest compared logit. bf16 weights and pools through 11 layers of which 10
# route (my chip runs, PR 30 review round, nine seeds): RMS 8.9e-3 to 1.6e-2,
# the largest logit 1.25e-1 to 2.48e-1 (an expert selected the other way at a
# near tie moves a few rows); the reference with 8-bit matrices against itself
# (this file's entry point, seed 2600000041): RMS 2.01e-1, largest 9.78e-1.
# Each limit lies between its two readings: RMS 3.1 times over the first and 4
# under the second, the largest logit 2.0 and 1.96.
RMS_TOLERANCE, MAX_TOLERANCE = 5e-2, 5e-1


def build_model(ctx):
    """(model config, params): weights made on the device by one jitted call
    from the seed, in the served dtype as the family casts them."""
    import jax
    import jax.numpy as jnp

    mc = ctx.repo_config().model_config
    model = mc.model()
    dtype = jnp.dtype(ctx.config["serve"]["weights_dtype"])
    init = jax.jit(lambda key: model.cast_params(model.init(mc, key), dtype))
    # the chip's own generator ("rbg"): 5.4 B values from the default counter-based one take a minute
    params = jax.block_until_ready(init(jax.random.key(ctx.seed32, impl="rbg")))
    ctx.log(f"weights: {model.count_params(params) / 1e9:.3f} B parameters, "
            f"{sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9:.3f} GB on the device")
    return mc, params


def make_engine(ctx, mc, params, es, **hooks):
    """The cell's ServeEngine: the timed one and the check's are this call."""
    from midgpt_tpu.sampling.serve import ServeEngine

    per_slot = es.get("pool_tokens_per_slot")  # absent: the engine's default pool
    ps = int(es["page_size"])
    return ServeEngine(
        mc, params,
        max_slots=int(es["max_slots"]),
        num_pages=None if per_slot is None else int(es["max_slots"]) * -(-int(per_slot) // ps) + 1,
        page_size=ps, prefill_chunk=int(es["prefill_chunk"]),
        decode_chunk=int(es["decode_chunk"]), temperature=float(es["temperature"]),
        seed=ctx.seed32, cache_dtype=es["cache_dtype"], prefix_cache=bool(es["prefix_cache"]),
        **hooks,
    )


def engine_logits(ctx, mc, params, es, check):
    """Serve the check's prompts through a ServeEngine; (sequences as served,
    compared rows of each, the engine's logits at those rows (n, V), what the
    engine counted)."""
    rng = np.random.default_rng([ctx.seed32, 11])
    n_new = 1 + int(check["decode_rounds"]) * int(es["decode_chunk"])
    prompts = [rng.integers(0, mc.vocab_size, min(int(p), mc.block_size - n_new - 1), dtype=np.int32)
               for p in check["prompts"]]
    got = {}  # uid -> [(row, logits)]
    eng = make_engine(ctx, mc, params, es,
                      on_first_logits=lambda uid, row: got[uid].append((len(by_uid[uid]) - 1, np.array(row, np.float32))))
    by_uid = {eng.submit(p, n_new): p for p in prompts}
    got.update({uid: [] for uid in by_uid})
    live_max = 0
    while not eng.idle:
        fed = {s.request.uid: s.length for s in eng.slots if s is not None}  # the row a slot's next step feeds
        for uid, row in eng.next_logits().items():
            got[uid].append((fed[uid], row))
        live_max = max(live_max, sum(s is not None for s in eng.slots))
        eng.step()
    seqs = [np.asarray(eng.finished[uid].tokens, np.int32) for uid in by_uid]
    rows = [np.asarray([r for r, _ in got[uid]], np.int32) for uid in by_uid]
    logits = np.concatenate([np.stack([l for _, l in got[uid]]) for uid in by_uid])
    counted = dict(eng.serve_counters(), prompts=[len(p) for p in prompts], preemptions=eng.stats()["preemptions"], live_max=live_max,
                   attn=eng.attn_impl, programs=eng.compile_stats())
    del eng  # its pools leave the device before the reference's float32 layers arrive
    gc.collect()
    return seqs, rows, logits, counted


def reference_logits(ctx, params, mc, seqs, rows, round_to=None):
    """The float32 reference's logits at `rows` of each sequence, every
    sequence padded to one length (one compile a layer)."""
    import jax.numpy as jnp

    reference = ctx.load(os.path.join("configs", ctx.cell["config"] + "_reference.py"))
    cfg = dataclasses.asdict(mc)
    T = -(-max(len(s) for s in seqs) // 128) * 128
    out = [reference.logits(params, jnp.asarray(np.pad(s, (0, T - len(s)))), cfg, rows=r, round_to=round_to)
           for s, r in zip(seqs, rows)]
    return np.concatenate([np.asarray(o, np.float32) for o in out])


def judge(got, want):
    """(error / std of the reference logits: RMS, largest; within both limits)."""
    d = got - want
    rms, worst = float(np.sqrt(np.mean(d ** 2)) / np.std(want)), float(np.max(np.abs(d)) / np.std(want))
    return rms, worst, bool(np.isfinite(rms) and rms <= RMS_TOLERANCE and worst <= MAX_TOLERANCE)


def check_engine_path(ctx, mc, params, es, check, control=None):
    """Whether the engine's logits agree with the reference's; with `control`
    (a dtype) also whether the reference with its matrices rounded to it does:
    (ok, control ok | None)."""
    seqs, rows, got, counted = engine_logits(ctx, mc, params, es, check)
    want = reference_logits(ctx, params, mc, seqs, rows)
    rms, worst, ok = judge(got, want)
    reclaimed = [v for k, v in counted.items() if k.startswith("kv.") and k.endswith("_pages_reclaimed")]
    served = ok and counted["preemptions"] == 0 and not counted.get("moe.dropped", 0) and (not reclaimed or max(reclaimed) > 0)
    ctx.log(f"correctness: ServeEngine ({counted['attn']}; prompts of {counted['prompts']} tokens served "
            f"incl. {int(check['decode_rounds'])} decode rounds of {es['decode_chunk']}, up to {counted['live_max']} of "
            f"{es['max_slots']} slots live, chunks of {es['prefill_chunk']}, pages of {es['page_size']}, "
            f"{es['cache_dtype']} pools; window pages reclaimed {reclaimed}, preemptions {counted['preemptions']}, "
            f"moe.dropped {counted.get('moe.dropped', 0)}) vs float32 reference logits of the same sequences, "
            f"{got.shape[0]} rows: error/std rms {rms:.3e} (limit {RMS_TOLERANCE:.1e}), max {worst:.3e} (limit "
            f"{MAX_TOLERANCE:.1e}) -> {'ok' if served else 'NOT CORRECT'}")
    if control is None:
        return served, None
    c_rms, c_worst, c_ok = judge(reference_logits(ctx, params, mc, seqs, rows, round_to=control), want)
    ctx.log(f"control: the reference with its matrices rounded to {np.dtype(control).name} in the program's place, "
            f"same rows and limits: error/std rms {c_rms:.3e}, max {c_worst:.3e} -> {'ok' if c_ok else 'NOT CORRECT'}")
    return served, c_ok


def warmup_plan(ctx, traffic, eng_spec, block_size: int):
    """serve_cell.py's plan (every (steps, page-bucket) decode program the
    traffic reaches), and a request for every PREFILL page bucket a chunk of
    the traffic runs at that the plan's own prompts do not pass: with chunks of
    hundreds of tokens a short prompt's one chunk runs at a bucket below the
    first decode bucket."""
    serve_cell = ctx.load("serve_cell.py")
    ps, chunk = int(eng_spec["page_size"]), int(eng_spec["prefill_chunk"])
    max_pages = -(-block_size // ps)

    def buckets(p):
        return {serve_cell.page_bucket(min(pos + chunk, p), ps, max_pages) for pos in range(0, p, chunk)}

    plan = serve_cell.warmup_plan(traffic, eng_spec, block_size)
    seen = set().union(*(buckets(p) for p, _ in plan)) if plan else set()
    for p in sorted(set(traffic.prompt_lens)):
        if buckets(p) - seen:
            plan.append((p, 2))
            seen |= buckets(p)
    return plan


def run(ctx) -> dict:
    import jax

    from midgpt_tpu.obs import Observability

    loadgen, serve_cell = ctx.load("loadgen.py"), ctx.load("serve_cell.py")
    Rec = serve_cell.Rec
    ctx.phases.mark("program_imports")
    spec = ctx.traffic
    es = spec["engine"]
    mc, params = build_model(ctx)
    ctx.phases.mark("weights")
    correct, _ = check_engine_path(ctx, mc, params, es, spec["check"])
    ctx.phases.mark("correctness_check")

    traffic = loadgen.Traffic(spec, ctx.seed32, mc.vocab_size)
    ctx.log("traffic resolved:", traffic.describe())
    ctx.log("length multiset of one cycle (prompt, output):", traffic.multiset())
    obs = Observability(capacity=1 << 19) if ctx.trace else None
    recs, by_uid, token_log = {}, {}, []

    def on_token(uid, tok, t):
        r = by_uid.get(uid)
        if r is None:
            return
        if r.n_out == 0:
            r.t_first = t
        r.t_last = t
        r.n_out += 1
        token_log.append((t, r.index, r.prompt_len + r.n_out - 1))

    finished = []
    eng = make_engine(ctx, mc, params, es, on_token=on_token, on_finish=finished.append, obs=obs)
    per_slot = es.get("pool_tokens_per_slot")
    pools = {k.name: (a.num_pages, sum(x.nbytes for x in eng.cache.pool_arrays()[2 * i:2 * i + 2]))
             for i, (k, a) in enumerate(zip(eng.kinds, eng.allocators))}
    ctx.log(f"engine: max_slots={eng.max_slots} pools (pages of {eng.page_size} tokens, bytes) {pools} "
            f"pool={eng.cache_hbm_bytes() / 1e9:.3f} GB ({'engine default' if per_slot is None else str(per_slot) + ' tokens a slot'}) prefill_chunk={eng.prefill_chunk} "
            f"decode_chunk={eng.decode_chunk} temperature={eng.temperature} attn={eng.attn_impl} "
            f"prefix_cache={eng.prefix_cache is not None} draft={eng.draft_params is not None}")
    if any(str(a.dtype) != {"bf16": "bfloat16"}.get(es["cache_dtype"], es["cache_dtype"]) for a in eng.cache.pool_arrays()):
        raise SystemExit(f"the engine's pools are {eng.cache.pool_arrays()[0].dtype}, the traffic file says {es['cache_dtype']}")
    ctx.phases.mark("engine_build")

    # ---- warm-up: the shapes this traffic reaches, by running them ----
    plan = warmup_plan(ctx, traffic, es, mc.block_size)
    wrng = np.random.default_rng([ctx.seed32, 13])
    for p, m in plan:
        eng.submit(wrng.integers(0, mc.vocab_size, p, dtype=np.int32), m)
        eng.run()
    eng.finished.clear()
    finished.clear()
    ctx.log(f"warm-up requests (prompt, max_new), each run alone: {plan}; programs now: {eng.compile_stats()}")
    ctx.phases.mark("warmup_shapes")

    # ---- the loop ----
    clock = time.perf_counter
    idle_clients = []
    client_of = {}
    occupancy = []

    def submit(req, client, primer=False, t_ref=None):
        uid = eng.submit(req.prompt, req.max_new_tokens)
        r = Rec(req.index, len(req.prompt), req.max_new_tokens,
                clock() if t_ref is None else t_ref, primer=primer)
        recs[req.index] = by_uid[uid] = r
        client_of[uid] = client
        return r

    def collect():
        for fr in finished:
            r = by_uid.pop(fr.uid, None)
            eng.finished.pop(fr.uid, None)
            if r is not None:
                r.status = fr.status
                idle_clients.append(client_of.pop(fr.uid))
        finished.clear()

    t_loop = clock()
    lateness = []
    next_open = None
    if traffic.loop == "closed":
        for c, req in enumerate(traffic.prime()):
            submit(req, c, primer=True)
        primers_left = lambda: any(r.primer and not r.status for r in recs.values())
    else:
        next_open = traffic.next()
        primers_left = lambda: clock() - t_loop < float(spec.get("ramp_seconds", 2.0))

    def pump():
        """Issue what is due, run one engine round, collect what finished."""
        nonlocal next_open
        if traffic.loop == "closed":
            while idle_clients:
                submit(traffic.next(), idle_clients.pop())
        else:
            now = clock()
            while next_open.due_s <= now - t_loop:
                due = t_loop + next_open.due_s
                lateness.append(now - due)
                submit(next_open, -1, t_ref=due)
                next_open = traffic.next()
            if eng.idle:
                time.sleep(max(0.0, min(0.002, t_loop + next_open.due_s - clock())))
                return
        eng.step()
        collect()
        occupancy.append(sum(s is not None for s in eng.slots))

    while primers_left():
        pump()
    ctx.phases.mark("ramp")

    # ---- measured window ----
    w0 = clock()
    setup_s = w0 - ctx.t_process
    compiles0, prefilled0, rounds0 = ctx.compiles.count, eng.prefilled_tokens, eng.rounds
    n_occ0 = len(occupancy)
    while clock() - w0 < ctx.seconds:
        pump()
    w1 = clock()
    window_compiles = ctx.compiles.count - compiles0
    prefilled = eng.prefilled_tokens - prefilled0
    rounds = eng.rounds - rounds0
    occ = occupancy[n_occ0:]
    spans = [(e[1], e[4], e[5]) for e in obs.tracer.events() if e[0] == "X" and w0 <= e[4] < w1] if obs else []

    # ---- traced extension (per-layer run only): the same loop goes on ----
    trace_summary, traced, traced_rounds = None, {}, 0
    if ctx.trace:
        t_sync = ctx.start_trace()
        t0 = clock()
        n_tok0, rounds_t0, prefilled_t0 = len(token_log), eng.rounds, eng.prefilled_tokens
        with jax.profiler.TraceAnnotation("bench.window"):
            while clock() - t0 < ctx.trace_seconds:
                pump()
        t1 = clock()
        window_compiles = ctx.compiles.count - compiles0
        tspans = [(e[1], e[4], e[5]) for e in obs.tracer.events() if e[0] == "X" and e[4] >= t0]
        trace_summary = ctx.stop_trace(t_sync, tspans)
        toks = [x for x in token_log[n_tok0:] if t0 <= x[0] < t1]
        traced = {"tokens": len(toks), "contexts": [c for _, _, c in toks], "seconds": t1 - t0,
                  # a request's first token comes from the prefill program: the decode steps made the others
                  "decode_contexts": [c for _, i, c in toks if c > recs[i].prompt_len],
                  "prefilled_tokens": eng.prefilled_tokens - prefilled_t0}
        traced_rounds = eng.rounds - rounds_t0

    # ---- what the clients saw ----
    done = [r for r in recs.values() if r.status and not r.primer and w0 <= r.t_last < w1]
    ttft = [r.t_first - r.t_submit for r in recs.values()
            if not r.primer and r.n_out and w0 <= r.t_submit and r.t_first < w1]
    tpot = [(r.t_last - r.t_first) / (r.n_out - 1) for r in done if r.n_out > 1]
    tokens_in = sum(1 for t, _, _ in token_log if w0 <= t < w1)
    attempted = sum(1 for r in recs.values() if not r.primer and w0 <= r.t_submit < w1)
    failed = sum(1 for r in done if r.status != "ok")
    stats = eng.stats()
    family = eng.serve_counters()  # the cache's counters by kind and the family's own (a device read, after the loops)
    ctx.log("engine counters:", {k: round(float(v), 3) for k, v in family.items()})
    if family.get("moe.dropped", 0):
        ctx.log(f"NOT CORRECT: {family['moe.dropped']} routed pairs assigned here were not computed")
    window_s = w1 - w0
    pct = ctx.percentile
    ctx.log(f"window: {window_s:.3f} s, {rounds} engine rounds, {attempted} requests submitted, "
            f"{len(done)} completed ({failed} failed), {tokens_in} output tokens delivered, "
            f"{prefilled} prompt tokens prefilled; samples: ttft {len(ttft)}, tpot {len(tpot)}; "
            f"preemptions {stats['preemptions']} timeouts {stats['timeouts']} shed {stats['shed']}")
    if lateness:
        ctx.log(f"open loop: generator lateness ms p50 {1e3 * pct(lateness, 50):.3f} "
                f"p99 {1e3 * pct(lateness, 99):.3f} max {1e3 * max(lateness):.3f}")
    if not ttft or not tpot:
        raise SystemExit(f"the window completed too few requests to report (ttft samples "
                         f"{len(ttft)}, tpot samples {len(tpot)}): run_seconds is too short for this traffic")
    e2e = {
        "setup_s": setup_s,
        "serve_tokens_per_s": tokens_in / window_s,
        "ttft_ms_mean": 1e3 * statistics.fmean(ttft),
        "tpot_ms_p90": 1e3 * pct(tpot, 90),
    }
    ctx.log(f"ttft ms mean {e2e['ttft_ms_mean']:.1f} p50 {1e3 * pct(ttft, 50):.1f} p90 {1e3 * pct(ttft, 90):.1f} max {1e3 * max(ttft):.1f}; "
            f"tpot ms p50 {1e3 * pct(tpot, 50):.2f} p90 {e2e['tpot_ms_p90']:.2f} max {1e3 * max(tpot):.2f}")
    return {
        "kind": "serve",
        "correct": correct and failed == 0 and stats["preemptions"] == 0 and not family.get("moe.dropped", 0),
        "attempted": attempted, "failed": failed, "end_to_end": e2e,
        "samples": {"ttft_s": ttft, "tpot_s": tpot, "occupancy": occ},
        "counters": {"window.compiles": window_compiles, "prefilled_tokens": prefilled,
                     "output_tokens": tokens_in, "rounds": rounds, "max_slots": eng.max_slots,
                     "completed": len(done), "kv_itemsize": 2, "prefill_chunk": eng.prefill_chunk,
                     "page_size": eng.page_size, "traced_rounds": traced_rounds,
                     "pool_pages": {k: v[0] for k, v in pools.items()},
                     **{k: float(v) for k, v in family.items()}},
        "traced": traced, "window_s": window_s, "spans": spans,
        "trace_summary": trace_summary, "model": dataclasses.asdict(mc),
    }


def main() -> int:
    """The 8-bit control (module docstring): set-up as far as the check, then
    the reference with 8-bit matrices through the same `judge`."""
    import argparse
    import json
    import sys
    import types

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true", help="test-only: tiny sizes on the CPU backend")
    args = ap.parse_args()
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        cell = harness.find_cell(json.load(f), args.workload)
    config = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config = harness.merge(config, config.get("rehearsal", {}))
        traffic = harness.merge(traffic, traffic.get("rehearsal", {}))
    sys.path.insert(0, harness.ROOT)
    import jax.numpy as jnp

    from midgpt_tpu.utils import compile_cache

    ctx = harness.Context(types.SimpleNamespace(seed=args.seed, seconds=0.0, trace=0, rehearse_cpu=args.rehearse_cpu),
                          cell, config, traffic)
    compile_cache.enable()
    mc, params = build_model(ctx)
    ok, control_ok = check_engine_path(ctx, mc, params, traffic["engine"], traffic["check"], control=jnp.float8_e4m3fn)
    print(json.dumps({"program_correct": ok, "control_correct": control_ok}))
    return 0 if ok and not control_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
