"""Serving cells (traffic kind "serve"): `ServeEngine` under generated load.

The system under test is `sampling/serve.py` ServeEngine (continuous batching
over a paged KV pool), built with only what the cell uses: no draft model, no
prefix cache, bf16 pool. One thread drives it, as `sample.py` does:

    set-up   weights on the device in one jitted call from --seed, in the
             dtype they are served in; correctness check (below); the engine;
             one warm-up request per decode page-bucket the traffic reaches, run
             alone, so that every (steps, bucket) decode program and every
             prefill bucket the traffic uses is compiled or loaded — and no
             other; then the closed loop's staggering requests (loadgen.prime).
    window   closed loop: a client submits its next request the moment its
             last one finishes. Open loop (`loop: "open"`): requests are
             submitted when due, latency counts from the due time and the
             generator's lateness is reported. The window opens when the last
             staggering request has finished and lasts --seconds; requests in
             flight at either edge count the tokens that fell inside.

The KV pool is the traffic file's: `engine.pool_tokens_per_slot` tokens for each
slot (rounded up to pages, plus the sink page), or, where the file gives none,
the engine's own default (half of what full-length contexts would take — what
`sample.py` users get).

End to end: `serve_tokens_per_s` = output tokens delivered inside the window /
window length; `ttft_ms_mean` = mean over ALL requests submitted (due) and first
answered inside the window of submit-to-first-token (the mean, because the
distribution moves in steps of one engine round and its median sits on a step); `tpot_ms_p90` = 90th percentile (nearest rank) of
(t_last - t_first) / (n_out - 1) over requests completed inside the window.

Correct: no request failed, and the logits of one seeded prompt prefilled in
chunks and decoded 8 tokens through the paged model path
(prefill_paged_chunk, decode_step_paged on a bf16 pool) agree with
reference.py's full float32 forward of the same weights.
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import numpy as np

# Error of the paged bf16 path against the float32 reference, over the logits
# of 9 positions, as a share of the reference logits' standard deviation (near
# 1). bf16 weights-times-activations and a bf16 KV pool through 12-24 layers
# leave an RMS error near 1.3e-2 and a largest error (of 450 thousand logits)
# of 4.4e-2 to 6.2e-2 (my chip runs, PR 23, four seeds). The RMS bound is what
# judges: a wrong page, position or mask gives an RMS near 1, 8-bit floating
# point weights or activations near 2e-1. An int8 pool alone would NOT be told
# from bf16 by this check (its error is of bf16's size): the pool dtype is
# asserted from the engine instead.
RMS_TOLERANCE, MAX_TOLERANCE = 4e-2, 2e-1
CHECK_PROMPT, CHECK_DECODE = 40, 8


def build_model(ctx):
    """(GPTConfig, params) — weights made on the device, in the served dtype,
    by one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.utils.precision import cast_floating

    mc = ctx.repo_config().model_config
    dtype = jnp.dtype(ctx.config["serve"]["weights_dtype"])
    init = jax.jit(lambda key: cast_floating(GPT.init(mc, key), dtype))
    params = jax.block_until_ready(init(jax.random.PRNGKey(ctx.seed32)))
    return mc, params


def check_paged_path(ctx, mc, params, eng_spec):
    """(ok, the numbers compared, each beside its limit)."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT, PagedKVCache

    reference = ctx.load("reference.py")
    ps, chunk = int(eng_spec["page_size"]), int(eng_spec["prefill_chunk"])
    P = min(CHECK_PROMPT, mc.block_size - CHECK_DECODE - 1)
    n_pages = -(-(P + CHECK_DECODE) // ps)
    rng = np.random.default_rng([ctx.seed32, 11])
    seq = rng.integers(0, mc.vocab_size, P + CHECK_DECODE, dtype=np.int32)
    cache = PagedKVCache.init(mc, num_pages=n_pages + 1, page_size=ps, dtype=jnp.bfloat16)
    table = jnp.asarray(np.arange(1, n_pages + 1, dtype=np.int32)[None])  # page 0 is the sink
    prefill = jax.jit(lambda p, t, s, n, c, row: GPT.prefill_paged_chunk(mc, p, t, s, n, c, row))
    decode = jax.jit(lambda p, tok, c, tab, ln, act: GPT.decode_step_paged(mc, p, tok, c, tab, ln, act))
    pos, got = 0, []
    while pos < P:
        n = min(chunk, P - pos)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :n] = seq[pos:pos + n]
        lg, cache = prefill(params, jnp.asarray(buf), jnp.asarray(pos, jnp.int32),
                            jnp.asarray(n, jnp.int32), cache, table)
        pos += n
    got.append(np.asarray(lg, np.float32)[0, n - 1])
    for i in range(P, P + CHECK_DECODE):
        # the next token is fed, not sampled: both sides see the same sequence
        lg, cache = decode(params, jnp.asarray(seq[i:i + 1]), cache, table,
                           jnp.asarray([i], jnp.int32), jnp.asarray([True]))
        got.append(np.asarray(lg, np.float32)[0])
    full = np.asarray(jax.jit(reference.logits, static_argnums=2)(params, jnp.asarray(seq[None]), mc.n_head))[0]
    want = full[P - 1:P + CHECK_DECODE]
    diff = np.stack(got) - want
    rms, worst = float(np.sqrt(np.mean(diff ** 2)) / np.std(want)), float(np.max(np.abs(diff)) / np.std(want))
    ok = bool(np.isfinite(rms) and rms <= RMS_TOLERANCE and worst <= MAX_TOLERANCE)
    ctx.log(f"correctness: paged path (prefill {P} tokens in chunks of {chunk}, then {CHECK_DECODE} "
            f"decode steps, bf16 pool) vs float32 reference logits of the same sequence: error/std "
            f"rms {rms:.3e} (tolerance {RMS_TOLERANCE:.0e}), max {worst:.3e} (tolerance "
            f"{MAX_TOLERANCE:.0e}) -> {'ok' if ok else 'NOT CORRECT'}")
    return ok, {"logits_rms_over_std": {"value": rms, "limit": RMS_TOLERANCE},
                "logits_max_over_std": {"value": worst, "limit": MAX_TOLERANCE}}


def page_bucket(tokens: int, page_size: int, max_pages: int) -> int:
    """The engine's pow2 page bucket (ServeEngine._page_bucket), re-stated
    here only to PLAN the warm-up; the engine decides its own buckets."""
    need, b = -(-tokens // page_size), 1
    while b < need:
        b *= 2
    return min(b, max_pages)


def warmup_plan(traffic, eng_spec, block_size: int):
    """(prompt_len, max_new) requests, each run alone, that visit every
    (steps, page-bucket) decode program the traffic can reach and no prefill
    bucket it does not use.

    Alone in the engine, a request decodes in rounds of n = the largest power
    of two <= min(decode_chunk, tokens still owed), at page bucket
    bucket(length + n). So per bucket b, in order of cost: a "tail" request
    (top-16, 16) runs rounds of 8, 4, 2, 1 at the top of b; where that prompt
    would need a prefill bucket the traffic never uses, "direct" requests
    (p, n+1) run one round of n each from the longest prompt allowed; where
    even those cannot reach b, one long decode from that prompt does."""
    ps, dc = int(eng_spec["page_size"]), int(eng_spec["decode_chunk"])
    max_pages = -(-block_size // ps)
    p_lo, p_hi = min(traffic.prompt_lens), max(traffic.prompt_lens)
    reach = max(p + o for p, o in zip(traffic.prompt_lens, traffic.output_lens))
    top_prefill = page_bucket(p_hi, ps, max_pages) * ps
    steps = [1 << i for i in range(dc.bit_length())]  # 1, 2, .., decode_chunk
    plan, b = [], page_bucket(p_lo + 1, ps, max_pages)
    while b <= page_bucket(reach, ps, max_pages):
        hi = min(b * ps, -(-reach // dc) * dc, block_size - 1)
        lo = b * ps // 2 + 1 if b > 1 else 1
        if 1 <= hi - 2 * dc <= top_prefill and hi - dc >= lo:
            plan.append((hi - 2 * dc, 2 * dc))
            b *= 2
            continue
        # a round of n steps from prompt p runs at bucket(p + n); a pair no
        # prompt >= 1 reaches cannot occur in traffic either
        direct = [(min(hi - n, top_prefill), n + 1) for n in steps
                  if min(hi - n, top_prefill) >= max(1, lo - n)]
        if len(direct) == len(steps) or hi <= top_prefill:
            plan.extend(direct)
        else:
            p = top_prefill - (hi - top_prefill) % dc
            plan.append((p, hi - p))
        b *= 2
    return plan


@dataclasses.dataclass
class Rec:
    """One request as the client saw it (host clock)."""
    index: int
    prompt_len: int
    max_new: int
    t_submit: float  # closed loop: the submit; open loop: the due time
    t_first: float = 0.0
    t_last: float = 0.0
    n_out: int = 0
    status: str = ""
    primer: bool = False


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.obs import Observability
    from midgpt_tpu.sampling.serve import ServeEngine

    loadgen = ctx.load("loadgen.py")
    ctx.phases.mark("program_imports")
    spec = ctx.traffic
    es = spec["engine"]
    mc, params = build_model(ctx)
    ctx.phases.mark("weights")
    correct, compared = check_paged_path(ctx, mc, params, es)
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
    per_slot = es.get("pool_tokens_per_slot")  # absent: the engine's default pool
    ps = int(es["page_size"])
    eng = ServeEngine(
        mc, params,
        max_slots=int(es["max_slots"]),
        num_pages=None if per_slot is None else int(es["max_slots"]) * -(-int(per_slot) // ps) + 1,
        page_size=int(es["page_size"]), prefill_chunk=int(es["prefill_chunk"]),
        decode_chunk=int(es["decode_chunk"]), temperature=float(es["temperature"]),
        seed=ctx.seed32, cache_dtype=es["cache_dtype"], prefix_cache=bool(es["prefix_cache"]),
        on_token=on_token, on_finish=finished.append, obs=obs,
    )
    ctx.log(f"engine: max_slots={eng.max_slots} pages={eng.allocator.num_pages} x {eng.page_size} tokens "
            f"pool={eng.cache_hbm_bytes() / 1e9:.3f} GB ({'engine default' if per_slot is None else str(per_slot) + ' tokens a slot'}) prefill_chunk={eng.prefill_chunk} "
            f"decode_chunk={eng.decode_chunk} temperature={eng.temperature} attn={eng.attn_impl} "
            f"prefix_cache={eng.prefix_cache is not None} draft={eng.draft_params is not None}")
    if str(eng.cache.k.dtype) != {"bf16": "bfloat16"}.get(es["cache_dtype"], es["cache_dtype"]):
        raise SystemExit(f"the engine's pool is {eng.cache.k.dtype}, the traffic file says {es['cache_dtype']}")
    ctx.phases.mark("engine_build")

    # ---- warm-up: the shapes this traffic reaches, by running them ----
    plan = warmup_plan(traffic, es, mc.block_size)
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
    trace_summary, traced = None, {}
    if ctx.trace:
        t_sync = ctx.start_trace()
        t0 = clock()
        n_tok0 = len(token_log)
        with jax.profiler.TraceAnnotation("bench.window"):
            while clock() - t0 < ctx.trace_seconds:
                pump()
        t1 = clock()
        window_compiles = ctx.compiles.count - compiles0
        tspans = [(e[1], e[4], e[5]) for e in obs.tracer.events() if e[0] == "X" and e[4] >= t0]
        trace_summary = ctx.stop_trace(t_sync, tspans)
        toks = [x for x in token_log[n_tok0:] if t0 <= x[0] < t1]
        traced = {"tokens": len(toks), "contexts": [c for _, _, c in toks], "seconds": t1 - t0}

    # ---- what the clients saw ----
    done = [r for r in recs.values() if r.status and not r.primer and w0 <= r.t_last < w1]
    ttft = [r.t_first - r.t_submit for r in recs.values()
            if not r.primer and r.n_out and w0 <= r.t_submit and r.t_first < w1]
    tpot = [(r.t_last - r.t_first) / (r.n_out - 1) for r in done if r.n_out > 1]
    tokens_in = sum(1 for t, _, _ in token_log if w0 <= t < w1)
    attempted = sum(1 for r in recs.values() if not r.primer and w0 <= r.t_submit < w1)
    failed = sum(1 for r in done if r.status != "ok")
    stats = eng.stats()
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
    parts = None
    if spec.get("window_parts"):
        window = {"w0": w0, "w1": w1, "parts": int(spec["window_parts"]),
                  "token_times": [t for t, _, _ in token_log if w0 <= t < w1],
                  "requests": [(r.t_submit, r.t_first if r.n_out else None, r.t_last if r.status else None, r.n_out)
                               for r in recs.values() if not r.primer]}
        try:
            parts = ctx.load("reduce.py").window_parts(window, pct)
            ctx.log(f"window in {window['parts']} parts of {window_s / window['parts']:.2f} s (a host stall shows "
                    f"as one part off the others; the medians over the parts are per-layer metrics): "
                    + "; ".join(f"{k} median {statistics.median(v):.6g} of {[float(f'{x:.5g}') for x in v]}"
                                for k, v in parts.items()))
        except ValueError as e:  # the three part medians are then left out of the line
            ctx.log(f"window parts: {e}")
    ctx.log(f"ttft ms mean {e2e['ttft_ms_mean']:.1f} p50 {1e3 * pct(ttft, 50):.1f} p90 {1e3 * pct(ttft, 90):.1f} max {1e3 * max(ttft):.1f}; "
            f"tpot ms p50 {1e3 * pct(tpot, 50):.2f} p90 {e2e['tpot_ms_p90']:.2f} max {1e3 * max(tpot):.2f}")
    return {
        "kind": "serve", "correct": correct and failed == 0 and stats["preemptions"] == 0,
        "attempted": attempted, "failed": failed, "end_to_end": e2e,
        "samples": {"ttft_s": ttft, "tpot_s": tpot, "occupancy": occ}, "window_parts": parts,
        "compared": {**compared,
                     "requests_failed": {"value": failed, "limit": 0},
                     "preemptions": {"value": stats["preemptions"], "limit": 0}},
        "counters": {"window.compiles": window_compiles, "prefilled_tokens": prefilled,
                     "output_tokens": tokens_in, "rounds": rounds, "max_slots": eng.max_slots,
                     "completed": len(done), "kv_itemsize": 2},
        "traced": traced, "window_s": window_s, "spans": spans,
        "trace_summary": trace_summary, "model": dataclasses.asdict(mc),
    }
