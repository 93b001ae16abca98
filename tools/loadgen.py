"""Arrival-process load harness for the serving front door: one JSON line.

bench_serve replays a fixed trace to completion — a throughput number.
Production serving is governed by DIFFERENT numbers: time-to-first-token
and time-per-output-token percentiles under an offered load, and what
fraction of traffic had to be shed to hold them (the error budget). This
harness generates a seeded arrival process (Poisson or bursty), a
prompt/output-length mixture (short chat-y requests vs long-document
requests, optionally a `--template-frac` share of template-headed
system-prompt traffic), drives the asyncio front door
(sampling/server.py) over a fresh `ServeEngine` at each offered-load
point, and emits ONE JSON line (driver contract, `serve_slo` profile in
analysis/bench_contract.py). With `--prefix-cache` the engines run with
the cross-request prefix cache on and per-point/headline
`prefix_hit_rate` fields report how much prefill the trie absorbed. With
`--fleet N` each point instead drives N replica engines behind the
prefix-affinity FleetRouter with its shared host-RAM KV spill tier
(sampling/fleet.py; docs/ROBUSTNESS.md "Fleet serving & failover") through
a synchronous step loop, and points + headline carry fleet_size /
failovers / fleet-wide prefix_hit_rate / spill_hits. Adding `--procs`
promotes every replica to a worker PROCESS behind the framed socket
transport (sampling/fleet_proc.py; docs/ROBUSTNESS.md "Cross-process
fleet") — the parent builds no engine and compiles nothing, and points +
headline add rpc_p50_ms / rpc_p95_ms / wire_bytes:

    python tools/loadgen.py --process poisson --rates 20,60 \
        [--scheduler slo] [--ttl-s 2.0] [--slo-ttft-ms 500 --slo-tpot-ms 50] \
        [--error-budget 0.2] [--cpu-devices 8] [--trace-out /tmp/traces]

Every engine runs under a per-point flight recorder (midgpt_tpu/obs/):
each point (and the headline, from the hottest point) carries
`round_host_ms`/`round_device_ms` p50/p95 — the decode-round split into
host work (batch assembly + jit enqueue + token commit) vs device wait
(docs/OBSERVABILITY.md) — plus `overlap_mode`/`round_group`/
`overlap_hidden_ms`, the round-overlap dispatch A/B identity driven by
`--overlap {off,double,group:k}` (docs/SERVING.md "Round-overlap
dispatch"; the TPOT-vs-mode comparison is THE acceptance A/B for ROADMAP
item 3). `--trace-out DIR` additionally dumps one Chrome-trace JSON
(+ .prom metrics) per point for Perfetto / tools/trace_view.py.

Client-perceived metrics: TTFT is measured from the client's submit
attempt (admission retries and queueing included — that is what a user
waits through), TPOT from first to last streamed token. `shed_frac`
counts requests refused by backpressure/SLO admission after the bounded
retry budget; `timeout_frac` counts TTL expiries. A point is `slo_ok`
when its p95s meet the (optional) SLO targets AND shed+timeout stays
inside the error budget.

Compile time is not a latency claim: every jit shape the workload can
touch is warmed by a synchronous pre-pass before the first timed point
(module-level jits — warm shapes are shared by every engine after it).
Arrivals, mixtures, and scheduling are all seeded/deterministic; the
measured times are wall-clock, so on the CPU test mesh treat percentiles
as scheduling-structure signal (CLAUDE.md), not kernel-speed signal.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import typing as tp

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _percentile_ms(xs: tp.List[float], q: float) -> float:
    """Percentile of a list of seconds, in ms; 0.0 for an empty list (a
    degenerate point — visible as completed == 0, never NaN: the JSON
    contract rejects non-finite constants)."""
    if not xs:
        return 0.0
    return round(float(np.percentile(np.asarray(xs), q)) * 1e3, 3)


def _arrivals(process: str, rate: float, n: int, rng, burst_size: int):
    """Seeded arrival offsets (seconds from point start) at offered rate
    `rate` req/s: exponential inter-arrivals (poisson) or bursts of
    `burst_size` simultaneous arrivals with exponential gaps sized so the
    long-run offered rate matches (bursty — the pathological shape
    continuous batching exists to absorb)."""
    t, out = 0.0, []
    if process == "poisson":
        for _ in range(n):
            t += float(rng.exponential(1.0 / rate))
            out.append(t)
    else:  # bursty
        while len(out) < n:
            t += float(rng.exponential(burst_size / rate))
            out.extend([t] * min(burst_size, n - len(out)))
    return out


def _mixture(
    rng, n: int, block_size: int, vocab: int, long_frac: float,
    templates: tp.Sequence[np.ndarray] = (), template_frac: float = 0.0,
):
    """Prompt/output-length mixture: mostly short interactive requests, a
    `long_frac` tail of long-document prompts with bigger budgets. With
    `template_frac` > 0, that fraction of requests instead share one of
    `templates` as a common prompt head (system-prompt traffic) with a
    short unique tail — the workload the cross-request prefix cache
    (sampling/prefix_cache.py) exists for. Templates are built once per
    SEED, not per point, so every offered-load point measures the same
    shared heads — points stay comparable even though each point's fresh
    engine starts with a cold trie."""
    reqs = []
    for _ in range(n):
        if templates and rng.random() < template_frac:
            head = templates[int(rng.integers(0, len(templates)))]
            tail = rng.integers(
                0, vocab, int(rng.integers(2, 8)), dtype=np.int64
            )
            prompt = np.concatenate([head, tail])
            m = min(int(rng.integers(6, 14)), block_size - len(prompt) - 1)
            reqs.append((prompt, m))
            continue
        if rng.random() < long_frac:
            if block_size >= 2048:
                # long-context regime (the split-K bucket rule's territory,
                # sampling/serve.py `_split_bucket`): near-context document
                # prompts with bigger output budgets, so the serve_slo line
                # tracks p95 TPOT with auto-split decode in the mix. The
                # small-block branch below is untouched — the default
                # harness geometry (and its pinned program census) draws
                # the exact same stream it always did.
                t0 = int(rng.integers(block_size // 2, block_size * 7 // 8))
                m = int(rng.integers(24, 48))
            else:
                t0 = int(rng.integers(block_size // 4, block_size // 2))
                m = int(rng.integers(12, 24))
        else:
            t0 = int(rng.integers(4, max(5, block_size // 8)))
            m = int(rng.integers(6, 14))
        m = min(m, block_size - t0 - 1)
        reqs.append((rng.integers(0, vocab, t0, dtype=np.int64), m))
    return reqs


def _warm_compile_grid(engine, cfg, decode_chunk, page_size, seed):
    """Compile the full reachable serving program set: for each pow2 page
    bucket and each pow2 decode-chunk tail, run one solo request whose
    prompt pins the bucket and whose budget pins the tail width (the
    bucket/tail scheme: sampling/serve.py `_page_bucket`/`_decode_round`).
    Sequential solo runs also sweep every prefill bucket on the way."""
    rng = np.random.default_rng(seed + 7919)
    S = cfg.block_size
    max_bucket = engine.max_pages_per_slot
    tails = []
    n = decode_chunk
    while n >= 1:
        tails.append(n)
        n //= 2
    b = 1
    while b <= max_bucket:
        # mid-page prompt: bucket stays pinned at b while the tail decodes
        prompt_len = max(2, (b - 1) * page_size + 2)
        for tail in tails:
            if prompt_len + 1 + tail >= S:
                continue
            engine.submit(
                rng.integers(0, cfg.vocab_size, prompt_len, np.int64),
                tail + 1,  # first token rides prefill; `tail` decode steps
            )
            engine.run()
        b *= 2


async def _drive_point(server, reqs, arrivals, ttl_s):
    """One offered-load point: a client task per request (sleep to its
    arrival, submit with the server's bounded backpressure retry, consume
    the stream). Returns per-request client-side records."""
    from midgpt_tpu.sampling.serve import BackpressureError
    from midgpt_tpu.sampling.server import ServerDraining

    t0 = time.perf_counter()
    records = []

    async def client(i, prompt, m, at):
        delay = at - (time.perf_counter() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"i": i, "status": "shed", "ttft_s": None, "tpot_s": None}
        records.append(rec)
        t_submit = time.perf_counter()
        try:
            uid = await server.submit(prompt, m, ttl_s=ttl_s)
        except (BackpressureError, ServerDraining):
            return
        times = []
        async for _tok in server.stream(uid):
            times.append(time.perf_counter())
        fr = server.result(uid)
        rec["status"] = fr.status if fr is not None else "lost"
        if times:
            rec["ttft_s"] = times[0] - t_submit
            if len(times) > 1:
                rec["tpot_s"] = (times[-1] - times[0]) / (len(times) - 1)

    await asyncio.gather(
        *(client(i, p, m, at)
          for i, ((p, m), at) in enumerate(zip(reqs, arrivals)))
    )
    return records


def _drive_fleet_point(router, reqs, arrivals, ttl_s, submit_retries=8):
    """One offered-load point against a FleetRouter, driven synchronously:
    the router's step loop IS the clock (sampling/fleet.py — replicas are
    in-process engines, so an asyncio front door would add nothing but
    scheduling noise). Arrivals submit when their offset passes, under a
    bounded per-request retry budget — a request still refused after
    `submit_retries` attempts stays a shed, mirroring the async path's
    bounded-retry front door. TTFT runs from the FIRST submit attempt
    (admission retries and queueing included, same client-perceived
    definition as _drive_point); token times ride the router's on_token
    relay, so across a failover the replayed stream's delivery is
    at-least-once and TPOT is measured over everything the client saw."""
    from midgpt_tpu.sampling.serve import BackpressureError

    t0 = time.perf_counter()
    records = [
        {"i": i, "status": "shed", "ttft_s": None, "tpot_s": None}
        for i in range(len(reqs))
    ]
    first_attempt: tp.Dict[int, float] = {}
    token_times: tp.Dict[int, tp.List[float]] = {}
    uid_to_i: tp.Dict[int, int] = {}

    def on_token(uid, tok, t):
        token_times.setdefault(uid, []).append(time.perf_counter())

    router.on_token = on_token
    order = sorted(range(len(reqs)), key=lambda i: arrivals[i])
    qi = 0
    waiting: tp.List[tp.List[int]] = []  # [request index, attempts so far]
    guard = 0
    while qi < len(order) or waiting or not router.idle:
        guard += 1
        if guard >= 1_000_000:
            raise SystemExit("fleet point did not converge")
        now = time.perf_counter() - t0
        while qi < len(order) and arrivals[order[qi]] <= now:
            waiting.append([order[qi], 0])
            qi += 1
        still: tp.List[tp.List[int]] = []
        for item in waiting:
            i = item[0]
            first_attempt.setdefault(i, time.perf_counter())
            try:
                uid = router.submit(reqs[i][0], reqs[i][1], ttl_s=ttl_s)
            except BackpressureError as e:
                item[1] += 1
                if item[1] < submit_retries and getattr(e, "retryable", False):
                    still.append(item)
                continue  # budget exhausted / terminal: stays "shed"
            uid_to_i[uid] = i
        waiting = still
        if qi < len(order) and router.idle and not waiting:
            # quiet fleet, next arrival in the future: sleep up to it
            delay = arrivals[order[qi]] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            continue
        router.step()
    for uid, i in uid_to_i.items():
        fr = router.finished.get(uid)
        rec = records[i]
        rec["status"] = fr.status if fr is not None else "lost"
        times = token_times.get(uid, [])
        if times:
            rec["ttft_s"] = times[0] - first_attempt[i]
            if len(times) > 1:
                rec["tpot_s"] = (times[-1] - times[0]) / (len(times) - 1)
    return records


def _point_stats(rate, records, error_budget, slo_ttft_ms, slo_tpot_ms):
    n = len(records)
    shed = sum(1 for r in records if r["status"] == "shed")
    timeouts = sum(1 for r in records if r["status"] == "timeout")
    completed = sum(1 for r in records if r["status"] == "ok")
    ttfts = [r["ttft_s"] for r in records if r["ttft_s"] is not None]
    tpots = [r["tpot_s"] for r in records if r["tpot_s"] is not None]
    stats = {
        "offered_rps": rate,
        "n_offered": n,
        "completed": completed,
        "shed": shed,
        "timeouts": timeouts,
        "shed_frac": round(shed / max(n, 1), 4),
        "timeout_frac": round(timeouts / max(n, 1), 4),
        "ttft_p50_ms": _percentile_ms(ttfts, 50),
        "ttft_p95_ms": _percentile_ms(ttfts, 95),
        "tpot_p50_ms": _percentile_ms(tpots, 50),
        "tpot_p95_ms": _percentile_ms(tpots, 95),
    }
    ok = (shed + timeouts) / max(n, 1) <= error_budget
    if slo_ttft_ms:
        ok = ok and stats["ttft_p95_ms"] <= slo_ttft_ms
    if slo_tpot_ms:
        ok = ok and stats["tpot_p95_ms"] <= slo_tpot_ms
    stats["slo_ok"] = bool(ok and completed > 0)
    return stats


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--process", choices=("poisson", "bursty"), default="poisson")
    ap.add_argument("--rates", type=str, default="20,60",
                    help="comma-separated offered loads (req/s), one timed "
                    "point each — >= 2 points make the SLO curve the "
                    "serve_slo contract expects")
    ap.add_argument("--n-requests", type=int, default=8,
                    help="requests offered per point")
    ap.add_argument("--burst-size", type=int, default=4,
                    help="--process bursty: simultaneous arrivals per burst")
    ap.add_argument("--long-frac", type=float, default=0.25,
                    help="fraction of long-document requests in the mixture. "
                    "At --block-size >= 2048 the long draws move to the "
                    "long-context regime (prompts of S/2..7S/8 tokens, "
                    "24-48 token budgets) so p95 TPOT under mixed load "
                    "exercises the auto split-K buckets (docs/SERVING.md "
                    "'Split-K decode'); smaller block sizes keep the "
                    "original S/4..S/2 draws")
    ap.add_argument("--template-frac", type=float, default=0.0,
                    help="fraction of requests sharing a template prompt "
                    "head (system-prompt traffic); pair with "
                    "--prefix-cache to measure cross-request reuse")
    ap.add_argument("--n-templates", type=int, default=2,
                    help="distinct shared prompt heads in the template mix")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the cross-request prefix cache "
                    "(sampling/prefix_cache.py) in every engine; per-point "
                    "and headline prefix_hit_rate fields are emitted")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", choices=("fcfs", "slo"), default="fcfs")
    ap.add_argument("--min-headroom-s", type=float, default=0.0,
                    help="--scheduler slo: shed requests whose deadline is "
                    "nearer than this at submit")
    ap.add_argument("--ttl-s", type=float, default=0.0,
                    help="per-request TTL (0 = none): expiries count "
                    "against the error budget as timeouts")
    ap.add_argument("--slo-ttft-ms", type=float, default=0.0,
                    help="p95 TTFT target (0 = unset)")
    ap.add_argument("--slo-tpot-ms", type=float, default=0.0,
                    help="p95 TPOT target (0 = unset)")
    ap.add_argument("--error-budget", type=float, default=0.2,
                    help="max shed+timeout fraction for a point to be slo_ok")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="directory to dump one Chrome-trace flight "
                    "recorder (+ .prom metrics) per offered-load point — "
                    "open in Perfetto or roll up with tools/trace_view.py")
    ap.add_argument("--hot-swap", action="store_true",
                    help="zero-downtime ops under load: at each point, a "
                    "verified-checkpoint blue/green weight swap is staged "
                    "through the async front door mid-arrival-window "
                    "(sampling/ops.py; docs/ROBUSTNESS.md 'Zero-downtime "
                    "model ops'). Points and headline carry the "
                    "weights_version transition; the SLO acceptance is the "
                    "curve staying inside the error budget THROUGH the "
                    "swap — same slo_ok computation, no special-casing")
    ap.add_argument("--fleet", type=int, default=0,
                    help=">= 2 runs every point against that many replica "
                    "engines behind the prefix-affinity FleetRouter "
                    "(sampling/fleet.py) with its shared host-RAM spill "
                    "tier, driven synchronously (the router step loop is "
                    "the clock). Implies --prefix-cache (the trie is the "
                    "affinity target). Points and headline carry "
                    "fleet_size / failovers / fleet-wide prefix_hit_rate "
                    "/ spill_hits (docs/ROBUSTNESS.md 'Fleet serving & "
                    "failover'). Incompatible with --hot-swap and --tp")
    ap.add_argument("--procs", action="store_true",
                    help="--fleet: replicas are separate worker PROCESSES "
                    "(sampling/fleet_proc.py) behind the framed socket "
                    "transport — the parent builds no engine and compiles "
                    "nothing; every point drives the same worker fleet. "
                    "Points and headline add rpc_p50_ms / rpc_p95_ms / "
                    "wire_bytes (docs/ROBUSTNESS.md 'Cross-process "
                    "fleet'). Round decomposition reads zero (the rounds "
                    "run in the workers); fcfs scheduler and --overlap "
                    "off only")
    ap.add_argument("--overlap", type=str, default="off",
                    help="round-overlap dispatch mode for every engine "
                    "(docs/SERVING.md 'Round-overlap dispatch'): 'off', "
                    "'double' (dispatch round N+1 before round N's host "
                    "phase), or 'group:k' (fuse k rounds per dispatch). "
                    "Fixed offered load + --overlap off vs double is the "
                    "TPOT A/B; points and headline carry overlap_mode / "
                    "round_group / overlap_hidden_ms either way")
    # engine/model shape (tiny defaults: the CPU-mesh scheduling testbed)
    ap.add_argument("--max-slots", type=int, default=3)
    ap.add_argument("--page-size", type=int, default=8)
    # 27, not 25: pool size is a jit program-key dim, and the tier-1
    # recompile pins (tests/test_recompile_pins.py) count compiles of the
    # 25-page f32 geometry from a pristine baseline — the in-process
    # bench-contract loadgen run must not pre-warm that program set.
    # 0 = auto: 27 below the long-context regime; at --block-size >= 2048
    # a 27-page pool cannot hold ONE long-mixture prompt, so auto sizes a
    # fully-resident pool (every slot can pin its largest bucket).
    ap.add_argument("--num-pages", type=int, default=0)
    ap.add_argument("--max-backlog-pages", type=int, default=0,
                    help="backpressure budget (0 = unbounded)")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=64)
    ap.add_argument("--vocab-size", type=int, default=96)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--n-head", type=int, default=2)
    ap.add_argument("--n-embd", type=int, default=32)
    ap.add_argument("--n-kv-heads", type=int, default=0,
                    help="GQA/MQA: KV heads shared by n_head/n_kv_heads "
                    "query-head groups (0 = MHA; docs/SERVING.md "
                    "'Attention variants'). Shrinks KV page bytes by the "
                    "group factor; the serve_slo model block carries the "
                    "variant knobs so GQA curves are not comparable-by-"
                    "accident with MHA ones")
    ap.add_argument("--sliding-window", type=int, default=0,
                    help="sliding-window attention: decode attends to the "
                    "last N positions only and the engine reclaims pages "
                    "behind the window (0 = full context)")
    ap.add_argument("--attn-sinks", type=int, default=0,
                    help="with --sliding-window: the first N positions "
                    "stay visible (and their pages resident) beyond the "
                    "window")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force CPU with this many virtual devices (0 = native)")
    ap.add_argument("--tp", type=int, default=0,
                    help="> 0 runs every engine tensor-parallel on a "
                    "(data=1, tp=N) serve mesh (parallel/serve_tp.py): "
                    "params sharded by the megatron tp rules, KV pool on "
                    "the head axis. The serve_slo line carries tp/mesh "
                    "fields so sharded and single-chip curves are "
                    "distinguishable. Pair with --cpu-devices >= N")
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if args.fleet:
        if args.fleet < 2:
            ap.error("--fleet needs >= 2 replicas (one cannot fail over)")
        if args.hot_swap or args.tp:
            ap.error("--fleet is incompatible with --hot-swap and --tp")
        args.prefix_cache = True  # the router's affinity target
    if args.procs:
        if not args.fleet:
            ap.error("--procs requires --fleet N (it spawns the replicas)")
        if args.scheduler != "fcfs":
            ap.error("--procs workers run the default fcfs scheduler")
        if args.overlap != "off":
            ap.error("--procs workers run with --overlap off")
        if args.max_backlog_pages:
            ap.error("--procs workers run with an unbounded backlog")
    if not args.num_pages:
        pages_per_slot = -(-args.block_size // args.page_size)
        args.num_pages = (
            27 if args.block_size < 2048
            else 1 + args.max_slots * pages_per_slot
        )

    import jax

    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)

    import jax.numpy as jnp

    from midgpt_tpu.models.gpt import GPT, GPTConfig
    from midgpt_tpu.obs import Observability
    from midgpt_tpu.sampling.scheduler import FCFSScheduler, SLOScheduler
    from midgpt_tpu.sampling.serve import ServeEngine, parse_overlap
    from midgpt_tpu.sampling.server import AsyncServeServer

    overlap_mode, overlap_group = parse_overlap(args.overlap)

    cfg = GPTConfig(
        block_size=args.block_size,
        vocab_size=args.vocab_size,
        n_layer=args.n_layer,
        n_head=args.n_head,
        n_embd=args.n_embd,
        n_kv_heads=args.n_kv_heads or None,
        sliding_window=args.sliding_window,
        attn_sinks=args.attn_sinks,
    )
    worker_procs: tp.List[tp.Any] = []
    proc_replicas: tp.List[tp.Any] = []
    if args.procs:
        # The parent builds no params and no engine: the replicas are
        # worker processes (own CPU mesh, own jit cache, same-seed
        # params), reused across every offered-load point. Warm each
        # worker's full compile grid over the wire so the first point's
        # percentiles measure scheduling, not worker-side compiles.
        import dataclasses as _dc

        from midgpt_tpu.sampling.fleet_proc import (
            connect_replica,
            parent_jax_config,
            spawn_workers,
        )

        spec = {
            "model": _dc.asdict(cfg),
            "seed": args.seed,
            "engine": {
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "num_pages": args.num_pages,
                "prefill_chunk": args.prefill_chunk,
                "decode_chunk": args.decode_chunk,
                "cache_dtype": "float32",
            },
            "cpu_devices": args.cpu_devices or 1,
            "jax_config": parent_jax_config(),
        }
        worker_procs = spawn_workers(spec, args.fleet)
        proc_replicas = [
            connect_replica(port, retry_base_s=0.05)
            for _, port in worker_procs
        ]
        for rep in proc_replicas:
            _warm_compile_grid(
                rep, cfg, args.decode_chunk, args.page_size, args.seed
            )
    else:
        params = GPT.init(cfg, jax.random.PRNGKey(args.seed))
    on_tpu = jax.default_backend() == "tpu"
    cache_dtype = jnp.bfloat16 if on_tpu else jnp.float32

    mesh = None
    if args.tp:
        from midgpt_tpu.parallel.serve_tp import make_serve_mesh

        if args.tp < 2 or args.tp > len(jax.devices()):
            raise SystemExit(
                f"--tp {args.tp} needs 2 <= tp <= {len(jax.devices())} devices"
            )
        if cfg.n_head % args.tp:
            raise SystemExit(f"--tp {args.tp} must divide n_head {cfg.n_head}")
        mesh = make_serve_mesh(tp_size=args.tp)

    def make_engine(obs=None, obs_tid="engine"):
        sched = (
            SLOScheduler(min_headroom_s=args.min_headroom_s)
            if args.scheduler == "slo"
            else FCFSScheduler()
        )
        return ServeEngine(
            cfg,
            params,
            obs_tid=obs_tid,
            max_slots=args.max_slots,
            page_size=args.page_size,
            num_pages=args.num_pages,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype=cache_dtype,
            max_backlog_pages=args.max_backlog_pages or None,
            scheduler=sched,
            prefix_cache=bool(args.prefix_cache),
            mesh=mesh,
            obs=obs,
            overlap=overlap_mode,
            round_group=overlap_group,
        )

    # Warm EVERY (decode-chunk tail x page bucket) program the workload
    # can reach, plus all prefill buckets — solo requests crafted per
    # combo. This matters more here than in bench_serve: arrivals are
    # sparse, so a request often decodes alone at a SMALL page bucket that
    # a concurrent warm trace would never touch, and one cold combo costs
    # ~1s on this host — enough to swamp a timed point's percentiles. The
    # jits are module-level, so every per-point engine dispatches warm.
    S = cfg.block_size
    # The warm engine runs prefix-enabled too (make_engine): the cache is
    # page-table indirection over the SAME program set — the grid below
    # stays exhaustive over the prefix-cache path with zero extra shapes,
    # and a warm run proving that is cheaper than trusting it.
    warm = None
    if not args.procs:
        warm = make_engine()
        _warm_compile_grid(
            warm, cfg, args.decode_chunk, args.page_size, args.seed
        )

    # --hot-swap: one verified checkpoint (training/checkpoint.py sha256
    # manifest) restored once; every point stages the same candidate, so
    # points stay comparable. Same shapes as the live params — the swap
    # must not compile anything (tests/test_recompile_pins.py pins it).
    swap_payload = None
    if args.hot_swap:
        import tempfile
        import types

        from midgpt_tpu.sampling.engine import restore_for_sampling
        from midgpt_tpu.training.checkpoint import CheckpointManager

        ckpt_dir = os.path.join(
            tempfile.mkdtemp(prefix="midgpt_loadgen_swap_"), "ckpt"
        )
        mgr = CheckpointManager(ckpt_dir, save_interval_steps=1)
        mgr.save(
            3, {"params": GPT.init(cfg, jax.random.PRNGKey(args.seed + 101))},
            force=True,
        )
        mgr.wait()
        swap_version = mgr.weights_version(3)
        mgr.close()
        shim = types.SimpleNamespace(
            model_config=cfg, fsdp_min_size=1 << 60, param_dtype="float32"
        )
        # Replicated restore (mesh=None — restore_for_sampling's mesh arg
        # wants a training fsdp mesh, not a serve mesh): stage_hot_swap
        # device_puts the candidate onto the LIVE params' shardings, which
        # re-shards it correctly for tp engines too.
        swap_params, _ = restore_for_sampling(ckpt_dir, shim)
        swap_payload = (swap_params, swap_version)

    # Shared prompt heads for the template mixture: ~3 pages each, built
    # once per seed (see _mixture on why once-per-seed matters).
    template_rng = np.random.default_rng(args.seed + 31)
    templates = [
        template_rng.integers(0, cfg.vocab_size, 3 * args.page_size, np.int64)
        for _ in range(args.n_templates)
    ] if args.template_frac > 0.0 else []

    points = []
    for pi, rate in enumerate(rates):
        point_rng = np.random.default_rng(args.seed + 1000 * pi)
        reqs = _mixture(
            point_rng, args.n_requests, S, cfg.vocab_size, args.long_frac,
            templates=templates, template_frac=args.template_frac,
        )
        arrivals = _arrivals(
            args.process, rate, args.n_requests, point_rng, args.burst_size
        )
        # One flight recorder per point: round decomposition percentiles
        # (dispatch / device_wait / host_post — docs/OBSERVABILITY.md) are
        # per-offered-load numbers, and a dumped trace must cover exactly
        # one point to be readable.
        obs = Observability()
        if args.fleet:
            from midgpt_tpu.sampling.fleet import (
                FleetRouter,
                assert_fleet_conserved,
            )

            if args.procs:
                # Fresh router per point (per-point ledger/counters) over
                # the PERSISTENT worker fleet: the workers' jit caches and
                # tries stay warm across points, like module-level jits do
                # for in-process replicas. Hit rate and wire bytes are
                # deltas over this point's drive; rpc percentiles are
                # transport-lifetime distributions.
                pm0 = sum(r._prefix_matched_tokens for r in proc_replicas)
                pa0 = sum(r._prefix_matchable_tokens for r in proc_replicas)
                router = FleetRouter(proc_replicas)
                wire0 = router.transport_stats()["wire_bytes"]
            else:
                # One recorder across the replicas (distinct tids): the
                # decomposition is a fleet-wide round picture for this
                # point.
                router = FleetRouter(
                    [
                        make_engine(obs, obs_tid=f"replica{k}")
                        for k in range(args.fleet)
                    ]
                )
            records = _drive_fleet_point(
                router, reqs, arrivals, args.ttl_s or None
            )
            assert_fleet_conserved(router, f"loadgen point {pi}")
            stats = _point_stats(
                rate, records, args.error_budget,
                args.slo_ttft_ms, args.slo_tpot_ms,
            )
            stats["fleet_size"] = args.fleet
            stats["failovers"] = router.failovers
            stats["spill_hits"] = router.spill.readopted
            if args.procs:
                pm1 = sum(r._prefix_matched_tokens for r in proc_replicas)
                pa1 = sum(r._prefix_matchable_tokens for r in proc_replicas)
                stats["prefix_hit_rate"] = round(
                    (pm1 - pm0) / max(pa1 - pa0, 1), 4
                )
                transport = router.transport_stats()
                stats["rpc_p50_ms"] = transport["rpc_p50_ms"]
                stats["rpc_p95_ms"] = transport["rpc_p95_ms"]
                stats["wire_bytes"] = transport["wire_bytes"] - wire0
                stats["proc_failovers"] = router.proc_failovers
            else:
                stats["prefix_hit_rate"] = round(router.prefix_hit_rate(), 4)
            decomp = obs.round_decomp()
            stats["rounds"] = decomp["rounds"]
            stats["round_host_ms"] = {
                "p50": round(
                    decomp["dispatch"]["p50_ms"]
                    + decomp["host_post"]["p50_ms"], 3
                ),
                "p95": round(
                    decomp["dispatch"]["p95_ms"]
                    + decomp["host_post"]["p95_ms"], 3
                ),
            }
            stats["round_device_ms"] = {
                "p50": decomp["device_wait"]["p50_ms"],
                "p95": decomp["device_wait"]["p95_ms"],
            }
            stats["overlap_mode"] = warm.overlap if warm else "off"
            stats["round_group"] = warm.round_group if warm else 1
            stats["overlap_hidden_ms"] = {
                "p50": decomp["overlap_hidden"]["p50_ms"],
                "p95": decomp["overlap_hidden"]["p95_ms"],
            }
            if args.trace_out:
                obs.dump(
                    args.trace_out,
                    filename=f"loadgen_point{pi}_r{rate:g}.json",
                )
            points.append(stats)
            continue
        engine = make_engine(obs)
        server = AsyncServeServer(engine, idle_poll_s=0.001)

        async def run_point():
            driver = asyncio.create_task(server.run())
            swapper = None
            if swap_payload is not None:
                # Stage mid-arrival-window (the median arrival): traffic
                # lands on both sides of the flip, so the point's
                # percentiles measure the swap's SLO cost, not a quiet
                # engine's.
                async def do_swap():
                    await asyncio.sleep(arrivals[len(arrivals) // 2])
                    await server.hot_swap(
                        swap_payload[0], version=swap_payload[1], config=cfg
                    )

                swapper = asyncio.create_task(do_swap())
            records = await _drive_point(
                server, reqs, arrivals, args.ttl_s or None
            )
            if swapper is not None:
                await swapper
            await server.drain()
            await driver
            return records

        records = asyncio.run(run_point())
        stats = _point_stats(
            rate, records, args.error_budget,
            args.slo_ttft_ms, args.slo_tpot_ms,
        )
        if swap_payload is not None:
            # The transition a metrics scrape would see on this point.
            stats["weights_version"] = engine.weights_version
            stats["hot_swaps"] = engine.hot_swaps
            stats["swap_flip_round"] = (
                engine.swap_history[-1]["flip_round"]
                if engine.swap_history else None
            )
        if args.prefix_cache:
            # Engine-side observability through the front door's stats()
            # passthrough — what a deployment's metrics scrape would read.
            stats["prefix_hit_rate"] = round(
                server.stats()["prefix"]["hit_rate"], 4
            )
        # Round timing decomposition, read the same way a deployment
        # would: through the stats() obs payload. host = dispatch (batch
        # assembly + jit enqueue) + host_post (token commit); device =
        # device_wait (enqueue -> array landed on the host, the round's one
        # sync point). Percentile sums are a summary convenience, not a joint
        # distribution claim.
        decomp = server.stats()["obs"]["round_decomp"]
        stats["rounds"] = decomp["rounds"]
        stats["round_host_ms"] = {
            "p50": round(
                decomp["dispatch"]["p50_ms"] + decomp["host_post"]["p50_ms"], 3
            ),
            "p95": round(
                decomp["dispatch"]["p95_ms"] + decomp["host_post"]["p95_ms"], 3
            ),
        }
        stats["round_device_ms"] = {
            "p50": decomp["device_wait"]["p50_ms"],
            "p95": decomp["device_wait"]["p95_ms"],
        }
        # round-overlap A/B identity (engine.round_group is the bucketed
        # value that actually ran) + the host time the overlap hid
        stats["overlap_mode"] = engine.overlap
        stats["round_group"] = engine.round_group
        stats["overlap_hidden_ms"] = {
            "p50": decomp["overlap_hidden"]["p50_ms"],
            "p95": decomp["overlap_hidden"]["p95_ms"],
        }
        if args.trace_out:
            obs.dump(args.trace_out, filename=f"loadgen_point{pi}_r{rate:g}.json")
        points.append(stats)

    worst = points[-1]  # rates ascending by convention: report the hottest
    print(
        json.dumps(
            {
                "bench": "serve_slo",
                # --procs: the workers' backend (the parent runs no engine)
                "backend": "cpu" if args.procs else jax.default_backend(),
                "process": args.process,
                "scheduler": args.scheduler,
                "seed": args.seed,
                "n_requests": args.n_requests,
                "long_frac": args.long_frac,
                "template_frac": args.template_frac or None,
                "prefix_cache": bool(args.prefix_cache),
                "ttl_s": args.ttl_s or None,
                "error_budget": args.error_budget,
                "slo_ttft_ms": args.slo_ttft_ms or None,
                "slo_tpot_ms": args.slo_tpot_ms or None,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": S,
                    # attention-variant provenance (docs/SERVING.md
                    # 'Attention variants'): a GQA or windowed curve has a
                    # different KV byte budget per slot than an MHA one
                    "n_kv_heads": cfg.kv_heads,
                    "kv_groups": cfg.kv_groups,
                    "sliding_window": cfg.sliding_window,
                    "attn_sinks": cfg.attn_sinks,
                },
                "max_slots": args.max_slots,
                "num_pages": args.num_pages,
                # sharding provenance: serve_slo lines from a tp-sharded
                # engine must not be comparable-by-accident with
                # single-chip curves (ServeEngine.stats() carries the same)
                "tp": args.tp or None,
                "mesh": warm.mesh_shape() if warm else None,
                "max_backlog_pages": args.max_backlog_pages or None,
                "points": points,
                # hottest-point headline numbers (driver contract fields)
                "ttft_p50_ms": worst["ttft_p50_ms"],
                "ttft_p95_ms": worst["ttft_p95_ms"],
                "tpot_p50_ms": worst["tpot_p50_ms"],
                "tpot_p95_ms": worst["tpot_p95_ms"],
                "shed_frac": worst["shed_frac"],
                "timeout_frac": worst["timeout_frac"],
                "round_host_ms": worst["round_host_ms"],
                "round_device_ms": worst["round_device_ms"],
                "overlap_mode": worst["overlap_mode"],
                "round_group": worst["round_group"],
                "overlap_hidden_ms": worst["overlap_hidden_ms"],
                "prefix_hit_rate": worst.get("prefix_hit_rate"),
                # --fleet: availability/affinity headline from the hottest
                # point (docs/ROBUSTNESS.md "Fleet serving & failover");
                # prefix_hit_rate above is then the FLEET-wide rate, the
                # number affinity routing exists to protect
                "fleet_size": args.fleet or None,
                "failovers": worst.get("failovers") if args.fleet else None,
                "spill_hits": worst.get("spill_hits") if args.fleet else None,
                # --procs: cross-process transport headline, hottest point
                # (docs/ROBUSTNESS.md "Cross-process fleet")
                "procs": bool(args.procs),
                "rpc_p50_ms": worst.get("rpc_p50_ms") if args.procs else None,
                "rpc_p95_ms": worst.get("rpc_p95_ms") if args.procs else None,
                "wire_bytes": worst.get("wire_bytes") if args.procs else None,
                # --hot-swap: the version transition every point rode
                # (docs/ROBUSTNESS.md 'Zero-downtime model ops'); slo_ok
                # below is then the "curve stays flat through the swap"
                # acceptance, with no special-casing.
                "weights_versions": (
                    ["inline", swap_payload[1]] if swap_payload else None
                ),
                "hot_swaps": (
                    sum(p.get("hot_swaps", 0) for p in points)
                    if swap_payload else None
                ),
                "slo_ok": bool(all(p["slo_ok"] for p in points)),
            }
        )
    )
    # --procs: explicit teardown of the worker fleet. Error paths need no
    # handling here — workers watch os.getppid() and self-exit when this
    # process dies (fleet_proc.run_worker's orphan check).
    if args.procs:
        import subprocess

        for rep in proc_replicas:
            rep.close()
        for proc, _port in worker_procs:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
