"""Continuous-batching serving bench: one JSON line (driver contract).

Runs a seeded synthetic mixed-length request trace twice through each mode —
the first pass warms every jit shape (compile time is not a serving-rate
claim), the second is timed:

  * continuous — sampling/serve.py ServeEngine: paged KV cache, chunked
    prefill interleaved with batched decode, admission the moment a slot
    frees.
  * sequential — the fixed-batch engine.generate, one request at a time
    (what the pre-serving repo could do for a stream of arriving requests).

Reported: aggregate tokens/sec for both modes (the ISSUE acceptance is
continuous > sequential), p50/p99 per-token latency and mean TTFT for the
continuous run (chunk-granular: a decode chunk's n tokens each count
gap/n), and the HBM high-water of each mode's cache (analytic bytes — the
paged pool vs the per-request contiguous cache — plus the device allocator
peak when the backend exposes one; a CPU-mesh run of this tool is
scheduling-structure signal — counts, parity, preemptions — never a device
time or rate). The
timed continuous run carries a flight recorder (midgpt_tpu/obs/): the
line reports `round_host_ms`/`round_device_ms` p50/p95 — the decode-round
host-vs-device split — plus `overlap_mode`/`round_group`/
`overlap_hidden_ms` (the round-overlap dispatch A/B identity, driven by
`--overlap {off,double,group:k}`), and `--trace-out DIR` dumps the
Chrome trace.

    python tools/bench_serve.py [--n-requests 12] [--max-slots 4] ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _quick_train(cfg, params, steps: int, seed: int):
    """Fit the synthetic model to a noisy Markov stream (x_{t+1} =
    perm[x_t] with prob 0.85, else uniform) for a handful of Adam steps.

    The spec bench needs a model whose early layers AGREE with its full
    stack — on random init the self-draft's greedy agreement is ~40%
    (measured on an earlier toolchain, not re-measured), an artifact of the
    init, not a property of
    speculation. A lightly-fitted model is the honest testbed: draft and
    target both approximate the data distribution, which is exactly the
    regime speculative decoding is built for."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from midgpt_tpu.models.gpt import GPT

    V = cfg.vocab_size
    rng = np.random.default_rng(seed + 1)
    perm = rng.permutation(V)

    def batch(n, T):
        x = np.zeros((n, T + 1), np.int64)
        x[:, 0] = rng.integers(0, V, n)
        for t in range(T):
            nxt = perm[x[:, t]]
            noise = rng.random(n) < 0.15
            x[:, t + 1] = np.where(noise, rng.integers(0, V, n), nxt)
        return jnp.asarray(x[:, :-1], jnp.int32), jnp.asarray(x[:, 1:], jnp.int32)

    opt = optax.adam(3e-3)
    ostate = opt.init(params)

    @jax.jit
    def step(params, ostate, x, y):
        def loss_fn(p):
            logits = GPT.apply(cfg, p, x, inference=True).astype(jnp.float32)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        up, ostate = opt.update(g, ostate)
        return optax.apply_updates(params, up), ostate, loss

    T = min(64, cfg.block_size)
    loss = None
    for _ in range(steps):
        x, y = batch(8, T)
        params, ostate, loss = step(params, ostate, x, y)
    return params, (0.0 if loss is None else float(loss))


def _latency_stats(done, t_start):
    """Per-token latency (chunk-granular), per-request TTFT and per-request
    tok/s from the finished map. A chunk of n tokens landing gap seconds
    after the previous event costs gap/n per token; a request's tok/s is
    its generated tokens over its total residency (queueing included — the
    user-visible rate)."""
    import numpy as np

    lat, ttft, req_rate = [], [], []
    for fr in done.values():
        ts = np.asarray(fr.token_times)
        if ts.size == 0:
            continue
        ttft.append(ts[0] - t_start)
        span = max(ts[-1] - t_start, 1e-9)
        req_rate.append(len(ts) / span)
        edges = np.flatnonzero(np.diff(ts) > 0) + 1
        groups = np.split(ts, edges)
        prev = ts[0]
        for g in groups[1:]:
            lat.extend([(g[0] - prev) / len(g)] * len(g))
            prev = g[0]
    lat = np.asarray(lat) if lat else np.zeros(1)
    ttft = np.asarray(ttft) if ttft else np.zeros(1)
    req_rate = np.asarray(req_rate) if req_rate else np.zeros(1)
    return lat, ttft, req_rate


def _greedy_match_frac(done_a, done_b, trace_uids) -> float:
    """Fraction of generated-token positions where two greedy runs of the
    same trace agree — the int8-vs-bf16 accuracy number (docs/SERVING.md
    'Quantized KV cache': on the quick-fitted bench model expect >= 0.99;
    on an UNTRAINED model near-uniform logits make argmax fragile under
    any perturbation, so a raw-init match fraction is meaningless)."""
    import numpy as np

    match = total = 0
    for uid, prompt_len in trace_uids:
        a = np.asarray(done_a[uid].tokens)[prompt_len:]
        b = np.asarray(done_b[uid].tokens)[prompt_len:]
        n = min(len(a), len(b))
        match += int(np.sum(a[:n] == b[:n]))
        total += max(len(a), len(b))
    return match / max(total, 1)


def _spec_bench(args, cfg, params, cache_dtype, trace, total_new) -> int:
    """--spec mode: speculative vs plain continuous engine, one JSON line
    ('serve_spec' profile, analysis/bench_contract.py)."""
    import jax

    from midgpt_tpu.sampling.serve import ServeEngine
    from midgpt_tpu.sampling.spec import self_draft

    draft_layers = args.spec_draft_layers or max(1, cfg.n_layer // 3)
    params, final_loss = _quick_train(cfg, params, args.train_steps, args.seed)
    draft_cfg, draft_params = self_draft(cfg, params, draft_layers)

    def run(draft):
        eng = ServeEngine(
            cfg,
            params,
            max_slots=args.max_slots,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype=cache_dtype,
            draft_params=draft_params if draft else None,
            draft_config=draft_cfg if draft else None,
            draft_shares_cache=draft,  # self-draft: prefix layers share the pool
            spec_k_max=args.spec_k,
        )
        for prompt, m in trace:
            eng.submit(prompt, m)
        t0 = time.perf_counter()
        eng.run()
        return eng, time.perf_counter() - t0

    run(draft=False)  # warm the plain prefill/decode shapes
    _, dt_base = run(draft=False)
    run(draft=True)  # warm draft prefill + each (k, page) bucket
    eng_spec, dt_spec = run(draft=True)
    stats = eng_spec.spec_stats()

    print(
        json.dumps(
            {
                "bench": "serve_spec",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "draft_layers": draft_layers,
                "spec_k_max": args.spec_k,
                "train_steps": args.train_steps,
                "train_loss": round(final_loss, 3),
                "baseline_tok_s": round(total_new / dt_base, 2),
                "spec_tok_s": round(total_new / dt_spec, 2),
                "speedup_spec": round(dt_base / dt_spec, 3),
                "accept_rate": round(stats["accept_rate"], 4),
                "tokens_per_verify": round(stats["tokens_per_verify"], 3),
                "kv_dtype": args.kv_dtype,
                "cache_hbm_bytes": int(eng_spec.cache_hbm_bytes()),
                "hbm_target_cache_bytes": int(eng_spec.cache_hbm_bytes()),
                # 0: the prefix self-draft rides the target pool's first
                # n_draft layers — speculation costs no extra cache HBM
                "hbm_draft_cache_bytes": 0
                if eng_spec.draft_cache is None
                else int(
                    eng_spec.draft_cache.k.nbytes + eng_spec.draft_cache.v.nbytes
                ),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _tp_bench(args, cfg, params, trace, total_new) -> int:
    """--tp mode: single-chip vs tensor-parallel mesh-sharded engine on the
    same greedy trace, one pass per cache mode — base dtype, int8, and
    self-draft speculation ('serve_tp' profile, analysis/bench_contract.py).

    The headline numbers are match_f32/match_int8/match_spec, each required
    EXACTLY 1.0: the tp engine shards head-aligned einsums whose megatron
    all-reduce restores the same f32 partials the single chip computes, so
    sharding must be bit-invisible to the token streams, the invariant
    tests/test_tp_serving.py pins per mode (the quick fit is belt-and-braces
    — parity holds on raw init too, but a fitted model makes the match
    robust to any future near-tie in the argmax). Per-shard HBM
    is reported because the pool is sharded on the head axis: each of the
    tp shards holds cache_hbm_bytes / tp, which is THE capacity lever tp
    serving buys (docs/SERVING.md 'Mesh-sharded serving')."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.parallel.serve_tp import make_serve_mesh
    from midgpt_tpu.sampling.serve import ServeEngine
    from midgpt_tpu.sampling.spec import self_draft

    n_dev = len(jax.devices())
    if args.tp < 2 or args.tp > n_dev:
        raise SystemExit(f"--tp {args.tp} needs 2 <= tp <= {n_dev} devices")
    if cfg.n_head % args.tp:
        raise SystemExit(f"--tp {args.tp} must divide n_head {cfg.n_head}")
    params, final_loss = _quick_train(cfg, params, args.train_steps, args.seed)
    mesh = make_serve_mesh(tp_size=args.tp)
    base_dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    draft_layers = args.spec_draft_layers or max(1, cfg.n_layer // 3)
    draft_cfg, draft_params = self_draft(cfg, params, draft_layers)

    def run(mesh_arg, mode):
        kw = {}
        if mode == "spec":
            kw = dict(
                draft_params=draft_params,
                draft_config=draft_cfg,
                draft_shares_cache=True,
                spec_k_max=args.spec_k,
            )
        eng = ServeEngine(
            cfg,
            params,
            max_slots=args.max_slots,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype="int8" if mode == "int8" else base_dtype,
            mesh=mesh_arg,
            **kw,
        )
        uids = [(eng.submit(p, m), len(p)) for p, m in trace]
        t0 = time.perf_counter()
        done = eng.run()
        return eng, done, time.perf_counter() - t0, uids

    fields = {}
    engines = {}
    for mode in ("f32", "int8", "spec"):
        run(None, mode)  # warm the single-chip shapes for this mode
        _, done_s, dt_s, uids = run(None, mode)
        run(mesh, mode)  # warm the tp-sharded shapes
        eng_tp, done_t, dt_t, _ = run(mesh, mode)
        engines[mode] = eng_tp
        fields[f"match_{mode}"] = round(
            _greedy_match_frac(done_s, done_t, uids), 4
        )
        fields[f"single_tok_s_{mode}"] = round(total_new / dt_s, 2)
        fields[f"tp_tok_s_{mode}"] = round(total_new / dt_t, 2)

    eng = engines["f32"]
    shard = int(eng.cache_hbm_bytes_per_shard())
    print(
        json.dumps(
            {
                "bench": "serve_tp",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "tp": args.tp,
                "n_devices": n_dev,
                "mesh": eng.mesh_shape(),
                "base_dtype": str(jnp.dtype(base_dtype)),
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "train_steps": args.train_steps,
                "train_loss": round(final_loss, 3),
                "draft_layers": draft_layers,
                "spec_k_max": args.spec_k,
                **fields,
                "num_pages": eng.allocator.num_pages,
                "int8_num_pages": engines["int8"].allocator.num_pages,
                # head-axis sharding: each shard holds exactly total/tp —
                # the contract checker re-derives both from the totals
                "cache_hbm_bytes": int(eng.cache_hbm_bytes()),
                "cache_hbm_bytes_per_shard": shard,
                "hbm_per_slot_per_shard_bytes": shard // args.max_slots,
                "int8_cache_hbm_bytes_per_shard": int(
                    engines["int8"].cache_hbm_bytes_per_shard()
                ),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _prefix_bench(args, cfg, params, cache_dtype) -> int:
    """--shared-prefix-frac mode: template-heavy workload (N shared system
    prompts x unique tails, plus exact-duplicate resubmissions that
    exercise the copy-on-write truncation path) through the SAME engine
    twice — prefix cache off, then on, at the same page budget. Emits the
    'serve_prefix' JSON profile (analysis/bench_contract.py): the headline
    numbers are prefix_hit_rate, the TTFT collapse (template prefill
    skipped), and greedy_match_frac, which must be EXACTLY 1.0 — shared
    pages hold bit-identical K/V to privately prefilled ones, so sharing
    is invisible to the streams (tests/test_prefix_cache.py pins this per
    cache mode)."""
    import jax
    import numpy as np

    from midgpt_tpu.sampling.serve import ServeEngine

    rng = np.random.default_rng(args.seed)
    V = cfg.vocab_size
    n_templates = args.prefix_templates
    t_len = args.template_tokens or 5 * args.page_size
    if t_len + 16 + 12 > cfg.block_size:
        raise SystemExit(
            f"--template-tokens {t_len} leaves no room for tails in "
            f"block_size {cfg.block_size}"
        )
    templates = [
        rng.integers(0, V, t_len, dtype=np.int64) for _ in range(n_templates)
    ]
    trace = []
    for i in range(args.n_requests):
        m = int(rng.integers(8, 13))
        if rng.random() < args.shared_prefix_frac:
            if trace and rng.random() < 0.25:
                # exact duplicate of an earlier templated prompt (a retried
                # query): its first post-template page prefix-matches a trie
                # page, so the capped match reports a COW truncation
                prompt = trace[rng.integers(0, len(trace))][0]
                while len(prompt) <= t_len:  # ensure it IS a templated one
                    prompt = trace[rng.integers(0, len(trace))][0]
            else:
                tail = rng.integers(
                    0, V, int(rng.integers(3, 9)), dtype=np.int64
                )
                prompt = np.concatenate([templates[i % n_templates], tail])
        else:
            prompt = rng.integers(
                0, V, int(rng.integers(4, 11)), dtype=np.int64
            )
        trace.append((prompt, m))
    total_new = sum(m for _, m in trace)
    pool_kw = (
        {"pool_hbm_bytes": args.pool_hbm_bytes} if args.pool_hbm_bytes else {}
    )

    def run(prefix_on):
        eng = ServeEngine(
            cfg,
            params,
            max_slots=args.max_slots,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype=cache_dtype,
            prefix_cache=prefix_on,
            **pool_kw,
        )
        uids = [(eng.submit(p, m), len(p)) for p, m in trace]
        t0 = time.perf_counter()
        done = eng.run()
        return eng, done, time.perf_counter() - t0, t0, uids

    run(False)  # warm every jit shape (a fresh engine per run: cold trie)
    eng_off, done_off, dt_off, t0_off, uids = run(False)
    eng_on, done_on, dt_on, t0_on, _ = run(True)
    _, ttft_off, _ = _latency_stats(done_off, t0_off)
    _, ttft_on, _ = _latency_stats(done_on, t0_on)
    st = eng_on.prefix_stats()

    print(
        json.dumps(
            {
                "bench": "serve_prefix",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "kv_dtype": args.kv_dtype,
                "num_pages": eng_on.allocator.num_pages,
                "pool_hbm_bytes": args.pool_hbm_bytes or None,
                "shared_prefix_frac": args.shared_prefix_frac,
                "n_templates": n_templates,
                "template_tokens": t_len,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "baseline_tok_s": round(total_new / dt_off, 2),
                "prefix_tok_s": round(total_new / dt_on, 2),
                "speedup_prefix": round(dt_off / dt_on, 3),
                "baseline_ttft_ms_p50": round(
                    float(np.percentile(ttft_off, 50)) * 1e3, 3
                ),
                "baseline_ttft_ms_p95": round(
                    float(np.percentile(ttft_off, 95)) * 1e3, 3
                ),
                "prefix_ttft_ms_p50": round(
                    float(np.percentile(ttft_on, 50)) * 1e3, 3
                ),
                "prefix_ttft_ms_p95": round(
                    float(np.percentile(ttft_on, 95)) * 1e3, 3
                ),
                "prefix_hit_rate": round(st["hit_rate"], 4),
                "cow_pages": st["cow_pages"],
                "baseline_prefill_tokens": eng_off.prefilled_tokens,
                "prefix_prefill_tokens": eng_on.prefilled_tokens,
                "baseline_preemptions": eng_off.preemptions,
                "prefix_preemptions": eng_on.preemptions,
                "trie_pages": st["trie_pages"],
                "reclaimed_pages": st["reclaimed_pages"],
                # exact by construction: shared pages ARE the pages a
                # private prefill of the same tokens would have written
                "greedy_match_frac": round(
                    _greedy_match_frac(done_off, done_on, uids), 4
                ),
                "cache_hbm_bytes": int(eng_on.cache_hbm_bytes()),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _gqa_bench(args, cfg, cache_dtype) -> int:
    """--gqa mode: KV-bytes capacity A/B ('serve_gqa' profile,
    analysis/bench_contract.py; docs/SERVING.md 'Attention variants').

    The same mixed-length greedy trace runs through an MHA engine and a
    GQA engine (n_kv_heads = n_head / G, optionally + sliding window) at
    the SAME fixed pool_hbm_bytes. A GQA page is G-fold smaller
    (PagedKVCache.page_bytes), so the byte budget admits G-fold more
    pages — which converts into admissible slots and strictly fewer
    recompute preemptions on an oversubscribed trace. Each variant's
    streams are compared against engine.generate on its OWN params
    (different projection layouts are different models — cross-variant
    token equality would be meaningless); both match fractions must be
    EXACTLY 1.0: paged reads are bit-identical to dense-cache reads per
    variant, so capacity is the only thing the A/B varies."""
    import collections
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
    from midgpt_tpu.sampling.engine import generate
    from midgpt_tpu.sampling.serve import ServeEngine

    G = args.gqa
    if cfg.n_head % G:
        raise SystemExit(f"--gqa {G} does not divide n_head={cfg.n_head}")
    gqa_cfg = _dc.replace(
        cfg,
        n_kv_heads=cfg.n_head // G,
        sliding_window=args.sliding_window,
        attn_sinks=args.attn_sinks,
    )

    rng = np.random.default_rng(args.seed)
    S = cfg.block_size
    trace = []
    for _ in range(args.n_requests):
        t0 = int(rng.integers(4, max(5, S // 2)))
        m = int(rng.integers(8, max(9, min(64, S - t0))))
        trace.append((rng.integers(0, cfg.vocab_size, t0, dtype=np.int64), m))
    total_new = sum(m for _, m in trace)
    ps = args.page_size
    req_pages = [-(-(len(p) + m) // ps) for p, m in trace]

    # Fixed byte budget, the independent variable: default sizes the MHA
    # pool to ~1/3 of the trace's worst-case page demand (but always at
    # least the largest single request), so the MHA side oversubscribes
    # and preempts while GQA's G-fold page count absorbs the same trace.
    mha_page_bytes = PagedKVCache.page_bytes(cfg, ps, cache_dtype)
    pool_hbm_bytes = args.pool_hbm_bytes or mha_page_bytes * (
        1 + max(max(req_pages), sum(req_pages) // 3)
    )

    Ref = collections.namedtuple("Ref", "tokens")

    def run(vcfg):
        params = GPT.init(vcfg, jax.random.PRNGKey(args.seed))
        if jax.default_backend() == "tpu":
            params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

        def once():
            eng = ServeEngine(
                vcfg,
                params,
                max_slots=args.max_slots,
                page_size=ps,
                prefill_chunk=args.prefill_chunk,
                decode_chunk=args.decode_chunk,
                temperature=0.0,
                cache_dtype=cache_dtype,
                pool_hbm_bytes=pool_hbm_bytes,
            )
            uids = [(eng.submit(p, m), len(p)) for p, m in trace]
            t0 = time.perf_counter()
            done = eng.run()
            return eng, done, time.perf_counter() - t0, uids

        once()  # warm the variant's jit shapes
        eng, done, dt, uids = once()
        refs = {
            uid: Ref(
                np.asarray(
                    generate(
                        vcfg, params, jnp.asarray(p, jnp.int32)[None], m,
                        temperature=0.0,
                    )[0]
                )
            )
            for (uid, _), (p, m) in zip(uids, trace)
        }
        return eng, done, refs, dt, uids

    eng_mha, done_mha, refs_mha, dt_mha, uids_mha = run(cfg)
    eng_gqa, done_gqa, refs_gqa, dt_gqa, uids_gqa = run(gqa_cfg)

    mean_req_pages = sum(req_pages) / len(req_pages)
    slots = lambda eng: int((eng.allocator.num_pages - 1) // mean_req_pages)
    print(
        json.dumps(
            {
                "bench": "serve_gqa",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "page_size": ps,
                "kv_dtype": args.kv_dtype,
                "pool_hbm_bytes": pool_hbm_bytes,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "kv_groups": G,
                "n_kv_heads": gqa_cfg.kv_heads,
                "sliding_window": args.sliding_window,
                "attn_sinks": args.attn_sinks,
                "mha_page_bytes": mha_page_bytes,
                "gqa_page_bytes": PagedKVCache.page_bytes(
                    gqa_cfg, ps, cache_dtype
                ),
                "mha_num_pages": eng_mha.allocator.num_pages,
                "gqa_num_pages": eng_gqa.allocator.num_pages,
                # the headline slots-per-HBM-byte win: pages (and mean-
                # request slots) admitted by the SAME byte budget
                "pages_ratio": round(
                    eng_gqa.allocator.num_pages / eng_mha.allocator.num_pages,
                    3,
                ),
                "mha_slots_capacity": slots(eng_mha),
                "gqa_slots_capacity": slots(eng_gqa),
                "mha_preemptions": eng_mha.preemptions,
                "gqa_preemptions": eng_gqa.preemptions,
                "mha_tok_s": round(total_new / dt_mha, 2),
                "gqa_tok_s": round(total_new / dt_gqa, 2),
                "window_reclaimed_pages": eng_gqa.window_reclaimed_pages,
                "greedy_match_frac_mha": round(
                    _greedy_match_frac(done_mha, refs_mha, uids_mha), 4
                ),
                "greedy_match_frac_gqa": round(
                    _greedy_match_frac(done_gqa, refs_gqa, uids_gqa), 4
                ),
                "mha_cache_hbm_bytes": int(eng_mha.cache_hbm_bytes()),
                "gqa_cache_hbm_bytes": int(eng_gqa.cache_hbm_bytes()),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _fleet_bench(args, cfg, params, cache_dtype) -> int:
    """--fleet mode: availability A/B ('serve_fleet' profile,
    analysis/bench_contract.py; docs/ROBUSTNESS.md 'Fleet serving &
    failover'). The same template-heavy trace runs through one
    prefix-cached engine, then through an N-replica FleetRouter with an
    engine_crash armed mid-trace. Both passes take an identical mid-trace
    trie flush (a pressure spike force-reclaiming unreferenced pages) at
    the half-way drain: the single engine loses that KV and re-prefills,
    while the fleet's replicas spill it to the shared host-RAM tier and
    the second half re-adopts — which is the tier's throughput story, and
    puts the checksum/adoption path inside the parity gate. Structural
    gates: the crash drops zero accepted streams, every fleet stream
    (survivors and failover replays) bit-matches the single-engine pass,
    and affinity routing keeps the fleet trie hit rate >= the single
    engine's instead of diluting toward 1/N (pinned:
    tests/test_bench_contract.py serve_fleet runner + checker-drift, and
    the fleet chaos gates in tests/test_chaos_serve.py)."""
    import jax
    import numpy as np

    from midgpt_tpu.robustness import faults
    from midgpt_tpu.sampling.fleet import FleetRouter, assert_fleet_conserved
    from midgpt_tpu.sampling.serve import ServeEngine

    if args.fleet < 2:
        raise SystemExit("--fleet needs >= 2 replicas (one cannot fail over)")
    if args.procs:
        return _proc_fleet_bench(args, cfg)

    rng = np.random.default_rng(args.seed)
    V = cfg.vocab_size
    n_templates = args.prefix_templates
    t_len = args.template_tokens or 5 * args.page_size
    templates = [
        rng.integers(0, V, t_len, dtype=np.int64) for _ in range(n_templates)
    ]
    trace = []
    for i in range(args.n_requests):
        tail = rng.integers(0, V, int(rng.integers(3, 9)), dtype=np.int64)
        prompt = np.concatenate([templates[i % n_templates], tail])
        trace.append((prompt, int(rng.integers(8, 13))))
    total_new = sum(m for _, m in trace)
    half = len(trace) // 2
    # 41: a fresh program-key pool geometry (see chaos_serve._engine's pin
    # note), roomy enough that max_slots full requests fit without
    # thrashing while the trie still feels pressure across the trace
    num_pages = 41

    def mk_engine(**kw):
        return ServeEngine(
            cfg,
            params,
            max_slots=args.max_slots,
            page_size=args.page_size,
            num_pages=num_pages,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype=cache_dtype,
            prefix_cache=True,
            **kw,
        )

    def run_single():
        faults.clear()
        eng = mk_engine()
        t0 = time.perf_counter()
        uids = [eng.submit(p, m) for p, m in trace[:half]]
        eng.run()
        eng._evict_shared_prefix_fault()  # the shared mid-trace flush
        uids += [eng.submit(p, m) for p, m in trace[half:]]
        eng.run()
        return eng, uids, time.perf_counter() - t0

    run_single()  # warm every jit shape at this geometry
    eng_single, single_uids, dt_single = run_single()
    single_tokens = {
        idx: np.asarray(eng_single.finished[uid].tokens)
        for idx, uid in enumerate(single_uids)
    }
    single_hit = eng_single.prefix_stats()["hit_rate"]

    faults.clear()
    faults.activate("engine_crash", step=args.fleet_crash_round)
    router = FleetRouter(
        [mk_engine(obs_tid=f"replica{i}") for i in range(args.fleet)]
    )

    def drive(pending, r):
        # trickled one per round so the crash finds streams in flight
        while pending or not router.idle:
            if pending:
                idx, (p, m) = pending.pop(0)
                uid_to_idx[router.submit_retry(p, m)] = idx
            router.step()
            r += 1
            if r >= 100_000:
                raise SystemExit("fleet drive did not converge")
        return r

    uid_to_idx: dict = {}
    t0 = time.perf_counter()
    r = drive(list(enumerate(trace[:half])), 0)
    for i, rep in enumerate(router.engines):
        if router.alive[i]:
            rep._evict_shared_prefix_fault()  # same flush — but spilled
    drive(list(enumerate(trace[half:], start=half)), r)
    dt_fleet = time.perf_counter() - t0
    faults.clear()
    assert_fleet_conserved(router, "fleet bench")

    match = total = dropped = parity_checked = 0
    for uid, idx in uid_to_idx.items():
        fr = router.finished.get(uid)
        if fr is None or fr.status != "ok":
            dropped += 1
            continue
        parity_checked += 1
        a = np.asarray(fr.tokens)
        b = single_tokens[idx]
        n = min(len(a), len(b))
        match += int(np.sum(a[:n] == b[:n]))
        total += max(len(a), len(b))

    print(
        json.dumps(
            {
                "bench": "serve_fleet",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "fleet_size": args.fleet,
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "kv_dtype": args.kv_dtype,
                "num_pages": num_pages,
                "n_templates": n_templates,
                "template_tokens": t_len,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "single_tok_s": round(total_new / dt_single, 2),
                "fleet_tok_s": round(total_new / dt_fleet, 2),
                "single_hit_rate": round(single_hit, 4),
                "fleet_hit_rate": round(router.prefix_hit_rate(), 4),
                "failovers": router.failovers,
                "failed_over_streams": router.failed_over_streams,
                "crash_round": args.fleet_crash_round,
                "alive": sum(router.alive),
                "dropped": dropped,
                "parity_checked": parity_checked,
                "greedy_match_frac": round(match / max(total, 1), 4),
                "spill_readopted_pages": sum(
                    e.spill_readopted_pages for e in router.engines
                ),
                "spill": router.spill.stats(),
                "pages_conserved": True,
                "procs": False,
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _proc_fleet_bench(args, cfg) -> int:
    """--fleet --procs: the fleet availability A/B with every replica a
    separate worker PROCESS (sampling/fleet_proc.py) behind the framed
    socket transport, and the mid-trace fault a real kill -9
    (docs/ROBUSTNESS.md 'Cross-process fleet'). Two differences from the
    in-process A/B, both forced by real process death:

      * the single-engine reference runs in its OWN worker process (same
        spec, same pinned CPU backend as the fleet workers) — an
        in-parent reference would compare across backends whenever the
        parent sits on the real TPU, and the parent must compile NOTHING
        (its jit census is snapshotted up front and pinned unchanged);
      * there is no fleet_hit_rate >= single_hit_rate gate: a SIGKILLed
        worker takes its per-process host-RAM tier with it, so the KV
        the in-process crash path spills and re-adopts is simply gone —
        the survivor re-prefills the failed-over streams (bit-exactly;
        the parity gate still covers every stream), which is honest
        misses. bench_contract.check_serve_fleet_bench branches on the
        `procs` field for exactly this reason.

    Both sides are timed with warm worker jit caches (one untimed pass
    each, like the in-process warm run) and hit rates are deltas over
    the timed window only. The line carries the transport A/B fields —
    rpc_p50_ms / rpc_p95_ms / wire_bytes / proc_failovers — pinned by
    tests/test_bench_contract.py."""
    import dataclasses as _dc
    import subprocess

    import numpy as np

    from midgpt_tpu.robustness import faults
    from midgpt_tpu.sampling.fleet import FleetRouter, assert_fleet_conserved
    from midgpt_tpu.sampling.fleet_proc import (
        connect_replica,
        parent_jax_config,
        spawn_workers,
    )
    from midgpt_tpu.sampling.serve import ServeEngine

    rng = np.random.default_rng(args.seed)
    V = cfg.vocab_size
    n_templates = args.prefix_templates
    t_len = args.template_tokens or 5 * args.page_size
    templates = [
        rng.integers(0, V, t_len, dtype=np.int64) for _ in range(n_templates)
    ]
    trace = []
    for i in range(args.n_requests):
        tail = rng.integers(0, V, int(rng.integers(3, 9)), dtype=np.int64)
        prompt = np.concatenate([templates[i % n_templates], tail])
        trace.append((prompt, int(rng.integers(8, 13))))
    total_new = sum(m for _, m in trace)
    half = len(trace) // 2
    num_pages = 41  # the in-process fleet-bench geometry; workers own
    # their jit caches, so the program-key ledger concern is per-process

    compiles_before = ServeEngine.compile_stats()
    spec = {
        "model": _dc.asdict(cfg),
        "seed": args.seed,
        "engine": {
            "max_slots": args.max_slots,
            "page_size": args.page_size,
            "num_pages": num_pages,
            "prefill_chunk": args.prefill_chunk,
            "decode_chunk": args.decode_chunk,
            "cache_dtype": "int8" if args.kv_dtype == "int8" else "bfloat16",
        },
        "cpu_devices": args.cpu_devices or 1,
        "jax_config": parent_jax_config(),
    }

    def prefix_counts(reps):
        return (
            sum(r._prefix_matched_tokens for r in reps),
            sum(r._prefix_matchable_tokens for r in reps),
        )

    def ref_pass(rep):
        # the run_single procedure over the wire: half, flush, half
        t0 = time.perf_counter()
        uids = [rep.submit(p, m) for p, m in trace[:half]]
        rep.run()
        rep._evict_shared_prefix_fault()
        uids += [rep.submit(p, m) for p, m in trace[half:]]
        rep.run()
        return uids, time.perf_counter() - t0

    procs = []
    try:
        # reference worker + N fleet workers, spawned concurrently
        procs = spawn_workers(spec, args.fleet + 1)
        ref = connect_replica(procs[0][1], retry_base_s=0.05)
        ref_pass(ref)  # warm the reference worker's jit cache
        m0, a0 = prefix_counts([ref])
        ref_uids, dt_single = ref_pass(ref)
        m1, a1 = prefix_counts([ref])
        single_hit = (m1 - m0) / max(a1 - a0, 1)
        single_tokens = {
            idx: np.asarray(ref.finished[uid].tokens)
            for idx, uid in enumerate(ref_uids)
        }
        ref.close()
        procs[0][0].kill()

        replicas = [
            connect_replica(port, retry_base_s=0.05) for _, port in procs[1:]
        ]
        for rep in replicas:
            ref_pass(rep)  # warm each fleet worker's jit cache
        wm, wa = prefix_counts(replicas)
        faults.clear()
        faults.activate("proc_kill9", step=args.fleet_crash_round)
        router = FleetRouter(replicas)

        uid_to_idx: dict = {}

        def drive(pending, r):
            # trickled one per round so the kill finds streams in flight
            while pending or not router.idle:
                if pending:
                    idx, (p, m) = pending.pop(0)
                    uid_to_idx[router.submit_retry(p, m)] = idx
                router.step()
                r += 1
                if r >= 100_000:
                    raise SystemExit("proc fleet drive did not converge")
            return r

        t0 = time.perf_counter()
        r = drive(list(enumerate(trace[:half])), 0)
        for i, rep in enumerate(router.engines):
            if router.alive[i]:
                rep._evict_shared_prefix_fault()  # same flush, over the wire
        drive(list(enumerate(trace[half:], start=half)), r)
        dt_fleet = time.perf_counter() - t0
        faults.clear()
        assert_fleet_conserved(router, "proc fleet bench")
        fm, fa = prefix_counts(replicas)
        fleet_hit = (fm - wm) / max(fa - wa, 1)

        match = total = dropped = parity_checked = 0
        for uid, idx in uid_to_idx.items():
            fr = router.finished.get(uid)
            if fr is None or fr.status != "ok":
                dropped += 1
                continue
            parity_checked += 1
            a = np.asarray(fr.tokens)
            b = single_tokens[idx]
            n = min(len(a), len(b))
            match += int(np.sum(a[:n] == b[:n]))
            total += max(len(a), len(b))

        transport = router.transport_stats()
        compiles_after = ServeEngine.compile_stats()
        assert compiles_after == compiles_before, (
            f"router process compiled programs for proc replicas: "
            f"{compiles_before} -> {compiles_after}"
        )

        print(
            json.dumps(
                {
                    "bench": "serve_fleet",
                    # the workers' backend — the parent dispatches nothing
                    "backend": "cpu",
                    "n_requests": args.n_requests,
                    "total_new_tokens": total_new,
                    "fleet_size": args.fleet,
                    "max_slots": args.max_slots,
                    "page_size": args.page_size,
                    "kv_dtype": args.kv_dtype,
                    "num_pages": num_pages,
                    "n_templates": n_templates,
                    "template_tokens": t_len,
                    "model": {
                        "n_layer": cfg.n_layer,
                        "n_head": cfg.n_head,
                        "n_embd": cfg.n_embd,
                        "block_size": cfg.block_size,
                    },
                    "single_tok_s": round(total_new / dt_single, 2),
                    "fleet_tok_s": round(total_new / dt_fleet, 2),
                    "single_hit_rate": round(single_hit, 4),
                    "fleet_hit_rate": round(fleet_hit, 4),
                    "failovers": router.failovers,
                    "failed_over_streams": router.failed_over_streams,
                    "crash_round": args.fleet_crash_round,
                    "alive": sum(router.alive),
                    "dropped": dropped,
                    "parity_checked": parity_checked,
                    "greedy_match_frac": round(match / max(total, 1), 4),
                    "spill_readopted_pages": sum(
                        e.spill_readopted_pages for e in router.engines
                    ),
                    "spill": router.spill.stats(),
                    "pages_conserved": True,
                    "procs": True,
                    "proc_failovers": router.proc_failovers,
                    "worker_pids": [rep.pid for rep in replicas],
                    "transport": transport,
                    "rpc_p50_ms": transport["rpc_p50_ms"],
                    "rpc_p95_ms": transport["rpc_p95_ms"],
                    "wire_bytes": transport["wire_bytes"],
                    "router_compiles_delta": 0,
                    "compile_counts": ServeEngine.compile_stats(),
                }
            )
        )
        return 0
    finally:
        faults.clear()
        for proc, _port in procs:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass


def _longctx_bench(args) -> int:
    """--long-ctx mode: the split-K decode A/B ('serve_longctx' profile,
    analysis/bench_contract.py).

    Three measurements, all through the real serve dispatch:

      * long point — decode-round latency of ONE active slot whose visible
        length ends at --t-long, unsplit vs the engine's auto split
        (docs/SERVING.md 'Split-K decode': the single-long-request regime
        is where an unsplit sweep serializes the whole key sequence);
      * short point — the same at --t-short. The no-regression guarantee
        at short T is STRUCTURAL: the auto bucket rule picks split 1 there
        (reported as split_k_short), so the engine runs the byte-identical
        pre-split-K program. The forced-split short latency is also
        reported as diagnostic context for the bucket threshold.
      * parity — the same greedy trace through two engines (forced split 4
        vs unsplit) on a quick-fitted model at a 1024-token block; the
        reported greedy_match_frac must be EXACTLY 1.0 (split-K reorders
        f32 reductions, so this pins that the margins survive — the same
        matrix tests/test_split_k.py locks per mode).

    Latency harness: raw `_serve_decode_chunk` calls (the engine's decode
    program), B=1, page table width rounded UP to a pow2 so the requested
    split divides it (a 513-page natural width would normalize every split
    back to 1 — the same rounding the engine's page buckets guarantee).
    Median of --rounds timed rounds after one warm round; each round ends
    in a host sync (float() of a result element)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache
    from midgpt_tpu.sampling.serve import ServeEngine, _serve_decode_chunk

    ps, chunk, rounds = args.page_size, args.decode_chunk, args.rounds
    on_tpu = jax.default_backend() == "tpu"
    baseline_dtype = jnp.bfloat16 if on_tpu else jnp.float32
    pool_dtype = jnp.int8 if args.kv_dtype == "int8" else baseline_dtype
    cache_dtype = "int8" if args.kv_dtype == "int8" else baseline_dtype
    if args.t_long < 2 * (rounds + 1) * chunk:
        raise SystemExit(f"--t-long {args.t_long} too short for "
                         f"{rounds} rounds of {chunk}-token chunks")

    cfg = GPTConfig(
        block_size=args.t_long,
        vocab_size=args.vocab_size,
        n_layer=args.n_layer,
        n_head=args.n_head,
        n_embd=args.n_embd,
    )
    params = GPT.init(cfg, jax.random.PRNGKey(args.seed))
    if on_tpu:
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)

    # The engine's own bucket rule decides the splits under test — the
    # bench measures what serving will actually dispatch, not a hand-picked
    # split (sampling/serve.py ServeEngine._split_bucket).
    eng = ServeEngine(cfg, params, max_slots=1, page_size=ps,
                      decode_chunk=chunk, temperature=0.0,
                      cache_dtype=cache_dtype)
    split_long = eng._split_bucket(args.t_long)
    split_short = eng._split_bucket(args.t_short)
    del eng

    def round_ms(t_total, split_k):
        pages = -(-t_total // ps)
        width = 1 << max(0, pages - 1).bit_length()  # pow2 ceil
        cache = PagedKVCache.init(cfg, 1 + width, ps, dtype=pool_dtype)
        table = jnp.asarray(1 + np.arange(width, dtype=np.int32))[None]
        active = jnp.ones((1,), bool)
        tok = jnp.zeros((1,), jnp.int32)
        lengths = t_total - (rounds + 1) * chunk
        times = []
        for r in range(rounds + 1):  # round 0 warms the compile
            t0 = time.perf_counter()
            cache, toks = _serve_decode_chunk(
                cfg, params, tok, cache, table,  # graftcheck: disable=GC011 — bench CLI: cfg is built once from argparse; one compile per A/B arm is the measured artifact
                jnp.full((1,), lengths, jnp.int32), active,
                chunk, 0.0, None, None, "auto", None, None, split_k,  # graftcheck: disable=GC011 — bench CLI: decode_chunk is a process-constant argparse knob
            )
            tok = toks[-1]
            float(tok.ravel()[0].astype(jnp.float32))  # force (CLAUDE.md)
            if r:
                times.append(time.perf_counter() - t0)
            lengths += chunk
        return 1000 * float(np.median(times))

    ms_long_1 = round_ms(args.t_long, 1)
    ms_long_s = round_ms(args.t_long, split_long)
    ms_short_1 = round_ms(args.t_short, 1)
    ms_short_4 = round_ms(args.t_short, 4)  # forced: auto stays unsplit

    # Exact greedy parity, split vs unsplit, on a model with real argmax
    # margins (the _quick_train rationale — raw-init near-ties make any
    # f32 reduction reorder look like corruption when it is not).
    match_bs = min(1024, args.t_long)
    mcfg = GPTConfig(
        block_size=match_bs,
        vocab_size=args.vocab_size,
        n_layer=args.n_layer,
        n_head=args.n_head,
        n_embd=args.n_embd,
    )
    mparams = GPT.init(mcfg, jax.random.PRNGKey(args.seed))
    if on_tpu:
        mparams = jax.tree.map(lambda p: p.astype(jnp.bfloat16), mparams)
    mparams, train_loss = _quick_train(mcfg, mparams, args.train_steps, args.seed)
    rng = np.random.default_rng(args.seed)
    mtrace = [
        (
            rng.integers(
                0, args.vocab_size,
                int(rng.integers(5 * match_bs // 8, 3 * match_bs // 4)),
                dtype=np.int64,
            ),
            24,
        )
        for _ in range(3)
    ]

    def run_match(split):
        m_eng = ServeEngine(mcfg, mparams, max_slots=2, page_size=ps,
                            prefill_chunk=args.prefill_chunk,
                            decode_chunk=chunk, temperature=0.0,
                            cache_dtype=cache_dtype, split_k=split)
        uids = [(m_eng.submit(p, m), len(p)) for p, m in mtrace]
        done = m_eng.run()
        return done, uids

    done_1, uids = run_match(1)
    done_s, _ = run_match(4)
    gmf = _greedy_match_frac(done_1, done_s, uids)

    print(
        json.dumps(
            {
                "bench": "serve_longctx",
                "backend": jax.default_backend(),
                "t_long": args.t_long,
                "t_short": args.t_short,
                "page_size": ps,
                "decode_chunk": chunk,
                "rounds": rounds,
                "kv_dtype": args.kv_dtype,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "split_k_long": split_long,
                "split_k_short": split_short,
                "ms_round_long_unsplit": round(ms_long_1, 3),
                "ms_round_long_split": round(ms_long_s, 3),
                "long_speedup": round(ms_long_1 / ms_long_s, 3),
                "ms_round_short_unsplit": round(ms_short_1, 3),
                "ms_round_short_forced_split": round(ms_short_4, 3),
                "short_ratio": round(ms_short_4 / ms_short_1, 3),
                "match_block_size": match_bs,
                "greedy_match_frac": round(gmf, 4),
                "train_steps": args.train_steps,
                "train_loss": round(train_loss, 3),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def _ops_bench(args, cfg, params, cache_dtype, trace, total_new) -> int:
    """--hot-swap mode: zero-downtime model ops ('serve_ops' profile,
    analysis/bench_contract.py; protocol: docs/ROBUSTNESS.md 'Zero-downtime
    model ops').

    One trickle-arrival pass through a live engine with two ops landing
    mid-trace: a blue/green weight swap from a sha256-verified checkpoint
    (staged at --swap-round, flipped at the drain boundary), then a live
    pool grow three rounds after the flip, while the new-weights side is
    still decoding. Two upfront reference passes (old weights / new
    weights, same trace, same geometry) provide the bit-exact parity
    oracles — greedy streams are batch-composition independent, the same
    property the preemption and disagg gates lean on (schema + parity
    split enforced in tests/test_bench_contract.py::
    test_bench_serve_ops_emits_conformant_json_line) — and double as the
    compile warm-up, so the swap window's jit-cache delta is the headline
    swap_recompiles == 0 claim: a same-shape swap device_puts onto the
    live shardings and must reuse every compiled program. The resize leg
    compiles its gather/adopt programs AFTER the window closes, which is
    why it runs second."""
    import tempfile
    import types

    import jax
    import numpy as np

    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.sampling import ops as mops
    from midgpt_tpu.sampling.engine import restore_for_sampling
    from midgpt_tpu.sampling.serve import ServeEngine
    from midgpt_tpu.training.checkpoint import CheckpointManager

    num_pages, grow_pages = 21, 23  # fresh geometries (program-key dims)

    ckpt_dir = os.path.join(
        tempfile.mkdtemp(prefix="midgpt_ops_bench_"), "ckpt"
    )
    mgr = CheckpointManager(ckpt_dir, save_interval_steps=1)
    mgr.save(
        7, {"params": GPT.init(cfg, jax.random.PRNGKey(args.seed + 101))},
        force=True,
    )
    mgr.wait()
    version = mgr.weights_version(7)
    mgr.close()
    shim = types.SimpleNamespace(
        model_config=cfg, fsdp_min_size=1 << 60, param_dtype="float32"
    )
    params_new, ckpt_step = restore_for_sampling(ckpt_dir, shim)

    def engine(p):
        return ServeEngine(
            cfg, p, max_slots=args.max_slots, page_size=args.page_size,
            prefill_chunk=args.prefill_chunk, decode_chunk=args.decode_chunk,
            temperature=0.0, cache_dtype=cache_dtype, num_pages=num_pages,
        )

    def reference(p):
        eng = engine(p)
        uids = [eng.submit(pr, m) for pr, m in trace]
        done = eng.run()
        return {u: np.asarray(done[u].tokens) for u in uids}

    def jit_total():
        return sum(v or 0 for v in ServeEngine.compile_stats().values())

    ref_old = reference(params)  # warms every shape at this geometry
    ref_new = reference(params_new)

    def drive():
        """One trickle pass with the swap staged at --swap-round and the
        pool grow landing three rounds after the flip. Run twice: the
        first pass warms every shape the ops schedule touches (incl. the
        resize's gather/adopt programs), so the second pass's jit-cache
        delta over [stage .. 3 post-flip rounds] isolates what the SWAP
        ITSELF compiles — the warm-then-count discipline the recompile
        pins use (tests/test_recompile_pins.py)."""
        eng = engine(params)
        pending = list(trace)
        jit0 = swap_recompiles = None
        post_flip = r = 0
        t0 = time.perf_counter()
        while pending or not eng.idle:
            if pending and r % 2 == 0:
                p, m = pending.pop(0)
                eng.submit(p, m)
            if r == args.swap_round:
                jit0 = jit_total()
                eng.hot_swap(params_new, version=version, config=cfg)
            eng.step()
            if eng.hot_swaps and swap_recompiles is None:
                post_flip += 1
                if post_flip == 3:  # 3 new-weights decode rounds in window
                    swap_recompiles = jit_total() - jit0
                    eng.resize(grow_pages)
            r += 1
            assert r < 10_000, "ops bench failed to drain"
        return eng, swap_recompiles, time.perf_counter() - t0

    drive()  # warm the trickle schedule's shapes end to end
    eng, swap_recompiles, dt = drive()
    done = eng.finished

    swap = eng.swap_history[0]
    rz = eng.resize_history[-1]
    old_uids = set(swap["served_uids_at_flip"])
    po = sum(
        1 for u in old_uids
        if np.array_equal(np.asarray(done[u].tokens), ref_old[u])
    )
    pn = sum(
        1 for u in done if u not in old_uids
        and np.array_equal(np.asarray(done[u].tokens), ref_new[u])
    )
    try:
        mops.assert_conserved(eng, "ops bench drain")
        conserved = True
    except AssertionError:
        conserved = False

    print(
        json.dumps(
            {
                "bench": "serve_ops",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "kv_dtype": args.kv_dtype,
                "num_pages": num_pages,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": cfg.block_size,
                },
                "checkpoint_step": ckpt_step,
                "weights_version_before": "inline",
                "weights_version_after": eng.weights_version,
                "swap_latency_ms": round(swap["swap_latency_s"] * 1e3, 3),
                "streams_in_flight_at_flip": len(swap["in_flight_at_stage"]),
                "staged_round": swap["staged_round"],
                "flip_round": swap["flip_round"],
                "dropped": sum(
                    1 for fr in done.values() if fr.status != "ok"
                ),
                "parity_old_side": po,
                "parity_new_side": pn,
                "swap_recompiles": swap_recompiles,
                "resize_from_pages": rz["from_pages"],
                "resize_to_pages": rz["to_pages"],
                "pages_migrated": rz["pages_migrated"],
                "pages_conserved": conserved,
                "fault_pass_tok_s": round(total_new / dt, 2),
                "compile_counts": ServeEngine.compile_stats(),
            }
        )
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=256)
    ap.add_argument("--vocab-size", type=int, default=512)
    # model shape: None resolves per mode below — the plain serve bench
    # keeps its r6 4L/128D shape; --spec defaults to 6L/384D, a shape where
    # the batched verify's GEMM efficiency (vs per-token GEMV decode) is
    # measurable even on the CPU mesh
    ap.add_argument("--n-layer", type=int, default=None)
    ap.add_argument("--n-head", type=int, default=None)
    ap.add_argument("--n-embd", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv_dtype", choices=("bf16", "int8"), default="bf16",
                    help="paged KV cache storage dtype. int8 stores pages "
                    "quantized (f32 absmax scales in a side buffer, "
                    "docs/SERVING.md 'Quantized KV cache'): the model is "
                    "quick-fitted first (--train-steps) so the reported "
                    "greedy_match_frac vs a bf16-cache run is meaningful, "
                    "and a bf16 engine at the SAME pool budget runs for "
                    "comparison (bf16_* fields)")
    ap.add_argument("--pool_hbm_bytes", type=int, default=0,
                    help="byte budget for the paged pool (0 = the default "
                    "half-of-dedicated sizing): num_pages is derived from "
                    "the cache dtype, so int8 admits 2x the pages of bf16 "
                    "at the same spend — THE lever the oversubscription "
                    "comparison measures")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force CPU with this many virtual devices (0 = native backend)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative-decoding bench: quick-train the model "
                    "on a synthetic Markov stream (an UNTRAINED model has "
                    "arbitrary draft agreement — speculation claims on it "
                    "are meaningless), then compare the continuous engine "
                    "with and without a self-draft on the same trace. Emits "
                    "the 'serve_spec' JSON profile instead of 'serve'")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="self-draft depth (0 = max(1, n_layer // 3))")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="spec_k_max for the speculative engine (pow2)")
    ap.add_argument("--train-steps", type=int, default=60,
                    help="--spec: quick-train steps before benchmarking")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="> 0 selects the prefix-cache bench: this fraction "
                    "of requests share one of --prefix-templates system "
                    "prompts (the rest are unique short prompts), and the "
                    "trace runs cache-off then cache-on at the same page "
                    "budget ('serve_prefix' JSON profile). 0.8 with "
                    "--n-requests 24 is the acceptance workload "
                    "(docs/SERVING.md 'Prefix cache')")
    ap.add_argument("--tp", type=int, default=0,
                    help="> 0 selects the tensor-parallel A/B bench: the "
                    "same trace through a single-chip engine and a mesh-"
                    "sharded engine (params via the megatron tp rules, KV "
                    "pool on the head axis) per cache mode — base dtype, "
                    "int8, self-draft spec — with every match_* required "
                    "exactly 1.0 ('serve_tp' JSON profile). Pair with "
                    "--cpu-devices 8 on this host (docs/SERVING.md "
                    "'Mesh-sharded serving')")
    ap.add_argument("--fleet", type=int, default=0,
                    help=">= 2 selects the fleet availability A/B: the same "
                    "template trace through one prefix-cached engine and "
                    "through N replicas behind the prefix-affinity "
                    "FleetRouter with an engine_crash armed mid-trace — "
                    "zero dropped streams, bit-exact parity (failover "
                    "replays and host-RAM spill re-adoption included), and "
                    "fleet trie hit rate >= the single engine's. Emits the "
                    "'serve_fleet' JSON profile (docs/ROBUSTNESS.md 'Fleet "
                    "serving & failover')")
    ap.add_argument("--fleet-crash-round", type=int, default=6,
                    help="--fleet: router round at which the armed "
                    "engine_crash kills the busiest replica")
    ap.add_argument("--procs", action="store_true",
                    help="--fleet: replicas are separate worker PROCESSES "
                    "(sampling/fleet_proc.py) behind the framed socket "
                    "transport, the single-engine reference runs in its "
                    "own worker, and the mid-trace fault is a real kill "
                    "-9 of the busiest worker. The serve_fleet line adds "
                    "procs/proc_failovers/rpc_p50_ms/rpc_p95_ms/"
                    "wire_bytes (docs/ROBUSTNESS.md 'Cross-process "
                    "fleet')")
    ap.add_argument("--prefix-templates", type=int, default=2,
                    help="distinct shared system prompts in the workload")
    ap.add_argument("--template-tokens", type=int, default=0,
                    help="template length (0 = 5 * page_size)")
    ap.add_argument("--gqa", type=int, default=0,
                    help="> 0 selects the GQA capacity A/B: the same greedy "
                    "trace through an MHA engine and a GQA engine with "
                    "n_kv_heads = n_head / THIS group factor, at the same "
                    "fixed --pool_hbm_bytes (default: ~1/3 of the trace's "
                    "MHA page demand, so the MHA side preempts). Emits the "
                    "'serve_gqa' JSON profile: pages/slots admitted per "
                    "byte, preemptions, and per-variant greedy parity vs "
                    "engine.generate, required exactly 1.0 (docs/SERVING.md "
                    "'Attention variants')")
    ap.add_argument("--sliding-window", type=int, default=0,
                    help="--gqa: the GQA variant also decodes with this "
                    "sliding window (0 = full causal); reclaimed "
                    "behind-window pages ride the line as "
                    "window_reclaimed_pages")
    ap.add_argument("--attn-sinks", type=int, default=0,
                    help="--gqa: always-visible sink prefix tokens for the "
                    "windowed variant (StreamingLLM-style)")
    ap.add_argument("--long-ctx", action="store_true",
                    help="long-context split-K A/B: decode-round latency of "
                    "ONE active slot at --t-long with the engine's auto "
                    "split vs unsplit, the same at --t-short (where auto "
                    "stays unsplit), plus an exact greedy-parity run split "
                    "vs unsplit on a quick-fitted model. Emits the "
                    "'serve_longctx' JSON profile (docs/SERVING.md "
                    "'Split-K decode')")
    ap.add_argument("--t-long", type=int, default=4096,
                    help="--long-ctx: long visible length (>= 1024 so the "
                    "auto bucket rule engages a split)")
    ap.add_argument("--t-short", type=int, default=256,
                    help="--long-ctx: short visible length (expected to "
                    "stay unsplit under the auto rule)")
    ap.add_argument("--rounds", type=int, default=6,
                    help="--long-ctx: timed decode rounds per variant "
                    "(median reported; one extra warm round rides first)")
    ap.add_argument("--hot-swap", action="store_true",
                    help="zero-downtime model-ops bench: a verified-"
                    "checkpoint blue/green weight swap lands mid-trace "
                    "(staged at --swap-round, flipped at the drain "
                    "boundary) followed by a live pool grow, with bit-"
                    "exact parity vs old-/new-weights references, zero "
                    "dropped streams, and a swap-window jit-cache delta "
                    "required to be 0. Emits the 'serve_ops' JSON profile "
                    "(docs/ROBUSTNESS.md 'Zero-downtime model ops')")
    ap.add_argument("--swap-round", type=int, default=5,
                    help="--hot-swap: engine round at which the candidate "
                    "weights are staged")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="plain serve profile: directory to dump the timed "
                    "continuous run's flight recorder as a Chrome-trace "
                    "JSON (+ .prom metrics) — open in Perfetto or roll up "
                    "with tools/trace_view.py (docs/OBSERVABILITY.md)")
    ap.add_argument("--overlap", type=str, default="off",
                    help="round-overlap dispatch A/B for the plain serve "
                    "profile (docs/SERVING.md 'Round-overlap dispatch'): "
                    "'off' (classic rounds), 'double' (dispatch round N+1 "
                    "before round N's host post-processing), or 'group:k' "
                    "(fuse k decode rounds into one on-device scan). The "
                    "line reports overlap_mode/round_group/"
                    "overlap_hidden_ms either way — an honest zero when "
                    "off — so A/B records are self-describing")
    args = ap.parse_args()
    if args.n_layer is None:
        args.n_layer = 6 if args.spec else 4
    if args.n_head is None:
        args.n_head = 6 if args.spec else 4
    if args.n_embd is None:
        args.n_embd = 384 if args.spec else 128

    import jax

    if args.cpu_devices:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_devices)

    import jax.numpy as jnp
    import numpy as np

    from midgpt_tpu.models.gpt import GPT, GPTConfig, KVCache
    from midgpt_tpu.sampling.engine import generate
    from midgpt_tpu.sampling.serve import ServeEngine, parse_overlap

    overlap_mode, round_group = parse_overlap(args.overlap)

    on_tpu = jax.default_backend() == "tpu"
    cfg = GPTConfig(
        block_size=args.block_size,
        vocab_size=args.vocab_size,
        n_layer=args.n_layer,
        n_head=args.n_head,
        n_embd=args.n_embd,
    )
    params = GPT.init(cfg, jax.random.PRNGKey(args.seed))
    if on_tpu:
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    baseline_dtype = jnp.bfloat16 if on_tpu else jnp.float32
    quantized = args.kv_dtype == "int8"
    if args.long_ctx:
        return _longctx_bench(args)

    if args.gqa:
        if quantized:
            raise SystemExit(
                "--gqa compares paged streams against dense-cache "
                "engine.generate, which is only bit-exact at the baseline "
                "cache dtype — int8 stacking is the existing quant bench's "
                "claim; run --gqa without --kv_dtype int8"
            )
        return _gqa_bench(args, cfg, baseline_dtype)

    train_loss = None
    if (
        quantized and not args.spec and not args.shared_prefix_frac
        and not args.tp and not args.fleet
        # (fleet parity, like prefix parity, compares same-dtype runs —
        # exact bitwise, nothing for a quick fit to make meaningful)
    ):
        # (the prefix bench skips the fit: its greedy_match_frac compares
        # cache-on vs cache-off at the SAME dtype, which is exact bitwise
        # — no numeric perturbation for training to make meaningful)
        # An untrained model's greedy argmax is fragile under ANY cache
        # perturbation (near-uniform logits), so the int8-vs-bf16 accuracy
        # number is only meaningful on a model that has learned something
        # — same reasoning as the --spec bench's quick fit.
        params, train_loss = _quick_train(cfg, params, args.train_steps, args.seed)
    cache_dtype = "int8" if quantized else baseline_dtype

    if args.shared_prefix_frac:
        return _prefix_bench(args, cfg, params, cache_dtype)

    if args.fleet:
        return _fleet_bench(args, cfg, params, cache_dtype)

    # Mixed-length trace: short chat-y prompts to near-context documents.
    rng = np.random.default_rng(args.seed)
    S = cfg.block_size
    trace = []
    for _ in range(args.n_requests):
        t0 = int(rng.integers(4, max(5, S // 2)))
        m = int(rng.integers(8, max(9, min(64, S - t0))))
        trace.append((rng.integers(0, cfg.vocab_size, t0, dtype=np.int64), m))
    total_new = sum(m for _, m in trace)

    if args.hot_swap:
        return _ops_bench(args, cfg, params, cache_dtype, trace, total_new)

    if args.tp:
        return _tp_bench(args, cfg, params, trace, total_new)

    if args.spec:
        return _spec_bench(args, cfg, params, cache_dtype, trace, total_new)

    pool_kw = (
        {"pool_hbm_bytes": args.pool_hbm_bytes} if args.pool_hbm_bytes else {}
    )

    def run_continuous(dtype, obs=None):
        eng = ServeEngine(
            cfg,
            params,
            max_slots=args.max_slots,
            page_size=args.page_size,
            prefill_chunk=args.prefill_chunk,
            decode_chunk=args.decode_chunk,
            temperature=0.0,
            cache_dtype=dtype,
            obs=obs,
            overlap=overlap_mode,
            round_group=round_group,
            **pool_kw,
        )
        uids = [(eng.submit(p, m), len(p)) for p, m in trace]
        t0 = time.perf_counter()
        done = eng.run()
        # Force everything to host (np conversion happened per chunk already).
        dt = time.perf_counter() - t0
        return eng, done, dt, t0, uids

    def run_sequential():
        t0 = time.perf_counter()
        outs = [
            generate(cfg, params, jnp.asarray(p, jnp.int32)[None], m, temperature=0.0)
            for p, m in trace
        ]
        outs = [np.asarray(o) for o in outs]  # force
        return time.perf_counter() - t0

    from midgpt_tpu.obs import Observability

    run_continuous(cache_dtype)  # warm every prefill/decode-chunk shape
    # Flight recorder on the TIMED run only: the serve profile reports the
    # decode-round host/device decomposition (docs/OBSERVABILITY.md) next
    # to its throughput, from the same pass.
    obs = Observability()
    eng, done, dt_cont, t_start, uids = run_continuous(cache_dtype, obs=obs)
    run_sequential()  # warm per-prompt-length prefills + decode chunks
    dt_seq = run_sequential()

    # int8 mode: a bf16-cache engine on the SAME trace and pool budget —
    # the capacity/throughput/accuracy comparison the quantized cache
    # exists for (at a fixed byte budget it gets HALF the pages, so on an
    # oversubscribed trace it preempts more and serves slower).
    bf16_fields = {}
    if quantized:
        # genuine bf16 even on the CPU mesh: the capacity claim (2x pages
        # at the same byte budget) and the accuracy claim (greedy match)
        # are both vs the bf16 production baseline, not vs the CPU test
        # mesh's f32 parity dtype
        run_continuous(jnp.bfloat16)  # warm the bf16-cache shapes
        eng_bf, done_bf, dt_bf, _, _ = run_continuous(jnp.bfloat16)
        bf16_fields = {
            "bf16_continuous_tok_s": round(total_new / dt_bf, 2),
            "bf16_num_pages": eng_bf.allocator.num_pages,
            "bf16_preemptions": eng_bf.preemptions,
            "greedy_match_frac": round(_greedy_match_frac(done, done_bf, uids), 4),
            "train_steps": args.train_steps,
            "train_loss": round(train_loss, 3),
        }

    lat, ttft, req_rate = _latency_stats(done, t_start)

    # Round split: host = dispatch (assembly + jit enqueue) + host_post
    # (token commit); device = device_wait (enqueue -> array landed).
    # Percentile sums are a summary convenience, not a joint distribution.
    decomp = obs.round_decomp()
    round_host_ms = {
        "p50": round(
            decomp["dispatch"]["p50_ms"] + decomp["host_post"]["p50_ms"], 3
        ),
        "p95": round(
            decomp["dispatch"]["p95_ms"] + decomp["host_post"]["p95_ms"], 3
        ),
    }
    round_device_ms = {
        "p50": decomp["device_wait"]["p50_ms"],
        "p95": decomp["device_wait"]["p95_ms"],
    }
    if args.trace_out:
        obs.dump(args.trace_out, filename="bench_serve.json")

    # HBM high-water of the caches (analytic; allocator peak if exposed).
    paged_bytes = eng.cache_hbm_bytes()
    itemsize = jnp.dtype(baseline_dtype).itemsize
    contiguous_bytes = (
        2 * cfg.n_layer * cfg.n_head * S * cfg.head_dim * itemsize
    )  # per-request KVCache the sequential engine allocates
    try:
        peak = jax.local_devices()[0].memory_stats().get("peak_bytes_in_use")
    except Exception:
        peak = None

    print(
        json.dumps(
            {
                "bench": "serve",
                "backend": jax.default_backend(),
                "n_requests": args.n_requests,
                "total_new_tokens": total_new,
                "max_slots": args.max_slots,
                "page_size": args.page_size,
                "kv_dtype": args.kv_dtype,
                "num_pages": eng.allocator.num_pages,
                "pool_hbm_bytes": args.pool_hbm_bytes or None,
                "preemptions": eng.preemptions,
                "prefill_chunk": args.prefill_chunk,
                "decode_chunk": args.decode_chunk,
                "model": {
                    "n_layer": cfg.n_layer,
                    "n_head": cfg.n_head,
                    "n_embd": cfg.n_embd,
                    "block_size": S,
                },
                "continuous_tok_s": round(total_new / dt_cont, 2),
                "sequential_tok_s": round(total_new / dt_seq, 2),
                "speedup": round(dt_seq / dt_cont, 3),
                "p50_token_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
                "p99_token_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
                "ttft_ms_mean": round(float(np.mean(ttft)) * 1e3, 3),
                "ttft_ms_p50": round(float(np.percentile(ttft, 50)) * 1e3, 3),
                "ttft_ms_p95": round(float(np.percentile(ttft, 95)) * 1e3, 3),
                "req_tok_s_p50": round(float(np.percentile(req_rate, 50)), 2),
                "req_tok_s_p95": round(float(np.percentile(req_rate, 95)), 2),
                "decode_rounds": decomp["rounds"],
                "round_host_ms": round_host_ms,
                "round_device_ms": round_device_ms,
                # round-overlap dispatch A/B identity + the host time the
                # overlap hid (docs/SERVING.md; eng.round_group is the
                # pow2-bucketed value that actually ran, not the CLI ask)
                "overlap_mode": eng.overlap,
                "round_group": eng.round_group,
                "overlap_hidden_ms": {
                    "p50": decomp["overlap_hidden"]["p50_ms"],
                    "p95": decomp["overlap_hidden"]["p95_ms"],
                },
                # pools + (int8) scale side buffers — the true cache spend
                "cache_hbm_bytes": int(paged_bytes),
                "hbm_paged_cache_bytes": int(paged_bytes),
                "hbm_sequential_cache_bytes": int(contiguous_bytes),
                "device_peak_bytes_in_use": peak,
                # Compiled-program census (ServeEngine.compile_stats): the
                # "request churn never recompiles" claim as a number drivers
                # can watch for drift (schema: analysis/bench_contract.py).
                "compile_counts": ServeEngine.compile_stats(),
                **bf16_fields,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
