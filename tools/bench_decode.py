"""Decode throughput bench: KV-cached generation on the real chip.

Measures ms/token of the sampling engine's chunked decode
(sampling/engine.py) on the 124M shape with random bf16 weights —
the methodology of the earlier rounds' inference table — plus an estimated
KV-cache HBM bytes/token column so cache-dtype wins are attributable:
decode is HBM-bandwidth-bound, and the cache read is the dominant stream,
so ms/token should track this column across dtypes far more closely than
it tracks FLOPs.

Two cache paths:

  * contiguous (default, `--kv_dtype bf16`): the fixed-batch engine's
    (L, B, H, S, C) cache — its attention reads the FULL block_size of
    keys per token (masked), so the traffic estimate uses S, not the
    used length.
  * paged (`--paged`, implied by `--kv_dtype int8` — the quantized mode
    exists only in the paged pool): B slots decoding through
    `sampling/serve._serve_decode_chunk` against a dedicated page table,
    bf16 or int8 pages. Reads are O(used length) through the page table.

Usage: python tools/bench_decode.py [--batch 8] [--tokens 512]
           [--prompt 128] [--kv_dtype bf16|int8] [--paged]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

PAGE_SIZE = 8


def est_kv_bytes_per_token(cfg, kv_dtype: str, read_len: int) -> int:
    """Estimated KV-cache HBM traffic per generated token: read `read_len`
    cached K+V positions + write one, all layers/heads; int8 adds the f32
    scale side-buffer stream (4 bytes per position per head per K/V —
    4/head_dim of the int8 page bytes, ops/quant.py)."""
    per_pos = 2 * cfg.n_layer * cfg.n_head * cfg.head_dim  # K+V elements
    item = 1 if kv_dtype == "int8" else 2
    traffic = per_pos * (read_len + 1) * item
    if kv_dtype == "int8":
        traffic += 2 * cfg.n_layer * cfg.n_head * 4 * (read_len + 1)
    return traffic


def _paged_bench(args, cfg, params, kv_dtype: str) -> float:
    """ms/token of the serve engine's batched paged decode chunk with every
    slot active — decode-loop cost only (no prefill: the pages hold zeros,
    which is fine for a throughput bench; values don't change the math's
    cost, and sampling is greedy so the token stream is just replayed
    through the embedding)."""
    from midgpt_tpu.models.gpt import PagedKVCache
    from midgpt_tpu.sampling.serve import _serve_decode_chunk

    B, ps = args.batch, PAGE_SIZE
    total = args.prompt + args.tokens
    pages_per_slot = -(-total // ps)
    num_pages = 1 + B * pages_per_slot
    dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    cache = PagedKVCache.init(cfg, num_pages, ps, dtype=dtype)
    table = np.zeros((B, pages_per_slot), np.int32)
    for b in range(B):
        table[b] = 1 + b * pages_per_slot + np.arange(pages_per_slot)
    table = jnp.asarray(table)
    active = jnp.ones((B,), bool)
    chunk = 8

    def run(n_tokens, cache, start_len):
        tok = jnp.zeros((B,), jnp.int32)
        lengths = start_len
        for _ in range(n_tokens // chunk):
            cache, toks = _serve_decode_chunk(
                cfg, params, tok, cache, table,  # graftcheck: disable=GC011 — bench CLI: geometry is fixed per process by argparse; one compile per run is the measured artifact
                jnp.full((B,), lengths, jnp.int32), active,
                chunk, 0.0, None, None, "auto", None, None, args.split_k,  # graftcheck: disable=GC011 — bench CLI: split_k is the swept argparse knob; each value compiles once by design
            )
            tok = toks[-1]
            lengths += chunk
        float(tok.ravel()[0].astype(jnp.float32))  # force (CLAUDE.md sync)
        return cache

    cache = run(min(64, args.tokens), cache, args.prompt)  # warm compile
    t0 = time.perf_counter()
    run(args.tokens, cache, args.prompt)
    dt = time.perf_counter() - t0
    return 1000 * dt / args.tokens


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--tokens", type=int, default=512)
    p.add_argument("--prompt", type=int, default=128)
    p.add_argument("--top-k", type=int, default=50)
    p.add_argument("--kv_dtype", choices=("bf16", "int8"), default="bf16",
                   help="KV cache storage dtype; int8 implies --paged "
                   "(the contiguous cache has no quantized mode)")
    p.add_argument("--paged", action="store_true",
                   help="bench the paged serve decode chunk instead of the "
                   "contiguous engine (required to compare dtypes on the "
                   "same code path)")
    p.add_argument("--split-k", type=int, default=1,
                   help="key-sequence partitions per attention call (paged "
                   "path only; normalized to a pow2 divisor of the table "
                   "width — docs/SERVING.md 'Split-K decode')")
    args = p.parse_args()
    if args.split_k != 1:
        args.paged = True
    if args.kv_dtype == "int8":
        args.paged = True

    from midgpt_tpu.configs.openwebtext import config as base
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.sampling.engine import generate

    cfg = base.model_config
    params = GPT.init(cfg, jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        params,
    )

    if args.paged:
        ms_tok = _paged_bench(args, cfg, params, args.kv_dtype)
        # paged attention reads O(used length): mean over the run
        read_len = args.prompt + args.tokens // 2
        est = est_kv_bytes_per_token(cfg, args.kv_dtype, read_len)
        tag = f",split{args.split_k}" if args.split_k != 1 else ""
        print(
            f"decode[paged,{args.kv_dtype}{tag}]: {ms_tok:.2f} ms/token  "
            f"({1000 * args.batch / ms_tok:,.0f} tok/s total, batch "
            f"{args.batch}, prompt {args.prompt}, {args.tokens} new)  "
            f"est_kv_bytes/token={est:,} (per slot, mean len {read_len})"
        )
        return 0

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt), dtype=np.int32)

    # warmup: 128 new tokens decompose as 1 (prefill) + 64+32+16+8+4+2+1 —
    # every power-of-two chunk length the engine can dispatch, so no XLA
    # compile can land inside the timed region below.
    out = generate(
        cfg, params, prompt, 128, top_k=args.top_k, key=jax.random.PRNGKey(1)
    )
    float(out.ravel()[0].astype(jnp.float32))

    t0 = time.perf_counter()
    out = generate(
        cfg, params, prompt, args.tokens, top_k=args.top_k,
        key=jax.random.PRNGKey(2),
    )
    float(out.ravel()[0].astype(jnp.float32))
    dt = time.perf_counter() - t0
    ms_tok = 1000 * dt / args.tokens
    # the contiguous cache's attention reads the FULL (masked) block_size
    est = est_kv_bytes_per_token(cfg, args.kv_dtype, cfg.block_size)
    print(
        f"decode[contiguous,{args.kv_dtype}]: {ms_tok:.2f} ms/token  "
        f"({args.batch * args.tokens / dt:,.0f} tok/s total, batch "
        f"{args.batch}, prompt {args.prompt}, {args.tokens} new)  "
        f"est_kv_bytes/token={est:,} (per slot, full S={cfg.block_size})"
    )
    return 0


if __name__ == "__main__":
    main()
