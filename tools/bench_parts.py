"""Decompose single-chip step time: fwd / fwd+bwd / optimizer / attention kernel.

Localizes the MFU gap before tuning: prints achieved TFLOP/s per phase so the
slow phase is obvious. Not part of the driver bench contract (bench.py is).

Usage: python tools/bench_parts.py [--batch N] [--attn flash|naive] [--remat ...]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(out):
    jax.block_until_ready(out)


def timeit(fn, *args, n=10, warmup=3):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / n


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--attn", type=str, default="flash")
    p.add_argument("--remat", type=str, default="flash",
                   choices=["off", "none", "dots", "dots_attn", "flash"])
    p.add_argument("--attn-block", type=int, default=1024)
    args = p.parse_args()

    import dataclasses

    from midgpt_tpu.configs.openwebtext import config as base
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.ops.loss import fused_linear_cross_entropy
    from midgpt_tpu.utils.precision import cast_floating

    mc = dataclasses.replace(
        base.model_config,
        attn_impl=args.attn,
        remat=args.remat != "off",
        remat_policy=args.remat if args.remat != "off" else "none",
        attn_block_size=args.attn_block,
    )
    B, T, D = args.batch, mc.block_size, mc.n_embd
    H, C = mc.n_head, mc.head_dim
    L, V = mc.n_layer, mc.vocab_size

    params = jax.jit(lambda k: GPT.init(mc, k))(jax.random.PRNGKey(0))
    params_c = cast_floating(params, jnp.bfloat16)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, V, (B, T), np.int32))
    labels = jnp.roll(tokens, -1, axis=-1)

    n_params = GPT.count_params(params)
    fwd_flops_tok = 2 * n_params + 4 * L * D * T  # fwd matmuls + attention
    print(f"params={n_params/1e6:.1f}M  B={B} T={T}  attn={args.attn} remat={args.remat}")

    # 1. forward only
    fwd = jax.jit(lambda p, t: GPT.apply(mc, p, t, inference=True))
    dt = timeit(fwd, params_c, tokens)
    print(f"fwd:        {dt*1e3:7.1f} ms  {B*T*fwd_flops_tok/dt/1e12:6.1f} TF/s")

    # 2. fwd+bwd of fused loss
    def loss_fn(p, t, y):
        h = GPT.hidden(mc, p, t, inference=True)
        return fused_linear_cross_entropy(h, p.lm_head, y, 8192)

    grad = jax.jit(jax.grad(loss_fn))
    dt = timeit(grad, params_c, tokens, labels)
    print(f"fwd+bwd:    {dt*1e3:7.1f} ms  {B*T*3*fwd_flops_tok/dt/1e12:6.1f} TF/s (assumes bwd=2x fwd)")

    # 3. attention kernel alone (all L layers' worth, fwd)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, T, C), jnp.bfloat16)
    from midgpt_tpu.ops.attention import multihead_attention

    att = jax.jit(
        lambda q: multihead_attention(
            q, q, q, impl=args.attn, inference=True, block_size=args.attn_block
        )
    )
    dt = timeit(att, q)
    attn_flops = 2 * 2 * B * H * T * T * C / 2  # qk + pv, causal half
    print(f"attn fwd:   {dt*1e3:7.1f} ms  {attn_flops/dt/1e12:6.1f} TF/s (x{L} layers = {L*dt*1e3:.1f} ms)")

    # 4. attention fwd+bwd
    attg = jax.jit(jax.grad(lambda q: multihead_attention(
        q, q, q, impl=args.attn, inference=True, block_size=args.attn_block
    ).sum()))
    dt = timeit(attg, q)
    print(f"attn f+b:   {dt*1e3:7.1f} ms  {3*attn_flops/dt/1e12:6.1f} TF/s (x{L} layers = {L*dt*1e3:.1f} ms)")

    # 5. big matmul reference point (MXU roofline sanity)
    a = jax.random.normal(jax.random.PRNGKey(2), (8192, 8192), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    dt = timeit(mm, a)
    print(f"8k matmul:  {dt*1e3:7.1f} ms  {2*8192**3/dt/1e12:6.1f} TF/s (achievable peak)")

    # 6. lm_head + loss epilogue alone
    h = jax.random.normal(jax.random.PRNGKey(3), (B, T, D), jnp.bfloat16)
    lm = params_c.lm_head
    lo = jax.jit(lambda h, w, y: fused_linear_cross_entropy(h, w, y, 8192))
    dt = timeit(lo, h, lm, labels)
    print(f"loss fwd:   {dt*1e3:7.1f} ms  {2*B*T*D*V/dt/1e12:6.1f} TF/s")

    log = jax.jit(jax.grad(lambda h, w, y: fused_linear_cross_entropy(h, w, y, 8192), argnums=(0, 1)))
    dt = timeit(log, h, lm, labels)
    print(f"loss f+b:   {dt*1e3:7.1f} ms  {6*B*T*D*V/dt/1e12:6.1f} TF/s")


if __name__ == "__main__":
    main()
