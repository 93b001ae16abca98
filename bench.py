"""Single-chip training benchmark. Prints ONE JSON line for the driver.

Measures the full compiled training step (fwd + bwd + optimizer, bf16
compute / fp32 params, remat) on the GPT-2-small 124M `openwebtext` shape and
reports MFU. Baseline for `vs_baseline` is the reference's published 47.8%
MFU on its headline 1.5B run (reference README; BASELINE.md) — MFU is the
hardware-normalized metric that is comparable across chip counts.

Needs a TPU: with no chip, or on a device_kind missing from the peaks table
(training/metrics.py), it exits non-zero naming the device — it never
measures the CPU.

Usage: python bench.py [--steps N] [--batch B] [--attn naive|flash|blockwise]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

BASELINE_MFU = 0.478  # reference 1.5B on v3-128 (BASELINE.md)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--warmup", type=int, default=5)
    parser.add_argument("--batch", type=int, default=None,
                        help="per-microbatch per-device batch size (default: "
                        "the shape config's measured optimum — 16 for 124m, "
                        "12 for wide)")
    parser.add_argument("--accum", type=int, default=1,
                        help="g_accum_iters: microbatches per step (the "
                        "production 124M recipe uses 16 — reference "
                        "configs/openwebtext.py:18)")
    parser.add_argument("--attn", type=str, default=None, choices=["naive", "flash", "blockwise"])
    parser.add_argument("--remat", type=str, default="off",
                        choices=["off", "none", "dots", "dots_attn", "flash"],
                        help="off = no per-block checkpoint; else checkpoint policy")
    parser.add_argument("--attn-block", type=int, default=1024, help="flash/blockwise tile size")
    parser.add_argument("--unroll", type=int, default=12, help="layer-scan unroll factor")
    parser.add_argument("--profile", type=str, default=None, help="capture a trace to this dir")
    parser.add_argument("--loss-chunk", type=int, default=None, help="fused CE chunk tokens")
    parser.add_argument("--seq", type=int, default=None, help="override sequence length (long-context bench)")
    parser.add_argument(
        "--shape", type=str, default="124m", choices=["124m", "wide"],
        help="model shape: '124m' = GPT-2-small (C=64); 'wide' = C=128 "
        "wide-head slice (n_embd=2048, n_head=16, reduced depth) — doubles "
        "attention MXU utilization to probe the >=55%% MFU target",
    )
    parser.add_argument("--layers", type=int, default=None, help="override n_layer")
    parser.add_argument("--vocab", type=int, default=None,
                        help="override vocab_size (default: the shape "
                        "config's padded vocab)")
    parser.add_argument("--rope", type=str, default=None,
                        choices=["interleaved", "split"],
                        help="RoPE lowering override (default: the shape "
                        "config's setting)")
    parser.add_argument("--attn-layout", type=str, default=None,
                        choices=["seq", "head"],
                        help="attention activation layout override")
    args = parser.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # A measurement path that finds no chip fails: a CPU timing is not
        # a slower TPU timing, and must never sit under a device metric.
        print(
            f"bench.py measures the TPU and found none: platform "
            f"{dev.platform!r}, device_kind {dev.device_kind!r} "
            f"({jax.device_count()} device(s))",
            file=sys.stderr,
        )
        return 1
    from midgpt_tpu.training.metrics import device_peak_flops, flops_per_token
    from midgpt_tpu.utils import compile_cache

    peak = device_peak_flops(dev)  # unknown device_kind: raises, naming it
    compile_cache.enable()  # before the first compile

    from midgpt_tpu.config import MeshConfig

    # One source of truth per shape: '124m' is the openwebtext recipe
    # (reference configs/openwebtext.py), 'wide' is the shipped
    # configs/wide610m.py — the same file launch.py trains, so the bench
    # number is reproducible through the normal CLI too.
    if args.shape == "wide":
        from midgpt_tpu.configs.wide610m import config as base_config
    else:
        from midgpt_tpu.configs.openwebtext import config as base_config
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.parallel.data import make_global_batch
    from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
    from midgpt_tpu.training.train import init_state, make_train_step

    n_dev = jax.device_count()
    model_cfg = base_config.model_config
    attn = args.attn or "flash"
    import dataclasses

    shape_overrides = {"n_layer": args.layers} if args.layers else {}
    # wide610m is a single-chip config, so its batch_size IS the per-device
    # optimum; the 124m shape keeps the bench's historical default (the
    # openwebtext preset's global batch is a multi-chip recipe value).
    per_dev_batch = args.batch or (
        base_config.batch_size if args.shape == "wide" else 16
    )
    model_cfg = dataclasses.replace(
        model_cfg,
        **shape_overrides,
        **({"vocab_size": args.vocab} if args.vocab else {}),
        **({"block_size": args.seq} if args.seq else {}),
        attn_impl=attn,
        remat=args.remat != "off",
        remat_policy=args.remat if args.remat != "off" else "none",
        scan_unroll=args.unroll,
        **({"attn_block_size": args.attn_block} if args.attn_block else {}),
        **({"rope_style": args.rope} if args.rope else {}),
        **({"attn_layout": args.attn_layout} if args.attn_layout else {}),
    )
    config = base_config.replace(
        **({"loss_chunk_tokens": args.loss_chunk} if args.loss_chunk else {}),
        batch_size=per_dev_batch * n_dev,
        g_accum_iters=args.accum,
        shard_model=n_dev > 1,
        mesh=MeshConfig(data=1, fsdp=n_dev, sp=1),
        model_config=model_cfg,
        debug=True,
    )

    mesh = make_mesh(config.mesh)
    params, opt_state, specs, optimizer = init_state(config, mesh)
    step, *_ = make_train_step(config, optimizer, mesh, specs)

    T = model_cfg.block_size
    B = config.batch_size
    rng = np.random.default_rng(0)
    x = rng.integers(0, model_cfg.vocab_size, (args.accum, B, T), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec())
    yg = make_global_batch(y, mesh, batch_spec())

    key = jax.random.PRNGKey(0)
    loss = None
    for i in range(args.warmup):
        key, k = jax.random.split(key)
        params, opt_state, loss = step(params, opt_state, xg, yg, k)
    jax.block_until_ready(loss)

    if args.profile:
        jax.profiler.start_trace(args.profile)
    t0 = time.perf_counter()
    for i in range(args.steps):
        key, k = jax.random.split(key)
        params, opt_state, loss = step(params, opt_state, xg, yg, k)
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    if args.profile:
        jax.profiler.stop_trace()

    tokens_per_sec = args.steps * args.accum * B * T / dt
    fpt = flops_per_token(model_cfg)
    mfu = tokens_per_sec * fpt / n_dev / peak

    result = {
        "metric": f"train_mfu_{args.shape}_{attn}_{dev.platform}"
        + (f"_accum{args.accum}" if args.accum > 1 else ""),
        "value": round(mfu * 100, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / BASELINE_MFU, 3),
        "detail": {
            "tokens_per_sec": round(tokens_per_sec, 0),
            "step_ms": round(1000 * dt / args.steps, 2),
            "batch": B,
            "g_accum_iters": args.accum,
            "seq_len": T,
            "n_devices": n_dev,
            "device": dev.device_kind,
            "final_loss": final_loss,
            # vs_baseline compares this 124M single-chip MFU against the
            # reference's published 47.8% MFU from a 1.5B v3-128 run — a
            # cross-scale, cross-topology ratio (MFU is hardware-normalized
            # but model shape still matters), not an apples-to-apples speedup.
            "baseline": "reference 1.5B openwebtext_xl on v3-128, 47.8% MFU (cross-scale)",
        },
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
