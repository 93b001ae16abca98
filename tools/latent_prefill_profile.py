"""Chip probe: what ONE full layer's prefill attention over cached latents costs on the device, by op.

    python3 tools/latent_prefill_profile.py CTX,MP[,KB,HG] [CTX,MP[,KB,HG] ...]

from the root of a checkout, on a TPU (from the sandbox: `chiprun -- python3
tools/latent_prefill_profile.py 10240,512`). A case is a chunk of 512 rows
whose last row sees CTX keys of a slot with a page table of MP pages of 32, at
dots3-note's published full-layer widths (128 heads, latent 512 + 64, qk 128 +
64, v 128, top 2,048 of seeded index scores). It traces 5 calls each of
`Dots3._prefill_sparse_sweep` (the XLA loop: the off-TPU lowering) and of
`Dots3._prefill_sparse_kernel` (the Mosaic call with its gather and its mask;
KB keys a block and HG heads a group where given, else the kernel's own), and
prints for each the device's busy ms and the host clock's ms a call, the busy ms a live block of 1,024 keys, its
largest ops with the scope that opened each and the bytes of their operands
and results, and the largest difference between the two outputs. PERF.md
section 6 PR 60's readings of the sweep and of the kernel alone are this
probe's."""
import json
import math
import os
import re
import sys
import tempfile
import time
import types

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np
import reduce as red  # benchmarks/reduce.py

import midgpt_tpu.kernels.latent_prefill as lp
from midgpt_tpu.config import load_config
from midgpt_tpu.models.dots3 import LATENT, Dots3, kth_largest, sortable_bits

T, PS, N = 512, 32, 5
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1, "u8": 1, "f16": 2}


def shape_bytes(text):
    """name -> bytes of every array an instruction of a compiled program defines."""
    out = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\(?[^=]*?) [\w\-]+\(", text, re.M):
        out[m.group(1)] = sum(_ITEM.get(d, 4) * math.prod(int(x) for x in dims.split(",") if x)
                              for d, dims in re.findall(r"\b([a-z]+\d+|pred)\[([\d,]*)\]", m.group(2)))
    return out


def profile(label, fn, args, blocks):
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(N):
        jax.block_until_ready(fn(*args))
    wall = (time.perf_counter() - t0) / N * 1e3
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(N):
                jax.block_until_ready(fn(*args))
        trace = red.load_xplane(red.find_xplane(d))
    ops = trace["devices"][0]["ops"]
    excl, count = red.exclusive_ns(ops)
    size = shape_bytes(text)
    line = {m.group(1): m.group(0) for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*", text, re.M)}
    busy = red.busy_ns(ops) / N / 1e6
    mem = compiled.memory_analysis()
    print(json.dumps({"case": label, "busy_ms_a_call": round(busy, 3), "host_clock_ms_a_call": round(wall, 3), "ms_a_1024_key_block": round(busy / blocks, 4),
                      "temp_mb": round(mem.temp_size_in_bytes / 1e6, 1), "mosaic_calls": text.count("tpu_custom_call")}), flush=True)
    rows = sorted(((ns / N / 1e6, count[n] / N, trace["names"][n]) for n, ns in excl.items()), reverse=True)
    for ms, c, name in rows[:12]:
        ln = line.get(name, "")
        scope = (re.search(r'op_name="([^"]*)"', ln) or [None, "?"])[1][-60:]
        operands = re.findall(r"%([\w.\-]+)", ln.split("(", 1)[1].split("metadata")[0]) if "(" in ln else []
        moved = size.get(name, 0) + sum(size.get(o, 0) for o in operands)
        per = moved * c / 1e6
        gbs = per / ms / 1e3 * 1e3 if ms else 0.0
        print(f"{ms:8.3f} ms x{c:6.1f}  {name:30s} {per:9.1f} MB a call {gbs:7.0f} GB/s  {scope}", flush=True)


def case(ctx, mp, kb=None, hg=None):
    mc = load_config("dots3_note").model_config
    g = mc.geom(LATENT)
    ks = jax.random.split(jax.random.PRNGKey(ctx), 4)
    p = types.SimpleNamespace(w_kvb=(jax.random.normal(ks[0], (g.n_head * (g.nope + g.v), g.kv_rank)) / math.sqrt(g.kv_rank)).astype(jnp.bfloat16))
    rows = jax.random.normal(ks[1], (1, 1, mp + 1, PS, g.latent_dim))
    pool = jnp.pad(rows, [(0, 0)] * 4 + [(0, 640 - g.latent_dim)]).astype(jnp.bfloat16)
    q = (0.35 * jax.random.normal(ks[2], (T, g.n_head, g.qk))).astype(jnp.bfloat16)
    table = 1 + jnp.arange(mp, dtype=jnp.int32)
    counts = ctx - T + 1 + jnp.arange(T, dtype=jnp.int32)
    col = jnp.arange(mp * PS, dtype=jnp.int32)
    scores = jnp.where(col[None] < counts[:, None], jax.random.normal(ks[3], (T, mp * PS)), -jnp.inf)
    thr, need = jax.jit(lambda x: kth_largest(sortable_bits(x), min(mc.index_topk, mp * PS)))(scores)
    args = (q, pool, table, counts, scores, thr, need)
    blocks = -(-ctx // 1024)
    def scoped(f):
        def run(*a):
            with jax.named_scope("attn_select"):
                return f(g, p, a[0], a[1], 0, *a[2:])
        return jax.jit(run)

    sweep, kern = scoped(Dots3._prefill_sparse_sweep), scoped(Dots3._prefill_sparse_kernel)
    own = lp.KEY_BLOCK, lp.HEAD_GROUP
    if kb:
        lp.KEY_BLOCK, lp.HEAD_GROUP = kb, hg or lp.HEAD_GROUP
    if not kb:
        profile(f"sweep ctx={ctx} mp={mp}", sweep, args, blocks)
    profile(f"kernel ctx={ctx} mp={mp} key_block={lp.KEY_BLOCK} head_group={lp.HEAD_GROUP}", kern, args, blocks)
    if not kb:
        a, b = np.asarray(sweep(*args), np.float32), np.asarray(kern(*args), np.float32)
        print(json.dumps({"max_abs_diff": float(np.abs(a - b).max()), "rms_sweep": float(np.sqrt((a * a).mean())),
                          "rms_diff": float(np.sqrt(((a - b) ** 2).mean()))}), flush=True)
    lp.KEY_BLOCK, lp.HEAD_GROUP = own


if __name__ == "__main__":
    print(json.dumps({"device": jax.devices()[0].device_kind, "cwd": os.getcwd()}), flush=True)
    for c in sys.argv[1:]:
        case(*map(int, c.split(",")))
