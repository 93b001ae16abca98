"""Chip probe: what ONE prefill call of the XL GPT costs on the device, by op.

    python3 tools/gpt_prefill_profile.py L,MP,T [L,MP,T ...]

from the root of a checkout, on a TPU (from the sandbox: `chiprun -- python3
tools/gpt_prefill_profile.py 8,128,16`; the parent's numbers come from the same
file run in an unpacked `git archive` of the parent). For each case it builds the
(16, T) prefill program of `openwebtext_xl` as the engine calls it
(`serve._serve_prefill_chunk`, attn_impl 'kernel', a bf16 pool of 2,049 pages of
8), with L live rows whose longest fills a page bucket of MP pages and the rest a
chunk shorter each, traces 10 calls and prints the device's busy ms a call, its
exclusive time by kind of op, and the 28 largest ops with the scope that opened
each. PERF.md section 6 PR 54's breakdown of the prefill program and its
template-against-gather readings at chunks of 16 to 128 are this probe's."""
import collections
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np
import reduce as red  # benchmarks/reduce.py

from midgpt_tpu.config import load_config
from midgpt_tpu.models.gpt import GPT, PagedKVCache
from midgpt_tpu.sampling import serve

W, N = 16, 10  # the engine's prefill width in both GPT cells; traced calls a case


def profile(mc, params, L, MP, T):
    cache = PagedKVCache.init(mc, 2049, 8, jnp.bfloat16, kernel_layout=True)
    tokens = np.random.default_rng(0).integers(0, mc.vocab_size, (W, T)).astype(np.int32)
    start, n_valid = np.zeros((W,), np.int32), np.zeros((W,), np.int32)
    start[:L] = np.maximum(MP * 8 - T - T * np.arange(L), 0)
    n_valid[:L] = T
    table = np.zeros((W, MP), np.int32)
    for r in range(L):
        table[r] = 1 + r * 128 + np.arange(MP)
    key = jax.random.key_data(jax.random.PRNGKey(0))

    def call():
        nonlocal cache
        first, _, cache, _ = serve._serve_prefill_chunk(
            mc, params, tokens, start, n_valid, cache, table, None, "kernel", 0.8, None, None, key
        )
        jax.block_until_ready(first)

    for _ in range(3):
        call()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(N):
                call()
        trace = red.load_xplane(red.find_xplane(d))
    ops = trace["devices"][0]["ops"]
    excl, count = red.exclusive_ns(ops)
    scope_of = {}
    for text in serve._serve_prefill_chunk.texts().values():
        for m in re.finditer(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text, re.M):
            scope_of.setdefault(m.group(1), m.group(2))
    kind, rows = collections.Counter(), []
    for n, ns in excl.items():
        name = trace["names"][n]
        kind[re.sub(r"[.\d]+$", "", name)] += ns / N / 1e6
        rows.append((ns / N / 1e6, count[n] / N, name, scope_of.get(name, "?")[-100:]))
    print(json.dumps({
        "cwd": os.getcwd(), "live_rows": L, "page_bucket": MP, "chunk": T,
        "busy_ms_a_call": red.busy_ns(ops) / N / 1e6,
        "by_op_kind_ms": {k: round(v, 3) for k, v in kind.most_common(14)},
    }), flush=True)
    for ms, c, name, path in sorted(rows, reverse=True)[:28]:
        print(f"{ms:8.3f} ms x{c:5.1f}  {name:34s} {path}", flush=True)


def main():
    mc = load_config("openwebtext_xl").model_config
    params = jax.block_until_ready(GPT.cast_params(GPT.init(mc, jax.random.PRNGKey(7)), jnp.bfloat16))
    for case in sys.argv[1:]:
        profile(mc, params, *map(int, case.split(",")))


if __name__ == "__main__":
    main()
