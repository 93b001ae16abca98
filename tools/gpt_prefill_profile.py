"""Chip probe: what ONE prefill call or ONE decode step of the XL GPT costs on the device, by op.

    python3 tools/gpt_prefill_profile.py [--program prefill|decode] L,MP,T[,K] [L,MP,T[,K] ...]

from the root of a checkout, on a TPU (from the sandbox: `chiprun -- python3
tools/gpt_prefill_profile.py 8,128,16`; the parent's numbers come from the same
file run in an unpacked `git archive` of the parent). Both modes build a program
of `openwebtext_xl` as the engine calls it (attn_impl 'kernel', a bf16 pool of
2,049 pages of 8, 16 rows or slots), trace 10 calls and print the device's busy
ms a call (decode: a step too), its exclusive time by kind of op, and the
largest ops with the scope that opened each.

prefill (the default): the (16, T) program of `serve._serve_prefill_chunk` with
L live rows whose longest fills a page bucket of MP pages and the rest a chunk
shorter each; the 28 largest ops. PERF.md section 6 PR 54's breakdown of the
prefill program and its template-against-gather readings at chunks of 16 to 128
are this mode's.

decode (`--program decode`, PR 57): the (16,) program of
`serve._serve_decode_chunk` at T steps a call (the GPT cells' `decode_chunk` is
8), L live slots whose contexts end T tokens short of a bucket of MP pages and
a page shorter each, with `split_k` = K (default 1; the engine runs 2 at the
128-page bucket, `ServeEngine._split_bucket`, and the two programs number their
ops differently); the ten largest ops. PERF.md section 6 PR 57's readings of
the rotary's gather and of the `wqkv` copy are this mode's."""
import argparse
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

W, N = 16, 10  # the engine's prefill width and slots in both GPT cells; traced calls a case


def page_table(L, MP):
    table = np.zeros((W, MP), np.int32)
    for r in range(L):
        table[r] = 1 + r * 128 + np.arange(MP)
    return table


def prefill_call(mc, params, L, MP, T, K=1):  # K is decode's: the prefill program has no split
    cache = PagedKVCache.init(mc, 2049, 8, jnp.bfloat16, kernel_layout=True)
    tokens = np.random.default_rng(0).integers(0, mc.vocab_size, (W, T)).astype(np.int32)
    start, n_valid = np.zeros((W,), np.int32), np.zeros((W,), np.int32)
    start[:L] = np.maximum(MP * 8 - T - T * np.arange(L), 0)
    n_valid[:L] = T
    table = page_table(L, MP)
    key = jax.random.key_data(jax.random.PRNGKey(0))

    def call():
        nonlocal cache
        first, _, cache, _ = serve._serve_prefill_chunk(
            mc, params, tokens, start, n_valid, cache, table, None, "kernel", 0.8, None, None, key
        )
        jax.block_until_ready(first)

    return call


def decode_call(mc, params, L, MP, T, K=1):
    cache = PagedKVCache.init(mc, 2049, 8, jnp.bfloat16, kernel_layout=True)
    token = np.random.default_rng(0).integers(0, mc.vocab_size, (W,)).astype(np.int32)
    lengths, active = np.zeros((W,), np.int32), np.zeros((W,), np.bool_)
    lengths[:L] = np.maximum(MP * 8 - T - 8 * np.arange(L), 1)
    active[:L] = True
    table = page_table(L, MP)
    key = jax.random.key_data(jax.random.PRNGKey(0))

    def call():
        nonlocal cache
        cache, toks, _ = serve._serve_decode_chunk(
            mc, params, token, cache, table, lengths, active, T, 0.8, None, None, "kernel", key, None, K
        )
        jax.block_until_ready(toks)

    return call


PROGRAMS = {  # mode: (the call's builder, the jitted program, ops listed)
    "prefill": (prefill_call, serve._serve_prefill_chunk, 28),
    "decode": (decode_call, serve._serve_decode_chunk, 10),
}


def profile(program, mc, params, L, MP, T, K=1):
    build, jitted, n_listed = PROGRAMS[program]
    call = build(mc, params, L, MP, T, K)
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
    for text in jitted.texts().values():
        for m in re.finditer(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text, re.M):
            scope_of.setdefault(m.group(1), m.group(2))
    kind, rows = collections.Counter(), []
    for n, ns in excl.items():
        name = trace["names"][n]
        kind[re.sub(r"[.\d]+$", "", name)] += ns / N / 1e6
        rows.append((ns / N / 1e6, count[n] / N, name, scope_of.get(name, "?")[-100:]))
    busy = red.busy_ns(ops) / N / 1e6
    head = {"cwd": os.getcwd(), "program": program, "live_rows": L, "page_bucket": MP,
            "chunk" if program == "prefill" else "steps_a_call": T, "busy_ms_a_call": busy}
    if program == "decode":
        head.update(split_k=K, busy_ms_a_step=busy / T)
    head["gather_ops_ms_a_call"] = round(sum(ms for ms, _, _, path in rows if path.endswith("gather")), 3)
    head["by_op_kind_ms"] = {k: round(v, 3) for k, v in kind.most_common(14)}
    print(json.dumps(head), flush=True)
    for ms, c, name, path in sorted(rows, reverse=True)[:n_listed]:
        print(f"{ms:8.3f} ms x{c:5.1f}  {name:34s} {path}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", choices=list(PROGRAMS), default="prefill")
    ap.add_argument("cases", nargs="+", help="L,MP,T[,K]: live rows or slots, page bucket, chunk tokens or steps a call, decode's split_k")
    args = ap.parse_args()
    mc = load_config("openwebtext_xl").model_config
    params = jax.block_until_ready(GPT.cast_params(GPT.init(mc, jax.random.PRNGKey(7)), jnp.bfloat16))
    for case in args.cases:
        profile(args.program, mc, params, *map(int, case.split(",")))


if __name__ == "__main__":
    main()
