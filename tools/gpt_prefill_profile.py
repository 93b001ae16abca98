"""Chip probe: what ONE prefill call or ONE decode step of the XL GPT costs on the device, by op.

    python3 tools/gpt_prefill_profile.py [--program prefill|decode] [--config NAME] [--slots N] L,MP,T[,K] [L,MP,T[,K] ...]

from the root of a checkout, on a TPU (from the sandbox: `chiprun -- python3
tools/gpt_prefill_profile.py 8,128,16`; the parent's numbers come from the same
file run in an unpacked `git archive` of the parent). Both modes build a program
of `openwebtext_xl` as the engine calls it (attn_impl 'kernel', a bf16 pool of
2,049 pages of 8, 16 rows or slots), trace 10 calls and print the device's busy
ms a call (decode: a step too), the program's `weight_copies` (utils/hlo.py),
its exclusive time by kind of op, and the largest ops, each with the bytes of
its result (so that a copy shows as bytes and not as a fusion number) and the
scope that opened it. `--config local_text_124m --slots 128` builds the 124M
cells' GPT at `serve_124m_sample`'s slots.

The two probes that priced PR 62's change (my chip run, PR 62, call 1; 16 live
slots; the parent's flat matmul over the indexed layer -> the per-third einsum
that `GPT._decode_layer_loop` takes since):

    --program decode 16,64,8,1 16,128,8,2   busy ms a step 7.396 -> 6.135 and 9.548 -> 8.279, weight_copies 2 -> 0
                                            (fusion.2053 / fusion.2154, 478.151 MB out, 1.230 ms a step, and
                                            fusion.2019 / fusion.2115, 125.829 MB out, 0.225: gone)
    8,128,16                                busy ms a call 10.348 -> 8.993, weight_copies 2 -> 0 (fusion.1421 1.231 ms,
                                            fusion.1422 0.225: gone)
    --program decode --config local_text_124m --slots 128 128,32,8,1     busy ms a step 3.374 -> 3.177

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
from midgpt_tpu.utils.hlo import hlo_computations, hlo_instructions, result_bytes, weight_copies

N = 10  # traced calls a case


def pool_pages(W, MP):
    return max(2049, W * MP + 1)  # 2,049: `serve_xl_chat`'s pool, 16 slots of 128 pages and the sink page


def page_table(W, L, MP):
    table = np.zeros((W, MP), np.int32)
    for r in range(L):
        table[r] = 1 + r * MP + np.arange(MP)
    return table


def prefill_call(mc, params, W, L, MP, T, K=1):  # K is decode's: the prefill program has no split
    cache = PagedKVCache.init(mc, pool_pages(W, MP), 8, jnp.bfloat16, kernel_layout=True)
    tokens = np.random.default_rng(0).integers(0, mc.vocab_size, (W, T)).astype(np.int32)
    start, n_valid = np.zeros((W,), np.int32), np.zeros((W,), np.int32)
    start[:L] = np.maximum(MP * 8 - T - T * np.arange(L), 0)
    n_valid[:L] = T
    table = page_table(W, L, MP)
    key = jax.random.key_data(jax.random.PRNGKey(0))

    def call():
        nonlocal cache
        first, _, cache, _ = serve._serve_prefill_chunk(
            mc, params, tokens, start, n_valid, cache, table, None, "kernel", 0.8, None, None, key
        )
        jax.block_until_ready(first)

    return call


def decode_call(mc, params, W, L, MP, T, K=1):
    cache = PagedKVCache.init(mc, pool_pages(W, MP), 8, jnp.bfloat16, kernel_layout=True)
    token = np.random.default_rng(0).integers(0, mc.vocab_size, (W,)).astype(np.int32)
    lengths, active = np.zeros((W,), np.int32), np.zeros((W,), np.bool_)
    lengths[:L] = np.maximum(MP * 8 - T - 8 * np.arange(L), 1)
    active[:L] = True
    table = page_table(W, L, MP)
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


def profile(program, mc, params, W, L, MP, T, K=1):  # W: rows or slots of the program (`--slots`)
    build, jitted, n_listed = PROGRAMS[program]
    call = build(mc, params, W, L, MP, T, K)
    for _ in range(3):
        call()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(N):
                call()
        trace = red.load_xplane(red.find_xplane(d))
    ops = trace["devices"][0]["ops"]
    excl, count = red.exclusive_ns(ops)
    scope_of, bytes_of, copies = {}, {}, 0
    for text in jitted.texts().values():
        for m in re.finditer(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?op_name="([^"]*)"', text, re.M):
            scope_of.setdefault(m.group(1), m.group(2))
        for lines in hlo_computations(text).values():
            for name, _, members in hlo_instructions(lines):
                bytes_of.setdefault(name, result_bytes(members))
        copies += weight_copies(text, [a.shape for a in jax.tree.leaves(params.blocks)])
    kind, rows = collections.Counter(), []
    for n, ns in excl.items():
        name = trace["names"][n]
        kind[re.sub(r"[.\d]+$", "", name)] += ns / N / 1e6
        rows.append((ns / N / 1e6, count[n] / N, name, bytes_of.get(name, 0) / 1e6, scope_of.get(name, "?")[-100:]))
    busy = red.busy_ns(ops) / N / 1e6
    head = {"cwd": os.getcwd(), "program": program, "live_rows": L, "page_bucket": MP,
            "chunk" if program == "prefill" else "steps_a_call": T, "busy_ms_a_call": busy}
    if program == "decode":
        head.update(split_k=K, busy_ms_a_step=busy / T)
    head["gather_ops_ms_a_call"] = round(sum(ms for ms, _, _, _, path in rows if path.endswith("gather")), 3)
    head["weight_copies"] = copies  # utils/hlo.py: instructions that write a layer of a stacked matrix out again
    head["by_op_kind_ms"] = {k: round(v, 3) for k, v in kind.most_common(14)}
    print(json.dumps(head), flush=True)
    for ms, c, name, mb, path in sorted(rows, reverse=True)[:n_listed]:
        print(f"{ms:8.3f} ms x{c:5.1f}  {name:34s} {mb:9.3f} MB out  {path}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", choices=list(PROGRAMS), default="prefill")
    ap.add_argument("--config", default="openwebtext_xl", help="the repo configuration whose GPT is built (local_text_124m: the 124M cells')")
    ap.add_argument("--slots", type=int, default=16, help="rows of the prefill program or slots of the decode program (16: serve_xl_chat's and the prefill width; serve_124m_sample: 128)")
    ap.add_argument("cases", nargs="+", help="L,MP,T[,K]: live rows or slots, page bucket, chunk tokens or steps a call, decode's split_k")
    args = ap.parse_args()
    mc = load_config(args.config).model_config
    params = jax.block_until_ready(GPT.cast_params(GPT.init(mc, jax.random.PRNGKey(7)), jnp.bfloat16))
    for case in args.cases:
        profile(args.program, mc, params, args.slots, *map(int, case.split(",")))


if __name__ == "__main__":
    main()
