"""At `prefill_width` 1 the engine's prefill round is the PARENT's, call for
call (sampling/serve.py `_prefill_round`): one slot's pages, its `(1, T_c)`
chunk with a SCALAR `start` / `n_valid` and the slot's own table rows, the
enqueue, the advance, the window reclaim, then the next slot.

The golden (`golden/prefill_round_width1_parent.json`) was generated on the
PARENT tree of PR 32 (commit 9047794, where `_prefill_round` calls
`_prefill_one` a slot and nothing batches) by this file run as a script there:

    for c in mimo gpt; do JAX_PLATFORMS=cpu python tests/test_prefill_width1_round.py $c; done

(one process a case, as the test runs them; the two lines merged into one object).

It holds, for a ramp of five requests over three slots on a pool short enough
to evict, (a) the sequence of `_ensure_pages` / `pool.tables` (recorded as
`device_tables`) / `_serve_prefill_chunk` / `pool.reclaim` (`reclaim_window`)
calls inside `_prefill_round`, each
program argument with its shape, dtype and weak type, and (b) how many
jax.monitoring trace / lower / compile events the whole process fired, by
function name. Two engines are of width 1: a `mimo_v2` toy (the family takes
one row a call) and a GPT whose shapes give 1 (3 slots, chunks of 512: a chunk
past the ridge alone). Each is recorded in a process of its own: what an
earlier test left in the process's trace caches would move the counts.

PR 35 moved the first token's sample into `_serve_prefill_chunk` (both engines
are greedy: an f32 argmax at the program's end) and the program now takes
`temperature`, `top_k`, `top_p` and `key` and hands back (tokens (1,), rows
(1, V), cache). `calls` and `events` were regenerated on PR 35's finished tree
by the same script; `tokens` and `preemptions` are the PARENT's objects, kept
byte for byte: greedy output did not move. The ORDER of the `_ensure_pages` /
`_device_tables` / `_serve_prefill_chunk` / `_reclaim_window` calls and every
aval the parent recorded are still the parent's (what was added: the sampling
arguments and the avals handed back); `lower` and `compile` came out the
parent's name for name and count for count (`PARENT_LOWERED`), `trace` gained
one `_argmax` a prefill program.

PR 37 hands the program's arguments over in numpy (the recorded avals are the
same: shape, dtype, not weak) and the program hands back a fourth value, the
engine's next sampling key (None here: both engines are greedy). The golden is
untouched.

The MimoV2 record has to equal the golden in everything. The GPT's prefill
PROGRAM is a text of PR 32's (a batch of B rows, here B = 1), so the functions
traced inside it differ from the parent's; what is held there: the calls, the
argument avals, and the programs lowered and compiled, by name."""

import collections
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = os.path.join(HERE, "golden", "prefill_round_width1_parent.json")
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}


# every function the PARENT's golden lowered (and compiled), either case: no later tree may add an eager program
PARENT_LOWERED = {
    "_normal", "_serve_decode_chunk", "_serve_prefill_chunk", "_threefry_seed", "_threefry_split", "_truncated_normal",
    "_unstack", "broadcast_in_dim", "convert_element_type", "dynamic_slice", "multiply", "reshape", "slice", "squeeze",
    "true_divide",
}


def record(case: str) -> dict:
    """Serve the ramp on a width-1 engine of `case`; what its prefill rounds
    called, and the process's compile events by function name."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")
    events = {k: collections.Counter() for k in _EVENTS.values()}

    def on_duration(name, secs, **kw):
        if name in _EVENTS:
            fun = str(kw.get("fun_name", "?"))
            events[_EVENTS[name]][fun[4:-1] if fun.startswith("jit(") and fun.endswith(")") else fun] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    import numpy as np

    from midgpt_tpu.sampling import serve

    if case == "mimo":
        from test_mimo_v2 import toy
        from midgpt_tpu.models.mimo_v2 import MimoV2

        cfg = toy()
        params = MimoV2.init(cfg, jax.random.PRNGKey(0))
        eng = serve.ServeEngine(cfg, params, max_slots=3, num_pages=24, page_size=4, prefill_chunk=8,
                                decode_chunk=4, cache_dtype="float32")
        work = [(37, 6), (5, 5), (50, 8), (11, 4), (23, 9)]
    else:
        from midgpt_tpu.models.gpt import GPT, GPTConfig

        cfg = GPTConfig(block_size=2048, vocab_size=96, n_layer=2, n_head=4, n_embd=32)
        params = GPT.init(cfg, jax.random.PRNGKey(0))
        eng = serve.ServeEngine(cfg, params, max_slots=3, num_pages=120, page_size=16, prefill_chunk=512,
                                decode_chunk=4, temperature=0.0, cache_dtype="float32")
        work = [(700, 6), (30, 5), (1100, 8), (520, 4), (1030, 9)]

    calls, inside = [], [False]
    slot_of = lambda slot: [eng.slots.index(slot), slot.request.uid]
    aval = lambda a: [list(a.shape), str(a.dtype), bool(getattr(a, "weak_type", False))]

    def hook(name, real, describe):
        def wrapped(*args):
            if inside[0]:
                calls.append([name, *describe(*args)])
            return real(*args)
        return wrapped

    eng._ensure_pages = hook("ensure_pages", eng._ensure_pages, lambda slot, upto: [slot_of(slot), upto])
    # the golden's names for what sampling/pages.py owns since PR 45
    eng.pool.reclaim = hook("reclaim_window", eng.pool.reclaim, lambda slot: [slot_of(slot)])
    eng.pool.tables = hook("device_tables", eng.pool.tables, lambda slots, n_pages, *rows: [n_pages])
    real_chunk, real_round = serve._serve_prefill_chunk, eng._prefill_round

    def chunk(config, p, tokens, start, n_valid, cache, table, mesh, attn_impl, temperature, top_k, top_p, key):
        first, logits, cache, next_key = real_chunk(
            config, p, tokens, start, n_valid, cache, table, mesh, attn_impl, temperature, top_k, top_p, key)
        calls.append([
            "serve_prefill_chunk", aval(tokens), aval(start), aval(n_valid),
            [aval(t) for t in jax.tree.leaves(table)], np.asarray(start).tolist(), np.asarray(n_valid).tolist(),
            attn_impl, [temperature, top_k, top_p, key], aval(first), list(logits.shape),
        ])
        return first, logits, cache, next_key

    def prefill_round():
        inside[0] = True
        try:
            real_round()
        finally:
            inside[0] = False

    serve._serve_prefill_chunk, eng._prefill_round = chunk, prefill_round
    rng = np.random.default_rng(3)
    for n, m in work:
        eng.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
    done = eng.run()
    return {
        "calls": calls,
        "events": {k: dict(sorted(v.items())) for k, v in events.items()},
        "preemptions": eng.preemptions,
        "tokens": {str(uid): np.asarray(r.tokens).tolist() for uid, r in sorted(done.items())},
    }


@pytest.mark.parametrize("case", ["mimo", "gpt"])
def test_width_1_round_is_the_parents_call_for_call(case):
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), case], capture_output=True, text=True, check=False,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    got, want = json.loads(out.stdout)[case], json.load(open(GOLDEN))[case]
    assert sum(c[0] == "serve_prefill_chunk" for c in got["calls"]) > 10 and got["preemptions"] > 0
    assert got["calls"] == want["calls"]
    assert got["tokens"] == want["tokens"] and got["preemptions"] == want["preemptions"]
    assert got["events"]["lower"] == want["events"]["lower"]
    assert got["events"]["compile"] == want["events"]["compile"]
    for kind in ("lower", "compile"):
        assert set(got["events"][kind]) <= PARENT_LOWERED and got["events"][kind]["_serve_prefill_chunk"] == 4
    if case == "mimo":
        assert got["events"]["trace"] == want["events"]["trace"]
    else:  # the programs' own traces; what is traced inside the new prefill text differs
        programs = set(want["events"]["lower"])
        for side in (got, want):
            side["events"]["trace"] = {f: n for f, n in side["events"]["trace"].items() if f in programs}
        assert got["events"]["trace"] == want["events"]["trace"]


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.dirname(HERE))
    print(json.dumps({sys.argv[1]: record(sys.argv[1])}))
