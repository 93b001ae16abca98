"""serving engine and experts, a family with kinds of cache: from the
counters `ServeEngine.serve_counters()` gave the cell after its loops.
`kv.global_pool_fill`: the global-layer pool's pages allocated at the peak, as
a share of the pool; `kv.window_tokens_per_slot_max`: the most window-layer
tokens one slot ever held (bounded by window + prefill chunk + page, whatever
the context); `serve.moe_experts_touched`: held experts with at least one pair
of an active slot, mean over decode steps and routed layers (the experts whose
matrices a step has to read: `ops/moe.py` `moe_experts_serving` sorts the rows
by expert and its one grouped matmul, `kernels/grouped_matmul.py`, streams an
expert only where a row block holds its rows); `serve.moe_load_max_over_mean`:
the most loaded held expert over the mean of the held, worst layer, over the
run's decode steps. A run whose counters hold none of these (every GPT cell,
the parent of PR 30) reports nothing."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or "kv.global_pages_live_max" not in c:
        return None
    out = {"kv.global_pool_fill": 100.0 * c["kv.global_pages_live_max"] / max(1, c["pool_pages"]["global"] - 1)}
    for name, key in (("kv.window_tokens_per_slot_max", "kv.window_tokens_per_slot_max"),
                      ("serve.moe_experts_touched", "moe.experts_touched"),
                      ("serve.moe_load_max_over_mean", "moe.load_max_over_mean")):
        if key in c:
            out[name] = float(c[key])
    return out
