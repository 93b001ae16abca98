"""serving engine, a family whose configuration file says which of the counters
`ServeEngine.serve_counters()` gave the cell are which metric (the `metrics`
group's `counter_metrics` {metric: counter} and `counter_ratios` {metric:
[numerator counter, denominator counter]}): a kind of cache whose name is not
one the older readers know (`kv.window_latent_*` for `kv.window_tokens_per_slot_max`:
the most window-layer tokens one slot ever held, bounded by window + prefill
chunk + page whatever the context), a second array in a kind
(`kv.index_bytes_per_token`: what the index keys keep of a token over the full
layers), and the selection's own ratio (`serve.dsa_selected_share`:
`dsa.rows_selected` / `dsa.keys_scored` over decoded tokens: 1.0 means the
traffic never made the selection drop a token; it DESCRIBES the traffic and
`index_topk`, and no change to the program may move it: `BENCHMARK.json` has
to give every metric a direction, and its "lower" says only that a cell of
this kind is worth less the closer it reads to 1). The latent kind's
`kv.latent_pool_fill` / `kv.latent_bytes_per_token` and the experts' counters
are serve_latent_cache.py's, which reads them on any cell whose counters hold a
kind called `latent`. A configuration without these groups (every cell before
PR 51) or a run without the counters (the parent of PR 51) reports nothing.
This file gates on no cell's and no family's name."""


def read(run):
    m = run["config"].get("metrics")
    c = run["counters"]
    if run["kind"] != "serve" or not isinstance(m, dict) or not (m.get("counter_metrics") or m.get("counter_ratios")):
        return None
    out = {name: float(c[key]) for name, key in m.get("counter_metrics", {}).items() if key in c}
    for name, (num, den) in m.get("counter_ratios", {}).items():
        if c.get(den):
            out[name] = float(c[num]) / float(c[den])
    return out
