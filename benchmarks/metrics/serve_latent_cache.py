"""serving engine and experts, a family served from a LATENT cache: from the
counters `ServeEngine.serve_counters()` gave the cell after its loops.
`kv.latent_pool_fill`: the latent pool's pages allocated at the peak, as a
share of the pool; `kv.latent_bytes_per_token`: the pool's bytes over its token
capacity, all layers (6,400 at 5 layers of 640 bf16 lanes: it guards "stored
once", a pool that kept K and V of every head would read 409,600);
`serve.moe_experts_touched` / `serve.moe_load_max_over_mean` (names the
benchmark has): held experts with at least one pair of an active slot, mean
over decode steps and routed layers; the most loaded held expert over the mean
of the held, worst layer. A run whose counters hold no `kv.latent_*` (every
other cell, the parent of PR 39) reports nothing."""


def read(run):
    c = run["counters"]
    if run["kind"] != "serve" or "kv.latent_pages_live_max" not in c:
        return None
    out = {"kv.latent_pool_fill": 100.0 * c["kv.latent_pages_live_max"] / max(1, c["pool_pages"]["latent"] - 1)}
    for name, key in (("kv.latent_bytes_per_token", "kv.latent_bytes_per_token"),
                      ("serve.moe_experts_touched", "moe.experts_touched"),
                      ("serve.moe_load_max_over_mean", "moe.load_max_over_mean")):
        if key in c:
            out[name] = float(c[key])
    return out
