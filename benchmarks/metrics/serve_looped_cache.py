"""serving engine and model step, a family whose configuration file says what
to read (serve_looped_scopes.py `settings`), from the counters
`ServeEngine.serve_counters()` gave the cell after its loops and, for the last,
the traced window.

`kv.<cache_kind>_pool_fill`: the pool's pages allocated at the peak, as a share
of the pool; `kv.<cache_kind>_bytes_per_token`: the pools' bytes over their
token capacity, ALL cache layers (1,572,864 for 192 layers of 16 x 128 bf16 K
and V: it guards "every pass keeps its own"; a cache of n_layer layers would
read a quarter). `loop.exit_pass_expected`: the mean pass of the exit gate's
distribution over decoded tokens (1..n_loop; counted, it changes no token at
the published threshold). `serve.weight_read_share`: the least time the chip
could take to read the weights a decode step must read (the configuration's
arithmetic module, `decode_step_weight_bytes`: the layers' matrices once a
PASS, the head once) over the measured device time of a decode step: the decode
program's device time in the traced window (serve_looped_scopes.attribute) over
the steps its dispatches say they ran (`decode.dispatch` spans' `steps`, the
recorder's events after the measured window's last span: the traced extension
follows it at once). A run whose configuration has no `metrics` group, or whose
counters hold no `kv.<cache_kind>_*`, reports nothing."""


def traced_decode_steps(run):
    """Decode steps the engine dispatched after the measured window (the traced extension); None where the spans carry none."""
    try:
        from midgpt_tpu.obs import live
    except ImportError:
        return None
    if not run["spans"]:
        return None
    after = max(s for _, s, _ in run["spans"])
    for obs in reversed(live()):
        steps = [e[7]["steps"] for e in obs.tracer.events()
                 if e[0] == "X" and e[1] == "decode.dispatch" and e[4] > after and e[7] and "steps" in e[7]]
        if steps:
            return sum(steps)
    return None


def read(run):
    scopes = run["load"]("metrics/serve_looped_scopes.py")
    cfg, c = scopes.settings(run), run["counters"]
    kind = cfg.get("cache_kind") if cfg else None
    if run["kind"] != "serve" or not kind or f"kv.{kind}_pages_live_max" not in c:
        return None
    out = {f"kv.{kind}_pool_fill": 100.0 * c[f"kv.{kind}_pages_live_max"] / max(1, c["pool_pages"][kind] - 1)}
    for key in (f"kv.{kind}_bytes_per_token", "loop.exit_pass_expected"):
        if key in c:
            out[key] = float(c[key])
    got, steps = scopes.attribute(run), traced_decode_steps(run)
    decode_ns = got["program"].get("decode", 0) / max(1, run["trace_summary"]["n_devices"]) if got else 0
    if decode_ns and steps and run["peaks"] is not None:
        floor_s = run["load"](cfg["arithmetic"]).decode_step_weight_bytes(run["model"]) / run["peaks"]["hbm_bytes_per_s"]
        out["serve.weight_read_share"] = 100.0 * floor_s * steps / (decode_ns / 1e9)
        run["log"](f"decode step: {decode_ns / 1e6 / steps:.2f} ms of device time a step over {steps} traced steps; "
                   f"its weight-read floor {1e3 * floor_s:.2f} ms: {out['serve.weight_read_share']:.2f} %")
    return out
