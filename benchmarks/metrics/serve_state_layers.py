"""model step (serve) and kernels, a family with a STATE kind of cache whose
configuration file says what to read: `metrics.state_scope` (the named scope
the serving programs open around the recurrence ALONE: the one-token update in
a decode program, the chunk-carrying scan in a prefill program) and
`metrics.state_metrics` (the names to report under). A configuration without
`state_scope` (every cell before PR 59), a program that opens no such scope (a
parent of PR 59) or a run without the counters reports nothing and raises
nothing.

From serve_kinds_scopes.py's attribution (called, not copied: exclusive op
time of the traced window by program and by innermost listed scope), with the
state scope listed in the configuration's `scope_metrics`:

`<update>_ms_per_token`: the decode programs' device time under the state
scope per token DECODED in the traced window (an active slot's step; a request's
first token comes from the prefill program); `<update>_roofline`: the least
time the chip could take for those tokens' updates (the arithmetic module's
`state_update_token`: the state read and written once, q, k, v, g, beta, o)
over that time. The time holds every slot's row a step touches, the inactive
slots' too, and the lanes the device's tiling pads; the work is the live
slots' at the published shapes: the share cannot pass 100 % unless an op of
the update is named after another scope (the compiler names a fusion after
one of the ops it holds).

`<scan>_ms_per_token`: the prefill programs' device time under the state scope
per prompt token prefilled in the traced window; `<scan>_roofline`: the least
time for those tokens (`prefill_scan_token`: the chunked form's matrix
products, the operands' and the carried state's bytes; the decay tiles the
vector unit forms are not counted as work) over that time.

`<fill>` (`state.pool_fill`): state rows held at the peak over the rows there
are, per cent, from the pool owner's counters (`state.rows_live_max`,
`state.rows`).

`serve.weight_read_share` (a name the benchmark has; serve_kinds_reads.py
reports it only for a family that counts experts touched): the least time the
chip could take to read the weights a decode step must read
(`decode_step_weight_bytes`) over the decode programs' device time a traced
step, as that file and serve_looped_cache.py define it.
"""


def read(run):
    cfg = run["config"].get("metrics")
    if run["kind"] != "serve" or not isinstance(cfg, dict) or not cfg.get("state_scope"):
        return None
    c, names, out = run["counters"], cfg.get("state_metrics", {}), {}
    if c.get("state.rows") and "fill" in names:
        out[names["fill"]] = 100.0 * c.get("state.rows_live_max", 0) / c["state.rows"]
    scopes = run["load"]("metrics/serve_kinds_scopes.py")
    got = scopes.attribute(run)
    if not got or run["peaks"] is None or not scopes.named_enough(run, got, "serve_state_layers"):
        return out or None
    import jax.numpy as jnp

    arith, own = run["load"]("arithmetic.py"), run["load"](cfg["arithmetic"])
    tr, nd, scope = run.get("traced") or {}, max(1, run["trace_summary"]["n_devices"]), cfg["state_scope"]
    itemsize = jnp.dtype(run["config"]["serve"]["weights_dtype"]).itemsize
    decoded, prefilled = len(tr.get("decode_contexts") or []), tr.get("prefilled_tokens", 0)
    for key, prog, tokens, count in (("update", "decode", decoded, lambda: own.state_update_token(run["model"], itemsize)),
                                     ("scan", "prefill", prefilled,
                                      lambda: own.prefill_scan_token(run["model"], itemsize, c.get("prefill_chunk", 512)))):
        ns = got["scope"].get(prog, {}).get(scope, 0) / nd
        if not ns or not tokens or key not in names:
            continue
        f, b = count()
        share, bound = arith.roofline_share(f * tokens, b * tokens, ns / 1e9, run["peaks"])
        out[f"{names[key]}_ms_per_token"], out[f"{names[key]}_roofline"] = ns / 1e6 / tokens, share
        run["log"](f"{names[key]} (scope {scope} of the {prog} programs): {ns / 1e6:.2f} ms for {tokens} tokens, "
                   f"{f * tokens / 1e9:.3f} GFLOP and {b * tokens / 1e9:.3f} GB credited ({b * tokens / ns:.1f} GB/s), "
                   f"{share:.2f} % of its roofline ({bound}-bound)")
    steps = run["load"]("metrics/serve_looped_cache.py").traced_decode_steps(run)
    decode_ns = got["program"].get("decode", 0) / nd
    if steps and decode_ns:
        floor_s = own.decode_step_weight_bytes(run["model"], itemsize) / run["peaks"]["hbm_bytes_per_s"]
        out["serve.weight_read_share"] = 100.0 * floor_s * steps / (decode_ns / 1e9)
        run["log"](f"decode step: {decode_ns / 1e6 / steps:.2f} ms of device time a step over {steps} traced steps; its "
                   f"weight-read floor {1e3 * floor_s:.2f} ms: {out['serve.weight_read_share']:.2f} %")
    return out
