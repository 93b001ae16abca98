"""model step (serve), a family whose configuration file says what to read
(serve_kinds_scopes.py `settings`): how close the decode step and its routed
experts come to the time the chip needs just to READ what they must read.

`serve.weight_read_share` (a name the benchmark has): the least time the chip
could take to read the weights a decode step must read (the configuration's
arithmetic module, `decode_step_weight_bytes(model, itemsize, experts_touched)`
at the run's mean of held experts touched a step a layer, the counter the
group's `experts_touched_counter` names) over the measured device time of a
decode step: the decode programs' device time in the traced window
(serve_kinds_scopes.attribute) over the steps their dispatches say they ran
(serve_looped_cache.traced_decode_steps).

`serve.moe_expert_read_share`: the least time the chip could take to read the
expert matrices a decode step's routed layers had to read (the run's mean of
held experts touched by an ACTIVE slot's pair a step a layer, the same counter,
x the routed layers x the module's `expert_bytes`: the counter is cumulative and
the cell does not snapshot it at the trace's start, so it is the run's mean a
decode step, times the traced steps) over the decode programs'
device time under the group's `expert_scopes` in the traced window: the routed
experts' share of their read roofline. The scopes are `moe_route` AND
`moe_experts` together. Since PR 50 the experts are one grouped matmul
(`ops/moe.py` `moe_experts_serving`: a plan, one gather of the tokens into rows
sorted by expert, one Pallas call, a gather back), and the plan and the gathers
open under `moe_route`; before it, when a loop walked tiles, the compiler fused
a tile's row gather with the matmul that read the expert's matrix and named the
fusion after either scope (over `moe_experts` alone the first chip run read
202 %: the time left out part of the work; PERF.md section 6 PR 46; ROADMAP S11
found the same from PR 43: "the two scopes are ONE cost"). Both are kept so
that the time holds all the work the bytes are credited to. The router's own
matmul and top-k ride in that time and are credited no bytes. An expert only
an inactive slot's garbage pair selects is read and not credited.

A configuration without the group, a run without those counters or without a
trace of decode steps reports nothing."""


def read(run):
    scopes, looped = run["load"]("metrics/serve_kinds_scopes.py"), run["load"]("metrics/serve_looped_cache.py")
    cfg, c = scopes.settings(run), run["counters"]
    got = scopes.attribute(run) if cfg else None
    if not got or run["peaks"] is None or not scopes.named_enough(run, got, "serve_kinds_reads"):
        return None
    steps = looped.traced_decode_steps(run)
    nd = max(1, run["trace_summary"]["n_devices"])
    decode_ns = got["program"].get("decode", 0) / nd
    if not steps or not decode_ns:
        return None
    import jax.numpy as jnp

    hbm = run["peaks"]["hbm_bytes_per_s"]
    own = run["load"](cfg["arithmetic"])
    out = {}
    touched = c.get(cfg.get("experts_touched_counter"))
    itemsize = jnp.dtype(run["config"]["serve"]["weights_dtype"]).itemsize
    if touched is not None:
        floor_s = own.decode_step_weight_bytes(run["model"], itemsize, experts_touched=float(touched)) / hbm
        out["serve.weight_read_share"] = 100.0 * floor_s * steps / (decode_ns / 1e9)
        run["log"](f"decode step: {decode_ns / 1e6 / steps:.2f} ms of device time a step over {steps} traced steps; its "
                   f"weight-read floor at {float(touched):.1f} experts touched a layer {1e3 * floor_s:.2f} ms: "
                   f"{out['serve.weight_read_share']:.2f} %")
    expert_ns = sum(got["scope"].get("decode", {}).get(s, 0) for s in cfg.get("expert_scopes", ())) / nd
    if touched and expert_ns:
        model = run["model"]
        step_bytes = float(touched) * (model["n_layer"] - model["n_dense_layers"]) * own.expert_bytes(model, itemsize)
        out["serve.moe_expert_read_share"] = 100.0 * step_bytes * steps / hbm / (expert_ns / 1e9)
        run["log"](f"routed experts (decode): {expert_ns / 1e6 / steps:.2f} ms a step under {' + '.join(cfg['expert_scopes'])}; "
                   f"{step_bytes / 1e9:.3f} GB of expert matrices to read a step: "
                   f"{out['serve.moe_expert_read_share']:.2f} % of its read floor")
    return out
