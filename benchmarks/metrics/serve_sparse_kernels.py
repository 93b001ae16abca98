"""kernels, a family whose full-attention layers SELECT what they attend to and
whose configuration file says what to read (serve_kinds_scopes.py `settings`,
the group's `sparse_scopes`): the DECODE programs' device time under each
listed scope, kernels and XLA ops alike, per decoded token, against what the
scope's work needs at the PUBLISHED widths, so that the yardstick reads the
same work whatever later implements it (an XLA gather today, a kernel after a
`perf_opt`).

    "sparse_scopes": {"dsa_index":   {"metric": "dsa_index_sweep",         "arithmetic": "index_sweep_token"},
                      "attn_select": {"metric": "select_decode_attention", "arithmetic": "select_attention_token"}}

For each entry: `<metric>_ms_per_token`, the decode programs' exclusive device
time under the scope (serve_kinds_scopes.attribute: each op put to its program
by the trace's `XLA Modules` line and to the INNERMOST listed scope on its
`op_name` path) per token DECODED in the traced window (a request's first
token comes from the prefill program and is left out); `<metric>_roofline`:
the least time the chip could take for the FLOPs and bytes those tokens need
(the configuration's arithmetic module, `<arithmetic>(model, context,
itemsize)` over each decoded token's context: the index sweep reads every
cached index key once, the selected attention min(context, index_topk) latent
rows once) over that time. A gathered copy, a padded lane, a block swept past a
slot's length and the top-k between the two scopes are time and no work. The
log line also gives the PREFILL programs' time under the same scope, ms an
engine round, apart (the ms-a-round metrics of serve_kinds_scopes.py sum both).

A configuration without the group (every cell before PR 51), a program without
these scopes (the parent of PR 51) or a run without a trace reports nothing.
This file gates on no cell's and no family's name."""


def read(run):
    scopes = run["load"]("metrics/serve_kinds_scopes.py")
    cfg = scopes.settings(run)
    spec = (cfg or {}).get("sparse_scopes")
    got = scopes.attribute(run) if spec else None
    tr = run.get("traced") or {}
    contexts = tr.get("decode_contexts") or []
    if not got or not contexts or not scopes.named_enough(run, got, "serve_sparse_kernels"):
        return None
    arith, own = run["load"]("arithmetic.py"), run["load"](cfg["arithmetic"])
    nd = max(1, run["trace_summary"]["n_devices"])
    rounds = max(1, run["counters"].get("traced_rounds") or 1)
    itemsize = run["counters"]["kv_itemsize"]
    out = {}
    for scope, s in spec.items():
        ns = got["scope"].get("decode", {}).get(scope, 0) / nd
        prefill_ns = got["scope"].get("prefill", {}).get(scope, 0) / nd
        if not ns:
            continue
        name = s["metric"]
        out[f"{name}_ms_per_token"] = ns / 1e6 / len(contexts)
        line = (f"{name} (scope {scope}, decode programs): {ns / 1e6:.2f} ms for {len(contexts)} decoded tokens (mean context "
                f"{sum(contexts) / len(contexts):.0f}), {ns / 1e6 / rounds:.2f} ms an engine round; the prefill programs' "
                f"{prefill_ns / 1e6 / rounds:.2f} ms a round under the same scope")
        if run["peaks"] is not None:
            flops = bytes_ = 0.0
            for c in contexts:
                f, b = getattr(own, s["arithmetic"])(run["model"], c, itemsize)
                flops, bytes_ = flops + f, bytes_ + b
            share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
            out[f"{name}_roofline"] = share
            line += f"; {bytes_ / 1e9:.3f} GB and {flops / 1e12:.3f} TFLOP credited: {share:.2f} % of its roofline ({bound}-bound)"
        run["log"](line)
    return out
