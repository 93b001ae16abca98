"""kernels, a family with KINDS of attention layer whose configuration file
says what to read (serve_kinds_scopes.py `settings`): the Pallas kernels its
serving programs call, found as the Mosaic custom calls under each scope of the
configuration's `attention_kernels` / under `kv_write` in the programs' texts,
not by a trace name; prefill's attention is XLA.

For each entry {scope: {"kind": k, "metric": name}}: `<name>_ms_per_token`, the
device time of the paged decode attention of the layers of kind k per token
DECODED in the traced window (a request's first token comes from the prefill
program and is left out): the custom calls' durations AND, where the call is
split over partitions of the keys, the XLA ops that merge the partitions'
partial results after it (serve_kinds_scopes.combine_of: they are part of the
attention and no other reader's kernel time has them: 2.0 ms beside 60 in the
cell's global layer); `<name>_roofline`, the least time the chip could take
for the FLOPs and bytes those tokens need at the PUBLISHED widths (the
configuration's arithmetic module, `decode_attention_token(model, k, context,
itemsize)` over each token's context: a window layer reads min(context, window)
keys, so the grid steps the kernel takes over blocks behind the window are time
and no work) over that time. `window_decode_attention_*` is the first reading
anywhere of kernels/attention_template.py's `sliding_window` path. The tokens
are the HOST's count over the traced window and the time is what the TRACE
kept of it: where the profiler drops the window's tail (serve_kinds_scopes'
`trace coverage` line; the cell's first runs lost 22 % and read the global
layer at 99.7-102.1 %) every share here reads too high, which is why the
traffic file of such a cell shortens the traced extension (`trace_seconds`).
`kv_write_ms_per_token` / `_roofline` (names the benchmark has): the in-place
write's time per token written (decoded or prefilled), both kinds' pools,
against the module's `kv_write_token`. A program without these kernels (a CPU
rehearsal), or a configuration without the group, reports nothing."""


def read(run):
    scopes = run["load"]("metrics/serve_kinds_scopes.py")
    got, cfg, tr = scopes.attribute(run), scopes.settings(run), run.get("traced") or {}
    if not got or not got["kernel"] or not scopes.named_enough(run, got, "serve_kinds_kernels"):
        return None
    arith, own = run["load"]("arithmetic.py"), run["load"](cfg["arithmetic"])
    nd = max(1, run["trace_summary"]["n_devices"])
    itemsize = run["counters"]["kv_itemsize"]
    out = {}
    contexts = tr.get("decode_contexts") or []
    for scope, spec in cfg.get("attention_kernels", {}).items():
        call_ns, merge_ns = got["kernel"].get(scope, 0) / nd, got["combine"].get(scope, 0) / nd
        ns, name = call_ns + merge_ns, spec["metric"]
        if not call_ns or not contexts:
            continue
        out[f"{name}_ms_per_token"] = ns / 1e6 / len(contexts)
        if run["peaks"] is not None:
            flops = bytes_ = 0.0
            for c in contexts:
                f, b = own.decode_attention_token(run["model"], spec["kind"], c, itemsize)
                flops, bytes_ = flops + f, bytes_ + b
            share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
            out[f"{name}_roofline"] = share
            run["log"](f"{name} (the {spec['kind']} layers): {call_ns / 1e6:.2f} ms in {got['calls'].get(scope, 0)} custom calls + "
                       f"{merge_ns / 1e6:.2f} ms merging their partitions, for {len(contexts)} decoded tokens (mean context "
                       f"{sum(contexts) / len(contexts):.0f}; {bytes_ / 1e9:.3f} GB credited, {bytes_ / ns:.1f} GB/s), "
                       f"{share:.2f} % of its roofline ({bound}-bound)")
    ns = got["kernel"].get("kv_write", 0) / nd
    written = len(contexts) + tr.get("prefilled_tokens", 0)
    if ns and written:
        out["kv_write_ms_per_token"] = ns / 1e6 / written
        if run["peaks"] is not None:
            f, b = own.kv_write_token(run["model"], itemsize)
            share, bound = arith.roofline_share(f * written, b * written, ns / 1e9, run["peaks"])
            out["kv_write_roofline"] = share
            run["log"](f"kv write: {ns / 1e6:.1f} ms for {written} tokens written, {share:.2f} % of its roofline ({bound}-bound)")
    return out
