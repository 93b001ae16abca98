"""kernels, a family whose configuration file says what to read
(serve_looped_scopes.py `settings`): the Pallas kernels its serving programs
call, found as the Mosaic custom calls under the configuration's
`attention_kernel_scope` / under `kv_write` in the programs' texts, not by a
trace name; prefill's attention is XLA.

`<attention_metric>_ms_per_token`: the paged decode attention's device time per
token DECODED in the traced window (a request's first token comes from the
prefill program and is left out); `<attention_metric>_roofline`: the least time
the chip could take for the FLOPs and bytes those tokens need at the PUBLISHED
widths (the configuration's arithmetic module, `decode_attention_token` over
each token's context) over that time. `kv_write_ms_per_token` / `_roofline`
(names the benchmark has): the in-place write's time per token written (decoded
or prefilled) against the module's `kv_write_token`. For the looped family both
count `n_loop * n_layer` cache layers a token (arithmetic_ouro.py): the same
kernels as the GPT's cells at the same head geometry, called n_loop times as
often a step at contexts of a few hundred tokens. A program without these
kernels, or a configuration without the `metrics` group, reports nothing."""


def read(run):
    scopes = run["load"]("metrics/serve_looped_scopes.py")
    got, cfg, tr = scopes.attribute(run), scopes.settings(run), run.get("traced") or {}
    if not got or not got["kernel"] or not scopes.named_enough(run, got, "serve_looped_kernels"):
        return None
    arith, own = run["load"]("arithmetic.py"), run["load"](cfg["arithmetic"])
    nd = max(1, run["trace_summary"]["n_devices"])
    itemsize, name = run["counters"]["kv_itemsize"], cfg["attention_metric"]
    out = {}
    contexts = tr.get("decode_contexts") or []
    ns = got["kernel"].get("attention", 0) / nd
    if ns and contexts:
        out[f"{name}_ms_per_token"] = ns / 1e6 / len(contexts)
        if run["peaks"] is not None:
            flops = bytes_ = 0.0
            for c in contexts:
                f, b = own.decode_attention_token(run["model"], c, itemsize)
                flops, bytes_ = flops + f, bytes_ + b
            share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
            out[f"{name}_roofline"] = share
            run["log"](f"{name}: {ns / 1e6:.1f} ms for {len(contexts)} decoded tokens (mean context "
                       f"{sum(contexts) / len(contexts):.0f}), {share:.2f} % of its roofline ({bound}-bound)")
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
