"""kernels, a family with kinds of layers: the Pallas kernels this family's
serving programs call with shapes the GPT's never give them. The paged decode
attention of the GLOBAL layers (kernels/attention_template.py with K 192 / V 128
and 16 query rows a K/V head; the window layers' decode and every prefill
attention are XLA) and the in-place K/V write of both kinds
(kernels/paged_write.py with K and V pages of different lanes). They are found
as the Mosaic custom calls under `attn_global` / `kv_write` in the serving
programs' texts (serve_family_scopes.attribute), not by a trace name.

`global_decode_attention_ms_per_token`: its device time per token DECODED in
the traced window (a request's first token comes from the prefill program and
is left out); `_roofline`: the least time the chip could take for the bytes and
FLOPs those tokens need at the published widths
(arithmetic_mimo_v2.decode_attention_token over each token's context) over
that time. `kv_write_ms_per_token` / `_roofline`: the write's time per token
written (decoded or prefilled), against the bytes of the rows themselves. A
program without these kernels (every GPT cell, the parent of PR 30) reports
nothing, and neither does a configuration without a `layer_pattern`: the
arithmetic here is that of a family whose layers' kinds the pattern names
(arithmetic_mimo_v2.layer_kinds), and a family that says its kinds another way
brings readers of its own (serve_kinds_kernels.py, serve_sparse_kernels.py)."""


def read(run):
    if "layer_pattern" not in run["model"]:
        return None
    got = run["load"]("metrics/serve_family_scopes.py").attribute(run)
    tr = run.get("traced") or {}
    if not got or not got["kernel"] or got["known"] < 0.98 * got["total"]:
        return None
    arith, am = run["load"]("arithmetic.py"), run["load"]("arithmetic_mimo_v2.py")
    nd = max(1, run["trace_summary"]["n_devices"])
    itemsize = run["counters"]["kv_itemsize"]
    out = {}
    contexts = tr.get("decode_contexts") or []
    ns = got["kernel"].get("attention", 0) / nd
    if ns and contexts:
        out["global_decode_attention_ms_per_token"] = ns / 1e6 / len(contexts)
        if run["peaks"] is not None:
            flops = bytes_ = 0.0
            for c in contexts:
                f, b = am.decode_attention_token(run["model"], "global", c, itemsize)
                flops, bytes_ = flops + f, bytes_ + b
            share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
            out["global_decode_attention_roofline"] = share
            run["log"](f"global decode attention: {ns / 1e6:.1f} ms for {len(contexts)} decoded tokens, "
                       f"{share:.2f} % of its roofline ({bound}-bound)")
    ns = got["kernel"].get("kv_write", 0) / nd
    written = len(contexts) + tr.get("prefilled_tokens", 0)
    if ns and written:
        out["kv_write_ms_per_token"] = ns / 1e6 / written
        if run["peaks"] is not None:
            f, b = am.kv_write_token(run["model"], itemsize)
            share, bound = arith.roofline_share(f * written, b * written, ns / 1e9, run["peaks"])
            out["kv_write_roofline"] = share
            run["log"](f"kv write: {ns / 1e6:.1f} ms for {written} tokens written, {share:.2f} % of its roofline ({bound}-bound)")
    return out
