"""kernels, a family served from a LATENT cache: the Pallas kernels its serving
programs call with shapes no other family gives them. The ABSORBED paged decode
attention (kernels/attention_template.py with `v_lanes`: 128 query rows against
the pool's one head of 640 lanes, the values a view of the same page's leading
512) and the in-place write of a latent row (kernels/paged_write.py with one
pool). They are found as the Mosaic custom calls under `attn_latent` /
`kv_write` in the serving programs' texts (serve_latent_scopes.attribute), not
by a trace name; prefill's attention is XLA.

`latent_decode_attention_ms_per_token`: its device time per token DECODED in the
traced window (a request's first token comes from the prefill program and is
left out); `_roofline`: the least time the chip could take for the FLOPs and
bytes those tokens need at the PUBLISHED widths
(arithmetic_pangu_ultra.latent_decode_attention_token over each token's
context: 2 x 128 x c x (576 + 512) FLOP, the c x 576 latent values read once)
over that time: at 242 FLOP / B the two bounds lie within 1 % of each other on
the v5e. `kv_write_ms_per_token` / `_roofline` (names the benchmark has): the
write's time per token written (decoded or prefilled), against the 576 values
of the row. A program without these kernels reports nothing."""


def read(run):
    got = run["load"]("metrics/serve_latent_scopes.py").attribute(run)
    tr = run.get("traced") or {}
    if not got or not got["kernel"] or got["known"] < 0.98 * got["total"]:
        return None
    arith, ap = run["load"]("arithmetic.py"), run["load"]("arithmetic_pangu_ultra.py")
    nd = max(1, run["trace_summary"]["n_devices"])
    itemsize = run["counters"]["kv_itemsize"]
    out = {}
    contexts = tr.get("decode_contexts") or []
    ns = got["kernel"].get("attention", 0) / nd
    if ns and contexts:
        out["latent_decode_attention_ms_per_token"] = ns / 1e6 / len(contexts)
        if run["peaks"] is not None:
            flops = bytes_ = 0.0
            for c in contexts:
                f, b = ap.latent_decode_attention_token(run["model"], c, itemsize)
                flops, bytes_ = flops + f, bytes_ + b
            share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
            out["latent_decode_attention_roofline"] = share
            run["log"](f"latent decode attention: {ns / 1e6:.1f} ms for {len(contexts)} decoded tokens, "
                       f"{share:.2f} % of its roofline ({bound}-bound)")
    ns = got["kernel"].get("kv_write", 0) / nd
    written = len(contexts) + tr.get("prefilled_tokens", 0)
    if ns and written:
        out["kv_write_ms_per_token"] = ns / 1e6 / written
        if run["peaks"] is not None:
            f, b = ap.kv_write_token(run["model"], itemsize)
            share, bound = arith.roofline_share(f * written, b * written, ns / 1e9, run["peaks"])
            out["kv_write_roofline"] = share
            run["log"](f"latent write: {ns / 1e6:.1f} ms for {written} tokens written, {share:.2f} % of its roofline ({bound}-bound)")
    return out
