"""kernels: the paged decode-attention Pallas kernel (kernels/
attention_template.py through decode_attention.py). Time is the summed device
duration of its events in the traced window per output token delivered in that
window; the roofline share divides the least time the chip could take for the
bytes and FLOPs those tokens need (arithmetic.paged_attention_token summed
over each token's context) by the kernel time. Memory-bound: each token reads
the K and V of its whole context."""

NAME = r"^closed_call\.\d+$"
INFO = {"hlo": r"custom-call\("}


def read(run):
    ts = run.get("trace_summary")
    tr = run.get("traced") or {}
    if run["kind"] != "serve" or not ts or not tr.get("tokens"):
        return None
    reduce, arith = run["load"]("reduce.py"), run["load"]("arithmetic.py")
    ns, _ = reduce.kernel_time(ts, ts["trace"], NAME, INFO)
    if ns == 0:
        return None
    out = {"paged_attention_ms_per_token": ns / 1e6 / tr["tokens"]}
    if run["peaks"] is not None:
        flops = bytes_ = 0.0
        for ctx_len in tr["contexts"]:
            f, b = arith.paged_attention_token(run["model"], ctx_len, run["counters"]["kv_itemsize"])
            flops, bytes_ = flops + f, bytes_ + b
        share, bound = arith.roofline_share(flops, bytes_, ns / 1e9, run["peaks"])
        out["paged_attention_roofline"] = share
        run["log"](f"paged attention: {ns / 1e6:.1f} ms for {tr['tokens']} tokens, "
                   f"{share:.2f} % of its roofline ({bound}-bound)")
    return out
