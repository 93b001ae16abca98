"""kernels, hybrid family: the flash-attention Pallas kernels as the MLA layers
call them. The v5e trace names a Mosaic custom call after its innermost scope
(PERF.md §7), and models/kimi_linear.py opens `mla` around the call and nothing
inside it, so the kernels are `mla.<n>` custom-calls (the GPT's are `attn.<n>`,
which flash_attention.py reads: neither reader sees the other's). Time is the
summed device duration per optimizer step; the roofline share divides the
least time the chip could take for the FLOPs and bytes the attention needs at
the PUBLISHED head widths (arithmetic_kimi_linear.mla_attention_step: q/k 192,
v 128) by that time: the channels the program pads to reach one kernel width
are time it spends and no work it is credited with."""

NAME = r"^mla\.\d+$"
INFO = {"hlo": r"custom-call\("}


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or not run["counters"]["traced_steps"]:
        return None
    reduce = run["load"]("reduce.py")
    ns, _ = reduce.kernel_time(ts, ts["trace"], NAME, INFO)
    if ns == 0:
        return None
    k = run["counters"]["traced_steps"]
    out = {"mla_attention_ms_per_step": ns / 1e6 / k}
    if run["peaks"] is not None:
        arith = run["load"]("arithmetic.py")
        flops, bytes_ = run["load"]("arithmetic_kimi_linear.py").mla_attention_step(
            run["model"], run["counters"]["n_sequences_per_step"])
        share, bound = arith.roofline_share(flops / run["chips"], bytes_ / run["chips"], ns / 1e9 / k, run["peaks"])
        out["mla_attention_roofline"] = share
        run["log"](f"mla attention: {ns / 1e6 / k:.2f} ms/step/chip, {share:.2f} % of its roofline ({bound}-bound)")
    return out
