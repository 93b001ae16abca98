"""step program: model FLOP/s utilization from the end-to-end throughput (all steps of the window over its length)
(arithmetic.flops_per_token; recomputed operations do not count), and the
device's busy time per optimizer step from the trace."""


def read(run):
    if run["kind"] != "train" or run["peaks"] is None:
        return None
    arith = run["load"]("arithmetic.py")
    tok_s = run["end_to_end"]["train_tokens_per_s"]
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    out = {"train.mfu": 100.0 * arith.flops_per_token(run["model"]) * tok_s / peak}
    ts = run.get("trace_summary")
    if ts and run["counters"]["traced_steps"]:
        out["step.device_ms"] = ts["busy_ns_mean"] / 1e6 / run["counters"]["traced_steps"]
    return out
