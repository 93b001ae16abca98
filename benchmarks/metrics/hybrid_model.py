"""step program and experts, hybrid family: `train.mfu_hybrid`, the share of
the chips' bf16 peak that the end-to-end rate is, with the FLOPs a token of
arithmetic_kimi_linear.flops_per_token (matmuls of the parameters a token
multiplies, routed experts by the token-expert pairs the program COUNTED as
assigned to experts held here, MLA's causal scores, the KDA recurrence;
recomputation not counted); `moe.load_max_over_mean`, the most loaded held
expert over the mean of the held, worst layer; and `moe.overflowed`, the routed
layers whose pairs did not fit the dispatch buffer and took ops/moe.py's exact
path (~10x the expert time; 0 in a healthy step). All from the program's own
counters (models/kimi_linear.py route_stats, which the train loop logs), as the
cell read them on the last step's batch after the window: an overflow in an
EARLIER step of the window shows as `train.step_ms_max`, not here. A run whose
counters hold no `moe.assignments_here` (every GPT cell) reports nothing."""


def read(run):
    c = run["counters"]
    if run["kind"] != "train" or "moe.assignments_here" not in c:
        return None
    out = {"moe.load_max_over_mean": float(c["moe.load_max_over_mean"]), "moe.overflowed": float(c["moe.overflowed"])}
    if run["peaks"] is not None:
        arith = run["load"]("arithmetic_kimi_linear.py")
        pairs_a_token = c["moe.assignments_here"] / c["moe.tokens"]
        flops = arith.flops_per_token(run["model"], pairs_a_token)
        peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
        out["train.mfu_hybrid"] = 100.0 * flops * run["end_to_end"]["train_tokens_per_s"] / peak
        run["log"](f"hybrid mfu: {flops / 1e9:.3f} GFLOP a token with {pairs_a_token:.3f} routed pairs a token "
                   f"computed here ({arith.flops_per_token(run['model']) / 1e9:.3f} at the balanced share)")
    return out
