"""kernels: the flash-attention Pallas kernels (forward and the fused
backward) in the training step. Time is the summed device duration of the
kernel events per optimizer step; the roofline share divides the least time
the chip could take for the FLOPs and bytes the algorithm needs
(arithmetic.flash_attention_step) by that time. Compute-bound at T=1024."""

# The Mosaic custom calls of kernels/flash_attention.py as the v5e trace names
# them (my chip runs, PR 23): the op takes the innermost scope as its name. On
# one chip that is the jax.named_scope "attn" of models/gpt.py block_apply
# (`attn.<n>`: 24 call sites in the 124M step, 12 forward and 12 fused
# backward); across chips the kernel runs inside ops/attention.py
# flash_attention_sharded's shard_map (`shard_map.<n>`). Either way the HLO
# instruction is a custom-call; other ops of those scopes are fusions.
NAME = r"^(attn|shard_map)\.\d+$"
INFO = {"hlo": r"custom-call\("}


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or not run["counters"]["traced_steps"]:
        return None
    reduce, arith = run["load"]("reduce.py"), run["load"]("arithmetic.py")
    ns, _ = reduce.kernel_time(ts, ts["trace"], NAME, INFO)
    if ns == 0:
        return None
    k = run["counters"]["traced_steps"]
    out = {"flash_attention_ms_per_step": ns / 1e6 / k}
    if run["peaks"] is not None:
        flops, bytes_ = arith.flash_attention_step(run["model"], run["counters"]["n_sequences_per_step"])
        share, bound = arith.roofline_share(flops / run["chips"], bytes_ / run["chips"], ns / 1e9 / k, run["peaks"])
        out["flash_attention_roofline"] = share
        run["log"](f"flash attention: {ns / 1e6 / k:.2f} ms/step/chip, {share:.2f} % of its roofline ({bound}-bound)")
    return out
