"""kernels, hybrid family: the KDA Pallas kernels (midgpt_tpu/kernels/kda.py,
one call forward and one backward a layer) as the step program calls them. The
v5e trace names a Mosaic custom call after the innermost scope or jit on its
path (PERF.md section 7); kernels/kda.py opens `kda_scan`, the scope
models/kimi_linear.py opens around `kda_chunked`, again around each
`pallas_call`, so the kernels are the custom calls named `kda_scan` or
`kda_scan.<n>` (the compiler leaves the first of a name without a number; the
flash kernels of the MLA layers are `mla.<n>`, which mla_attention.py reads:
neither reader sees the other's). Time is the summed device duration per
optimizer step, forward (twice under whole-layer remat) and backward together.
It says that the kernels ran and how much of `step.kda_scan_ms` they are; a
program whose chunked recurrence is plain XLA ops (off the TPU; before the
kernels) has no such call and reports nothing. No roofline share: what counts
as the kernels' work is not settled here."""

NAME = r"^kda_scan(\.\d+)?$"
INFO = {"hlo": r"custom-call\("}


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or not run["counters"]["traced_steps"]:
        return None
    ns, n = run["load"]("reduce.py").kernel_time(ts, ts["trace"], NAME, INFO)
    if ns == 0:
        return None
    k = run["counters"]["traced_steps"]
    run["log"](f"kda kernels: {ns / 1e6 / k:.2f} ms/step/chip in {n} call(s) of the traced window")
    return {"kda_kernel_ms_per_step": ns / 1e6 / k}
