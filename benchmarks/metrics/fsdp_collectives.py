"""parallelism: time in all-gather / all-reduce / reduce-scatter operations
per optimizer step (mean over the chips), and the part of it during which no
other operation ran on that chip (exposed). Nothing to read on one chip."""


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or run["chips"] < 2 or not run["counters"]["traced_steps"]:
        return None
    k = run["counters"]["traced_steps"]
    return {"fsdp.collective_ms_per_step": ts["collective_ns_mean"] / 1e6 / k,
            "fsdp.exposed_collective_ms_per_step": ts["exposed_collective_ns_mean"] / 1e6 / k}
