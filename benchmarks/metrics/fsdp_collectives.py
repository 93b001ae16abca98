"""parallelism: the time per optimizer step (mean over the chips) during which
a collective (all-gather, all-reduce, reduce-scatter, all-to-all,
collective-permute) ran and no other operation ran on that chip (exposed). The
collectives' time IN FLIGHT is not reported: under the authored schedule the
permutes overlap the backward, and their sum is no cost (PERF.md section 6,
PR 44). Nothing to read on one chip."""


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or run["chips"] < 2 or not run["counters"]["traced_steps"]:
        return None
    k = run["counters"]["traced_steps"]
    return {"fsdp.exposed_collective_ms_per_step": ts["exposed_collective_ns_mean"] / 1e6 / k}
