"""train loop: the median and the longest step of the window (a host stall
shows in the longest step; the end-to-end rate is the whole window's), and the
host's batch assembly + device_put per step (the benchmark's own spans around
dataset.batch and make_global_batch)."""

import statistics


def read(run):
    if run["kind"] != "train":
        return None
    steps = run["samples"]["step_s"]
    per_step = {}
    for name, start, dur in run["spans"]:
        if name in ("bench.data", "bench.put"):
            per_step.setdefault(name, []).append(dur)
    batch = [a + b for a, b in zip(per_step.get("bench.data", []), per_step.get("bench.put", []))]
    out = {
        "train.step_ms_max": 1e3 * max(steps),
        "train.step_ms_p50": 1e3 * statistics.median(steps),
    }
    if batch:
        out["data.batch_ms_p50"] = 1e3 * statistics.median(batch)
    return out
