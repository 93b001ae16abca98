"""train loop: the median and the longest step of the window (a host stall
shows in the longest step; the end-to-end rate is the whole window's). The
host's time to produce a step's batch is `train.feed_ms_p50`
(metrics/train_feed.py), from the program's own span."""

import statistics


def read(run):
    if run["kind"] != "train":
        return None
    steps = run["samples"]["step_s"]
    return {"train.step_ms_max": 1e3 * max(steps),
            "train.step_ms_p50": 1e3 * statistics.median(steps)}
