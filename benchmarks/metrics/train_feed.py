"""train loop, from spans the PROGRAM opens: host time to feed one step, i.e.
batch assembly (`data.batch`, opened in data/dataset.py Dataset.batch) plus the
puts of x and y (`data.put`, opened in parallel/data.py make_global_batch).
Both go to the process-global flight recorder (`midgpt_tpu.obs.flight_recorder()`,
on time.perf_counter like the benchmark's own spans), so the program's loop and
the benchmark's copy of it are timed by the same spans. A step's feed is one
`data.batch` and the `data.put`s up to the next one. The window is placed from
`run["spans"]`: from their earliest start, for `run["window_s"]`. A program
that opens no such spans (the parent of PR 24) reports nothing."""

import statistics


def read(run):
    if run["kind"] != "train" or not run["spans"]:
        return None
    from midgpt_tpu.obs import flight_recorder

    lo = min(s for _, s, _ in run["spans"])
    hi = lo + run["window_s"]
    feeds = []
    for e in flight_recorder().tracer.events():
        if e[0] != "X" or not lo <= e[4] < hi:
            continue
        if e[1] == "data.batch":
            feeds.append(e[5])
        elif e[1] == "data.put" and feeds:
            feeds[-1] += e[5]
    if not feeds:
        run["log"]("train_feed: the flight recorder holds no data.batch span in the window "
                   "(a program from before PR 24?); train.feed_ms_p50 left out")
        return None
    return {"train.feed_ms_p50": 1e3 * statistics.median(feeds)}
