"""model step (serve): what the clients saw per request, from the same samples
as the end-to-end metrics: time per output token (median and 90th percentile,
nearest rank) and the median time to first token. The MEAN time to first
token is `req.queue_ms_mean` + `req.prefill_ms_mean` (metrics/engine_requests.py),
and the cell prints it on its `ttft ms mean ... p50 ... p90 ... max` line. In a
closed loop these compose the throughput: clients x tokens out / (time to
first token + tokens out x time per token)."""

import statistics


def read(run):
    if run["kind"] != "serve":
        return None
    tpot, ttft = run["samples"]["tpot_s"], run["samples"]["ttft_s"]
    return {"serve.tpot_ms_p50": 1e3 * statistics.median(tpot),
            "serve.tpot_ms_p90": 1e3 * run["percentile"](tpot, 90),
            "serve.ttft_ms_p50": 1e3 * run["percentile"](ttft, 50)}
