"""model step (serve): what the clients saw per request, from the same samples
as the end-to-end metrics — time per output token (median and 90th percentile,
nearest rank) and time to first token (mean and median) — and the host's
enqueue time per prefill chunk (the engine's `prefill.chunk` spans; a chunk's
device time is in the trace). In a closed loop these compose the throughput:
clients x tokens out / (time to first token + tokens out x time per token)."""

import statistics


def read(run):
    if run["kind"] != "serve":
        return None
    tpot, ttft = run["samples"]["tpot_s"], run["samples"]["ttft_s"]
    out = {"serve.tpot_ms_p50": 1e3 * statistics.median(tpot),
           "serve.tpot_ms_p90": 1e3 * run["percentile"](tpot, 90),
           "serve.ttft_ms_mean": 1e3 * statistics.fmean(ttft),
           "serve.ttft_ms_p50": 1e3 * run["percentile"](ttft, 50)}
    chunks = [d for n, _, d in run["spans"] if n == "prefill.chunk"]
    if chunks:
        out["prefill.chunk_ms_p50"] = 1e3 * statistics.median(chunks)
    return out
