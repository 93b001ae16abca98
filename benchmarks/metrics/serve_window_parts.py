"""model step (serve), steadied: where the traffic file names `window_parts`,
the MEDIAN over the window's equal parts of each part's own figure of the
three client-side end-to-end metrics (`reduce.window_parts`). The end-to-end
metrics stay what they are, all the work over all the window and the tail of
all requests, so a host stall of a second moves them; it sits in one part, and
these medians do not move. Read the two side by side: an end-to-end metric that
moved while its part median stayed met a stall, not a change. A host that is
slower for minutes moves both (PERF.md section 6, PR 49). A cell whose traffic
file has no `window_parts` reports nothing here."""

import statistics


def read(run):
    parts = run.get("window_parts")  # serve_cell.py's, cut once for its log line and for here
    if run["kind"] != "serve" or not parts:
        return None
    return {"serve.tokens_per_s_parts_p50": statistics.median(parts["tokens_per_s"]),
            "serve.ttft_ms_mean_parts_p50": statistics.median(parts["ttft_ms_mean"]),
            "serve.tpot_ms_p90_parts_p50": statistics.median(parts["tpot_ms_p90"])}
