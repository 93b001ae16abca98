"""device: idle share of the traced window (1 - union of device-op intervals /
window, mean over the chips) and peak HBM on the fullest chip after the
window. Named by the traffic kind because a per-layer metric names ONE
end-to-end metric it should move, and train and serve cells report different
ones."""


def read(run):
    kind = run["kind"]
    out = {}
    ts = run.get("trace_summary")
    if ts:
        out[f"{kind}.device_idle_share"] = 100.0 * (1.0 - ts["busy_ns_mean"] / ts["window_ns"])
    peak = run["device"]["memory_peak_bytes"]
    if peak:
        out[f"{kind}.peak_hbm_gb"] = peak / 1e9
    return out
