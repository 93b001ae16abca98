"""serving engine: host time per decode round (the engine's own
`decode.dispatch` + `decode.host_post` spans), how full the slots were (active
slots / max_slots sampled after every round), and the share of processed
tokens that were prompt tokens (eng.prefilled_tokens against delivered output
tokens)."""

import statistics


def read(run):
    if run["kind"] != "serve":
        return None
    c = run["counters"]
    out = {}
    occ = run["samples"]["occupancy"]
    if occ:
        out["engine.occupancy"] = 100.0 * statistics.fmean(occ) / c["max_slots"]
    if c["prefilled_tokens"] + c["output_tokens"]:
        out["engine.prefill_token_share"] = (
            100.0 * c["prefilled_tokens"] / (c["prefilled_tokens"] + c["output_tokens"]))
    disp = [d for n, _, d in run["spans"] if n == "decode.dispatch"]
    post = [d for n, _, d in run["spans"] if n == "decode.host_post"]
    if disp and len(disp) == len(post):
        out["engine.round_host_ms_p50.serve"] = 1e3 * statistics.median(a + b for a, b in zip(disp, post))
    return out
