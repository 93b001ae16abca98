"""serving engine: how full the slots were (active slots / max_slots sampled
after every round), and the share of processed tokens that were prompt tokens
(eng.prefilled_tokens against delivered output tokens). The host's time per
decode round is read span by span: metrics/engine_dispatch.py
(`decode.dispatch_ms_p50`, `decode.host_post_ms_p50`) and metrics/engine_requests.py
(`engine.round_self_ms_p50`)."""

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
    return out
