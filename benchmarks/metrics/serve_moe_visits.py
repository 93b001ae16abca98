"""model step (serve), routed experts: how many times a decode step's grouped
matmul streams an expert's matrices for every held expert it has to touch.

`serve.moe_visits_per_expert_touched`: `moe.expert_visits` (the row blocks in
use, a decode step a routed layer: each block's grid steps fetch ONE expert's
matrices, `midgpt_tpu/kernels/grouped_matmul.py`) over `moe.experts_touched`
(held experts with at least one pair of an active slot, the same mean), both
from `ServeEngine.serve_counters()`. At 1.0 every touched expert is streamed
once a call; above it an expert's run took more than one row block (or a slot
no request holds selected an expert no active slot did: the blocks cover every
row of the step, the experts touched only the active slots' pairs).

A program whose counters hold no `moe.expert_visits` (every cell without routed
serving experts, and the parent of PR 50) reports nothing."""


def read(run):
    c = run["counters"]
    visits, touched = c.get("moe.expert_visits"), c.get("moe.experts_touched")
    if run["kind"] != "serve" or visits is None or not touched:
        return None
    return {"serve.moe_visits_per_expert_touched": float(visits) / float(touched)}
