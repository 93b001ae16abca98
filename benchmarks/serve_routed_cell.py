"""Serving cells of a family whose routed expert layers are held WHOLE (traffic
kind "serve_routed"): `serve_family_cell.py`'s run, engine, compared rows,
reference and control as they are, under a judgment of this kind's own: EVERY
compared row, against the reference under the nearest choice of experts.

Why (my chip runs, PR 46, `serve_trinity_mini_reason`: bf16 weights and pools
through 5 layers of which 4 route over 128 experts, ALL of them held; 32
compared rows a check; thirteen seeds for the program, five for the control;
PERF.md section 6 PR 46 has the table). The top-k is not continuous. At a token
the 8th and 9th of the 128 `s + expert_bias` of a routed layer lie ~0.004 apart
in the mean; the bf16 program's router input differs from the float32
reference's by rounding, so in one routed layer in fifteen the program picks
the 9th where the reference picks the 8th. Both are right, and the two rows of
logits then differ by a whole expert's weight (2.826 / 8 beside the shared
expert, NORMED before it is added): RMS 0.12-0.57 of the logits' standard
deviation, where a row with the same experts reads 0.95e-2 to 1.55e-2. With
every expert held, 2 to 12 rows of 32 in a check are of that kind (99 of 416),
and the reference with 8-bit matrices reads 0.12-0.60 on EVERY row: no limit on
a statistic over the rows as they stand lies between program and control. Nor
does the reference's own gap at the boundary tell which rows to leave out: rows
that did not flip have gaps down to 1e-4, rows that did up to 1.8e-3, and the
expert taken lay up to 5.4e-3 below the boundary, a gap that 31 rows of 416
exceed. (The review round's first judgment took the lower-quartile row, and was
blind to three rows in four.)

So a row is compared with what a right program must equal: the reference under
THE PROGRAM'S choice of experts at that token. The engine does not say what it
chose, and need not: the candidates are few. `nearest_choice` walks them best
first. At each routed layer it runs the row's token again (the reference's
`token_attention` / `token_scores` / `token_experts` against the kept streams
of the other tokens), takes the experts within `TIE` of the top-k's boundary,
and tries the reference's own choice, then every swap of one or two of them, in
order of how far below the boundary the swapped-in experts lie, summed over the
layers (a swap changes the later layers' scores: they are computed again under
it). It stops at the first choice under which the row is inside both limits, or
after `NODES` steps, and the row is judged by the nearest choice found.

    a row is right:  RMS <= ROW_RMS_TOLERANCE and largest logit <= ROW_MAX_TOLERANCE,
                     in units of the reference logits' standard deviation,
                     against the reference under the nearest choice within TIE
    the check:       every row is right (and nothing preempted, dropped or unreclaimed)

The readings that set the numbers. Under the nearest choice the program reads
0.95e-2 to 1.55e-2 on all 416 rows (99 of them under an exchange: 95 at one
layer, 4 at two), largest logit 0.043-0.073; the walk took at most 31 steps.
The control's 160 rows read 0.109-0.550 under THEIR nearest choice (the walk
takes the control's flips away too: what is left is the 8-bit matrices),
largest logit 0.48-2.9: every row of it is refused, by both limits. RMS limit
5e-2: 3.2 times over the program's largest row, 2.2 under the control's
smallest; largest logit 2.5e-1: 3.4 over, 1.9 under. `TIE` 2e-2: the 103
exchanges found gave up 9e-5 to 5.4e-3 of `s + expert_bias` (mean 1.0e-3, nine
in ten under 2.3e-3); at a mean of 1e-3 an exchange beyond 2e-2 is e^-20.

What it cannot see: a program whose router picks, among experts that lie within
`TIE` of the boundary, systematically the wrong one. The test suite compares
every row at float32, where nothing is exchanged (tests/test_trinity.py).

`trace_seconds` of the traffic file (this kind only): the traced extension's
length where the harness's own is too long for the profiler. This family's
expert loop gives the device trace ~1.0 M events a second and the profiler
keeps ~4.96 M: of the harness's 6 s the last 1.35 s came back EMPTY, so every
per-round and per-step figure divided what the trace kept by what the host
counted over the whole window (PERF.md section 6 PR 46, review round).

    python3 benchmarks/serve_routed_cell.py --workload <cell> --seed <n>

is the control (exit 0 = the program is correct AND the 8-bit reference is not).
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import os
import sys

import numpy as np

# Set from the readings in PERF.md section 6 PR 46 (seeds and values there).
ROW_RMS_TOLERANCE, ROW_MAX_TOLERANCE = 5e-2, 2.5e-1
TIE = 2e-2  # in s + expert_bias: an expert this close to the top-k's boundary may have been picked the other way
NODES = 96  # steps of the walk (a token through one layer under one choice) before a row is given up


def choices(selects, k, tie=None):
    """[(deficit, experts chosen (k,), swap)] at one token of one layer:
    `selects` (n_experts,) is what the router selects by. The top-k itself
    (deficit 0, swap ()), then every exchange of one or two of its members for
    as many from below, all of them within `tie` of each other; the deficit is
    how much of `selects` the exchange gives up."""
    tie = TIE if tie is None else tie
    order = np.argsort(-selects, kind="stable")
    top, rest = order[:k], order[k:]
    out_ = [int(a) for a in top if selects[a] - selects[rest[0]] < tie]  # may leave
    in_ = [int(b) for b in rest if selects[top[-1]] - selects[b] < tie]  # may enter
    found = [(0.0, np.sort(top), ())]
    for r in (1, 2):
        for gone in itertools.combinations(out_, r):
            for come in itertools.combinations(in_, r):
                if max(selects[list(gone)]) - min(selects[list(come)]) < tie:
                    chosen = np.sort(np.concatenate([np.setdiff1d(top, gone), come]))
                    found.append((float(selects[list(gone)].sum() - selects[list(come)].sum()), chosen, (gone, come)))
    return sorted(found, key=lambda c: c[0])


def nearest_choice(reference, params, cfg, streams, t, row, sd):
    """The walk of the module docstring for the token at position `t`, whose
    logits came out as `row` (V,): {"rms", "max": of the nearest choice found,
    "own_rms": under the reference's own choice, "swaps": ((layer, gone, come,
    deficit), ...) of the nearest, "gap": the smallest distance between the
    top-k's last and the next expert over the routed layers on the reference's
    own path, "nodes": steps taken}."""
    first, last, k = cfg["n_dense_layers"], cfg["n_layer"], cfg["moe_top_k"]
    best = {"rms": np.inf, "max": np.inf, "own_rms": np.nan, "swaps": (), "gap": np.inf}
    tick = itertools.count()  # ties in the heap are broken by age, never by comparing arrays
    heap = [(0.0, next(tick), first, streams[first][t], None, ())]  # (deficit, age, layer, its input, experts, swaps)
    nodes = 0
    while heap and nodes < NODES:
        deficit, _, i, x, chosen, swaps = heapq.heappop(heap)
        nodes += 1
        if chosen is not None:  # the routed layer before this one, under the choice that led here
            x = reference.token_experts(params.layers[i - 1], x, chosen, cfg)
        if i == last:
            d = np.asarray(reference.token_logits(params, x, cfg), np.float32) - row
            rms, worst = float(np.sqrt(np.mean(d ** 2)) / sd), float(np.max(np.abs(d)) / sd)
            if not swaps:
                best["own_rms"] = rms
            if rms < best["rms"]:
                best.update(rms=rms, max=worst, swaps=swaps)
            if rms <= ROW_RMS_TOLERANCE and worst <= ROW_MAX_TOLERANCE:
                break
            continue
        h = reference.token_attention(params.layers[i], streams[i], x, t, cfg, i)
        selects = np.asarray(reference.token_scores(params.layers[i], h, cfg))
        if not swaps:
            ranked = np.sort(selects)[::-1]
            best["gap"] = min(best["gap"], float(ranked[k - 1] - ranked[k]))
        for cost, experts, swap in choices(selects, k):
            here = swaps + ((i, *swap, round(cost, 5)),) if swap else swaps
            heapq.heappush(heap, (deficit + cost, next(tick), i + 1, h, experts, here))
    return dict(best, nodes=nodes)


def judge(found):
    """Whether every row is right under its nearest choice (`nearest_choice`'s results)."""
    return all(np.isfinite(f["rms"]) and f["rms"] <= ROW_RMS_TOLERANCE and f["max"] <= ROW_MAX_TOLERANCE for f in found)


def _said(name, found, positions, log):
    ok = judge(found)
    log(f"{name}: row by row (position: rms under the reference's own choice -> under the nearest within TIE {TIE:g}; "
        f"the own path's smallest gap at the boundary; swaps (layer, gone, come, deficit)):")
    for pos, f in zip(positions, found):
        log(f"{name}:   {pos:5d}: {f['own_rms']:.3e} -> {f['rms']:.3e} (largest logit {f['max']:.3e}); gap {f['gap']:.5f}; "
            f"{f['nodes']} steps; {list(f['swaps']) or 'the same experts'}")
    swapped = [f for f in found if f["swaps"]]
    log(f"{name}: error/std of the reference logits over {len(found)} rows, each under its nearest choice: largest row rms "
        f"{max(f['rms'] for f in found):.3e} (limit {ROW_RMS_TOLERANCE:.1e}), largest logit {max(f['max'] for f in found):.3e} "
        f"(limit {ROW_MAX_TOLERANCE:.1e}); {len(swapped)} rows with a tie decided the other way, deficits up to "
        f"{max([s[-1] for f in swapped for s in f['swaps']], default=0.0):.5f} (TIE {TIE:g}) -> {'ok' if ok else 'NOT CORRECT'}")
    return ok


def _with_judgment(family):
    """`serve_family_cell.py` with its `check_engine_path` replaced by this
    kind's: the same engine, rows, reference and served conditions, every row
    judged under its nearest choice."""
    import jax.numpy as jnp

    def check_engine_path(ctx, mc, params, es, check, control=None):
        seqs, rows, got, counted = family.engine_logits(ctx, mc, params, es, check)
        reclaimed = [v for k, v in counted.items() if k.startswith("kv.") and k.endswith("_pages_reclaimed")]
        ctx.log(f"correctness: ServeEngine ({counted['attn']}; prompts of {counted['prompts']} tokens served incl. "
                f"{int(check['decode_rounds'])} decode rounds of {es['decode_chunk']}, up to {counted['live_max']} of "
                f"{es['max_slots']} slots live, chunks of {es['prefill_chunk']}, pages of {es['page_size']}, "
                f"{es['cache_dtype']} pools; window pages reclaimed {reclaimed}, preemptions {counted['preemptions']}, "
                f"moe.dropped {counted.get('moe.dropped', 0)}) vs float32 reference logits of the same sequences")
        reference = ctx.load(os.path.join("configs", ctx.cell["config"] + "_reference.py"))
        cfg = dataclasses.asdict(mc)
        T = -(-max(len(s) for s in seqs) // 128) * 128  # every sequence padded to one length: one compile a layer
        found, positions, n = {"program": [], "control": []}, [], 0
        for s, r in zip(seqs, rows):
            tokens, streams = jnp.asarray(np.pad(s, (0, T - len(s)))), []
            want = np.asarray(reference.logits(params, tokens, cfg, rows=r, keep=streams), np.float32)
            theirs = {"program": got[n:n + len(r)]}
            if control is not None:
                theirs["control"] = np.asarray(reference.logits(params, tokens, cfg, rows=r, round_to=control), np.float32)
            for who, block in theirs.items():
                found[who] += [nearest_choice(reference, params, cfg, streams, int(t), row, float(np.std(want)))
                               for t, row in zip(r, block)]
            positions += [int(t) for t in r]
            n += len(r)
        served = (_said("program", found["program"], positions, ctx.log) and counted["preemptions"] == 0
                  and not counted.get("moe.dropped", 0) and (not reclaimed or max(reclaimed) > 0))
        if control is None:
            return served, None
        ctx.log(f"control: the reference with its matrices rounded to {np.dtype(control).name} in the program's place")
        return served, _said("control", found["control"], positions, ctx.log)

    family.check_engine_path = check_engine_path
    return family


def run(ctx) -> dict:
    if ctx.traffic.get("trace_seconds"):
        ctx.trace_seconds = min(ctx.trace_seconds, float(ctx.traffic["trace_seconds"]))
    return _with_judgment(ctx.load("serve_family_cell.py")).run(ctx)


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    return _with_judgment(harness.load_module(os.path.join(here, "serve_family_cell.py"))).main()


if __name__ == "__main__":
    raise SystemExit(main())
