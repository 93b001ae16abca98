"""Serving cells of a family whose full-attention layers SELECT what they attend
to (traffic kind "serve_sparse": an indexer's exact top-k over the cached
tokens): `serve_family_cell.py`'s run, engine, compared rows, `judge` and 8-bit
control as they are, under limits of this family's own, and with a SECOND
control that the same limits must refuse: the reference WITHOUT the selection.

Why a second control. A program that does not select (dense attention over the
whole context), or that selects something else (the newest k), is a different
model that rounds like the right one: the 8-bit control says nothing about it.
`reference.logits(..., select="dense")` is that model: every causal position
attended. It is judged on the compared rows whose context passes `index_topk`
(the only rows where the selection drops anything; on the others it IS the
reference), by the same `judge` and the same limits, and must come out NOT
correct. A selected position traded at the boundary for its neighbour (the
program's bf16 index scores against the reference's float32) moves a row by
about 1 / index_topk of one value and needs no judgment of its own.

Why own limits (my chip runs, PR 51, `serve_dots3_note_longctx`: bf16 weights and
pools through 5 layers of which 4 route over 16 held experts of 256, the
indexer's top-2,048 at two of them; 32 compared rows a check, 16 of them past
index_topk; the check's prompts are the traffic file's `check.prompts`, so the
readings are the same whatever the window's traffic; PERF.md section 6 PR 51 has
each call). The program, over the 31 seeds of calls 3-10: RMS 1.06e-2 to 1.71e-2
of the reference logits' standard deviation (the rows past index_topk alone
1.19e-2 to 2.27e-2: the boundary trades of a selection made from bf16 inputs).
The two controls, over the 9 seeds of calls 3, 6, 7 and 9 (six of them in call
7, each beside the program's reading on the same seed): the reference with
8-bit matrices against itself RMS 1.09e-1 to 1.11e-1; the reference WITHOUT the
selection on the 16 rows past index_topk RMS 1.007e-1 to 1.057e-1. RMS limit
4.1e-2, the geometric middle: 2.40 times over the program's largest reading,
2.46 times under the controls' smallest. (Calls 3-7 printed other limits while
they were being set, 5.0e-2, 3.3e-2, 4.2e-2: a line prints its limit beside the
reading, and no reading of any call lies between 1.8e-2 and 1.0e-1.)

NO largest-logit limit (as `serve_latent_cell.py`, and for its reason). Nine
checks of 31 read a largest logit of 0.067-0.099; in the other 22 ONE row had
an expert selected the other way at a near tie of the router and read
0.137-0.234 (that check's RMS 1.19e-2 to 1.71e-2 for 1.06e-2 to 1.17e-2);
`mimo_v2`, whose routed weight and held share are this family's, has read such
rows up to 0.25. The controls' largest logit is 0.53-0.64: no value lies
twice over the one and twice under the other. The RMS, which is over all rows,
is what tells a program that is off everywhere (or selects wrongly) from one
that decided a tie the other way.

    python3 benchmarks/serve_sparse_cell.py --workload <cell> --seed <n>

runs both controls (exit 0 = the program is correct AND the 8-bit reference is
not AND the dense-attention reference is not: by the RMS).
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np

# error / std of the reference logits over the compared rows: RMS, and the largest compared logit.
# Set from the chip readings above (program over its seeds; both controls).
RMS_TOLERANCE, MAX_TOLERANCE = 4.1e-2, float("inf")


def _with_judgment(family):
    """`serve_family_cell.py` with this kind's limits and its `check_engine_path`
    replaced by one that adds the dense-attention control."""
    import jax.numpy as jnp

    family.RMS_TOLERANCE, family.MAX_TOLERANCE = RMS_TOLERANCE, MAX_TOLERANCE

    def dense_logits(ctx, params, mc, seqs, rows):
        """The reference WITHOUT the selection at `rows` of each sequence (`family.reference_logits`'s padding)."""
        reference = ctx.load(os.path.join("configs", ctx.cell["config"] + "_reference.py"))
        cfg = dataclasses.asdict(mc)
        T = -(-max(len(s) for s in seqs) // 128) * 128
        out = [reference.logits(params, jnp.asarray(np.pad(s, (0, T - len(s)))), cfg, rows=r, select="dense")
               for s, r in zip(seqs, rows)]
        return np.concatenate([np.asarray(o, np.float32) for o in out])

    def check_engine_path(ctx, mc, params, es, check, control=None):
        seqs, rows, got, counted = family.engine_logits(ctx, mc, params, es, check)
        want = family.reference_logits(ctx, params, mc, seqs, rows)
        rms, worst, ok = family.judge(got, want)
        past = np.concatenate(rows) + 1 > mc.index_topk  # rows whose context the selection cuts
        reclaimed = [v for k, v in counted.items() if k.startswith("kv.") and k.endswith("_pages_reclaimed")]
        served = (ok and counted["preemptions"] == 0 and not counted.get("moe.dropped", 0)
                  and (not reclaimed or max(reclaimed) > 0))
        ctx.log(f"correctness: ServeEngine ({counted['attn']}; prompts of {counted['prompts']} tokens served "
                f"incl. {int(check['decode_rounds'])} decode rounds of {es['decode_chunk']}, up to {counted['live_max']} of "
                f"{es['max_slots']} slots live, chunks of {es['prefill_chunk']}, pages of {es['page_size']}, "
                f"{es['cache_dtype']} pools; window pages reclaimed {reclaimed}, preemptions {counted['preemptions']}, "
                f"moe.dropped {counted.get('moe.dropped', 0)}; {int(past.sum())} of {len(past)} rows past index_topk "
                f"{mc.index_topk}; decoded tokens kept {counted.get('dsa.rows_selected', 0)} of {counted.get('dsa.keys_scored', 0)} "
                f"keys scored) vs float32 reference logits of the same sequences, {got.shape[0]} rows: error/std rms {rms:.3e} "
                f"(limit {RMS_TOLERANCE:.1e}), max {worst:.3e} (limit {MAX_TOLERANCE:.1e}) -> {'ok' if served else 'NOT CORRECT'}")
        if past.any():
            p_rms, p_worst, _ = family.judge(got[past], want[past])
            ctx.log(f"correctness, the {int(past.sum())} rows past index_topk alone: error/std rms {p_rms:.3e}, max {p_worst:.3e}")
        if control is None:
            return served, None
        c_rms, c_worst, c_ok = family.judge(family.reference_logits(ctx, params, mc, seqs, rows, round_to=control), want)
        ctx.log(f"control 1: the reference with its matrices rounded to {np.dtype(control).name} in the program's place, "
                f"same rows and limits: error/std rms {c_rms:.3e}, max {c_worst:.3e} -> {'ok' if c_ok else 'NOT CORRECT'}")
        if not past.any():
            ctx.log("control 2: no compared row's context passes index_topk: the check's prompts cannot tell a program "
                    "that does not select: counted as a control that PASSED")
            return served, True
        dense = dense_logits(ctx, params, mc, seqs, rows)
        d_rms, d_worst, d_ok = family.judge(dense[past], want[past])
        ctx.log(f"control 2: the reference WITHOUT the selection (dense attention) in the program's place, on the "
                f"{int(past.sum())} rows past index_topk, same limits: error/std rms {d_rms:.3e}, max {d_worst:.3e} -> "
                f"{'ok' if d_ok else 'NOT CORRECT'}")
        return served, c_ok or d_ok  # a control that passes fails the entry point

    family.check_engine_path = check_engine_path
    return family


def run(ctx) -> dict:
    return _with_judgment(ctx.load("serve_family_cell.py")).run(ctx)


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    return _with_judgment(harness.load_module(os.path.join(here, "serve_family_cell.py"))).main()


if __name__ == "__main__":
    raise SystemExit(main())
