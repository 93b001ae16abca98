"""Serving cells of a family served from a LATENT cache (traffic kind
"serve_latent"): `serve_family_cell.py`'s run, check and control as they are
(the loop, the window, the engine built like the timed one, the compared rows,
`judge`), under limits of this family's own. Nothing else differs, so this file
sets the limits and hands over.

Why own limits (my chip runs, PR 39, `serve_pangu_ultra_longctx`: bf16 weights
and pool through 5 layers of which 4 route, 32 compared rows a check, eleven
seeds). A row's error is one of two kinds. Most rows read RMS 1.6e-2 to 2.1e-2
of the reference logits' std and a largest logit of 0.06-0.09, on every seed.
In two checks of three, one or two rows had an expert SELECTED THE OTHER WAY at
a near tie (the router's 8th and 9th scores of 256 lie ~0.055 apart, the bf16
forward's activations differ from the float32 reference's by ~2 %; one flip in
eight involves a held expert): such a row reads RMS 0.14-0.6 and a largest
logit of 0.5-1.8, because here a routed expert weighs 2.5 / 8 beside the shared
expert, the sum is NORMED before it is added, and with the seeded router
balanced (models/pangu_ultra.py POST_ATTN_NORM_INIT) the MLP stream is most of
the residual. (models/mimo_v2.py's routed weight is 1 / 8 with no norm after
it: its flipped rows read 0.12-0.25, which `serve_family_cell.py`'s 5e-1 was
set over.) Over the seeds run the program's check read RMS 1.8e-2 (no flip) to
1.15e-1 and a largest logit of 0.09 to 1.81.

The 8-bit control (the reference with `float8_e4m3fn` matrices against itself,
two seeds): RMS 4.53e-1 and 4.57e-1, EVERY row 0.38-0.59; largest logit 2.36
and 2.56, a row's 1.47-2.56.

RMS limit 2e-1: 1.7 times over the program's largest reading (it takes four
rows of 32 flipped as badly as the worst one seen) and 2.3 under the control's
smallest. NO largest-logit limit: one flipped row of the program reads what
EVERY row of the control reads (1.8 against 1.5-2.6), so no value lies between
the two with room on both sides, and a limit between them would refuse a
correct run in ten; the RMS, which is over all rows, is what tells a program
that is off everywhere from one that decided a tie the other way.

    python3 benchmarks/serve_latent_cell.py --workload <cell> --seed <n>

is the control (exit 0 = the program is correct AND the 8-bit reference is not: by the RMS).
"""

from __future__ import annotations

import os
import sys

RMS_TOLERANCE, MAX_TOLERANCE = 2e-1, float("inf")


def _with_limits(family):
    family.RMS_TOLERANCE, family.MAX_TOLERANCE = RMS_TOLERANCE, MAX_TOLERANCE
    return family


def run(ctx) -> dict:
    return _with_limits(ctx.load("serve_family_cell.py")).run(ctx)


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    return _with_limits(harness.load_module(os.path.join(here, "serve_family_cell.py"))).main()


if __name__ == "__main__":
    raise SystemExit(main())
