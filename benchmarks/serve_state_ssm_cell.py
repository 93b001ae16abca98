"""Serving cells of a STATE-SPACE family under a state kind of cache (traffic
kind "serve_state_ssm"): `serve_state_cell.py`'s run, dirty-row check, books and
control as they are (every state row dirtied before a compared request lands
on it, the compared requests beside decoding neighbours, the pool owner's
counts held, `compared` returned), under limits of this family's own. Nothing
else differs, so this file sets the limits and hands over.

Why own limits (my chip runs, PR 63, `serve_granite4_h_sessions`: bf16
weights, keys, values and convolution history, the SSM state float32, through
all 40 layers; 32 compared rows a check). `serve_state_cell.py`'s limits (RMS
1.2e-1, largest logit 6.5e-1 of the reference logits' standard deviation) lie
between Olmo-Hybrid's program and ITS 8-bit control, a stack that NORMS every
branch's output before adding it. This family adds each branch times 0.22
(`residual_multiplier`) to a stream that begins at 12 x the embedding and
divides the logits by 8: both the program's rounding and the 8-bit control's
land smaller, and the control PASSED under those limits on all three seeds it
was run under them (6300000079, 6300000083, 6300000097).
Readings, as shares of the reference logits' standard deviation over all
compared rows:

  the program, twenty seeds (6300000001, 6300000011, 6300000023, 6300000037,
  6300000041, 6300000053, 6300000067, 6300000103, 6300000209, 6300000307,
  6300000401, 6300000503, 6300000601, 6300000701 through run.py; 6300000079,
  6300000083, 6300000097, 6300000809, 6300000907, 6300001009 through this
  control): RMS 4.27e-3 to 4.45e-3, largest logit 8.7e-2 to 1.156e-1;
  the reference with 8-bit matrices (`float8_e4m3fn`) against itself, six
  seeds (6300000079, 6300000083, 6300000097, 6300000809, 6300000907,
  6300001009): RMS 9.06e-2 to 9.29e-2, largest logit 4.22e-1 to 5.12e-1,
  which must fail.

RMS limit 2.0e-2: the geometric middle, 4.5 times over the program's largest
reading and 4.5 under the control's smallest. Largest logit 2.2e-1: 1.9 over,
1.9 under.

    python3 benchmarks/serve_state_ssm_cell.py --workload <cell> --seed <n>

is that control (exit 0 = the program is correct AND the 8-bit reference is not).
"""

from __future__ import annotations

import os
import sys

RMS_TOLERANCE, MAX_TOLERANCE = 2.0e-2, 2.2e-1


def _with_limits(state):
    """`serve_state_cell.py` under this family's limits (it hands them to `serve_family_cell.py`'s `judge` and logs them)."""
    state.RMS_TOLERANCE, state.MAX_TOLERANCE = RMS_TOLERANCE, MAX_TOLERANCE
    return state


def run(ctx) -> dict:
    return _with_limits(ctx.load("serve_state_cell.py")).run(ctx)


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    return _with_limits(harness.load_module(os.path.join(here, "serve_state_cell.py"))).main()


if __name__ == "__main__":
    raise SystemExit(main())
