"""Summarize a JAX profiler trace: per-op exclusive device time, grouped.

The tensorboard profile UI is rarely available on TPU-VM hosts; this reads
the xplane protobuf a `jax.profiler.start_trace` capture writes (e.g.
`python bench.py --profile /tmp/trace` or `launch.py --debug`) and prints
the top ops by exclusive time plus a category rollup — the exact workflow
that drove the round-2 MFU work.

Usage:
    python tools/profile_summary.py <trace-dir-or-xplane.pb> [--steps N] [--top K]

`--steps` divides totals by the number of profiled steps so numbers read as
per-step costs. To line host spans up with the device timeline, use the
`obs.sync` mark `launch.py --debug` leaves in both the profile and the
flight recorder (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import sys


def _find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        sys.exit(f"no .xplane.pb under {path}")
    return hits[-1]


def _categorize(full_name: str) -> str:
    # match on the op name only — the full HLO text embeds OPERAND names
    # (e.g. "%fusion.153 = ... fusion(%copy-done.166 ...)"), which would
    # misbin fusions as copies
    name = full_name.split(" = ", 1)[0]
    if "closed_call" in name or "checkpoint" in name or "rematted" in name:
        return "pallas-kernels"
    if "slice-start" in name or "slice-done" in name:
        return "async-slice"
    if "copy-start" in name or "copy-done" in name or "copy" in name:
        return "copies"
    if "transpose" in name:
        return "transpose"
    if "dynamic-update-slice" in name:
        return "dyn-update-slice"
    if (
        "all-reduce" in name
        or "all-gather" in name
        or "reduce-scatter" in name
        or "all-to-all" in name
        or "collective" in name
    ):
        return "collectives"
    if "while" in name:
        return "while-wrapper"
    if "fusion" in name or "convolution" in name or "dot" in name:
        return "fusions(matmul+elementwise)"
    return "other"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace", help="trace dir or xplane.pb file")
    p.add_argument("--steps", type=int, default=1, help="profiled step count")
    p.add_argument("--top", type=int, default=25)
    args = p.parse_args()

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        sys.exit("needs tensorflow (for the xplane proto); pip install tensorflow-cpu")

    xs = xplane_pb2.XSpace()
    with open(_find_xplane(args.trace), "rb") as f:
        xs.ParseFromString(f.read())

    for plane in xs.planes:
        if "TPU" not in plane.name and "GPU" not in plane.name:
            continue
        ev_names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = sorted(
                (ev.offset_ps, ev.offset_ps + ev.duration_ps, ev_names.get(ev.metadata_id, "?"))
                for ev in line.events
            )
            # events nest on a line: exclusive time = duration - children
            excl: collections.Counter = collections.Counter()
            cats: collections.Counter = collections.Counter()
            cnt: collections.Counter = collections.Counter()
            stack: list = []
            for start, end, name in evs:
                while stack and stack[-1][1] <= start:
                    stack.pop()
                if stack:
                    excl[stack[-1][2]] -= end - start
                    cats[_categorize(stack[-1][2])] -= end - start
                excl[name] += end - start
                cats[_categorize(name)] += end - start
                cnt[name] += 1
                stack.append((start, end, name))

            total = sum(excl.values())
            print(f"== {plane.name} :: {line.name} — {total/1e9/args.steps:.2f} ms/step ==")
            print("\n-- categories --")
            for cat, t in cats.most_common():
                print(f"{t/1e9/args.steps:9.2f} ms  {cat}")
            print(f"\n-- top {args.top} ops (exclusive) --")
            for name, t in excl.most_common(args.top):
                print(f"{t/1e9/args.steps:9.2f} ms x{cnt[name]//max(args.steps,1):<4} {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
