"""Summarize a JAX profiler trace: per-op exclusive device time, grouped.

The tensorboard profile UI is rarely available on TPU-VM hosts; this reads
the xplane protobuf a `jax.profiler.start_trace` capture writes (e.g.
`python bench.py --profile /tmp/trace` or `launch.py --debug`) and prints
the top ops by exclusive time plus a category rollup — the exact workflow
that drove the round-2 MFU work.

Usage:
    python tools/profile_summary.py <trace-dir-or-xplane.pb> [--steps N] [--top K]
        [--correlate <flight-recorder.json-or-dir>]

`--steps` divides totals by the number of profiled steps so numbers read as
per-step costs. `--correlate` lines the flight recorder's host-side
`train.step` spans (midgpt_tpu/obs/, dumped to the rundir) up against the
xplane's device ms/step: host span minus device time is host overhead
(feed + enqueue) when positive; a host span much SHORTER than device time
means dispatch ran ahead and the wall cost surfaces at the log-interval
sync instead (docs/OBSERVABILITY.md).
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


def correlate_flight_recorder(path: str, device_ms_per_step: float) -> None:
    """Print host-side train.step span stats from a flight-recorder dump
    next to the xplane's device ms/step (module docstring on reading the
    difference). JAX-free: reuses tools/trace_view.py's loader."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from trace_view import find_trace, load_trace

    evs = load_trace(find_trace(path))
    spans = [
        e["dur"] / 1e3
        for e in evs
        if e.get("ph") == "X" and e.get("name") == "train.step"
    ]
    print("\n== flight-recorder correlation ==")
    if not spans:
        print("no train.step spans in the dump — was the recorder on "
              "during the profiled steps?")
        return
    spans.sort()
    host_ms = sum(spans) / len(spans)
    print(f"host train.step spans: n={len(spans)}  mean={host_ms:.2f} ms  "
          f"p50={spans[len(spans) // 2]:.2f} ms  max={spans[-1]:.2f} ms")
    if device_ms_per_step > 0:
        print(f"device (xplane):       {device_ms_per_step:.2f} ms/step")
        delta = host_ms - device_ms_per_step
        if delta >= 0:
            print(f"host - device:         {delta:+.2f} ms/step host overhead "
                  "(feed + enqueue)")
        else:
            print(f"host - device:         {delta:+.2f} ms/step — dispatch "
                  "runs ahead; the wall cost lands at the log-interval sync")


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace", help="trace dir or xplane.pb file")
    p.add_argument("--steps", type=int, default=1, help="profiled step count")
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--correlate", default=None, metavar="FLIGHT_RECORDER",
                   help="flight_recorder.json (or a dir holding one): print "
                   "host train.step span stats against the device ms/step")
    args = p.parse_args()

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        sys.exit("needs tensorflow (for the xplane proto); pip install tensorflow-cpu")

    xs = xplane_pb2.XSpace()
    with open(_find_xplane(args.trace), "rb") as f:
        xs.ParseFromString(f.read())

    device_ms_per_step = 0.0
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
            device_ms_per_step = max(device_ms_per_step, total / 1e9 / args.steps)
            print(f"== {plane.name} :: {line.name} — {total/1e9/args.steps:.2f} ms/step ==")
            print("\n-- categories --")
            for cat, t in cats.most_common():
                print(f"{t/1e9/args.steps:9.2f} ms  {cat}")
            print(f"\n-- top {args.top} ops (exclusive) --")
            for name, t in excl.most_common(args.top):
                print(f"{t/1e9/args.steps:9.2f} ms x{cnt[name]//max(args.steps,1):<4} {name[:110]}")
    if args.correlate:
        correlate_flight_recorder(args.correlate, device_ms_per_step)
    return 0


if __name__ == "__main__":
    sys.exit(main())
