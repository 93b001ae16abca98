"""Profiler trace -> numbers. The yardstick: no later PR may change this file.

Two stages, so the arithmetic can be checked on a small recorded trace
(benchmarks/fixtures/, `run.py --selfcheck`) without a chip:

  load_xplane(path)  reads the `.xplane.pb` the JAX profiler writes, with
                     `jax.profiler.ProfileData` and nothing else, into a plain
                     dict ("reduced trace") of device-op events and the
                     benchmark's own host marks, all in nanoseconds on the
                     profiler's clock;
  the functions below take that dict (and host spans already moved onto the
                     same clock) and return busy/idle seconds, exclusive time
                     by op name, kernel time by pattern, collective time and
                     its exposed part, and idle gaps attributed to host spans.

Device planes are the planes named `/device:TPU:<n>` (their `XLA Ops` line).
A CPU trace has none: there the events carrying an `hlo_op` stat on the host
plane stand in as one pseudo-device, which is what lets the rehearsal drive
this code; nothing computed from a CPU trace is ever printed under a device
metric's name (run.py refuses to report without a TPU).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import typing as tp

GAP_FLOOR_NS = 50_000  # gaps under 50 us are "between operations", not attributed
MARK_PREFIX = "bench."
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_INFO_KEYS = ("hlo_category", "tf_op", "long_name", "hlo_op", "hlo_module")


def short_name(event_name: str) -> str:
    """`%fusion.12 = bf16[..] fusion(...)` -> `fusion.12`: the HLO op's own
    name, stable across shapes; the instruction text is kept under info["hlo"]."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load_xplane(path: str) -> dict:
    """Read an xplane file into the reduced-trace dict (module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    names: tp.Dict[str, int] = {}
    info: tp.Dict[int, dict] = {}
    devices, marks, pseudo = [], [], []

    def intern(ev) -> int:
        i = names.get(ev.name)
        if i is None:
            i = names[ev.name] = len(names)
            st = {k: str(v)[:300] for k, v in ev.stats if k in _INFO_KEYS}
            if " = " in ev.name:  # a TPU op event is named by its whole HLO instruction
                st["hlo"] = ev.name[:400]
            if st:
                info[i] = st
        return i

    for plane in pd.planes:
        if plane.name.startswith("/device:TPU") or plane.name.startswith("/device:GPU"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[intern(e), int(e.start_ns), int(e.duration_ns)] for e in line.events]
                    ops.sort(key=lambda o: (o[1], -o[2]))
                    devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK_PREFIX):
                        marks.append([e.name, int(e.start_ns), int(e.duration_ns)])
                    elif not devices and any(k == "hlo_op" for k, _ in e.stats):
                        pseudo.append([intern(e), int(e.start_ns), int(e.duration_ns)])
    if not devices and pseudo:
        pseudo.sort(key=lambda o: (o[1], -o[2]))
        devices.append({"name": "/host:CPU (pseudo-device: hlo_op events)", "ops": pseudo})
    inv = [None] * len(names)
    for n, i in names.items():
        inv[i] = short_name(n)
    return {"names": inv, "info": {str(k): v for k, v in info.items()},
            "devices": devices, "marks": sorted(marks, key=lambda m: m[1])}


# -- interval arithmetic ----------------------------------------------------


def clip(ops: tp.Sequence[tp.Sequence[int]], lo: int, hi: int) -> tp.List[tp.List[int]]:
    out = []
    for n, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append([n, a, b - a])
    return out


def union(intervals: tp.Iterable[tp.Tuple[int, int]]) -> tp.List[tp.Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: tp.List[tp.List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: tp.Iterable[tp.Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: tp.List[tp.Tuple[int, int]], b: tp.List[tp.Tuple[int, int]]) -> tp.List[tp.Tuple[int, int]]:
    """Parts of merged intervals `a` not covered by merged intervals `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaf_ops(ops) -> tp.List[tp.Sequence[int]]:
    """Ops with no op nested inside them, for ops sorted by (start, -duration).
    A control-flow wrapper (`while`, `conditional`, `call`) spans its whole body,
    idle gaps included; only leaves say when the device was really working."""
    out, stack = [], []  # stack of [end, op, has_child]
    for op in ops:
        s, e = op[1], op[1] + op[2]
        while stack and stack[-1][0] <= s:
            top = stack.pop()
            if not top[2]:
                out.append(top[1])
        if stack:
            stack[-1][2] = True
        stack.append([e, op, False])
    out.extend(t[1] for t in stack if not t[2])
    out.sort(key=lambda o: o[1])
    return out


def busy_ns(ops) -> int:
    """Union of the leaf-op intervals (see leaf_ops)."""
    return total(union((s, s + d) for _, s, d in leaf_ops(ops)))


def exclusive_ns(ops) -> tp.Tuple[collections.Counter, collections.Counter]:
    """Per name-index exclusive time (duration minus nested children) and
    call count, for ops sorted by (start, -duration)."""
    excl: collections.Counter = collections.Counter()
    count: collections.Counter = collections.Counter()
    stack: tp.List[tp.Tuple[int, int]] = []  # (end, name)
    for n, s, d in ops:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            excl[stack[-1][1]] -= d
        excl[n] += d
        count[n] += 1
        stack.append((s + d, n))
    return excl, count


def matching(trace: dict, name_pattern: str, info_patterns: tp.Optional[tp.Dict[str, str]] = None) -> tp.Set[int]:
    """Name indices whose op name matches `name_pattern` and whose recorded
    info fields (e.g. {"hlo": r"custom-call"}) match theirs — all of them."""
    rx = re.compile(name_pattern)
    extra = {k: re.compile(v) for k, v in (info_patterns or {}).items()}
    hit = set()
    for i, name in enumerate(trace["names"]):
        info = trace["info"].get(str(i), {})
        if rx.search(name) and all(r.search(info.get(k, "")) for k, r in extra.items()):
            hit.add(i)
    return hit


def kernel_ns(ops, which: tp.Set[int]) -> tp.Tuple[int, int]:
    """(summed duration, call count) of the ops whose name index is in `which`.
    Kernels are leaf ops, so duration is exclusive time."""
    ns = calls = 0
    for n, _, d in ops:
        if n in which:
            ns += d
            calls += 1
    return ns, calls


def kernel_time(summary: dict, trace: dict, name_pattern: str,
                info_patterns: tp.Optional[tp.Dict[str, str]] = None) -> tp.Tuple[float, int]:
    """(mean nanoseconds per device, calls on all devices) of the matching
    kernel's events inside a summarized window."""
    which = matching(trace, name_pattern, info_patterns)
    ns = calls = 0
    for dev in summary["devices"]:
        a, b = kernel_ns(dev["ops"], which)
        ns, calls = ns + a, calls + b
    return ns / max(1, summary["n_devices"]), calls


def collectives_ns(trace: dict, ops) -> tp.Tuple[int, int]:
    """(collective, exposed) nanoseconds on one device.

    A synchronous collective op occupies its whole duration. An async pair
    (`x-start` ... `x-done`) is in flight from the start op's beginning to the
    done op's end. Exposed = the in-flight time during which no other
    (non-collective) leaf op runs on that device."""
    names = trace["names"]
    coll, compute = [], []
    pending: tp.Dict[str, tp.List[int]] = collections.defaultdict(list)
    for n, s, d in leaf_ops(ops):
        name = names[n]
        m = _COLLECTIVE.match(name)
        if m is None:
            compute.append((s, s + d))
            continue
        kind = m.group(1)
        rest = name[len(kind):]
        if rest.startswith("-start"):
            pending[kind].append(s)
            coll.append((s, s + d))
        elif rest.startswith("-done"):
            begin = pending[kind].pop(0) if pending[kind] else s
            coll.append((begin, s + d))
        else:
            coll.append((s, s + d))
    cu = union(coll)
    return total(cu), total(subtract(cu, union(compute)))


# -- host spans against device gaps -------------------------------------------


def sanitize(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", name)[:64]


def attribute_gaps(ops, spans_ns: tp.Sequence[tp.Tuple[str, int, int]], lo: int, hi: int) -> tp.Dict[str, int]:
    """Idle nanoseconds inside [lo, hi) by the host span open at each gap.

    `spans_ns` are (name, start, duration) on the trace's clock. A gap goes to
    the innermost (latest-started) span covering its midpoint; gaps under
    50 us are pooled, and gaps no span covers go to `no_span_open`."""
    busy = union((s, s + d) for _, s, d in leaf_ops(ops))
    gaps = subtract([(lo, hi)], busy)
    spans = sorted(spans_ns, key=lambda x: x[1])
    starts = [s[1] for s in spans]
    out: tp.Dict[str, int] = collections.defaultdict(int)
    for a, b in gaps:
        if b - a < GAP_FLOOR_NS:
            out["between_operations (gaps under 50 us)"] += b - a
            continue
        mid = (a + b) // 2
        k = bisect.bisect_right(starts, mid)
        owner = "no_span_open"
        for name, s, d in reversed(spans[max(0, k - 64):k]):
            if s <= mid < s + d:
                owner = name
                break
        out[owner] += b - a
    return dict(out)


def top(pairs: tp.Dict[str, float], n: int = 10, rest_label: str = "other") -> tp.List[tp.List]:
    items = sorted(pairs.items(), key=lambda kv: -kv[1])
    head = items[: n - 1] if len(items) > n else items
    out = [[sanitize(k), v] for k, v in head]
    if len(items) > n:
        rest = items[n - 1:]
        out.append([sanitize(f"{rest_label} ({len(rest)})"), sum(v for _, v in rest)])
    return out


def window_parts(window: dict, percentile) -> tp.Dict[str, tp.List[float]]:
    """A serving window cut into `window["parts"]` equal parts, each part's own
    figure of the three client-side metrics: tokens delivered in the part /
    its length; mean submit-to-first-token over the requests SUBMITTED in it
    (and first answered inside the window); 90th percentile of time per output
    token over the requests that FINISHED in it. A host stall sits in one part
    (two, across an edge) and moves the whole-window figures; the median over
    the parts stays. `window` is what serve_cell.py hands over: `w0`, `w1`,
    `token_times` (sorted) and `requests` as (t_submit, t_first or None,
    t_last or None if unfinished, n_out). A part with nothing to read raises."""
    n, w0, w1 = int(window["parts"]), window["w0"], window["w1"]
    edges = [w0 + (w1 - w0) * k / n for k in range(n + 1)]
    times = window["token_times"]
    out: tp.Dict[str, tp.List[float]] = {"tokens_per_s": [], "ttft_ms_mean": [], "tpot_ms_p90": []}
    for k, (a, b) in enumerate(zip(edges, edges[1:])):
        ttft = [f - s for s, f, _, _ in window["requests"] if a <= s < b and f is not None and f < w1]
        tpot = [(l - f) / (m - 1) for _, f, l, m in window["requests"] if l is not None and a <= l < b and m > 1]
        if not ttft or not tpot:
            raise ValueError(f"part {k + 1} of {n} ({b - a:.2f} s) holds {len(ttft)} submitted and {len(tpot)} "
                             f"finished requests: too few parts' worth of traffic for `window_parts` {n}")
        out["tokens_per_s"].append((bisect.bisect_left(times, b) - bisect.bisect_left(times, a)) / (b - a))
        out["ttft_ms_mean"].append(1e3 * sum(ttft) / len(ttft))
        out["tpot_ms_p90"].append(1e3 * percentile(tpot, 90))
    return out


def summarize(trace: dict, spans_s: tp.Sequence[tp.Tuple[str, float, float]],
              clock_offset_s: float) -> dict:
    """Everything the per-layer readers need from one traced window.

    The window is the `bench.window` mark; `spans_s` are host spans on the
    host's clock and `clock_offset_s` moves them onto the trace's clock
    (trace time = host time + offset, from the `bench.sync` mark)."""
    win = [m for m in trace["marks"] if m[0] == "bench.window"]
    if not win:
        raise ValueError("trace holds no bench.window mark")
    lo, hi = win[-1][1], win[-1][1] + win[-1][2]
    spans_ns = [(n, int((s + clock_offset_s) * 1e9), int(d * 1e9)) for n, s, d in spans_s]
    per_dev, excl_all, gaps_all = [], collections.Counter(), collections.Counter()
    coll = exposed = 0
    for dev in trace["devices"]:
        ops = clip(dev["ops"], lo, hi)
        per_dev.append({"name": dev["name"], "busy_ns": busy_ns(ops), "n_ops": len(ops), "ops": ops})
        excl, _ = exclusive_ns(ops)
        for n, v in excl.items():
            excl_all[trace["names"][n]] += v
        for k, v in attribute_gaps(ops, spans_ns, lo, hi).items():
            gaps_all[k] += v
        c, e = collectives_ns(trace, ops)
        coll, exposed = coll + c, exposed + e
    nd = max(1, len(per_dev))
    return {
        "window_ns": hi - lo, "lo": lo, "hi": hi, "n_devices": len(per_dev),
        "busy_ns_mean": sum(d["busy_ns"] for d in per_dev) / nd,
        "devices": per_dev,
        "exclusive_s": {k: v / nd / 1e9 for k, v in excl_all.items()},
        "gaps_s": {k: v / nd / 1e9 for k, v in gaps_all.items()},
        "collective_ns_mean": coll / nd, "exposed_collective_ns_mean": exposed / nd,
    }
