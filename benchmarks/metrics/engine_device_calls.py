"""serving engine: every dispatch tied to its execution on the device (PR 53).

Since PR 53 `ServeEngine` numbers every jit call it enqueues (`call`) and, with
obs on, opens a `jax.profiler.TraceAnnotation` `engine.dispatch` with that
number around the call; the spans that bracket the same calls carry it
(`prefill.chunk`, `decode.enqueue`; `decode.host_post` says whose tokens it
committed). This reader joins three things inside the traced window:

  marks       the host plane's `engine.dispatch` events, by `call`
  executions  the device plane's `XLA Modules` events whose name holds one of
              the engine's six program names, in start order
              (`serve_prefill.module_events`)
  spans       the recorder's events, through `midgpt_tpu.obs.live()` (the seam
              `engine_dispatch.py` uses), moved onto the trace's clock by the
              MEDIAN of (mark start - span start) over the joined calls: every
              dispatch is a sync mark, `bench.sync` is not needed

Execution k of the engine's programs IS dispatch k (one stream a device; on a
mesh: per chip, then the mean). `join` checks it and the reader REFUSES TO
REPORT, with one log line saying what it found, unless the marks' numbers are
consecutive, the counts agree once the calls cut by the trace's ends are
trimmed, each execution runs the program its span's kind implies, and ONE
shift of the device plane puts every execution after its own mark and before
its tokens are on the host.

That shift is needed because the profiler aligns its device plane to its host
plane to about a millisecond and no better (my chip runs, PR 53: the same
engine read as launching 0.5 ms BEFORE its dispatch in one trace and 0.8 ms
after it in three). `plane_shift` takes the midpoint of the shifts the two
certainties allow and says the half width: the time from a mark to its
execution (launch) and from an execution's end to the landing of its tokens
are each good to that (0.8-1.0 ms a call), their sum is exact, and so is
every idle instant that touches neither end of an execution: what the host
did between a landing and its next dispatch.

From the join: the device time of one prefill or decode execution (by rows,
steps, bucket: the logged table) and what a token costs the device. The time
from ONE mark to its execution is not reported: it is good to the half width
and no better, which is as large as the value; `engine.launch_idle_share` sums
it over the window, where the sum is exact. From the marks
and ALL module executions: the device's idle time outside executions, cut at
every mark into LAUNCH (a dispatch has begun and its execution has not: the
call's own work and the transfer of its numpy arguments) and STARVED (nothing
pending: the host had not asked for anything). Starved time goes to the
recorder's spans BY OVERLAP, each instant to the innermost span open at it
(the latest started; where spans nest that is a span's self time, its
interval minus its children's): never by midpoint, no cap on the spans looked
at (`reduce.attribute_gaps` does both, PERF.md section 7 row 10 d). Idle time
INSIDE an execution stays with `serve.device_idle_share` and is the logged
residual. Both sums are checked to 1 % of the window.

`host.offcpu_share` needs no trace: over the measured WINDOW's
`decode.dispatch`, `decode.host_post` and `prefill.assemble` spans,
100 x (1 - sum `cpu_s` / sum wall): time the engine's thread was not running.
On the chip machines the thread CPU clock ticks at 10 ms, so the sum is a
count of ticks and the share is reported only from MIN_TICKS of them.

A program without the marks (the parent of PR 53), a trace without an `XLA
Modules` line (the CPU rehearsal) or a training cell reports nothing of the
join and says so.
"""

import collections
import os
import statistics

MARK = "engine.dispatch"
# span that brackets a call -> the programs its call may run (sampling/serve.py)
SPAN_PROGRAMS = {
    "prefill.chunk": ("_serve_prefill_chunk",),
    "decode.enqueue": ("_serve_decode_chunk", "_serve_decode_group"),
    "spec.draft_enqueue": ("_spec_draft_chunk",),
    "spec.verify_enqueue": ("_spec_verify_chunk",),
}
ENGINE_PROGRAMS = ("_serve_prefill_chunk", "_serve_decode_chunk", "_serve_decode_group",
                   "_serve_decode_logits", "_spec_draft_chunk", "_spec_verify_chunk")
CPU_SPANS = ("decode.dispatch", "decode.host_post", "prefill.assemble")
# where starved time under a span goes; every other span and time no span covers:
# `starved.round_self_share` (engine.round's and engine.expire/admit/prefill's self
# time; decode.device_wait's part is the landing of a finished program's tokens)
GROUPS = {
    "decode.host_post": "starved.host_post_share",
    "prefill.first_token": "starved.first_token_share",
    **{n: "starved.dispatch_share" for n in (
        "decode.dispatch", "decode.assemble", "decode.key", "decode.put", "decode.enqueue",
        "prefill.assemble", "prefill.put", "prefill.key", "prefill.chunk")},
}
REST = "starved.round_self_share"
SUM_TOLERANCE = 0.01  # of the window
# host.offcpu_share: a thread CPU clock whose smallest step is this long is counted in ticks, and a
# share is reported from this many of them (+-6 points at 250: what a host-bound cell's window holds)
TICK_FLOOR_S, MIN_TICKS = 1e-3, 250
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".work", "trace")


class Refusal(Exception):
    """The join's checks failed: nothing is reported, the message is logged."""


def dispatch_marks(path):
    """[(call, start_ns, duration_ns)] of the host planes' `engine.dispatch` events, by call."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        out.append((int(dict(e.stats)["call"]), int(e.start_ns), int(e.duration_ns)))
    return sorted(out)


def recorder_events(run):
    """The ring of the newest live recorder that holds the window's spans, or None with a log line."""
    try:
        from midgpt_tpu.obs import live
    except ImportError:
        run["log"]("engine_device_calls: this program has no obs.live(); nothing to join")
        return None
    want = {(n, s) for n, s, _ in run["spans"]}
    for obs in reversed(live()):
        events = obs.tracer.events()
        if any(e[0] == "X" and (e[1], e[4]) in want for e in events):
            return events
    run["log"]("engine_device_calls: no live recorder holds the window's spans; nothing to join")
    return None


def is_engine_program(name):
    return any(p in name for p in ENGINE_PROGRAMS)


def clock_offset_ns(marks, spans_by_call):
    """(median, spread) of mark start - span start over the calls both have: what moves the
    recorder's events onto the trace's clock. The spread is the distance between the quartiles."""
    deltas = sorted(s - spans_by_call[c][4] * 1e9 for c, s, _ in marks if c in spans_by_call)
    if not deltas:
        raise Refusal("no span carries the `call` of any engine.dispatch mark in the trace")
    q = statistics.quantiles(deltas, n=4) if len(deltas) > 1 else [deltas[0]] * 3
    return statistics.median(deltas), q[2] - q[0]


def pair_up(marks, execs, spans_by_call, head):
    """[(call, mark start, execution (name, start, duration) or None)]: mark k with execution
    `head` + k of one device's engine executions in start order, or Refusal. Marks past the last
    execution are dispatches the trace's end cut (their execution None)."""
    execs = execs[head:]
    if len(execs) > len(marks):
        raise Refusal(f"{len(execs)} executions of the engine's programs for {len(marks)} marks (calls {marks[0][0]}.."
                      f"{marks[-1][0]}), {head} dropped at the head: a program ran that no dispatch was counted for")
    out = []
    for k, (call, m_start, _) in enumerate(marks):
        ex, span = execs[k] if k < len(execs) else None, spans_by_call.get(call)
        if ex is not None and span is not None and not any(p in ex[0] for p in SPAN_PROGRAMS[span[1]]):
            raise Refusal(f"call {call} is a {span[1]} on the host and ran {ex[0]} on the device "
                          f"({head} execution(s) dropped at the head)")
        out.append((call, m_start, ex))
    return out


def join(marks, execs, spans_by_call, landed_by_call):
    """(pairs, head, shift, half width) for one device, or Refusal. Execution k of the engine's
    programs is dispatch k, but for what the trace's ends cut: executions of dispatches made
    before the profiler ran (`head` of them, dropped) and marks whose execution came after it
    stopped. `head` is the surplus of executions over marks, or up to two more where the trace's
    end cut as many marks: the first under which every execution runs the program its span's kind
    implies (`pair_up`) and one shift of the device plane puts every execution after its mark and
    before its tokens' landing (`plane_shift`); the first candidate's refusal where none does."""
    calls = [c for c, _, _ in marks]
    if calls != list(range(calls[0], calls[0] + len(calls))):
        raise Refusal(f"the marks' calls are not consecutive ({calls[0]}..{calls[-1]} in {len(calls)} marks): "
                      "the profiler dropped host events")
    surplus, first = max(0, len(execs) - len(marks)), None
    for head in range(surplus, surplus + 3):
        try:
            pairs = pair_up(marks, execs, spans_by_call, head)
            return (pairs, head, *plane_shift(pairs, landed_by_call))
        except Refusal as e:
            first = first or e
    raise first


def plane_shift(pairs, landed_by_call):
    """(shift, half width) in ns: what to ADD to a device plane's times so that they sit on the
    host plane's clock, or Refusal. The profiler aligns the two planes to about a millisecond and
    no better (my chip runs, PR 53: the same engine's programs read as starting 0.5 ms BEFORE
    their dispatch in one trace and 0.8 ms after it in three others), and two things are certain
    of every call: its execution starts AFTER its mark and ends BEFORE its tokens are on the host
    (`landed_by_call`: call -> that instant on the trace's clock). The shifts that respect both
    for every call of the trace are an interval [lo, hi]; none: execution k is not dispatch k, or
    the clocks are further apart than a call lasts. The reader takes its MIDPOINT: the time from a
    mark to its execution (launch) and from an execution's end to its tokens' landing are then
    each good to the half width, their SUM is exact, and so is every idle instant that touches
    neither end of an execution."""
    lo = max((m - ex[1] for _, m, ex in pairs if ex is not None), default=None)
    hi = min((landed_by_call[c] - (ex[1] + ex[2]) for c, _, ex in pairs if ex is not None and c in landed_by_call),
             default=None)
    if lo is None or hi is None:
        raise Refusal("no execution of the trace has both a mark and a landing: the device plane cannot be anchored")
    if lo > hi:
        raise Refusal(f"no shift of the device plane puts every execution after its mark ({lo / 1e3:+.1f} us at the "
                      f"least) and before its tokens' landing ({hi / 1e3:+.1f} us at the most): execution k is not "
                      "dispatch k here")
    return (lo + hi) // 2, (hi - lo) // 2


def split_by_innermost(intervals, spans):
    """{span name or None: ns} of the sorted, disjoint `intervals`, each instant given to the
    innermost span open at it: the latest started (a later record wins a tie: a child recorded
    after its parent), None where no span is open. `spans`: (start, seq, end, name)."""
    spans = sorted(spans)
    out, stack, k = collections.Counter(), [], 0
    for a, b in intervals:
        t = a
        while t < b:
            while k < len(spans) and spans[k][0] <= t:
                stack.append(spans[k])
                k += 1
            while stack and stack[-1][2] <= t:
                stack.pop()
            nxt = min(b, stack[-1][2] if stack else b, spans[k][0] if k < len(spans) else b)
            out[stack[-1][3] if stack else None] += nxt - t
            t = nxt
    return out


def device_calls(pairs, spans_by_call, commits_by_call, lo, hi):
    """The calls whole inside [lo, hi): {"prefill": [(device ns, args)], "decode": [(device ns,
    args, tokens committed or None)]} and the executions whose call no span carries (a separate
    draft model's prefill, `next_logits`)."""
    out, unowned = {"prefill": [], "decode": []}, collections.Counter()
    for call, m_start, ex in pairs:
        if ex is None or m_start < lo or ex[1] + ex[2] > hi:
            continue
        span = spans_by_call.get(call)
        if span is None:
            unowned[ex[0].split("(")[0]] += 1
        elif span[1] == "prefill.chunk":
            out["prefill"].append((ex[2], span[7]))
        elif span[1] == "decode.enqueue":
            commit = commits_by_call.get(call)
            out["decode"].append((ex[2], span[7], None if commit is None else commit[7].get("tokens")))
    return out, unowned


def idle_split(reduce, pairs, modules, leaf_busy, spans, lo, hi):
    """One device's idle nanoseconds inside [lo, hi): launch, starved (and by span name), the
    residual inside executions and what the leaf ops say in all. `modules`: (name, start,
    duration) of EVERY program's executions; `leaf_busy`: merged busy intervals of the leaf ops;
    `spans`: (start, seq, end, name) on the trace's clock."""
    window = [(lo, hi)]
    clipped = lambda ivs: [(max(a, lo), min(b, hi)) for a, b in ivs if min(b, hi) > max(a, lo)]
    busy = reduce.union(clipped((s, s + d) for _, s, d in modules))
    idle = reduce.subtract(window, busy)
    pending = reduce.union(clipped((m, hi if ex is None else ex[1]) for _, m, ex in pairs))
    starved = reduce.subtract(idle, pending)
    leaf = reduce.union(clipped(leaf_busy))
    return {"launch": reduce.total(idle) - reduce.total(starved), "starved": reduce.total(starved),
            "by_span": split_by_innermost(starved, spans),
            "residual": reduce.total(reduce.subtract(busy, leaf)),
            "idle_leaf": (hi - lo) - reduce.total(leaf)}


def plane_metrics(calls, idle, rode, window):
    """One chip's metrics from its joined calls, its idle split and the args of the window's
    `decode.dispatch` spans; a metric with nothing to read is None. The idle split is left out
    (and `closed` False) where its sums do not close within SUM_TOLERANCE of the window."""
    med_ms = lambda ns: statistics.median(ns) / 1e6 if ns else None
    share = lambda ns: 100.0 * ns / window
    swept = sum(a.get("blocks_swept", 0) for a in rode)
    out = {
        "prefill.call_device_ms_p50": med_ms([d for d, _ in calls["prefill"]]),
        "prefill.device_us_per_token": per_token(calls["prefill"], lambda c: c[1]["tokens"]),
        "decode.step_device_ms_p50": med_ms([d / a["steps"] for d, a, _ in calls["decode"]]),
        "decode.device_us_per_token": per_token([c for c in calls["decode"] if c[2] is not None], lambda c: c[2]),
        "decode.live_block_share": 100.0 * sum(a["blocks_live"] for a in rode if "blocks_swept" in a) / swept
        if swept else None,
    }
    parts = idle["launch"] + idle["starved"] + idle["residual"]
    closed = (abs(sum(idle["by_span"].values()) - idle["starved"]) <= SUM_TOLERANCE * window
              and abs(parts - idle["idle_leaf"]) <= SUM_TOLERANCE * window)
    if closed:
        out["engine.launch_idle_share"] = share(idle["launch"])
        out["engine.host_starved_share"] = share(idle["starved"])
        for metric in (*dict.fromkeys(GROUPS.values()), REST):
            out[metric] = share(sum(ns for n, ns in idle["by_span"].items() if GROUPS.get(n, REST) == metric))
    return out, closed


def summarize(reduce, marks, modules, leaf_by_plane, events, lo, hi, log):
    """The metrics of one traced window (per chip, then the mean), or Refusal. `modules`: (plane,
    name, start, duration); `leaf_by_plane`: {plane: merged busy intervals of its leaf ops};
    `events`: the recorder's ring. The log lines are the first chip's."""
    if not marks:
        raise Refusal("the trace holds no engine.dispatch mark")
    complete = [e for e in events if e[0] == "X"]
    has_call = lambda e: bool(e[7]) and "call" in e[7]
    spans_by_call = {e[7]["call"]: e for e in complete if e[1] in SPAN_PROGRAMS and has_call(e)}
    commits_by_call = {e[7]["call"]: e for e in complete if e[1].endswith(".host_post") and has_call(e)}
    by_seq = {e[10]: e for e in complete}
    offset, spread = clock_offset_ns(marks, spans_by_call)
    # when a call's tokens were on the host: its commit's start; a prefill call's, the end of the
    # first `prefill.first_token` that names it (the call's one force)
    landed_by_call = {c: int(e[4] * 1e9 + offset) for c, e in commits_by_call.items()}
    for e in complete:
        if e[1] == "prefill.first_token" and has_call(e):
            landed_by_call.setdefault(e[7]["call"], int((e[4] + e[5]) * 1e9 + offset))
    spans = [(int(e[4] * 1e9 + offset), e[10], int((e[4] + e[5]) * 1e9 + offset), e[1]) for e in complete]
    spans = [s for s in spans if s[2] > lo and s[0] < hi]
    window, per_plane = hi - lo, []
    for plane in sorted({p for p, _, _, _ in modules}):
        mods = sorted(((n, s, d) for p, n, s, d in modules if p == plane), key=lambda m: m[1])
        pairs, head, shift, half = join(marks, [m for m in mods if is_engine_program(m[0])], spans_by_call,
                                        landed_by_call)
        mods = [(n, s + shift, d) for n, s, d in mods]
        pairs = [(c, m, None if ex is None else (ex[0], ex[1] + shift, ex[2])) for c, m, ex in pairs]
        leaf = [(a + shift, b + shift) for a, b in leaf_by_plane.get(plane, [])]
        calls, unowned = device_calls(pairs, spans_by_call, commits_by_call, lo, hi)
        idle = idle_split(reduce, pairs, mods, leaf, spans, lo, hi)
        # the census of the window's dispatches: the parent of each decode.enqueue whole in the window
        rode = [by_seq[spans_by_call[c][9]][7] or {} for c, m, ex in pairs
                if ex is not None and m >= lo and ex[1] + ex[2] <= hi and c in spans_by_call
                and spans_by_call[c][1] == "decode.enqueue" and spans_by_call[c][9] in by_seq]
        metrics, closed = plane_metrics(calls, idle, rode, window)
        if not per_plane:
            log_join(log, marks, pairs, head, calls, unowned, modules, spans_by_call, spread, shift, half, lo, hi)
            log_idle(log, idle, window, closed)
            log_table(log, calls)
        per_plane.append(metrics)
    out = {}
    for k in per_plane[0]:
        vals = [m[k] for m in per_plane if m.get(k) is not None]
        out[k] = statistics.fmean(vals) if vals else None
    if out["decode.live_block_share"] is None:
        log("engine_device_calls: the window's decode.dispatch spans carry no blocks_swept (the gather lowering, or "
            "a family whose decode program has two kernels of different geometry); decode.live_block_share left out")
    return out


def log_join(log, marks, pairs, head, calls, unowned, modules, spans_by_call, spread, shift, half, lo, hi):
    cut = sum(ex is None for _, _, ex in pairs)
    others = collections.Counter(n.split("(")[0] for _, n, s, d in modules
                                 if not is_engine_program(n) and s + d > lo and s < hi)
    log(f"engine_device_calls: {len(marks)} engine.dispatch marks (calls {marks[0][0]}..{marks[-1][0]}) joined to "
        f"{len(pairs) - cut} executions of the engine's programs: {head} execution(s) from before the profiler "
        f"dropped, {cut} dispatch(es) cut by the trace's end; whole inside the window: "
        f"{len(calls['prefill'])} prefill + {len(calls['decode'])} decode calls; every execution runs its span's "
        f"program; the recorder's clock from {sum(c in spans_by_call for c, _, _ in marks)} marks, quartiles "
        f"{spread / 1e3:.1f} us apart; the device plane moved {shift / 1e3:+.0f} us onto the host plane's clock, the "
        f"midpoint of the shifts under which every execution starts after its mark and ends before its tokens land "
        f"(launch and landing each good to +-{half / 1e3:.0f} us, their sum exact); programs in the window that no "
        f"dispatch span owns: {dict(unowned + others) or 'none'}")


def log_idle(log, idle, window, closed):
    by_name = ", ".join(f"{n or 'no span open'} {ns / 1e6:.1f}" for n, ns in idle["by_span"].most_common())
    log(f"engine_device_calls: device idle {idle['idle_leaf'] / 1e6:.1f} ms of the {window / 1e6:.0f} ms window "
        f"({100.0 * idle['idle_leaf'] / window:.2f} %) = launch {idle['launch'] / 1e6:.1f} (a dispatch begun, its "
        f"execution not) + starved {idle['starved'] / 1e6:.1f} (nothing pending) + inside executions "
        f"{idle['residual'] / 1e6:.1f} (stays with serve.device_idle_share); starved ms by the innermost span "
        f"open: {by_name or 'none'}")
    if not closed:
        log(f"engine_device_calls: the idle split does NOT close within {SUM_TOLERANCE:.0%} of the window (spans "
            f"{sum(idle['by_span'].values()) / 1e6:.2f} for starved {idle['starved'] / 1e6:.2f} ms; parts "
            f"{(idle['launch'] + idle['starved'] + idle['residual']) / 1e6:.2f} for idle {idle['idle_leaf'] / 1e6:.2f} "
            "ms: leaf ops outside every module execution?); the split is left out")


def per_token(calls, tokens):
    """Microseconds of device time a token, over calls as (device ns, ...)."""
    n = sum(tokens(c) for c in calls)
    return sum(c[0] for c in calls) / 1e3 / n if n else None


def log_table(log, calls):
    """Per (kind, rows or steps, bucket): the count and the median device ms of the window's calls."""
    table = collections.defaultdict(list)
    for d, a in calls["prefill"]:
        table[("prefill", f"rows {a['rows']} of {a.get('width', 1)}", a["bucket"])].append(d)
    for d, a, _ in calls["decode"]:
        table[("decode", f"steps {a['steps']}", a["bucket"])].append(d)
    rows = "; ".join(f"{kind} {what} bucket {bucket}: {len(ds)} x {statistics.median(ds) / 1e6:.3f}"
                     for (kind, what, bucket), ds in sorted(table.items()))
    log(f"engine_device_calls: device ms of a call, median, by (kind, rows or steps, page bucket): {rows or 'no call'}")


def offcpu_share(run, events):
    """100 x (1 - CPU seconds / wall seconds) over the measured window's spans that say `cpu_s`,
    or None with a log line. Where the thread's CPU clock TICKS (the chip machines' kernel counts
    it in jiffies of 10 ms: a phase's `cpu_s` is 0 or a multiple of the tick) the sum is a count of
    ticks that fell inside the spans: unbiased, with a relative error of 1 / sqrt(ticks), so a
    share from fewer than MIN_TICKS of them is left out as noise."""
    want = {(n, s) for n, s, _ in run["spans"] if n in CPU_SPANS}
    said = [(e[5], e[7]["cpu_s"]) for e in events
            if e[0] == "X" and (e[1], e[4]) in want and e[7] and "cpu_s" in e[7]]
    wall, cpu = sum(w for w, _ in said), sum(c for _, c in said)
    if not wall:
        run["log"]("engine_device_calls: the window's spans say no cpu_s in this program; host.offcpu_share left out")
        return None
    tick = min((c for _, c in said if c > 0), default=0.0)  # the clock's step, if it has one over a phase's length
    ticks = round(cpu / tick) if tick >= TICK_FLOOR_S else None
    note = ("the thread CPU clock is finer than a phase" if ticks is None else
            f"the thread CPU clock ticks at {1e3 * tick:.0f} ms here: {ticks} ticks fell inside the spans, so the "
            f"share is good to +-{100.0 * cpu / wall / max(1, ticks) ** 0.5:.1f} points")
    share = 100.0 * (1.0 - cpu / wall)
    run["log"](f"engine_device_calls: host.offcpu_share {share:.2f} % over {len(said)} spans of the window "
               f"({wall:.3f} s wall, {cpu:.3f} s on the CPU); {note}"
               + ("" if ticks is None or ticks >= MIN_TICKS else f"; under {MIN_TICKS} ticks: left out as noise"))
    return share if ticks is None or ticks >= MIN_TICKS else None


def read(run):
    if run["kind"] != "serve" or not run["spans"]:
        return None
    events = recorder_events(run)
    if events is None:
        return None
    out = {"host.offcpu_share": offcpu_share(run, events)}
    ts = run.get("trace_summary")
    if not ts:
        return out
    reduce = run["load"]("reduce.py")
    try:
        path = reduce.find_xplane(TRACE_DIR)
    except FileNotFoundError:
        return out
    modules = run["load"]("metrics/serve_prefill.py").module_events(path)
    if not modules:
        run["log"]("engine_device_calls: the trace has no XLA Modules line on a TPU plane; nothing to join")
        return out
    marks = dispatch_marks(path)
    if not marks:
        run["log"]("engine_device_calls: the trace holds no engine.dispatch mark (this program opens none: the parent "
                   "of PR 53); nothing to join")
        return out
    leaf = {d["name"]: reduce.union((s, s + n) for _, s, n in reduce.leaf_ops(d["ops"])) for d in ts["devices"]}
    try:
        out.update(summarize(reduce, marks, modules, leaf, events, ts["lo"], ts["hi"], run["log"]))
    except Refusal as e:
        run["log"](f"engine_device_calls: REFUSES TO REPORT: {e}")
    return out
