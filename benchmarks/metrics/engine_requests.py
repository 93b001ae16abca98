"""serving engine, from spans the ENGINE opens (sampling/serve.py): how long a
request waited to be admitted and how long from admission to its first token
(the async `req.queue` / `req.prefill` tracks, id = uid), and how long a
scheduler round is and what of it is the scheduler's own (`engine.round` and
the phase spans nested in it).

`req.queue_ms_mean` + `req.prefill_ms_mean` ARE the mean time to first token
(the two stand for it in the per-layer table: their sum read equal to the
client's mean to four digits in all seven serving cells, ledger, PR 57; the
cells still print the client's on their `ttft ms mean ... p50 ... p90 ... max`
line, and `serve_124m_sample` reports it end to end), so they are means over
the SAME requests: those submitted and first answered inside the window. The
engine stamps both tracks with the clock readings it hands the client
(`on_token`), so per request queue + prefill is the client's time to first
token to within the microseconds between the client's own clock read and
`submit`'s. A request preempted before its first token has several
queue and prefill legs; they are summed.

The async tracks are not in `run["spans"]` (it holds complete spans only), and
a reader gets no handle on the engine, so they are read from the newest
`midgpt_tpu.obs.live()` recorder that holds such tracks: a process-global seam
until the cells hand `obs` to the readers (PERF.md, Open questions). A program
without `live()` or without the tracks (the parent of PR 24) reports nothing.
The window is placed from `run["spans"]`: it opened between the end of the last
span before its first span and that first span's start (only the window's own
bookkeeping and the first submits lie between), and lasts `run["window_s"]`.
"""

import statistics

# the spans the engine opens directly under `engine.round` (their own children,
# `prefill.chunk`, `trie.match`, `spec.*_enqueue`, lie inside these)
PHASES = ("engine.expire", "engine.admit", "engine.prefill",
          "decode.dispatch", "decode.device_wait", "decode.host_post",
          "spec.dispatch", "spec.device_wait", "spec.host_post")


def rounds(spans):
    """[(duration, self time)] of the `engine.round` spans; self time is the
    duration minus the parts of it the phase spans cover."""
    rs = sorted((s, s + d) for n, s, d in spans if n == "engine.round")
    kids = sorted((s, s + d) for n, s, d in spans if n in PHASES)
    out, k = [], 0
    for a, b in rs:
        while k < len(kids) and kids[k][0] < a:
            k += 1
        covered, j = 0.0, k
        while j < len(kids) and kids[j][0] < b:
            covered += min(kids[j][1], b) - kids[j][0]
            j += 1
        k = j
        out.append((b - a, b - a - covered))
    return out


def request_legs(events, lo, hi):
    """{uid: {"req.queue": s, "req.prefill": s}} for requests whose first
    `req.queue` began at or after `lo` and whose first token (the end of a
    `req.prefill` leg that has `prompt_tokens` in its args) came before `hi`."""
    open_, legs, first_queue, first_token = {}, {}, {}, {}
    for e in events:
        kind, name, t, uid = e[0], e[1], e[4], e[6]
        if name not in ("req.queue", "req.prefill"):
            continue
        if kind == "b":
            open_[(uid, name)] = t
            if name == "req.queue":
                first_queue.setdefault(uid, t)
        elif kind == "e" and (uid, name) in open_ and uid not in first_token:
            legs.setdefault(uid, {"req.queue": 0.0, "req.prefill": 0.0})[name] += t - open_.pop((uid, name))
            if name == "req.prefill" and "prompt_tokens" in (e[7] or {}):
                first_token[uid] = t
    return {u: legs[u] for u, t in first_token.items()
            if first_queue.get(u, lo - 1.0) >= lo and t < hi}


def read(run):
    if run["kind"] != "serve":
        return None
    out = {}
    rs = rounds(run["spans"])
    if rs:
        out["engine.round_ms_p50"] = 1e3 * statistics.median(r[0] for r in rs)
        out["engine.round_self_ms_p50"] = 1e3 * statistics.median(r[1] for r in rs)
    else:
        run["log"]("engine_requests: no engine.round span in the window; round metrics left out")
    if not run["spans"]:
        return out
    try:
        from midgpt_tpu.obs import live
    except ImportError:
        run["log"]("engine_requests: this program has no obs.live(); req.* left out")
        return out
    first = min(s for _, s, _ in run["spans"])
    for obs in reversed(live()):
        events = obs.tracer.events()
        if any(e[1] == "req.prefill" for e in events):
            break
    else:
        run["log"]("engine_requests: no live recorder holds req.* tracks; req.* left out")
        return out
    before = [e[4] + e[5] for e in events if e[0] == "X" and e[4] < first]
    lo = max(before) if before else first
    legs = request_legs(events, lo, first + run["window_s"])
    if not legs:
        run["log"]("engine_requests: no request was submitted and first answered inside the window")
        return out
    out["req.queue_ms_mean"] = 1e3 * statistics.fmean(v["req.queue"] for v in legs.values())
    out["req.prefill_ms_mean"] = 1e3 * statistics.fmean(v["req.prefill"] for v in legs.values())
    run["log"](f"requests submitted and first answered in the window: {len(legs)}; ms mean: queue "
               f"{out['req.queue_ms_mean']:.1f} + prefill {out['req.prefill_ms_mean']:.1f} = "
               f"{out['req.queue_ms_mean'] + out['req.prefill_ms_mean']:.1f} (client's ttft mean "
               f"{1e3 * statistics.fmean(run['samples']['ttft_s']):.1f}); recorder dropped "
               f"{obs.tracer.dropped} events")
    return out
