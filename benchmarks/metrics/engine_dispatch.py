"""serving engine, the host side of a round seen from inside (PR 36): what rode
each decode round, what its dispatch and its commit are made of, and what a
prefill call costs before its enqueue.

The engine says it in the `args` of spans it already emits
(`midgpt_tpu/obs/__init__.py` `record_round`, `sampling/serve.py`):

  decode.dispatch    steps (the program ran), slots (active in it), chunk (the
                     most steps it could run), limit ("chunk", "remaining",
                     "block"; the grouped dispatch: "chunk", "need")
  decode.host_post   tokens (committed), finished (requests that ended),
                     callback_s (seconds of the commit inside the CLIENT's
                     on_token: here the load generator's own bookkeeping)
  decode.assemble / .key / .put / .enqueue   children tiling decode.dispatch
  prefill.assemble   a prefill call's host time before `prefill.chunk` opens
  prefill.chunk      rows (slot-chunks that rode the program), tokens, bucket

`run["spans"]` carries (name, start, duration) and no args, and a reader gets
no handle on the engine, so the window's events are looked up by (name, start)
in the newest `midgpt_tpu.obs.live()` recorder that holds them: the seam
`engine_requests.py` uses, not a new one (PERF.md, Open questions). A program
whose spans carry no such args (the parent of PR 36) reports the two span
medians that need none and says which metrics it leaves out.
"""

import collections
import statistics

CHILDREN = ("assemble", "key", "put", "enqueue")


def window_events(run):
    """The window's complete events as ring tuples, or None with a log line."""
    try:
        from midgpt_tpu.obs import live
    except ImportError:
        run["log"]("engine_dispatch: this program has no obs.live(); span args left out")
        return None
    want = {(n, s) for n, s, _ in run["spans"]}
    for obs in reversed(live()):
        events = [e for e in obs.tracer.events() if e[0] == "X" and (e[1], e[4]) in want]
        if events:
            return events
    run["log"]("engine_dispatch: no live recorder holds the window's spans; span args left out")
    return None


def median_ms(durations):
    return 1e3 * statistics.median(durations) if durations else None


def read(run):
    if run["kind"] != "serve" or not run["spans"]:
        return None
    by_name = {}
    for n, _, d in run["spans"]:
        by_name.setdefault(n, []).append(d)
    out = {"decode.dispatch_ms_p50": median_ms(by_name.get("decode.dispatch")),
           "decode.host_post_ms_p50": median_ms(by_name.get("decode.host_post")),
           "prefill.assemble_ms_p50": median_ms(by_name.get("prefill.assemble"))}
    for part in CHILDREN:
        out[f"decode.{part}_ms_p50"] = median_ms(by_name.get(f"decode.{part}"))
    if by_name.get("decode.dispatch") and out["decode.assemble_ms_p50"] is None:
        run["log"]("engine_dispatch: decode.dispatch has no children in this program; "
                   "decode.assemble / key / put / enqueue left out")
    if by_name.get("prefill.chunk") and out["prefill.assemble_ms_p50"] is None:
        run["log"]("engine_dispatch: this program opens no prefill.assemble span; left out")

    args = collections.defaultdict(list)  # span name -> the args of its events that carry any
    for e in window_events(run) or []:
        if e[7]:
            args[e[1]].append(e[7])
    rounds = [a for a in args["decode.dispatch"] if "steps" in a]
    commits = [a for a in args["decode.host_post"] if "tokens" in a]
    calls = [a for a in args["prefill.chunk"] if "rows" in a]
    if rounds:
        out["decode.steps_per_round_mean"] = statistics.fmean(a["steps"] for a in rounds)
        out["decode.tail_limited_share"] = 100.0 * sum(
            a["limit"] == "remaining" and a["steps"] < a["chunk"] for a in rounds) / len(rounds)
    elif by_name.get("decode.dispatch"):
        run["log"]("engine_dispatch: decode.dispatch carries no steps / limit in this program; "
                   "decode.steps_per_round_mean, decode.tail_limited_share, decode.round_fill left out")
    if commits:
        tokens = sum(a["tokens"] for a in commits)
        host_s = sum(by_name["decode.dispatch"]) + sum(by_name["decode.host_post"])
        if tokens:
            out["decode.host_ms_per_token"] = 1e3 * host_s / tokens
        out["decode.callback_share"] = 100.0 * sum(a["callback_s"] for a in commits) / sum(by_name["decode.host_post"])
        if rounds:
            # useful outcomes over attempts: tokens committed over the tokens the
            # rounds' programs had room for (every slot, every step of the chunk)
            room = run["counters"]["max_slots"] * sum(a["chunk"] for a in rounds)
            out["decode.round_fill"] = 100.0 * tokens / room
    elif by_name.get("decode.host_post"):
        run["log"]("engine_dispatch: decode.host_post carries no tokens / callback_s in this program; "
                   "decode.host_ms_per_token, decode.callback_share, decode.round_fill left out")
    if calls:
        out["prefill.rows_per_call_mean"] = statistics.fmean(a["rows"] for a in calls)
    elif by_name.get("prefill.chunk"):
        run["log"]("engine_dispatch: prefill.chunk carries no rows in this program; "
                   "prefill.rows_per_call_mean left out")
    if rounds and commits:
        count = lambda key: dict(sorted(collections.Counter(a[key] for a in rounds).items()))
        run["log"](f"decode rounds in the window: {len(rounds)}; steps {count('steps')}; "
                   f"limit {count('limit')}; slots a round "
                   f"{statistics.fmean(a['slots'] for a in rounds):.1f}; tokens a round "
                   f"{sum(a['tokens'] for a in commits) / len(commits):.1f}; requests ended "
                   f"{sum(a['finished'] for a in commits)}")
    return out
