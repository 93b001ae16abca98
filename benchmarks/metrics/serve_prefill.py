"""model step (serve), the prefill side: the share of the window the host spent
blocked in the first-token sample after a prompt's last chunk (the engine's
`prefill.first_token` spans: the force of the final chunk's logits plus the
host-side sample), and the share of the traced window's device time that ran
the prefill program.

The device share comes from the trace's `XLA Modules` line: one event per
execution of a compiled program, named after the jitted function
(`jit__serve_prefill_chunk(<id>)` for sampling/serve.py `_serve_prefill_chunk`),
clipped to the traced window; prefill executions over all executions, mean over
the chips. (The v5e trace's op events carry no `tf_op` / `hlo_module`, my chip
run, PR 24, and two programs reuse instruction names, so the ops cannot be put
to their program; the module line can.) reduce.py does not load that line, so
this reader opens the run's xplane file itself. A trace without the line (the
CPU rehearsal) reports nothing."""

import os

PROGRAM = "_serve_prefill_chunk"
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".work", "trace")


def module_events(path):
    """[(plane, module name, start_ns, duration_ns)] of the TPU planes' `XLA Modules` lines."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out.extend((plane.name, e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events)
    return out


def prefill_share(events, lo, hi):
    """100 x prefill-program time / all-program time inside [lo, hi); None without events."""
    total = prefill = 0
    for _, name, s, d in events:
        inside = min(s + d, hi) - max(s, lo)
        if inside > 0:
            total += inside
            prefill += inside if PROGRAM in name else 0
    return 100.0 * prefill / total if total else None


def read(run):
    if run["kind"] != "serve":
        return None
    out = {}
    sync = [d for n, _, d in run["spans"] if n == "prefill.first_token"]
    if sync and run["window_s"] > 0:
        out["prefill.first_token_sync_share"] = 100.0 * sum(sync) / run["window_s"]
    ts = run.get("trace_summary")
    if not ts:
        return out
    try:
        events = module_events(run["load"]("reduce.py").find_xplane(TRACE_DIR))
    except FileNotFoundError:
        events = []
    share = prefill_share(events, ts["lo"], ts["hi"])
    if share is None:
        run["log"]("serve_prefill: the trace has no XLA Modules line on a TPU plane; "
                   "serve.prefill_device_share left out")
    else:
        run["log"]("programs in the traced window: " + ", ".join(sorted({n.split("(")[0] for _, n, _, _ in events})))
        out["serve.prefill_device_share"] = share
    return out
