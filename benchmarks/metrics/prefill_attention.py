"""kernels: the paged-attention template as the GPT's PREFILL program calls it
(PR 54: `GPT.prefill_paged_chunk` reads each chunk row's own pages through the
template's multi-row spec, kernels/decode_attention.py `paged_verify_attention`,
where it used to gather every row's page bucket in XLA). The call sits in an
innermost `jax.named_scope("prefill_attn")`, so its device events are named
`prefill_attn.<n>`: NOT `closed_call.<n>`, which paged_attention.py reads as
the decode kernel.

  prefill_attention_ms_per_token   summed device time of those events in the
                                   traced window per prompt token prefilled
                                   in it

The denominator comes from the args of the engine's `prefill.chunk` spans
(`tokens`), looked up in the newest `midgpt_tpu.obs.live()` recorder that holds
the window's spans (engine_device_calls.py `recorder_events`). The traced
extension follows the window with the same loop and the recorder stops with it,
so its calls are the `prefill.chunk` spans that start after the window's last
span.

No roofline share yet: the kernel's bytes and FLOPs belong in arithmetic.py,
which is the benchmark's own file (PERF.md section 7). A program whose trace
holds no such event (the parent of PR 54, every family but the GPT) reports
nothing.
"""

NAME = r"^prefill_attn\.\d+$"
INFO = {"hlo": r"custom-call\("}
SPAN = "prefill.chunk"


def traced_chunk_args(events, window_starts):
    """Args of the recorder ring's prefill.chunk spans that start after the
    window's last one (`window_starts`: the starts of the window's own)."""
    last = max(window_starts, default=float("inf"))
    chunks = sorted(((e[4], e[7] or {}) for e in events if e[0] == "X" and e[1] == SPAN), key=lambda c: c[0])
    return [a for t, a in chunks if t > last]


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "serve" or not run["spans"] or not ts:
        return None
    ns, calls = run["load"]("reduce.py").kernel_time(ts, ts["trace"], NAME, INFO)
    if not ns:
        return None
    # the ring of the newest live recorder that holds the window's spans (logs where there is none)
    events = run["load"]("metrics/engine_device_calls.py").recorder_events(run)
    if events is None:
        return None
    traced = traced_chunk_args(events, [s for n, s, _ in run["spans"] if n == SPAN])
    tokens = sum(a.get("tokens", 0) for a in traced)
    if not tokens:
        return None
    run["log"](f"prefill attention: {calls} events named prefill_attn.<n>, {ns / 1e6:.1f} ms for {tokens} "
               f"prompt tokens in {len(traced)} prefill calls of the traced extension")
    return {"prefill_attention_ms_per_token": ns / 1e6 / tokens}
