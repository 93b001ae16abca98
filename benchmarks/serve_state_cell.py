"""Serving cells of a family with a STATE kind of cache (traffic kind
"serve_state": a recurrent layer's memory is a row a SLOT, not a page a token;
`midgpt_tpu/sampling/pages.py` "State kinds"): `serve_family_cell.py`'s run,
reference, `judge` and control as they are, with an `engine_logits` of this
kind's own in place and limits of this family's own, and `compared` returned so
that the numbers stand last in the result line.

What the engine's check must see here and `serve_family_cell.py`'s cannot: a
stale page is masked by its slot's length, a stale state row is a wrong answer.
So BEFORE the compared requests the check engine admits `max_slots` throw-away
requests, all live at once (one a slot): every state row is DIRTY. Those in
the LAST slots (as many as there are compared prompts) are short and finish;
the others go on decoding and HOLD the low slots, as the timed window's
neighbours do. The compared requests are submitted then, so they are admitted
to the high rows the short ones left dirty, and a row that was not reset shows
in that request's every logit; they prefill and decode beside a batch of
active slots (20 of 24 in the cell), so a row written at the wrong index, an
inactive slot's row touched, or a decode fault that needs many active slots
shows too. The compared prompts (the traffic file's `check.prompts`: 200,
1,300, 2,600 and 4,800 tokens, all live at once, `check.decode_rounds` decode
rounds) cross 1 to 9 chunk boundaries, so a chunk's state lost or carried
wrongly, or the convolution's history cut at the wrong row, shows; the slots
that still prefill sit out the others' decode rounds with their rows untouched.
When the last compared request has finished the neighbours are cancelled. The
check asks that the pool owner counted one reset an admission, that every row
was held at once, and that every neighbour was still live at that moment.
Compared, per request, as `serve_family_cell.py` compares: the prefill
program's logits at the prompt's last position and the logits of the first
step of every later decode round, against the float32 reference's full forward
of the tokens the engine produced.

The limits, as shares of the reference logits' standard deviation over all
compared rows, each between two readings on the chip (my chip runs, PR 59,
`serve_olmo_hybrid_docchat`; PERF.md section 6 PR 59 has the seeds): bf16
weights, keys, values and convolution history, the state float32, through 16
layers of which every one adds a NORMED branch to the stream (no row is off
by a decision taken the other way: nothing here selects). The program's check
read RMS 2.78e-2 to 3.01e-2 and a largest logit of 1.50e-1 to 1.80e-1 over
fourteen seeds (nine with the compared requests alone in the engine, as this
file's first form ran them; five beside 20 decoding neighbours: the same
range); the reference with 8-bit matrices (`float8_e4m3fn`) against itself RMS
4.84e-1 to 4.91e-1, largest logit 2.45 to 2.72 (three seeds), which must fail.
RMS limit 1.2e-1: 4.0 times over the program's largest reading and 4.0 under
the control's smallest; largest logit 6.5e-1: 3.6 over, 3.8 under.

    python3 benchmarks/serve_state_cell.py --workload <cell> --seed <n>

is that control (exit 0 = the program is correct AND the 8-bit reference is not).
"""

from __future__ import annotations

import gc
import os
import sys

import numpy as np

# error / std of the reference logits over the 32 compared rows: RMS, and the largest compared logit; each the
# geometric middle of the program's largest reading and the 8-bit control's smallest (module docstring)
RMS_TOLERANCE, MAX_TOLERANCE = 1.2e-1, 6.5e-1


def _with_dirty_rows(family):
    """`serve_family_cell.py` under this kind's limits, its `engine_logits`
    replaced by one that dirties every state row first and its
    `check_engine_path` by one that also holds the pool owner's counts and
    keeps what it compared."""
    family.RMS_TOLERANCE, family.MAX_TOLERANCE = RMS_TOLERANCE, MAX_TOLERANCE
    compared = {}

    def engine_logits(ctx, mc, params, es, check):
        rng = np.random.default_rng([ctx.seed32, 11])
        n_new = 1 + int(check["decode_rounds"]) * int(es["decode_chunk"])
        prompts = [rng.integers(0, mc.vocab_size, min(int(p), mc.block_size - n_new - 1), dtype=np.int32)
                   for p in check["prompts"]]
        got = {}  # uid -> [(row, logits)]
        on_first = lambda uid, row: got[uid].append((len(by_uid[uid]) - 1, np.array(row, np.float32))) if uid in got else None
        eng = family.make_engine(ctx, mc, params, es, on_first_logits=on_first)
        # every state row dirty: one throw-away request a slot, all live at once. The first `hold` of them go on
        # decoding (the compared requests' neighbours); the last ones end after one decode round and leave their
        # rows, the HIGH ones, dirty for the compared requests
        slots, chunk, group = int(es["max_slots"]), int(es["prefill_chunk"]), int(es["decode_chunk"])
        hold = max(0, slots - len(prompts))
        throwaway = lambda i: rng.integers(0, mc.vocab_size, max(2, min(chunk // 4 + i, mc.block_size // 2)), dtype=np.int32)
        neighbours = [eng.submit(p, min(64 * group, mc.block_size - len(p) - 1)) for p in map(throwaway, range(hold))]
        short = [eng.submit(throwaway(i), 1 + group) for i in range(hold, slots)]
        while not all(uid in eng.finished for uid in short):
            eng.step()
        dirty_live = int(eng.serve_counters()["state.rows_live_max"])  # rows held at once: the pool owner's count
        by_uid = {eng.submit(p, n_new): p for p in prompts}
        got.update({uid: [] for uid in by_uid})
        live_max, slots_of, neighbours_live = 0, {}, hold
        while not all(uid in eng.finished for uid in by_uid):
            fed = {s.request.uid: s.length for s in eng.slots if s is not None}  # the row a slot's next step feeds
            slots_of.update({s.request.uid: i for i, s in enumerate(eng.slots) if s is not None and s.request.uid in by_uid})
            for uid, row in eng.next_logits().items():
                if uid in by_uid:
                    got[uid].append((fed[uid], row))
            live_max = max(live_max, sum(s is not None for s in eng.slots))
            eng.step()
            neighbours_live = min(neighbours_live, sum(s is not None and s.request.uid in neighbours for s in eng.slots))
        for uid in neighbours:
            eng.cancel(uid)
        eng.run()
        seqs = [np.asarray(eng.finished[uid].tokens, np.int32) for uid in by_uid]
        rows = [np.asarray([r for r, _ in got[uid]], np.int32) for uid in by_uid]
        logits = np.concatenate([np.stack([l for _, l in got[uid]]) for uid in by_uid])
        counted = dict(eng.serve_counters(), prompts=[len(p) for p in prompts], preemptions=eng.stats()["preemptions"],
                       live_max=live_max, dirty_live=dirty_live, attn=eng.attn_impl, programs=eng.compile_stats(),
                       conserved=eng.pool.conserved(eng.slots), hold=hold, neighbours_live=neighbours_live,
                       compared_slots=[slots_of[uid] for uid in by_uid])
        del eng  # its pools leave the device before the reference's float32 layers arrive
        gc.collect()
        return seqs, rows, logits, counted

    def check_engine_path(ctx, mc, params, es, check, control=None):
        seqs, rows, got, counted = engine_logits(ctx, mc, params, es, check)
        want = family.reference_logits(ctx, params, mc, seqs, rows)
        rms, worst, ok = family.judge(got, want)
        slots, chunk = int(es["max_slots"]), int(es["prefill_chunk"])
        resets_want = slots + len(seqs)
        books = (counted.get("state.resets") == resets_want and counted["dirty_live"] == slots and counted["conserved"]
                 and counted.get("state.rows_live") == 0 and counted["neighbours_live"] == counted["hold"])
        served = ok and counted["preemptions"] == 0 and books
        ctx.log(f"correctness: ServeEngine ({counted['attn']}; first {slots} throw-away requests, {counted['dirty_live']} live at "
                f"once: every state row dirty; {counted['hold']} of them held their slots and decoded on ({counted['neighbours_live']} "
                f"still live when the last compared request finished); then prompts of {counted['prompts']} tokens "
                f"({[-(-p // chunk) - 1 for p in counted['prompts']]} chunk boundaries crossed) admitted to slots "
                f"{counted['compared_slots']} and served incl. {int(check['decode_rounds'])} decode rounds of {es['decode_chunk']}, up to "
                f"{counted['live_max']} of {slots} slots live, chunks of {chunk}, pages of {es['page_size']}, {es['cache_dtype']} "
                f"pools; rows reset, one an admission: {counted.get('state.resets')} (want {resets_want}), rows held now "
                f"{counted.get('state.rows_live')}, books conserved {counted['conserved']}, {counted.get('state.bytes_per_slot')} B of state "
                f"a slot, preemptions {counted['preemptions']}) vs float32 reference logits of the same sequences, {got.shape[0]} rows: "
                f"error/std rms {rms:.3e} (limit {RMS_TOLERANCE:.1e}), max {worst:.3e} (limit {MAX_TOLERANCE:.1e}) -> "
                f"{'ok' if served else 'NOT CORRECT'}")
        compared.update({"logits_rms_over_std": {"value": rms, "limit": RMS_TOLERANCE},
                         "logits_max_over_std": {"value": worst, "limit": MAX_TOLERANCE},
                         "check_preemptions": {"value": counted["preemptions"], "limit": 0},
                         "state_rows_reset": {"value": counted.get("state.resets"), "limit": resets_want},
                         "neighbours_live": {"value": counted["neighbours_live"], "limit": counted["hold"]}})
        if control is None:
            gc.collect()  # the reference's programs and float32 layers leave before the timed engine's pools arrive
            return served, None
        c_rms, c_worst, c_ok = family.judge(family.reference_logits(ctx, params, mc, seqs, rows, round_to=control), want)
        ctx.log(f"control: the reference with its matrices rounded to {np.dtype(control).name} in the program's place, "
                f"same rows and limits: error/std rms {c_rms:.3e}, max {c_worst:.3e} -> {'ok' if c_ok else 'NOT CORRECT'}")
        return served, c_ok

    family.engine_logits, family.check_engine_path = engine_logits, check_engine_path
    return family, compared


def run(ctx) -> dict:
    family, compared = _with_dirty_rows(ctx.load("serve_family_cell.py"))
    out = family.run(ctx)
    c = out["counters"]
    ctx.log(f"state kind: {int(c.get('state.rows', 0))} rows of {int(c.get('state.bytes_per_slot', 0))} B (as the arrays declare them), "
            f"{int(c.get('state.rows_live_max', 0))} held at the peak, {int(c.get('state.resets', 0))} admissions (each reset by its first chunk); "
            f"linear layers took {int(c.get('gdn.prefill_tokens', 0))} prompt tokens in {int(c.get('gdn.prefill_chunks', 0))} chunks "
            f"and {int(c.get('gdn.decode_tokens', 0))} decode steps of an active slot")
    import jax

    m = jax.local_devices()[0].memory_stats() or {}  # what the chip holds and reserves NOW (the timed engine is gone) beside the run's peaks
    ctx.log("memory after the window, bytes: " + ", ".join(f"{k} {int(m[k])}" for k in (
        "bytes_in_use", "bytes_reserved", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit") if k in m))
    compared["failed_requests"] = {"value": out["failed"], "limit": 0}
    out["compared"] = compared
    return out


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as harness

    return _with_dirty_rows(harness.load_module(os.path.join(here, "serve_family_cell.py")))[0].main()


if __name__ == "__main__":
    raise SystemExit(main())
