"""Hung-step watchdog: a bounded deadline around device syncs.

A wedged dispatch — hung device, stuck collective, device restart —
blocks the *sync point* (`float(arr)` / `np.asarray(arr)` /
`block_until_ready`), and a Python thread cannot interrupt a
main thread parked inside that native wait. So the guard inverts control:
`StepWatchdog.sync(fn)` runs the sync in a fresh daemon worker thread and
bounds the main thread's wait on it. If the worker doesn't land inside
`deadline_s` (measured on the injected clock), the watchdog

  1. dumps the process-global flight recorder (`flight_recorder.json` +
     `.prom`) into `rundir` for the postmortem,
  2. calls the optional `on_expire(step, waited_s)` hook (the supervisor
     ledger's HUNG mark rides this),
  3. escalates: `escalate="raise"` raises StepHangError in the *caller* —
     the supervisor treats it like a divergence and restarts from the last
     verified checkpoint; `escalate="exit"` hard-exits with EXIT_CODE for
     a cluster layer that restarts whole processes (a wedged native wait
     cannot be unwound, so sys.exit would just hang in atexit).

The abandoned worker is a daemon thread: it either lands late (into a box
nothing reads anymore — each sync gets a fresh one) or stays parked until
process exit without blocking it.

Cost discipline: `deadline_s <= 0` disables the guard and `sync` degrades
to a plain call — no thread, no clock read, nothing. The watchdog is
host-side only and JAX-free: arming it compiles zero XLA programs and adds
zero jit statics (pinned with the obs-off pin in tests/test_robustness.py).
Clock-injected per the observability discipline (graftcheck GC012): the
defaults reference `time.monotonic` but the module never *calls* into the
`time` module, so deadline arithmetic is testable on a fake clock.
"""

from __future__ import annotations

import os
import threading
import time
import typing as tp

from midgpt_tpu.robustness.errors import StepHangError

# Distinct from ordinary failure exits so a supervisor/cluster layer can
# tell "hung device" from "crashed python" without parsing logs.
EXIT_CODE = 17


class StepWatchdog:
    """Deadline guard for device syncs (module docstring has the model).

    One instance guards one run; `sync` may be called from exactly one
    thread at a time (the train/engine loop — there is one sync point per
    step by design)."""

    def __init__(
        self,
        deadline_s: float,
        *,
        escalate: str = "raise",
        rundir: str = "",
        clock: tp.Callable[[], float] = time.monotonic,
        poll_s: float = 0.05,
        on_expire: tp.Optional[tp.Callable[[tp.Optional[int], float], None]] = None,
    ):
        if escalate not in ("raise", "exit"):
            raise ValueError(
                f"unknown escalate {escalate!r} ('raise' or 'exit')"
            )
        self.deadline_s = deadline_s
        self.escalate = escalate
        self.rundir = rundir
        self.poll_s = poll_s
        self.on_expire = on_expire
        self._clock = clock
        self.syncs = 0
        self.expiries = 0

    @property
    def enabled(self) -> bool:
        return self.deadline_s > 0

    def sync(
        self,
        fn: tp.Callable[[], tp.Any],
        *,
        step: tp.Optional[int] = None,
        label: str = "step",
    ) -> tp.Any:
        """Run `fn` (a device sync) under the deadline; return its result.

        Disabled watchdog: a plain call, zero machinery. An exception from
        `fn` itself (e.g. the divergence guard's float() of a NaN carrier
        raising downstream) propagates unchanged."""
        if not self.enabled:
            return fn()
        self.syncs += 1
        box: tp.Dict[str, tp.Any] = {}
        landed = threading.Event()

        def _worker() -> None:
            try:
                box["value"] = fn()
            except BaseException as e:  # propagate to the caller, not the log
                box["error"] = e
            finally:
                landed.set()

        t0 = self._clock()
        threading.Thread(
            target=_worker, daemon=True, name=f"midgpt-watchdog-{label}"
        ).start()
        while not landed.wait(self.poll_s):
            waited = self._clock() - t0
            if waited >= self.deadline_s:
                return self._expire(step, label, waited)
        if "error" in box:
            raise box["error"]
        return box.get("value")

    def _expire(self, step: tp.Optional[int], label: str, waited: float):
        self.expiries += 1
        # Postmortem artifacts FIRST — the raise/exit below may be the last
        # thing this process does. Deferred import keeps module import free.
        from midgpt_tpu.obs import dump_flight_recorder, flight_recorder

        flight_recorder().tracer.instant(
            "watchdog.expired", "watchdog", "train",
            args={
                "step": step, "label": label,
                "deadline_s": self.deadline_s,
                "waited_s": round(waited, 3),
            },
        )
        if self.rundir and not self.rundir.startswith("gs://"):
            dump_flight_recorder(self.rundir)
        if self.on_expire is not None:
            self.on_expire(step, waited)
        msg = (
            f"device sync '{label}' did not land within "
            f"{self.deadline_s:g}s (waited {waited:.3f}s"
            + (f" at step {step}" if step is not None else "")
            + ") — wedged dispatch or hung device. Flight recorder "
            + (f"dumped to {self.rundir}." if self.rundir else "not dumped "
               "(no rundir).")
        )
        if self.escalate == "exit":
            print(f"watchdog: {msg} hard-exiting {EXIT_CODE}.", flush=True)
            os._exit(EXIT_CODE)
        raise StepHangError(
            msg, step=step, waited_s=waited, rundir=self.rundir
        )
