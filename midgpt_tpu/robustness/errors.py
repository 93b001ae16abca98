"""Exception types shared by the training loop, checkpointing, and the run
supervisor. Deliberately dependency-free (no jax import) so every layer can
import them without ordering constraints.
"""

from __future__ import annotations

import typing as tp


class DivergenceError(FloatingPointError):
    """Training produced a non-finite loss/grad (the sticky health carrier).

    Subclasses FloatingPointError so existing callers that catch/match the
    pre-supervisor divergence guard keep working; carries the structured
    fields the supervisor needs to roll back and skip the poisoned window.

    `step` is the loop iteration at which the poisoning was *noticed* (a log
    or save sync); the actual bad batch lies in (last_good_step, step] —
    stickiness guarantees it cannot be earlier than the last verified save.
    """

    def __init__(
        self,
        message: str,
        *,
        step: int,
        last_good_step: tp.Optional[int] = None,
        rundir: str = "",
    ):
        super().__init__(message)
        self.step = step
        self.last_good_step = last_good_step
        self.rundir = rundir


class StepHangError(RuntimeError):
    """A watchdog-guarded device sync did not land inside its deadline
    (robustness/watchdog.py) — the hung-device / wedged-dispatch failure
    mode that otherwise stalls a run forever.

    `step` is the loop iteration whose sync was armed (None for
    non-training guards, e.g. the serving engine's round sync); `waited_s` is how
    long the watchdog's clock says it waited before giving up, which is
    >= the configured deadline by at most one poll interval.
    """

    def __init__(
        self,
        message: str,
        *,
        step: tp.Optional[int] = None,
        waited_s: float = 0.0,
        rundir: str = "",
    ):
        super().__init__(message)
        self.step = step
        self.waited_s = waited_s
        self.rundir = rundir


class CheckpointCorruptError(ValueError):
    """A checkpoint failed its manifest verification (missing/truncated/
    bit-flipped item). `problems` lists one human-readable line per
    mismatch."""

    def __init__(self, message: str, *, step: int, problems: tp.Sequence[str] = ()):
        super().__init__(message)
        self.step = step
        self.problems = list(problems)


class CheckpointWriteError(OSError):
    """A checkpoint save still failed after the configured retry budget.

    `step` is the step whose save was abandoned, `attempts` the retry
    budget that was exhausted (training/checkpoint.py `write_retries`),
    and `directory` the checkpoint root — the fields the supervisor's
    emergency-save path and the chaos gate (`ckpt_enospc*2`) report
    without re-parsing the message.
    """

    def __init__(
        self,
        message: str,
        *,
        step: int,
        attempts: int,
        directory: str = "",
    ):
        super().__init__(message)
        self.step = step
        self.attempts = attempts
        self.directory = directory


class SimulatedPreemption(BaseException):
    """Raised by the `kill_mid_save` fault to model the process dying between
    the TensorStore write and the manifest commit.

    Subclasses BaseException (like KeyboardInterrupt) on purpose: a real
    SIGKILL is not catchable, so no `except Exception` recovery path may
    swallow its simulation either — only the fault-injection tests catch it
    explicitly.
    """
