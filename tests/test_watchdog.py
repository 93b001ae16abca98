"""Hung-step watchdog unit pins (robustness/watchdog.py): deadline
arithmetic on a fake clock, passthrough/exception transparency, both
escalation modes, and the disabled-is-free contract. JAX-free — the
watchdog is pure host machinery, so these run in milliseconds."""

import threading

import pytest

from midgpt_tpu.robustness import watchdog as wd_mod
from midgpt_tpu.robustness.errors import StepHangError
from midgpt_tpu.robustness.watchdog import EXIT_CODE, StepWatchdog


class FakeClock:
    """Injected monotonic clock the hang closures can advance."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _hang_forever(clock, at=100.0):
    """A sync that never lands: advance the fake clock past any deadline,
    then park on a never-set event (the hung-device model)."""

    def fn():
        clock.t = at
        threading.Event().wait()

    return fn


def test_disabled_is_a_plain_call():
    calls = []
    wd = StepWatchdog(0.0, clock=lambda: calls.append(1) or 0.0)
    assert not wd.enabled
    assert wd.sync(lambda: "ok") == "ok"
    # no thread, no clock read, no counter: zero machinery when disabled
    assert calls == [] and wd.syncs == 0 and wd.expiries == 0


def test_passthrough_returns_value_and_counts():
    clock = FakeClock()
    wd = StepWatchdog(5.0, clock=clock, poll_s=0.001)
    assert wd.sync(lambda: 42) == 42
    assert wd.sync(lambda: None) is None
    assert wd.syncs == 2 and wd.expiries == 0


def test_worker_exception_propagates_unchanged():
    clock = FakeClock()
    wd = StepWatchdog(5.0, clock=clock, poll_s=0.001)

    def boom():
        raise FloatingPointError("divergence guard fired inside the sync")

    with pytest.raises(FloatingPointError, match="divergence guard"):
        wd.sync(boom)
    assert wd.expiries == 0  # an exception is a LANDED sync, not a hang


def test_expiry_raises_structured_steph_hang_error(tmp_path):
    clock = FakeClock()
    seen = []
    wd = StepWatchdog(
        5.0, clock=clock, poll_s=0.001, rundir=str(tmp_path),
        on_expire=lambda step, waited: seen.append((step, waited)),
    )
    with pytest.raises(StepHangError) as ei:
        wd.sync(_hang_forever(clock), step=12, label="train.loss_sync")
    e = ei.value
    assert e.step == 12 and e.waited_s >= 5.0 and e.rundir == str(tmp_path)
    assert "train.loss_sync" in str(e)
    assert wd.expiries == 1
    # the supervisor's HUNG-mark hook saw the expiry
    assert seen == [(12, e.waited_s)]
    # postmortem artifacts landed in the rundir
    assert (tmp_path / "flight_recorder.json").exists()
    assert (tmp_path / "flight_recorder.prom").exists()


def test_deadline_not_reached_is_not_an_expiry():
    """A slow-but-landing sync under the deadline returns normally: the
    fake clock advances to just UNDER the deadline before landing."""
    clock = FakeClock()
    wd = StepWatchdog(5.0, clock=clock, poll_s=0.001)

    def slow():
        clock.t = 4.9
        return "landed"

    assert wd.sync(slow) == "landed"
    assert wd.expiries == 0


def test_escalate_exit_hard_exits_with_exit_code(monkeypatch, capsys):
    clock = FakeClock()
    exited = []
    # os._exit cannot be caught; intercept it to observe the code
    monkeypatch.setattr(
        wd_mod.os, "_exit", lambda code: exited.append(code) or (_ for _ in ()).throw(SystemExit(code))
    )
    wd = StepWatchdog(5.0, escalate="exit", clock=clock, poll_s=0.001)
    with pytest.raises(SystemExit):
        wd.sync(_hang_forever(clock), step=3)
    assert exited == [EXIT_CODE]
    assert "hard-exiting" in capsys.readouterr().out


def test_escalate_validation():
    with pytest.raises(ValueError, match="escalate"):
        StepWatchdog(1.0, escalate="reboot")


def test_hang_error_is_runtime_error():
    """The supervisor (and chaos_run's catch) depend on the hierarchy."""
    e = StepHangError("x", step=1, waited_s=2.0, rundir="/r")
    assert isinstance(e, RuntimeError)
    assert e.step == 1 and e.waited_s == 2.0 and e.rundir == "/r"
