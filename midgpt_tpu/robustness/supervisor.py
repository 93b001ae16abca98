"""Run supervisor: restart-on-divergence with data-window skip.

`supervise(config)` wraps `train(config)` in a bounded restart policy:

  1. `train` raises DivergenceError when the sticky health carrier goes
     non-finite (training/train.py). The poisoned batch lies in
     `(last_good_step, step]` — stickiness guarantees nothing before the
     last verified checkpoint can be bad.
  2. The supervisor rolls back by simply re-entering `train`: resume picks
     `latest_verified_step()` automatically. It advances
     `config.data_step_offset` so the replayed iterations sample data PAST
     the detected window (train threads `itr + data_step_offset` into the
     positional sampler and the dropout key stream), exactly as if the
     poisoned shard had been cut out of the stream — deterministically,
     because the offset is plain config.
  3. Attempts share one TrainRuntime, so the rollback path reuses the
     already-compiled train step — zero recompiles per restart (pinned in
     tests/test_robustness.py).
  4. After `max_restarts` rollbacks (or a divergence with no verified
     checkpoint to return to) it fails loudly with a diagnosis of every
     skipped window, so an operator can tell data poisoning apart from an
     optimization-level divergence (bad lr/warmup shifts with the data and
     keeps recurring).

The rollback ledger (current offset + skipped windows) is persisted to
`rundir/supervisor_state.json`, so a supervisor relaunched after a
preemption resumes with the same skips and the trajectory stays exactly
reproducible. A corrupt ledger (truncated write, disk damage) is
quarantined to `supervisor_state.json.corrupt` with a warning and the run
proceeds on a fresh ledger — a damaged sidecar must never brick a resume
whose checkpoints are intact.

Beyond divergence, the supervisor handles two more failure families:

* **Hung steps** (StepHangError from the watchdog, robustness/watchdog.py):
  restart WITHOUT advancing the data offset — a wedged device sync says
  nothing about the data, so the replay re-runs the same window from the
  last verified checkpoint. Each hang is marked in the ledger
  (`hung_steps`) and counts against the same `max_restarts` budget.
* **Topology changes** (elastic resume): each attempt's mesh geometry is
  recorded in the ledger (`mesh` / `mesh_history`). On resume with a
  DIFFERENT device count, `on_resume_mesh="same"` (default) refuses
  loudly; `"any"` rebuilds the runtime with the data axis re-derived for
  the new count (make_runtime's `devices=` path) and restores the
  checkpoint through the new mesh's shardings.
"""

from __future__ import annotations

import json
import os
import time
import typing as tp

from midgpt_tpu.config import ExperimentConfig
from midgpt_tpu.obs import dump_flight_recorder, flight_recorder
from midgpt_tpu.robustness import faults
from midgpt_tpu.robustness.errors import DivergenceError, StepHangError
from midgpt_tpu.training.train import TrainRuntime, make_runtime, train

STATE_NAME = "supervisor_state.json"


def _state_path(rundir: str) -> tp.Optional[str]:
    if not rundir or rundir.startswith("gs://"):
        return None
    return os.path.join(rundir, STATE_NAME)


def _load_state(rundir: str) -> tp.Dict[str, tp.Any]:
    path = _state_path(rundir)
    if path is None or not os.path.exists(path):
        return {}
    try:
        with open(path) as fh:
            state = json.load(fh)
        if not isinstance(state, dict):
            raise ValueError(f"expected a JSON object, got {type(state).__name__}")
        return state
    except (json.JSONDecodeError, ValueError, OSError) as e:
        # A damaged ledger must never brick a resume whose CHECKPOINTS are
        # intact (the ledger is a sidecar, not the source of truth).
        # Quarantine the bytes for postmortems and start a fresh ledger —
        # losing the skip history is recoverable (the supervisor re-detects
        # a recurring divergence); refusing to start is not.
        quarantine = path + ".corrupt"
        try:
            os.replace(path, quarantine)
        except OSError:
            quarantine = "(could not quarantine)"
        print(
            f"WARNING: supervisor ledger {path} is corrupt ({e}); "
            f"quarantined to {quarantine} and starting a fresh ledger"
        )
        return {}


def append_note(rundir: str, note: tp.Dict[str, tp.Any]) -> None:
    """Append an operator-visible event to the ledger's `notes` list (e.g.
    train's preempt_grace_s save-skip) — load/modify/atomic-replace, so a
    note survives later supervisor state writes."""
    if _state_path(rundir) is None:
        return
    state = _load_state(rundir)
    state.setdefault("notes", []).append(dict(note))
    _save_state(rundir, state)


def _save_state(rundir: str, state: tp.Dict[str, tp.Any]) -> None:
    path = _state_path(rundir)
    if path is None:
        return
    os.makedirs(rundir, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(state, fh, indent=1)
    os.replace(tmp, path)


def supervise(
    config: ExperimentConfig,
    *,
    runtime: tp.Optional[TrainRuntime] = None,
    max_restarts: tp.Optional[int] = None,
    backoff_sec: tp.Optional[float] = None,
    sleep_fn: tp.Callable[[float], None] = time.sleep,
) -> dict:
    """Run `train(config)` under the restart policy (module docstring).

    Returns train's result dict with a `"supervisor"` summary added.
    `max_restarts`/`backoff_sec` default to the config knobs; `sleep_fn` is
    injectable so tests don't pay real backoff."""
    import jax  # deferred: keep module import JAX-free for tools

    if max_restarts is None:
        max_restarts = config.max_restarts
    if backoff_sec is None:
        backoff_sec = config.restart_backoff_sec
    # Activate the fault plan ONCE per supervised run (not per attempt): a
    # consumed fault must stay consumed across rollbacks, like the real
    # failure it models.
    plan = config.fault_plan or os.environ.get("MIDGPT_FAULTS", "")
    if plan:
        faults.activate_plan(plan)

    persisted = _load_state(config.rundir)
    offset = max(config.data_step_offset, int(persisted.get("data_step_offset", 0)))
    windows: tp.List[tp.List[int]] = [
        list(w) for w in persisted.get("windows_skipped", [])
    ]
    restarts = int(persisted.get("restarts", 0))
    hung: tp.List[int] = [int(s) for s in persisted.get("hung_steps", [])]
    mesh_history: tp.List[tp.Dict[str, tp.Any]] = [
        dict(m) for m in persisted.get("mesh_history", [])
    ]

    # Topology policy (elastic resume): compare this attempt's device count
    # against the geometry the ledger recorded for the previous attempt.
    rt = runtime
    n_prev = (
        int(persisted["mesh"]["n_devices"]) if persisted.get("mesh") else None
    )
    n_now = (
        len(rt.mesh.devices.flatten()) if rt is not None else jax.device_count()
    )
    if n_prev is not None and n_prev != n_now:
        if config.on_resume_mesh == "same":
            raise RuntimeError(
                f"supervised run in {config.rundir} previously ran on "
                f"{n_prev} device(s) "
                f"(mesh {persisted['mesh'].get('axes')}), but this resume "
                f"sees {n_now}; on_resume_mesh='same' refuses the topology "
                "change. Set on_resume_mesh='any' to reshard-resume across "
                "meshes (the checkpoint restores through the new mesh's "
                "shardings; the positional sampler keeps the batch order)."
            )
        if rt is None:
            # "any": re-derive the data axis for the new count.
            rt = make_runtime(config, devices=list(jax.devices()))
    if rt is None:
        rt = make_runtime(config)
    geom = {
        "n_devices": n_now,
        "axes": {k: int(v) for k, v in rt.mesh.shape.items()},
    }
    if not mesh_history or mesh_history[-1] != geom:
        mesh_history.append(geom)

    def _persist() -> None:
        # Re-load first so notes appended by train (append_note) mid-attempt
        # survive this write.
        state = _load_state(config.rundir)
        state.update(
            {
                "data_step_offset": offset,
                "windows_skipped": windows,
                "restarts": restarts,
                "hung_steps": hung,
                "mesh": geom,
                "mesh_history": mesh_history,
            }
        )
        _save_state(config.rundir, state)

    _persist()  # record this attempt's geometry before training starts

    while True:
        cfg = (
            config
            if offset == config.data_step_offset
            else config.replace(data_step_offset=offset)
        )
        try:
            result = train(cfg, runtime=rt)
            result["supervisor"] = {
                "restarts": restarts,
                "windows_skipped": windows,
                "data_step_offset": offset,
                "hung_steps": hung,
                "mesh_history": mesh_history,
                "faults_fired": faults.fired_counts(),
            }
            return result
        except StepHangError as e:
            # A wedged device sync says NOTHING about the data: restart from
            # the last verified checkpoint WITHOUT advancing the offset (the
            # replay re-runs the same window), mark the step HUNG in the
            # ledger, and spend one restart from the shared budget. The
            # watchdog already dumped the flight recorder at expiry.
            hung.append(int(e.step) if e.step is not None else -1)
            if restarts >= max_restarts:
                _persist()
                raise RuntimeError(
                    f"step hung {len(hung)} time(s) (steps {hung}); restart "
                    f"budget ({max_restarts}) exhausted. A recurring hang "
                    "at the SAME step suggests a wedged compile or input "
                    "pipeline; across different steps, a flaky device or "
                    f"interconnect. Underlying: {e}"
                ) from e
            restarts += 1
            flight_recorder().tracer.instant(
                "supervisor.hung_restart", "supervisor", "train",
                args={"step": e.step, "waited_s": e.waited_s,
                      "restart": restarts},
            )
            _persist()
            if jax.process_index() == 0:
                print(
                    f"supervisor: step {e.step} HUNG after {e.waited_s:.1f}s; "
                    f"restarting from the last verified checkpoint "
                    f"(restart {restarts}/{max_restarts})"
                )
            sleep_fn(backoff_sec * (2 ** (restarts - 1)))
        except DivergenceError as e:
            # Postmortem artifact FIRST, before any re-raise path: the
            # flight recorder's tail (train.step spans, ckpt events, the
            # train.divergence instant) as a loadable Chrome trace
            # (docs/OBSERVABILITY.md "Crash dumps").
            if config.rundir and not config.rundir.startswith("gs://"):
                dump_flight_recorder(config.rundir)
            if e.last_good_step is None:
                raise RuntimeError(
                    f"training diverged at step {e.step} with NO verified "
                    "checkpoint to roll back to (divergence before the first "
                    "save). Nothing to resume; fix learning_rate/warmup_steps "
                    f"or the data and restart. Underlying: {e}"
                ) from e
            # Poisoned DATA window, in sampler (data-index) coordinates.
            lo = e.last_good_step + 1 + offset
            hi = e.step + offset
            if restarts >= max_restarts:
                raise RuntimeError(
                    f"training diverged {restarts + 1} time(s); restart "
                    f"budget ({max_restarts}) exhausted. Data windows "
                    f"skipped so far: {windows}; the final divergence was "
                    f"detected in data window [{lo}, {hi}]. Recurring "
                    "divergence across DIFFERENT data windows points at the "
                    "optimization (lower learning_rate / raise "
                    "warmup_steps), not at one bad shard."
                ) from e
            windows.append([lo, hi])
            restarts += 1
            offset += max(1, e.step - e.last_good_step)
            flight_recorder().tracer.instant(
                "supervisor.rollback", "supervisor", "train",
                args={
                    "step": e.step,
                    "last_good_step": e.last_good_step,
                    "window": [lo, hi],
                    "restart": restarts,
                },
            )
            _persist()
            if jax.process_index() == 0:
                print(
                    f"supervisor: divergence at step {e.step}; rolling back "
                    f"to verified step {e.last_good_step}, skipping data "
                    f"window [{lo}, {hi}] (restart {restarts}/{max_restarts})"
                )
            sleep_fn(backoff_sec * (2 ** (restarts - 1)))
