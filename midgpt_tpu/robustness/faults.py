"""Fault-injection registry: named, bounded failures for recovery testing.

A fault is (kind, optional step, remaining firings). Production code calls
`should_fire(kind, step=...)` at the few places a real failure would strike;
with an empty registry (the default, always) that is a list scan over
nothing — no fault machinery is reachable unless a plan was activated.

Kinds (each exercised end to end by tests/test_robustness.py and drivable
via tools/chaos_run.py):

  nan_grad           poison the train step's sticky loss carrier at data
                     step k — models a bad batch NaN-ing the gradients. The
                     key is the DATA step index (itr + data_step_offset), so
                     a supervisor rollback that skips the window also skips
                     the fault, exactly like a real poisoned shard.
  ckpt_io_error      raise IOError from the next N checkpoint-save attempts
                     (a transient TensorStore/filesystem failure) — the
                     manager's retry/backoff must absorb it.
  kill_mid_save      after the TensorStore write lands, truncate one item
                     and raise SimulatedPreemption before the manifest is
                     written — models SIGKILL between write and commit.
  truncate_ckpt_item truncate one item file AFTER the manifest committed —
                     models later corruption (bit rot, partial copy);
                     verification must catch it at restore/resume time.
  preempt            set the preemption flag at data step k, as if SIGTERM
                     arrived mid-step — drives the emergency-save path
                     without depending on signal-delivery timing.
  hang_step          the step's device sync at data step k never lands (the
                     hung-device / wedged-dispatch failure): the guarded
                     float() blocks on a never-set event, so only the
                     hung-step watchdog (robustness/watchdog.py) can end
                     the wait — dump, ledger HUNG mark, escalation.
  ckpt_enospc        the next N checkpoint-save attempts fail with
                     OSError(ENOSPC) after partial bytes land in the step
                     directory — disk exhaustion mid-write. The atomic
                     manifest commit must leave no partial step visible to
                     latest_verified_step, the retry/backoff path must
                     recover when space frees, and verified-only GC must
                     never delete the last good checkpoint over it.
  resume_reshard     request a preemption exit at data step k so the driver
                     (tools/chaos_run.py) can restart the run on a DIFFERENT
                     device count — the cross-mesh resharding resume path
                     (train restores the checkpoint through the new mesh's
                     shardings; supervise checks on_resume_mesh).

Serving kinds (hooked in sampling/serve.py `ServeEngine.step`, the async
front door sampling/server.py, and the chaos scenario driver
robustness/chaos_serve.py; the step key is the engine's ROUND counter or —
submit_storm — the workload's arrival index, so a seeded trace makes every
firing deterministic):

  kill_mid_decode    the round's decode/spec dispatch dies before its
                     tokens land (device restart mid-dispatch); every
                     decode-ready slot is recompute-preempted and the
                     token streams must come out identical to an
                     unfaulted run.
  kill_overlapped_round  the IN-FLIGHT round N+1 dispatch dies while round
                     N's host work runs (overlap="double" engines keep two
                     rounds in the pipe — sampling/serve.py
                     `_step_overlapped`): the unsettled handle is dropped
                     without forcing, its slots recompute-preempt, the
                     watchdog still bounds a hung settle, and bystander
                     streams plus the page pool must come through
                     bit-identical / conserved (chaos_serve.py gate).
  poisoned_page      corrupt one live slot's first pool page in place
                     (HBM damage); page isolation must keep every OTHER
                     slot's stream bit-identical while the engine keeps
                     serving.
  slow_client        a streaming client stops draining its token queue;
                     the server's bounded per-client buffer must shed
                     exactly that client (status "slow_client") without
                     stalling the engine or its neighbors.
  submit_storm       a burst of simultaneous submissions beyond the
                     backpressure budget; admission must shed the excess
                     (BackpressureError) and serve the admitted rest to
                     completion.
  evict_shared_prefix  force-reclaim every unreferenced prefix-cache trie
                     page at once (a pressure spike flushing hot shared
                     nodes, LRU protection ignored); referenced entries
                     must survive — a shared node is never evicted out
                     from under a live reader — so live streams stay
                     bit-identical while later requests just re-prefill
                     and re-populate the trie, with pages + refcounts
                     conserved through the flush.
  hot_swap_mid_decode  stage a blue/green weight swap mid-trace (payload
                     from the engine's `swap_source` hook): admissions
                     pause, in-flight streams finish on the old weights
                     bit-exactly, queued arrivals take the new ones, zero
                     streams dropped, pool + trie conserved across the
                     flip (sampling/ops.py).
  pool_resize        live-resize the paged KV pool to the next target on
                     the engine's `resize_plan` (grow then shrink in the
                     chaos gate): resident pages migrate through the
                     adoption scatter with int8 scales, conservation
                     holds at every boundary, and live streams stay
                     greedy-bit-exact vs a no-resize run.

Fleet kinds (hooked in sampling/fleet.py `FleetRouter.step`, keyed on the
ROUTER round counter; scenarios in robustness/chaos_serve.py):

  engine_crash       kill the alive replica holding the most accepted
                     streams mid-trace: its finished results are
                     harvested, every accepted-but-unfinished stream
                     fails over to survivors through the bounded handoff
                     queue, and the replays must come out greedy
                     bit-identical to a fault-free pass — zero dropped
                     accepted streams, cross-tier conservation intact.
  handoff_stall      wedge the host page transport: the spill tier's next
                     consult that WOULD return pages refuses instead
                     (stays armed until one would), and the admission
                     falls back to plain re-prefill — slower, never
                     wrong, streams bit-identical.
  spill_corrupt      flip a byte in the most recently spilled host-RAM
                     page without updating its checksum (stays armed
                     until something is resident): the take-side crc32
                     verification must discard it and re-prefill — a
                     corrupt spill page never yields a token mismatch.

Cross-process fleet kinds (hooked in sampling/fleet.py
`FleetRouter._fire_proc_faults`, keyed on the ROUTER round counter;
targets the busiest alive ProcReplica — sampling/fleet_proc.py; scenario
in robustness/chaos_serve.py `_run_proc_fleet_chaos`):

  proc_kill9         SIGKILL the busiest worker PROCESS mid-decode — no
                     drain, no flush, no goodbye. The router must detect
                     the death purely through the wire (step RPCs fail
                     with ReplicaGoneError until the consecutive-failure
                     health check fires), then run the exact engine_crash
                     failover: zero dropped accepted streams, greedy
                     bit-parity on the survivor, router + spill ledgers
                     closing across the process boundary.
  conn_drop          abruptly close the live router->worker connection;
                     the transport must reconnect transparently on the
                     next RPC (counted `reconnects`) with zero stream
                     impact — the worker keeps its state, only the socket
                     died.
  wire_corrupt       flip a byte in the next received frame BEFORE
                     verification: the crc32 check must reject it
                     pre-decode (WireFrameError, counted
                     `corrupt_frames`), drop the desynced connection, and
                     recover by retrying the RPC on a fresh one — corrupt
                     bytes never reach a decode, mirroring spill_corrupt.
  wire_stall         the next RPC's response never lands inside its
                     deadline (wedged worker / dead connection): the deadline
                     must expire into a structured TransportError
                     (counted `deadline_expiries`) and the bounded
                     backoff retry must absorb it.

Activation: programmatic (`activate(...)`), or a plan string from config
(`ExperimentConfig.fault_plan`) / the MIDGPT_FAULTS env var, parsed by
`activate_plan`: comma-separated `kind[@step][*times]`, e.g.
`"nan_grad@12,ckpt_io_error*2"`. The supervisor activates the configured
plan exactly once per supervised run — NOT once per restart attempt — so a
consumed fault stays consumed across rollbacks.
"""

from __future__ import annotations

import dataclasses
import re
import typing as tp

KINDS = (
    "nan_grad",
    "ckpt_io_error",
    "kill_mid_save",
    "truncate_ckpt_item",
    "preempt",
    "hang_step",
    "ckpt_enospc",
    "resume_reshard",
    # serving (sampling/serve.py, sampling/server.py, chaos_serve.py)
    "kill_mid_decode",
    "kill_overlapped_round",
    "poisoned_page",
    "slow_client",
    "submit_storm",
    "evict_shared_prefix",
    "hot_swap_mid_decode",
    "pool_resize",
    # fleet (sampling/fleet.py FleetRouter.step, chaos_serve.py)
    "engine_crash",
    "handoff_stall",
    "spill_corrupt",
    # cross-process fleet (sampling/fleet.py _fire_proc_faults against
    # fleet_proc.py ProcReplica workers, chaos_serve.py)
    "proc_kill9",
    "conn_drop",
    "wire_corrupt",
    "wire_stall",
)

# One-line summaries for operator tooling (`tools/chaos_run.py --serve
# --list-faults` and unknown-fault diagnostics). The module docstring above
# stays the full contract; this is the discoverable index of it.
DESCRIPTIONS: tp.Dict[str, str] = {
    "nan_grad": "poison the train step's loss at data step k (bad batch)",
    "ckpt_io_error": "raise IOError from the next checkpoint-save attempts",
    "kill_mid_save": "truncate one ckpt item + die before the manifest lands",
    "truncate_ckpt_item": "corrupt one ckpt item AFTER its manifest committed",
    "preempt": "set the preemption flag at data step k (SIGTERM mid-step)",
    "hang_step": "the step's device sync never lands; the watchdog must end it",
    "ckpt_enospc": "ENOSPC mid checkpoint write, partial bytes left behind",
    "resume_reshard": "preempt at data step k; driver restarts on another mesh",
    "kill_mid_decode": "the round's decode dispatch dies; slots recompute-preempt",
    "kill_overlapped_round": "the in-flight overlapped dispatch dies mid host phase",
    "poisoned_page": "corrupt one live slot's pool page in place (HBM damage)",
    "slow_client": "a streaming client stops draining; bounded buffer sheds it",
    "submit_storm": "submission burst beyond the backpressure budget; excess sheds",
    "evict_shared_prefix": "force-flush every unreferenced prefix-trie page at once",
    "hot_swap_mid_decode": "blue/green weight swap mid-trace (engine swap_source)",
    "pool_resize": "live KV pool resize to the engine's next resize_plan target",
    "engine_crash": "kill the busiest fleet replica; streams fail over to survivors",
    "handoff_stall": "wedge the spill-tier transport; admissions re-prefill instead",
    "spill_corrupt": "bit-flip a spilled host-RAM KV page; checksum must catch it",
    "proc_kill9": "SIGKILL the busiest worker process; wire-detected failover",
    "conn_drop": "drop the live router->worker socket; next RPC reconnects",
    "wire_corrupt": "bit-flip the next wire frame; crc32 rejects pre-decode",
    "wire_stall": "next RPC response misses its deadline; backoff absorbs it",
}

# kind names may carry digits (proc_kill9); `@` still separates the step
_PLAN_RE = re.compile(
    r"^(?P<kind>[a-z_][a-z0-9_]*?)(?:@(?P<step>\d+))?(?:\*(?P<times>\d+))?$"
)


@dataclasses.dataclass
class Fault:
    kind: str
    step: tp.Optional[int] = None  # fire only when the hook's step matches
    times: int = 1  # remaining firings
    fired: int = 0  # total firings so far


_active: tp.List[Fault] = []

# Optional firing observer (tools/chaos_run.py timestamps detection latency
# with it — the wall clock stays in tools/, keeping this module free of
# clock reads per the GC012 discipline). Called once per consumed firing.
_on_fire: tp.Optional[tp.Callable[[Fault], None]] = None


def set_on_fire(cb: tp.Optional[tp.Callable[[Fault], None]]) -> None:
    global _on_fire
    _on_fire = cb


def activate(kind: str, *, step: tp.Optional[int] = None, times: int = 1) -> Fault:
    if kind not in KINDS:
        raise ValueError(f"unknown fault kind {kind!r}; known: {KINDS}")
    f = Fault(kind, step=step, times=times)
    _active.append(f)
    return f


def activate_plan(plan: str) -> tp.List[Fault]:
    """Parse and activate `kind[@step][*times]` comma-separated specs."""
    out = []
    for spec in filter(None, (s.strip() for s in plan.split(","))):
        m = _PLAN_RE.match(spec)
        if not m:
            raise ValueError(
                f"bad fault spec {spec!r} (want kind[@step][*times], e.g. "
                "'nan_grad@12' or 'ckpt_io_error*2')"
            )
        out.append(
            activate(
                m.group("kind"),
                step=int(m.group("step")) if m.group("step") else None,
                times=int(m.group("times")) if m.group("times") else 1,
            )
        )
    return out


def clear() -> None:
    global _on_fire
    _active.clear()
    _on_fire = None


def active() -> tp.List[Fault]:
    return list(_active)


def fired_counts() -> tp.Dict[str, int]:
    out: tp.Dict[str, int] = {}
    for f in _active:
        out[f.kind] = out.get(f.kind, 0) + f.fired
    return out


def should_fire(kind: str, *, step: tp.Optional[int] = None) -> bool:
    """Consume one firing of the first matching armed fault.

    A step-scoped fault only fires when the hook reports that exact step; a
    stepless fault fires on any matching hook call."""
    for f in _active:
        if f.kind != kind or f.times <= 0:
            continue
        if f.step is not None and step != f.step:
            continue
        f.times -= 1
        f.fired += 1
        if _on_fire is not None:
            _on_fire(f)
        return True
    return False
