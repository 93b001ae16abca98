"""Chaos harness: injected faults against the REAL recovery paths, one
JSON summary line (schema: midgpt_tpu/analysis/bench_contract.py).

Training mode (PR 3) — a supervised run through the real rollback/retry/
verification machinery:

    python tools/chaos_run.py --config=shakespeare_char --rundir=/tmp/chaos \
        --fault nan_grad@12 --fault ckpt_io_error*2 \
        [--set max_steps=40 ...] [--max-restarts 3]

Serving mode (`--serve`) — a seeded request trace through the continuous-
batching engine (and, for client faults, the async front door) with one of
the serving fault kinds armed; asserts graceful degradation (engine alive,
pages conserved, unaffected greedy streams bit-identical to a fault-free
run — robustness/chaos_serve.py) and reports shed/timeout counts:

    python tools/chaos_run.py --serve --fault kill_mid_decode@6
    python tools/chaos_run.py --serve --fault poisoned_page@8 --fault slow_client@1

Zero-downtime model-ops gates (docs/ROBUSTNESS.md): a verified-checkpoint
blue/green weight swap mid-trace, and a live grow-then-shrink pool resize
on an int8 cache, both with bit-exact greedy parity and zero drops:

    python tools/chaos_run.py --serve --fault hot_swap_mid_decode@5
    python tools/chaos_run.py --serve --fault pool_resize@4 --fault pool_resize@8

Fleet gates (docs/ROBUSTNESS.md "Fleet serving & failover") — the trace
runs through TWO replicas behind the prefix-affinity FleetRouter with its
shared host-RAM spill tier (sampling/fleet.py): a mid-trace replica kill
drops zero accepted streams (failovers replay bit-identically on the
survivor), and a stalled or corrupted spill page costs a re-prefill, never
a token, with page conservation extended across replicas and tiers:

    python tools/chaos_run.py --serve --fault engine_crash@6
    python tools/chaos_run.py --serve --fault handoff_stall
    python tools/chaos_run.py --serve --fault spill_corrupt

Degraded-IO / elastic-topology gates (docs/ROBUSTNESS.md "Elastic resume
& watchdog") — these train-mode kinds emit the `train_chaos` bench-contract
profile (detected_at_ms, restarts, final_mesh, loss_parity vs an unfaulted
reference run) on the summary line:

    python tools/chaos_run.py --config=... --rundir=... \
        --fault hang_step@12 --set watchdog_deadline_s=2
    python tools/chaos_run.py --config=... --rundir=... --fault ckpt_enospc*2
    python tools/chaos_run.py --config=... --rundir=... --fault resume_reshard@6

(`resume_reshard` ends the first attempt like a preemption; the driver then
restarts on HALF the visible devices with on_resume_mesh="any", exercising
the cross-mesh checkpoint resharding resume, and runs to completion.)

`--list-faults` prints the registered kinds — training, serving, and fleet
in one table — with one-line descriptions;
unknown `--fault` kinds fail up front with that same list.

With `--rundir`, serving mode records the fault pass under a flight
recorder and leaves `flight_recorder.json` (Chrome trace — open in
Perfetto or summarize with tools/trace_view.py) plus `.prom` metrics
there, even when a degradation invariant fails (docs/OBSERVABILITY.md).

Fault spec grammar: `kind[@step][*times]` (robustness/faults.py;
MIDGPT_FAULTS env works too). Serving step keys: engine round for
kill_mid_decode/poisoned_page, victim uid for slow_client, arrival index
for submit_storm.

Platform selection is JAX's own: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8
drives recovery scenarios on the virtual CPU mesh.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_launch():
    """launch.py is a top-level script, not a package module."""
    spec = importlib.util.spec_from_file_location(
        "launch_mod",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "launch.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _list_faults() -> int:
    """--list-faults: the registered fault kinds with their one-line
    descriptions (robustness/faults.py DESCRIPTIONS) — the discoverable
    index of the registry, so operators don't read the module to learn
    what `--fault` accepts."""
    from midgpt_tpu.robustness import faults

    width = max(len(k) for k in faults.KINDS)
    for kind in faults.KINDS:
        print(f"  {kind:<{width}}  {faults.DESCRIPTIONS[kind]}")
    return 0


def _validate_fault_specs(parser, specs) -> None:
    """Fail unknown --fault kinds up front with the described kind list
    instead of a deep ValueError (or nothing happening at all)."""
    from midgpt_tpu.robustness import faults

    for spec in specs:
        m = faults._PLAN_RE.match(spec.strip())
        kind = m.group("kind") if m else spec
        if m is None or kind not in faults.KINDS:
            lines = "\n".join(
                f"  {k}: {faults.DESCRIPTIONS[k]}" for k in faults.KINDS
            )
            parser.error(
                f"unknown fault spec {spec!r} (want KIND[@STEP][*TIMES]). "
                f"Registered kinds:\n{lines}"
            )


def _serve_main(args) -> int:
    """--serve: one serving chaos scenario, one JSON line. A broken
    degradation invariant (AssertionError) is the chaos verdict — reported
    as data with a nonzero exit, same contract as training mode."""
    from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

    t0 = time.time()
    status = "ok"
    error = None
    result: dict = {}
    try:
        result = run_serving_chaos(
            ",".join(args.fault), seed=args.seed, n_requests=args.n_requests,
            trace_dir=args.rundir,
        )
    except AssertionError as e:
        status = "failed"
        error = str(e)
    summary = {
        "tool": "chaos_run",
        "mode": "serve",
        "status": status,
        "wall_s": round(time.time() - t0, 3),
        "faults_requested": args.fault,
        **result,
    }
    if error is not None:
        summary["error"] = error
    print(json.dumps(summary))
    return 0 if status == "ok" else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", type=str, default=None)
    parser.add_argument("--rundir", type=str, default=None)
    parser.add_argument(
        "--fault",
        action="append",
        default=[],
        metavar="KIND[@STEP][*TIMES]",
        help="fault to inject (repeatable) — robustness/faults.py",
    )
    parser.add_argument("--max-restarts", type=int, default=None)
    parser.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="dotted config override (same semantics as launch.py)",
    )
    parser.add_argument(
        "--serve", action="store_true",
        help="serving chaos: drive a seeded trace through the continuous-"
        "batching engine with the armed faults (robustness/chaos_serve.py) "
        "instead of a supervised training run",
    )
    parser.add_argument("--seed", type=int, default=0,
                        help="--serve: trace/model seed")
    parser.add_argument("--n-requests", type=int, default=5,
                        help="--serve: requests in the seeded trace")
    parser.add_argument(
        "--list-faults", action="store_true",
        help="print the registered fault kinds with one-line descriptions "
        "and exit (robustness/faults.py)",
    )
    args = parser.parse_args()

    if args.list_faults:
        return _list_faults()
    _validate_fault_specs(parser, args.fault)

    import jax

    if args.serve:
        return _serve_main(args)
    if args.config is None or args.rundir is None:
        parser.error("--config and --rundir are required (unless --serve)")

    from midgpt_tpu.config import load_config
    from midgpt_tpu.robustness import faults, preempt
    from midgpt_tpu.robustness.supervisor import supervise
    from midgpt_tpu.training.train import make_runtime

    launch_mod = _load_launch()
    config = load_config(args.config)
    if args.set:
        config = launch_mod.apply_overrides(
            config, [kv.partition("=")[::2] for kv in args.set]
        )
    config = config.replace(rundir=os.path.abspath(args.rundir))
    if args.fault:
        config = config.replace(fault_plan=",".join(args.fault))
    if args.max_restarts is not None:
        config = config.replace(max_restarts=args.max_restarts)

    # Degraded-IO / elastic-topology gates (`train_chaos` bench-contract
    # profile): when one of these kinds is requested, the summary grows
    # detection latency, driver-level restart counts, the final mesh, and a
    # loss-parity verdict against an unfaulted reference run.
    TRAIN_CHAOS_KINDS = {"hang_step", "ckpt_enospc", "resume_reshard"}
    requested_kinds = {
        (faults._PLAN_RE.match(s.strip()).group("kind")) for s in args.fault
    }
    train_chaos = bool(requested_kinds & TRAIN_CHAOS_KINDS)

    preempt.install_handlers()
    t0 = time.time()
    # Detection latency: the registry's firing observer timestamps each
    # kind's FIRST firing (the wall clock stays here in tools/, keeping
    # robustness/ clock-free per the GC012 discipline).
    fire_ms: dict = {}
    faults.set_on_fire(
        lambda f: fire_ms.setdefault(f.kind, round((time.time() - t0) * 1000.0, 1))
    )
    status = "ok"
    error = None
    result = None
    # train_chaos drives the runtime explicitly so the driver can (a) report
    # the final mesh and (b) reuse the compiled step for the parity
    # reference run; plain chaos keeps the historical supervise-owned path.
    rt = make_runtime(config) if train_chaos else None
    reshard_restarts = 0
    # The summary line below is the ONLY stdout this tool may produce (the
    # one-JSON-line driver contract); the supervised run's step logs and
    # supervisor prints go to stderr, where operators still see them.
    import contextlib

    _to_stderr = contextlib.redirect_stdout(sys.stderr)
    try:
        with _to_stderr:
            result = supervise(config, runtime=rt)
            # resume_reshard ends the attempt like a preemption; the driver
            # then plays the scheduler: restart on HALF the devices with
            # on_resume_mesh="any" (the cross-mesh resharding resume) and
            # run to completion. Fault re-injection is NOT replayed on
            # restart — the registry keeps the consumed firing, like a real
            # one-shot failure.
            while (
                result is not None
                and result["metrics"].get("preempted")
                and "resume_reshard" in fire_ms
                and reshard_restarts < 4
            ):
                preempt.reset()
                preempt.install_handlers()
                devs = list(jax.devices())
                n_new = len(devs) // 2 if reshard_restarts % 2 == 0 else len(devs)
                n_new = max(1, n_new)
                cfg2 = config.replace(on_resume_mesh="any", fault_plan="")
                rt = rt.rebuild(cfg2, devices=devs[:n_new])
                reshard_restarts += 1
                result = supervise(cfg2, runtime=rt)
    except (RuntimeError, FloatingPointError) as e:
        # Budget exhaustion / unrecoverable divergence: that outcome IS the
        # chaos result — report it as data, nonzero exit.
        status = "failed"
        error = str(e)
    summary = {
        "tool": "chaos_run",
        "config": args.config,
        "rundir": config.rundir,
        "status": status,
        "wall_s": round(time.time() - t0, 3),
        "faults_requested": args.fault,
        "faults_fired": faults.fired_counts(),
    }
    if result is not None:
        summary["supervisor"] = {
            k: v for k, v in result["supervisor"].items() if k != "faults_fired"
        }
        summary["loss_final"] = result["metrics"].get("loss/final")
        summary["preempted"] = bool(result["metrics"].get("preempted", False))
    if train_chaos:
        import numpy as np

        summary["bench"] = "train_chaos"
        fired_ms = [fire_ms[k] for k in TRAIN_CHAOS_KINDS if k in fire_ms]
        summary["detected_at_ms"] = min(fired_ms) if fired_ms else None
        summary["restarts"] = (
            int(result["supervisor"]["restarts"]) if result is not None else 0
        ) + reshard_restarts
        if rt is not None:
            summary["final_mesh"] = {
                "n_devices": int(len(rt.mesh.devices.flatten())),
                "axes": {k: int(v) for k, v in rt.mesh.shape.items()},
            }
            summary["n_devices_final"] = summary["final_mesh"]["n_devices"]
        loss_parity = False
        if status == "ok" and result is not None and summary["loss_final"] is not None:
            # Parity verdict: an UNFAULTED run of the same config (fresh
            # rundir, empty registry) on the final runtime — shares the
            # compiled step, so this costs steps, not compiles. rtol covers
            # the f32 reassociation of a re-derived data-axis all-reduce
            # after a mesh change (~1e-8 measured); the batch order itself
            # is positional and exact.
            faults.clear()
            preempt.reset()
            cfg_ref = config.replace(
                rundir=config.rundir + "_ref", fault_plan="",
                on_resume_mesh="any",
            )
            with contextlib.redirect_stdout(sys.stderr):
                ref = supervise(cfg_ref, runtime=rt)
            ref_loss = ref["metrics"].get("loss/final")
            summary["loss_ref"] = ref_loss
            loss_parity = bool(
                ref_loss is not None
                and np.isfinite(summary["loss_final"])
                and np.allclose(
                    summary["loss_final"], ref_loss, rtol=1e-5, atol=1e-6
                )
            )
        summary["loss_parity"] = loss_parity
    if error is not None:
        summary["error"] = error
    print(json.dumps(summary))
    return 0 if status == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
