"""Fleet replica worker process (docs/ROBUSTNESS.md "Cross-process
fleet"). Spawned by `midgpt_tpu.sampling.fleet_proc.spawn_worker`: builds
one ServeEngine on its OWN CPU mesh (own jax backend, own jit cache, own
host-RAM SpillTier) and serves the framed socket protocol until drained
(SIGTERM -> preempt flag), told bye, orphaned, or SIGKILLed — the last
being the `proc_kill9` chaos gate's whole point.

Deliberately no `jax.distributed`: nothing here is a collective. Replicas
share no arrays; the only thing crossing the process boundary is plain
host data inside crc32-verified frames.

CPU-only by construction: a chip belongs to one process at a time, the
router parent (sampling/fleet_proc.spawn_worker) already holds JAX, and a
child that needed the chip would fail or hang — so every worker pins
`jax_platforms="cpu"` below. This path is a process-boundary / failover
test bed, not a way to serve from a chip, and chip_smoke.py keeps out of it.

Stdout carries exactly one line ("PORT <n>") for the spawner; everything
diagnostic goes to stderr so a worker under a bench driver can never
pollute a one-line JSON stdout contract.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--spec-json",
        required=True,
        help="JSON spec: {model, seed, engine, cpu_devices, jax_config}",
    )
    ap.add_argument(
        "--port",
        type=int,
        default=0,
        help="listen port (default 0: ephemeral, announced on stdout)",
    )
    args = ap.parse_args()
    spec = json.loads(args.spec_json)

    # CPU pin BEFORE backend init (workers are CPU-only by design, module
    # docstring), plus the parent's numerics knobs
    # (fleet_proc.parent_jax_config) so same-seed params match the
    # router-side reference bit for bit.
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(spec.get("cpu_devices", 1)))
    for knob, value in spec.get("jax_config", {}).items():
        jax.config.update(knob, value)

    from midgpt_tpu.sampling.fleet_proc import run_worker

    def announce(port: int) -> None:
        print(f"PORT {port}", flush=True)

    run_worker(spec, port=args.port, announce=announce)


if __name__ == "__main__":
    main()
