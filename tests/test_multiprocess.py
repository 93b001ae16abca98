"""Multi-process distributed test: 2 local processes, jax.distributed on CPU.

Exercises the code paths no single-process test can: per-process dataset
sharding (TokenDataset shard_by_process), cross-process global-array assembly
(make_global_batch under process_count() > 1), and a compiled SPMD train step
spanning both processes. The reference has no equivalent — its distributed
smoke scripts require a real TPU pod (reference scripts/test_jax.py,
test_ckpt.py).
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The parent (pytest) holds JAX on the CPU backend and each worker pins the
# CPU platform itself, so several JAX processes coexist here. On a chip they
# could not: a chip belongs to one process at a time (see chip_smoke.py).

# Seconds a pair of workers may take: above the workers' own hang deadline
# (multiproc_worker.WORKER_DEADLINE_S, which dumps the stack and exits), so
# a hang surfaces as the worker's traceback, not as this timeout.
WORKERS_TIMEOUT_S = 180


def test_fleet_proc_has_no_jax_distributed_dependency():
    """Cross-process SERVING never rides jax.distributed:
    sampling/fleet_proc.py deliberately uses plain sockets and zero
    collectives (replicas share no arrays), so tests/test_fleet_proc.py's
    process-boundary gates need none of the machinery the SPMD tests below
    exercise. (AST, not text: the module docstring SAYS "no
    jax.distributed".)"""
    import ast
    import inspect

    import midgpt_tpu.sampling.fleet_proc as fleet_proc

    tree = ast.parse(inspect.getsource(fleet_proc))
    refs = [
        node
        for node in ast.walk(tree)
        if isinstance(node, ast.Attribute) and node.attr == "distributed"
    ]
    assert not refs, "fleet_proc.py grew a jax.distributed dependency"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_train_step(tmp_path):
    rng = np.random.default_rng(0)
    for split in ("train", "val"):
        rng.integers(0, 64, 4096, dtype=np.uint16).astype(np.uint16).tofile(
            tmp_path / f"{split}.bin"
        )
    losses = _run_workers(tmp_path, "train", marker="LOSS")
    assert np.isfinite(losses[0])
    # SPMD: every process computes the identical global loss
    assert abs(losses[0] - losses[1]) < 1e-6, losses


def _run_workers(tmp_path, mode, rundir="", marker="CONT"):
    """Run the 2-process worker pair in `mode`, bounded by
    WORKERS_TIMEOUT_S; returns each worker's `<marker> <float>` value.
    Whatever happens, no worker outlives the call."""
    coordinator = f"localhost:{_free_port()}"
    env = {
        **os.environ,
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    worker = os.path.join(REPO, "tests", "multiproc_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, coordinator, "2", str(i), str(tmp_path), mode, rundir],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    deadline = time.monotonic() + WORKERS_TIMEOUT_S
    try:
        outs = [
            p.communicate(timeout=max(1.0, deadline - time.monotonic()))[0]
            for p in procs
        ]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} ({mode}) failed:\n{out}"
    vals = []
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith(marker + " ")]
        assert lines, f"no {marker} line in:\n{out}"
        vals.append(float(lines[0].split()[1]))
    return vals


def test_two_process_checkpoint_roundtrip(tmp_path):
    """Sharded checkpoint round-trip across process restarts: 2 processes
    train 2 steps and save (each writing its own shards), a FRESH pair of
    processes restores and continues — the continued-training loss must
    equal the oracle that never stopped. A no-op or partial restore would
    diverge (2-step-trained params differ from init)."""
    rng = np.random.default_rng(0)
    for split in ("train", "val"):
        rng.integers(0, 64, 4096, dtype=np.uint16).astype(np.uint16).tofile(
            tmp_path / f"{split}.bin"
        )
    rundir = str(tmp_path / "ckpt")

    oracle = _run_workers(tmp_path, "ckpt_save", rundir)
    resumed = _run_workers(tmp_path, "ckpt_restore", rundir)

    assert np.isfinite(oracle[0])
    assert abs(oracle[0] - oracle[1]) < 1e-6, oracle
    assert abs(resumed[0] - resumed[1]) < 1e-6, resumed
    assert abs(oracle[0] - resumed[0]) < 1e-6, (oracle, resumed)
