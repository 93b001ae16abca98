"""Worker for the 2-process distributed CPU tests (run via subprocess).

Each process: jax.distributed.initialize on localhost, 2 local CPU devices
(4 global), per-process data shard via TokenDataset(shard_by_process=True),
global batch assembly via make_global_batch, compiled train steps over a
(data=2, fsdp=2) mesh.

Modes (argv[5], default "train"):
  * train        — one step, print `LOSS <value>`: the parent asserts both
                   processes print the same finite number (proving global
                   array assembly, not just single-process SPMD).
  * ckpt_save    — two steps, save a SHARDED checkpoint (each process writes
                   its shards) to argv[6], then run step 2 and print
                   `CONT <loss>` — the continued-training oracle.
  * ckpt_restore — fresh processes RESTORE the sharded checkpoint from
                   argv[6] (never recomputing steps 0-1), run step 2, print
                   `CONT <loss>`. The parent asserts it matches the oracle:
                   a failed or no-op restore would diverge, because restored
                   params+opt state after 2 steps differ from a fresh init.
    This beats the reference's pod-only checkpoint smoke (reference
    scripts/test_ckpt.py:8-24, print-only) — it runs anywhere and asserts.

Usage: python multiproc_worker.py <coordinator> <n_proc> <proc_id> <data_dir>
           [mode] [rundir]
"""

import faulthandler
import sys

import jax

# A worker that is still running after this many seconds has hung (a healthy
# one finishes in ~20 s): dump every thread's stack to the parent's pipe and
# exit non-zero, so the parent test fails with the place it hung instead of
# waiting out its own, longer, limit.
WORKER_DEADLINE_S = 150
faulthandler.dump_traceback_later(WORKER_DEADLINE_S, exit=True)

coordinator, n_proc, proc_id, data_dir = (
    sys.argv[1],
    int(sys.argv[2]),
    int(sys.argv[3]),
    sys.argv[4],
)
mode = sys.argv[5] if len(sys.argv) > 5 else "train"
rundir = sys.argv[6] if len(sys.argv) > 6 else ""

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.distributed.initialize(
    coordinator_address=coordinator, num_processes=n_proc, process_id=proc_id
)


from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.data.dataset import TokenDataset
from midgpt_tpu.models.gpt import GPTConfig
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
from midgpt_tpu.training.train import init_state, make_train_step

assert jax.process_count() == n_proc, jax.process_count()
assert jax.device_count() == 2 * n_proc, jax.device_count()

config = ExperimentConfig(
    rundir="",
    data_dir=data_dir,
    learning_rate=1e-3,
    batch_size=8,  # global
    warmup_steps=2,
    min_lr=1e-4,
    lr_decay_steps=10,
    max_steps=10,
    beta2=0.95,
    weight_decay=1e-4,
    eval_interval=5,
    param_dtype="float32",
    compute_dtype="float32",
    g_accum_iters=2,
    shard_model=True,
    fsdp_min_size=0,
    mesh=MeshConfig(data=2, fsdp=2, sp=1),
    model_config=GPTConfig(block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=32),
)

mesh = make_mesh(config.mesh)
dataset = TokenDataset(data_dir, seed=7, shard_by_process=True)
# each process must hold a distinct, equal-length contiguous slice
n_total = 4096
assert len(dataset["train"]) == n_total // n_proc, len(dataset["train"])

params, opt_state, specs, optimizer = init_state(config, mesh)
step, *_ = make_train_step(config, optimizer, mesh, specs)

local_bs = config.batch_size // n_proc
base_key = jax.random.PRNGKey(0)


def run_step(itr, params, opt_state):
    x, y = dataset.batch(
        "train", itr, config.model_config.block_size, local_bs, config.g_accum_iters
    )
    xg = make_global_batch(x, mesh, batch_spec())
    yg = make_global_batch(y, mesh, batch_spec())
    assert xg.shape == (
        config.g_accum_iters, config.batch_size, config.model_config.block_size,
    )
    return step(params, opt_state, xg, yg, jax.random.fold_in(base_key, itr))


if mode == "train":
    params, opt_state, loss = run_step(0, params, opt_state)
    print(f"LOSS {float(loss):.6f}", flush=True)
elif mode == "ckpt_save":
    from midgpt_tpu.training.checkpoint import CheckpointManager

    for itr in (0, 1):
        params, opt_state, loss = run_step(itr, params, opt_state)
    mngr = CheckpointManager(rundir, max_to_keep=1, save_interval_steps=1)
    mngr.save(1, {"params": params, "opt_state": opt_state}, force=True)
    mngr.close()
    params, opt_state, loss = run_step(2, params, opt_state)  # oracle
    print(f"CONT {float(loss):.6f}", flush=True)
elif mode == "ckpt_restore":
    from midgpt_tpu.training.checkpoint import CheckpointManager

    mngr = CheckpointManager(rundir, max_to_keep=1, save_interval_steps=1)
    assert mngr.latest_step() == 1, mngr.latest_step()
    state = mngr.restore(1, {"params": params, "opt_state": opt_state})
    params, opt_state = state["params"], state["opt_state"]
    mngr.close()
    params, opt_state, loss = run_step(2, params, opt_state)
    print(f"CONT {float(loss):.6f}", flush=True)
else:
    raise SystemExit(f"unknown mode {mode!r}")
