"""Kimi-Linear through the entry points: `make_runtime` / `train()` as launch.py
drives them (loss falling, checkpoint, resume), the benchmark cell's CPU
rehearsal, and the serving entry points' refusal. The model against its
reference, and the ops, are in tests/test_kimi_linear.py (two files so that
the suite's workers can share them)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from midgpt_tpu.config import from_json, to_json
from test_kimi_linear import ROOT, tiny, tiny_experiment
from rehearsal_tree import run_rehearsal


def test_serving_entry_points_refuse_the_checkpoint(tmp_path):
    from midgpt_tpu.sampling.serve import ServeEngine

    with pytest.raises(NotImplementedError, match="ServeEngine cannot serve a kimi_linear checkpoint"):
        ServeEngine(tiny(), None)
    config = tiny_experiment(rundir=str(tmp_path))
    (tmp_path / "config.json").write_text(to_json(config))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "sample.py"), f"--ckpt_dir={tmp_path}"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "sample.py cannot serve a kimi_linear checkpoint" in proc.stderr


def test_trains_through_make_runtime_and_resumes_to_the_same_loss(tmp_path):
    """launch.py's path: make_runtime / train() on learnable data: the loss
    falls, the MoE counters are logged, and a resumed run starts from the
    loss the first one ended at."""
    from midgpt_tpu.training.train import train

    n = 40000
    tokens = (np.arange(n) % 7 * 3 + np.arange(n) % 5).astype(np.uint16)
    tokens[:36000].tofile(tmp_path / "train.bin")
    tokens[36000:].tofile(tmp_path / "val.bin")
    config = tiny_experiment(
        data_dir=str(tmp_path), rundir=str(tmp_path / "run"), batch_size=8, g_accum_iters=2, max_steps=24,
        eval_interval=8, eval_steps=2, warmup_steps=4, lr_decay_steps=24, log_interval=4,
        learning_rate=3e-3, compute_dtype="float32",
    ).replace(model_config=tiny(block_size=32))
    first = train(config)["metrics"]
    assert first["loss/final"] < 0.5 * np.log(64), first
    assert first["moe.dropped"] == 0.0 and first["moe.assignments_here"] > 0 and first["moe.load_max_over_mean"] >= 1.0
    saved = from_json((tmp_path / "run" / "config.json").read_text()) if (tmp_path / "run" / "config.json").exists() else config
    assert saved.model_config == config.model_config
    again = train(config.replace(max_steps=25))["metrics"]
    assert again["loss/val"] == pytest.approx(first["loss/final"], abs=1e-6)  # the eval at the resumed step


def test_benchmark_cell_rehearses_on_the_cpu(tmp_path):
    """`run.py --workload train_kimi_linear_t8k --rehearse-cpu` exits 0 and
    names every metric declared for the cell that a CPU run can produce: all
    but the three that read the TPU's Mosaic custom calls (`mla_attention_*`:
    the rehearsal's attention is the naive one; `kda_kernel_ms_per_step`: off
    the TPU `kda_chunked` is its jnp body) and those that need the
    chip's peaks or its memory counters (`train.mfu_hybrid`, `step.device_ms`,
    `train.peak_hbm_gb`, as in the GPT cells' rehearsals)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"] for m in bench["per_layer"] if "train_kimi_linear_t8k" in m.get("workloads", [])}
    assert {"step.kda_ms", "step.kda_scan_ms", "step.mla_ms", "step.moe_route_ms", "step.moe_experts_ms",
            "train.mfu_hybrid", "mla_attention_ms_per_step", "mla_attention_roofline",
            "moe.load_max_over_mean", "moe.overflowed", "setup.programs"} <= declared
    proc = run_rehearsal(tmp_path, "train_kimi_linear_t8k", seconds="2")  # a tree of its own: tests/rehearsal_tree.py
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"]
    cpu_cannot = {"mla_attention_ms_per_step", "mla_attention_roofline", "kda_kernel_ms_per_step", "train.mfu_hybrid",
                  "step.device_ms", "train.peak_hbm_gb"}  # the last two: run.py gives a CPU no peaks and no memory_stats
    assert declared - cpu_cannot <= set(last["would_report"]), sorted(declared - cpu_cannot - set(last["would_report"]))
    assert "moe.dropped 0" in proc.stdout


@pytest.mark.parametrize("fault,caught", [(None, False), ("decay_on_every_leaf", True), ("beta2", True), ("rate", True)])
def test_the_cells_update_check_tells_the_stated_optimizer_from_a_faulty_one(fault, caught, monkeypatch):
    """benchmarks/train_hybrid_cell.py check (d): two updates of the repo's
    optimizer on seeded gradients, then the cell's fit and residual. The
    optimizer as the configuration states it passes both limits; decay on the
    leaves that take none, another beta2 or another rate does not."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    import optax

    from midgpt_tpu.models.kimi_linear import KimiLinear
    from midgpt_tpu.training.optim import make_optimizer

    spec = importlib.util.spec_from_file_location("train_hybrid_cell", os.path.join(ROOT, "benchmarks", "train_hybrid_cell.py"))
    cell = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cell)
    config = tiny_experiment(warmup_steps=20)
    stated = config
    if fault == "decay_on_every_leaf":
        monkeypatch.setattr(cell, "NO_DECAY", ())
    elif fault == "beta2":
        stated = config.replace(beta2=0.99)
    params = KimiLinear.init(config.model_config, jax.random.PRNGKey(0))
    optimizer, _ = make_optimizer(config)
    opt_state, before = optimizer.init(params), None
    for i in range(2):
        keys = jax.random.split(jax.random.PRNGKey(10 + i), len(jax.tree.leaves(params)))
        grads = jax.tree.unflatten(jax.tree.structure(params), [
            1e-3 * jax.random.normal(k, p.shape) for k, p in zip(keys, jax.tree.leaves(params))])
        before = params
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
    mu, nu = (optax.tree_utils.tree_get(opt_state, k) for k in ("mu", "nu"))
    fit, residual = cell.stated_update(stated)
    du, uu = (float(a) for a in fit(before, params, mu, nu, 2.0))
    rate = -du / uu
    rate_stated = config.learning_rate * (2.0 if fault == "rate" else 1.0) / config.warmup_steps
    d2, w2 = (float(a) for a in residual(before, params, mu, nu, 2.0, rate))
    ok = abs(rate / rate_stated - 1.0) <= cell.RATE_TOLERANCE and (d2 / w2) ** 0.5 <= cell.UPDATE_TOLERANCE
    assert ok == (not caught), (fault, rate, rate_stated, (d2 / w2) ** 0.5)
