"""chip_smoke.py, held to its contract without a chip.

Two things can be shown here: the script's own plumbing works end to end
(through its documented TEST-ONLY seam, `--rehearse-cpu`, which changes the
ARGUMENTS the phases are run with — toy sizes, attn_impl=blockwise by name —
and never what a phase does when the device is missing), and the real
invocation refuses to pass without a TPU: the no-fallback rule as a test.
What it proves on the chip is the chip run's to show.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, timeout, **env):
    """chip_smoke.py as a subprocess (it starts JAX children of its own, and
    must never share a process with JAX). The compile cache goes to the
    test's directory — which is also the JAX_COMPILATION_CACHE_DIR half of
    the cache contract: set from outside, no path set in code."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        env={
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
            **env,
        },
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr
    return proc, lines, json.loads(lines[-1])


def test_real_invocation_fails_without_a_tpu(tmp_path):
    """No seam, no chip: exit code non-zero, last line `"ok": false`, and it
    stops at the device phase — no training on the CPU, no later phase."""
    proc, lines, last = _run([], tmp_path, 120)
    assert proc.returncode != 0
    assert last["ok"] is False and last["failed_phase"] == "device"
    assert last["device"] is None
    assert not any("run  train" in l for l in lines), lines
    assert not (tmp_path / "out" / "run").exists()


def test_four_chip_invocation_fails_without_four_tpus(tmp_path):
    proc, _, last = _run(["--chips", "4"], tmp_path, 120, JAX_NUM_CPU_DEVICES="4")
    assert proc.returncode != 0
    assert last["ok"] is False and last["failed_phase"] == "device"


def test_rehearsal_runs_every_one_chip_phase_on_cpu(tmp_path):
    """device -> data -> train -> 3x serve -> kernels, through launch.py and
    sample.py, at toy size; every check the script makes on the chip except
    the Mosaic-call counts it cannot meet here."""
    proc, lines, last = _run(["--rehearse-cpu"], tmp_path, 600)
    assert proc.returncode == 0, "\n".join(lines[-30:])
    assert last == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "rehearsal": True,
    }
    said = "\n".join(lines)
    for needle in (
        "ok   train: last loss",
        "ok   train: verified checkpoint on disk",
        "ok   serve_spec_bf16: 4 requests returned 64 tokens each",
        "ok   serve_int8: engine compiled the 'gather' paged attention",
        "ok   serve_batch: 4 requests returned 64 tokens each",
        "speculative: accept_rate",
        "compile-cache hit(s) on programs an earlier process compiled",
        "ok   kernels: every max error within its stated tolerance",
    ):
        assert needle in said, needle
    # the cache went where the environment said, and nowhere in the checkout
    assert any((tmp_path / "cache").iterdir())


def test_rehearsal_runs_the_four_chip_phase_on_virtual_devices(tmp_path):
    proc, lines, last = _run(
        ["--rehearse-cpu", "--chips", "4"], tmp_path, 600, JAX_NUM_CPU_DEVICES="4"
    )
    assert proc.returncode == 0, "\n".join(lines[-30:])
    assert last["ok"] is True and last["device"]["count"] == 4
    said = "\n".join(lines)
    assert "ok   fsdp4: gspmd and shard_map per-step losses agree" in said
    assert "run  train" not in said  # with the option no one-chip phase runs


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_compile_cache_dir_contract(from_env, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no path (JAX reads
    the variable); unset, the cache is <checkout>/.jax_cache — a fixed path.
    In a child: enabling the cache is process-wide, and the suite keeps it
    off."""
    code = (
        "import jax\n"
        "from midgpt_tpu.utils import compile_cache\n"
        "stats = compile_cache.enable()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(stats.dir)\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed_from_outside")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]
