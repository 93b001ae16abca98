"""A traced CPU rehearsal of a benchmark cell, run from a tree of its own.

`benchmarks/run.py --trace 1` writes its profile to `<its own directory>/.work/
trace` and the per-layer readers look there (`HERE/.work`): every traced
rehearsal of the checkout shares that one directory, so two of them at once (the
suite runs six workers) read each other's trace or find it deleted. The
benchmark's files may not be edited for it and a lock would put the rehearsals
in a row; instead each test runs the SAME files from a temporary tree of
symlinks (`benchmarks/`'s entries one by one, `BENCHMARK.json`, `midgpt_tpu`),
in which `HERE/.work` is the test's own. Nothing resolves the links: run.py and
the readers take `os.path.abspath(__file__)`.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearsal_tree(tmp_path) -> str:
    """`tmp_path/tree`: the checkout as run.py needs it, by symlinks; its `benchmarks/.work` is nobody else's."""
    tree = os.path.join(str(tmp_path), "tree")
    os.makedirs(os.path.join(tree, "benchmarks"))
    for name in ("BENCHMARK.json", "midgpt_tpu"):
        os.symlink(os.path.join(ROOT, name), os.path.join(tree, name))
    for name in os.listdir(os.path.join(ROOT, "benchmarks")):
        if name not in (".work", "__pycache__"):
            os.symlink(os.path.join(ROOT, "benchmarks", name), os.path.join(tree, "benchmarks", name))
    return tree


def run_rehearsal(tmp_path, cell: str, *, seconds: str = "2", trace: str = "1", timeout: int = 900, script: str = "run.py"):
    """`benchmarks/<script> --workload <cell> --rehearse-cpu` from a tree of its
    own (with `--seconds` / `--trace` where the script is run.py), on the CPU,
    with a compile cache under `tmp_path`; the finished process."""
    tree = rehearsal_tree(tmp_path)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=os.path.join(str(tmp_path), "cache"), JAX_PLATFORMS="cpu")
    args = [sys.executable, os.path.join(tree, "benchmarks", script), "--workload", cell, "--seed", "3000000019", "--rehearse-cpu"]
    if script == "run.py":
        args += ["--seconds", seconds, "--trace", trace]
    return subprocess.run(args, cwd=tree, env=env, capture_output=True, text=True, timeout=timeout)
