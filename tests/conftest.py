"""Test harness: 8 virtual CPU devices so mesh/FSDP/collective code paths run
without TPUs (the test infra the reference lacks — SURVEY.md §4).

The platform and device count are forced through the config API, before
first backend use, so the suite is CPU-only whatever the caller's
environment says. The persistent compilation cache stays off here: only the
entry points turn it on (midgpt_tpu/utils/compile_cache.py).
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_threefry_partitionable", True)
# This JAX build defaults matmuls to reduced (bf16-style) precision even on
# CPU; force full f32 so numerical parity tests are meaningful.
jax.config.update("jax_default_matmul_precision", "highest")

import gc  # noqa: E402

import pytest  # noqa: E402

# Every program XLA:CPU compiles maps six or seven regions of its own into the process, and they stay as long as the
# jit caches hold the executable. A worker of the suite (six of them, `--dist load`) compiles nearly ten thousand
# programs and the kernel gives a process 65,530 mappings (`vm.max_map_count`): past that the next compile's mmap fails
# and LLVM ABORTS the worker (PR 59's driver run lost gw0 that way in its last tests, at 1,099 tests; sampled since:
# 47,000 mappings at two thirds of the run). Between two tests, once a worker is past this many, the caches are dropped:
# `jax.clear_caches()` unmaps them (2,407 -> 591 for 300 small programs) and what a later test needs compiles again.
_MAPS_BEFORE_A_CLEAR = 45_000


@pytest.fixture(autouse=True)
def _compiled_programs_stay_under_the_kernels_map_count():
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count
        return
    if held > _MAPS_BEFORE_A_CLEAR:
        jax.clear_caches()
        gc.collect()
