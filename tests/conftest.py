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
