"""Trace-level compile checks for the at-scale configs.

The 7B-class configs can't be materialized on a CPU test host, but the whole
training step — FSDP sharding specs, ring/flash attention dispatch, grad
accumulation, optimizer — can be traced and lowered against abstract inputs.
This catches shape/sharding/spec bugs in exactly the configurations that
only ever run on pods (`jit.lower` runs full tracing + SPMD spec checks; it
stops short of backend codegen).
"""

import dataclasses

import jax
import pytest

from midgpt_tpu.parallel.mesh import fit_mesh_config, make_mesh
from midgpt_tpu.utils.hlo import lower_abstract_train_step as _lower_train_step


@pytest.mark.parametrize("schedule", ["compiler", "authored"])
@pytest.mark.parametrize(
    "name", ["llama7b_long", "llama7b_32k", "openwebtext_xl", "wide610m"]
)
def test_at_scale_config_train_step_lowers(name, schedule, monkeypatch):
    """Under both collective schedules. On the eight-device test mesh every one
    of these presets DERIVES the authored ZeRO-3 schedule (fsdp_mode 'auto');
    the compiler's is forced by name. The interpreter cannot run a Pallas
    kernel inside a shard_map that checks varying axes (jax 0.9.0), so the
    authored leg swaps a configured flash for blockwise BY NAME, here in the
    test, and leaves the ring on its jnp pair path: flash inside the authored
    body is compiled by the chip's own compiler in tests/test_chip_compile.py."""
    import importlib

    if schedule == "compiler":
        # attn_impl='flash' configs lower their real Pallas kernels (interpret
        # mode off-TPU); without this the train step refuses to trace on CPU —
        # a configured flash is never quietly swapped for blockwise.
        fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
        monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    config = importlib.import_module(f"midgpt_tpu.configs.{name}").config
    # Shrink only what tracing doesn't need big: steps/batch stay as-is,
    # layer count drops (the scan makes depth O(1) for tracing anyway, but
    # 32 unrolled grad-accum microsteps x 32 layers is slow to trace).
    config = config.replace(
        g_accum_iters=min(config.g_accum_iters, 2),
        # Single-chip configs (wide610m: batch 12) must still shard over the
        # 8-device test mesh — round the batch up, shapes are abstract anyway.
        batch_size=-(-config.batch_size // 8) * 8,
        model_config=dataclasses.replace(config.model_config, n_layer=2),
        # serving-only knob: must shrink with n_layer (validated against
        # it) and is irrelevant to the train step being lowered here
        spec_layers=min(config.spec_layers, 1),
    )
    # the pod meshes (fsdp=16, sp=8) are re-derived for the 8-device test
    # mesh explicitly; make_mesh itself never resizes an axis
    config = config.replace(mesh=fit_mesh_config(config.mesh, jax.device_count()))
    if schedule == "compiler":
        config = config.replace(fsdp_mode="gspmd")
    elif config.model_config.attn_impl == "flash":
        config = config.replace(
            model_config=dataclasses.replace(config.model_config, attn_impl="blockwise")
        )
    assert config.fsdp_schedule(make_mesh(config.mesh).shape) == schedule
    lowered = _lower_train_step(config)
    assert "main" in lowered.as_text()[:2000]
