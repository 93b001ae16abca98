"""The main path's Pallas kernels, compiled by the chip's own compiler for a
described (not attached) TPU v5e — what interpret mode cannot show: a slice
off the tiling, too much VMEM, a kernel Mosaic refuses. Nothing runs, so
these say nothing about results or times (chip_smoke.py does, on the chip).

The topology is described ONLY inside the module-scoped fixture below:
loading the TPU library belongs to one process at a time, every xdist worker
imports this file, so nothing here may touch it while a module is imported.
All of these tests stay in this one file for the same reason — the worker
that is handed the file loads the library once and keeps it.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("midgpt_tpu.kernels.flash_attention")
at = importlib.import_module("midgpt_tpu.kernels.attention_template")


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the missing/locked library raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device can be written to the persistent
    # cache but not read back without a chip: keep it off around these.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The kernels lower for Mosaic instead of the interpreter, as the
    program runs them. Both `_interpret` names are steered
    (attention_template imports it by name), and the suite-wide "highest"
    matmul precision (tests/conftest.py, for CPU parity) gives way to the
    default the entry points run under — Mosaic refuses an fp32-precision
    contraction of bf16 operands, which is a property of that test setting,
    not of the kernels."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(at, "_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        yield


def _mosaic_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize(
    "shape",
    [
        (16, 12, 1024, 64),  # local_text_124m: microbatch 16, 12 heads x 64
        (12, 16, 1024, 128),  # wide610m / openwebtext_xl heads: 16 x 128
    ],
    ids=["c64", "c128"],
)
def test_flash_fwd_bwd_compiles_for_v5e(shape, one_chip, compiled_kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, 512, 1024).astype(jnp.float32))

    # forward kernel + the fused backward kernel
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) >= 2


# 124M serving geometry: 12 heads x 64, 8-token pages, a 1024-token table.
H, C, PS, MAX_PAGES, N_PAGES, B = 12, 64, 8, 128, 257, 4


def _paged_args(dev, n_rows, quantized, h_q=H, h_kv=H):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=dev)
    pool = sds((h_kv, N_PAGES, PS, C), jnp.int8 if quantized else jnp.bfloat16)
    args = [
        sds((B, h_q, n_rows, C), jnp.bfloat16),
        pool,
        pool,
        sds((B, MAX_PAGES), jnp.int32),
        sds((B, n_rows), jnp.int32),
    ]
    if quantized:
        scale = sds((N_PAGES, h_kv, PS), jnp.float32)
        args += [scale, scale]
    return args


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("n_rows", [1, 5], ids=["decode", "verify5"])
def test_paged_template_compiles_for_v5e(n_rows, quantized, one_chip, compiled_kernels):
    """n_rows 1 is plain decode, 5 is the verify of spec_k_max=4 drafts."""
    args = _paged_args(one_chip, n_rows, quantized)
    assert _mosaic_calls(at.paged_attention_template, *args) == 1


def test_paged_template_split_k_compiles_for_v5e(one_chip, compiled_kernels):
    args = _paged_args(one_chip, 1, False)
    fn = lambda *a: at.paged_attention_template(*a, split_k=4)
    assert _mosaic_calls(fn, *args) == 1


def test_paged_template_window_sinks_gqa_compiles_for_v5e(one_chip, compiled_kernels):
    """Sliding window + sinks over a GQA pool (16 query / 4 KV heads)."""
    args = _paged_args(one_chip, 1, False, h_q=16, h_kv=4)
    fn = lambda *a: at.paged_attention_template(
        *a, sliding_window=256, attn_sinks=4
    )
    assert _mosaic_calls(fn, *args) == 1


@pytest.mark.parametrize("fsdp_mode", ["gspmd", "shard_map"])
def test_flash_train_step_compiles_on_four_chips(fsdp_mode, topo, compiled_kernels, monkeypatch):
    """The whole train step with attn_impl='flash' under FSDP over the 2x2
    mesh, at toy depth and real head geometry (T=1024, C=128). Under GSPMD
    the compiler cannot partition a Mosaic kernel, so the runtime maps the
    call over the batch axes itself (ops/attention.flash_attention_sharded);
    inside the explicit ZeRO-3 shard_map the kernel's outputs must carry
    their varying axes. On a CPU mesh neither shows: interpreted kernels are
    plain HLO."""
    import numpy as np
    from jax.sharding import Mesh

    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.parallel.mesh import AXES
    from midgpt_tpu.utils.hlo import lower_abstract_train_step

    # the dispatcher asks the BACKEND whether the kernel can run; here the
    # backend is the CPU and the target is the described chip
    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    config = ExperimentConfig(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=8,
        warmup_steps=2, min_lr=1e-5, lr_decay_steps=10, max_steps=10,
        beta2=0.95, weight_decay=1e-4, eval_interval=5,
        param_dtype="float32", compute_dtype="bfloat16", g_accum_iters=2,
        shard_model=True, fsdp_mode=fsdp_mode,
        mesh=MeshConfig(data=-1, fsdp=4, sp=1),
        model_config=GPTConfig(
            block_size=1024, vocab_size=2048, n_layer=2, n_head=2, n_embd=256,
            attn_impl="flash",
        ),
    )
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1, 1, 1, 1), axis_names=AXES)
    hlo = lower_abstract_train_step(config, mesh=mesh).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2  # flash forward and backward
    assert "all-gather" in hlo  # the weights really are sharded over fsdp
    # ... and the eval program the loop runs before the first step: the
    # implicit-GSPMD forward under EITHER mode (the first four-chip run of
    # the smoke died here under shard_map)
    eval_hlo = (
        lower_abstract_train_step(config, mesh=mesh, eval_program=True)
        .compile()
        .as_text()
    )
    assert eval_hlo.count("tpu_custom_call") >= 1
