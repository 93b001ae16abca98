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
pw = importlib.import_module("midgpt_tpu.kernels.paged_write")
gm = importlib.import_module("midgpt_tpu.kernels.grouped_matmul")
lp = importlib.import_module("midgpt_tpu.kernels.latent_prefill")


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
    program runs them. Every `_interpret` name is steered (attention_template
    and paged_write import it by name), and the suite-wide "highest"
    matmul precision (tests/conftest.py, for CPU parity) gives way to the
    default the entry points run under — Mosaic refuses an fp32-precision
    contraction of bf16 operands, which is a property of that test setting,
    not of the kernels."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(at, "_interpret", lambda: False)
    monkeypatch.setattr(pw, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(lp, "_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        yield


def _mosaic_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count("tpu_custom_call")


# The flash kernels' Mosaic custom calls as the v5e trace names them: the
# instruction takes the innermost scope's name, which is what
# benchmarks/metrics/flash_attention.py finds them by (no name= on the
# pallas_call, no named_scope opened around it).
_FLASH_CALL = r"%((?:attn|shard_map)\.\d+) = [^\n]*tpu_custom_call"


def _custom_call_names(hlo: str):
    import re

    named = re.findall(_FLASH_CALL, hlo)
    assert len(named) == hlo.count('custom_call_target="tpu_custom_call"'), (
        "a Mosaic custom call of the step is not named attn.<n> / shard_map.<n>"
    )
    return named


@pytest.mark.parametrize("tile", [256, 512], ids=["t256", "t512"])
@pytest.mark.parametrize(
    "shape",
    [
        (16, 12, 1024, 64),  # local_text_124m: microbatch 16, 12 heads x 64
        (12, 16, 1024, 128),  # wide610m / openwebtext_xl heads: 16 x 128
    ],
    ids=["c64", "c128"],
)
def test_flash_fwd_bwd_compiles_for_v5e(shape, tile, one_chip, compiled_kernels):
    """The tiled kernels (one KV block at T=1024) at both benchmark widths,
    at the tile the policy derives (256) and the other one it was measured
    against."""
    from midgpt_tpu.ops.attention import flash_block_sizes

    assert flash_block_sizes(1024, 1024) == (256, 1024)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, tile, 1024).astype(jnp.float32))

    # forward kernel + the fused backward kernel
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) >= 2


def test_flash_one_kv_block_past_1024_compiles_for_v5e(one_chip, compiled_kernels):
    """T=2048 in one KV block is past the tiled kernels (a head's operands
    whole in VMEM): the multi-block grid kernels serve it with n_k = 1."""
    x = jax.ShapeDtypeStruct((2, 4, 2048, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, 256, 2048).astype(jnp.float32))

    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 3  # fwd, dq, dk/dv


def test_124m_train_step_compiles_and_names_its_flash_calls(topo, compiled_kernels, monkeypatch):
    """`train_124m`'s step program (local_text_124m: 12 heads of 64, T=1024,
    microbatch 16, layer scan unrolled, remat off) at reduced depth, for one
    described v5e: the tiled kernels compile inside it, and each custom call
    is still named by its innermost scope, `attn.<n>` (one forward and one
    fused backward a layer)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from midgpt_tpu.config import MeshConfig, load_config
    from midgpt_tpu.parallel.mesh import AXES
    from midgpt_tpu.utils.hlo import lower_abstract_train_step

    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    config = load_config("local_text_124m")
    config = config.replace(
        batch_size=16, g_accum_iters=2, spec_layers=0, mesh=MeshConfig(data=1, fsdp=1, sp=1),
        model_config=dataclasses.replace(config.model_config, n_layer=2, scan_unroll=2),
    )
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1, 1, 1, 1, 1), axis_names=AXES)
    names = _custom_call_names(lower_abstract_train_step(config, mesh=mesh).compile().as_text())
    assert len(names) == 4 and all(n.startswith("attn.") for n in names), names


# 124M serving geometry: 12 heads x 64 in a pool of whole 128-lane rows (as
# the engine allocates it on the kernel path: PagedKVCache "Layout
# contract"), 8-token pages, a 1024-token table.
H, C, LANES, PS, MAX_PAGES, N_PAGES, B = 12, 64, 128, 8, 128, 257, 4


def _paged_args(dev, n_rows, quantized, h_q=H, h_kv=H):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=dev)
    pool = sds((h_kv, N_PAGES, PS, LANES), jnp.int8 if quantized else jnp.bfloat16)
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


# Both benchmark cells' kernel-path pools (PagedKVCache "Layout contract":
# 128 lanes a row) at their decode geometry: (slots, H_kv, head_dim, widest
# table in pages, split_k at that table). serve_124m_sample's contexts reach
# 160 tokens (tables of 1-32 pages); serve_xl_chat's reach 880, and the
# 1,024-token bucket splits in two (ServeEngine._split_bucket).
BENCH_SHAPES = {"124m": (48, 12, 64, 32, 1), "xl": (16, 16, 128, 128, 2)}
# variant: (query rows, int8 pool, query heads per pool head, template kwargs)
BENCH_VARIANTS = {
    "decode": (1, False, 1, {}),
    "verify5": (5, False, 1, {}),
    "int8": (1, True, 1, {}),
    "int8_verify5": (5, True, 1, {}),
    "split4": (1, False, 1, dict(split_k=4)),
    "gqa4": (1, False, 4, {}),
    "window_sinks": (1, False, 1, dict(sliding_window=256, attn_sinks=4)),
}


def _bench_args(dev, shape, table, n_rows, quantized, groups):
    slots, h, c, _, _ = BENCH_SHAPES[shape]
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=dev)
    n_pages, layers = slots * table + 1, 2
    pool = sds((layers, h, n_pages, PS, LANES), jnp.int8 if quantized else jnp.bfloat16)
    scale = sds((layers, n_pages, h, PS), jnp.float32) if quantized else None
    return [
        sds((slots, h * groups, n_rows, c), jnp.bfloat16), pool, pool,
        sds((slots, table), jnp.int32), sds((slots, n_rows), jnp.int32),
        scale, scale,
    ]


@pytest.mark.parametrize("variant", list(BENCH_VARIANTS))
@pytest.mark.parametrize("shape", list(BENCH_SHAPES))
def test_paged_template_variants_compile_at_benchmark_shapes(shape, variant, one_chip, compiled_kernels):
    """Every spec of the blocked template at both cells' widest table, with
    the block width the call derives (the page copies' slices, the double
    buffers' VMEM and the scalar loops are what Mosaic can refuse)."""
    n_rows, quantized, groups, kw = BENCH_VARIANTS[variant]
    _, _, _, table, split = BENCH_SHAPES[shape]
    kw = {"split_k": split, **kw}
    args = _bench_args(one_chip, shape, table, n_rows, quantized, groups)
    fn = lambda *a: at.paged_attention_template(*a, layer=jnp.int32(1), **kw)
    assert _mosaic_calls(fn, *args) == 1


def test_paged_template_compiles_for_a_pool_off_the_layout_contract(one_chip, compiled_kernels):
    """A pool of 64-channel rows (no `kernel_layout`: what the benchmark's
    correctness check allocates) still compiles: the wrapper pads it."""
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pool = sds((12, H, 7, PS, C), jnp.bfloat16)
    args = [sds((1, H, 1, C), jnp.bfloat16), pool, pool, sds((1, 6), jnp.int32), sds((1, 1), jnp.int32)]
    fn = lambda *a: at.paged_attention_template(*a, layer=jnp.int32(3))
    assert _mosaic_calls(fn, *args) == 1


@pytest.mark.parametrize("table", [1, 2, 4, 8, 16])
def test_paged_template_compiles_at_every_narrow_table(table, one_chip, compiled_kernels):
    """serve_124m_sample's smaller page buckets: a table narrower than the
    derived block is one block of its own width."""
    args = _bench_args(one_chip, "124m", table, 1, False, 1)
    fn = lambda *a: at.paged_attention_template(*a, layer=jnp.int32(0))
    assert _mosaic_calls(fn, *args) == 1


# ----------------------------------------------------------------------
# The serving programs' pool layout census (PagedKVCache "Layout contract")
# ----------------------------------------------------------------------

# Both benchmark configurations' head shapes at toy depth and vocabulary:
# what decides the pool's layout is (H, ps, C), not L or V. C = 64 is the
# shape whose own DEFAULT device layout puts the page dim minor, which is
# why a kernel-path pool is allocated at whole 128-lane rows. The pool is
# 2,049 pages (50-130 MB, nothing is allocated): a pool of a few MB the
# compiler moves into fast memory with a pool-sized `copy-start`, which the
# census counts and no real pool can see.
SERVE_HEADS = {"h12c64": (12, 64), "h16c128": (16, 128)}
SERVE_L, SERVE_SLOTS, SERVE_PAGES, SERVE_TABLE = 2, 8, 2049, 16


def _serve_program_args(heads, dev, cache_dtype):
    from midgpt_tpu.models.gpt import GPT, GPTConfig, PagedKVCache

    n_head, head_dim = SERVE_HEADS[heads]
    cfg = GPTConfig(
        block_size=1024, vocab_size=512, n_layer=SERVE_L, n_head=n_head,
        n_embd=n_head * head_dim,
    )
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)
    params = jax.tree.map(
        lambda a: sds(jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)),
        jax.eval_shape(lambda k: GPT.init(cfg, k), jax.random.PRNGKey(0)),
    )
    # the pool as the engine allocates it on the kernel path
    cache = jax.tree.map(
        sds,
        jax.eval_shape(
            lambda: PagedKVCache.init(
                cfg, SERVE_PAGES, PS, cache_dtype, kernel_layout=True
            )
        ),
    )
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    return cfg, params, cache, arr


def _compiled_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= 1
    return text


def _pool_census(lowered, cache) -> int:
    from midgpt_tpu.analysis.hlo_audit import pool_relayouts

    return pool_relayouts(_compiled_text(lowered), [cache.k.shape, cache.v.shape])


@pytest.mark.parametrize("heads", list(SERVE_HEADS))
@pytest.mark.parametrize(
    "program", ["decode1", "decode8", "decode8_int8", "prefill", "verify5"]
)
def test_serving_program_never_relays_out_the_pool(program, heads, one_chip, compiled_kernels):
    """The TPU-compiled text of the decode (1 step: straight-line; 8 steps:
    a while loop), prefill, speculative-verify and int8-pool decode programs
    holds NO copy or transpose as large as the pool or as one layer of it
    (analysis/hlo_audit.pool_relayouts == 0): the pool keeps the one layout
    the paged kernels read, from the program's parameter to its result.

    On the parent of PR 25 the same census read 4 + 2L for a bf16 pool
    (28 for the 12-layer 124M decode program: an entry and an exit relayout
    per pool tensor around the XLA scatter's preferred layout, and one
    layer-sized copy per tensor per layer for the attention custom call);
    each decode STEP paid the 2L, each program CALL the 4.

    The same text holds NO gather over an activation's channels
    (`rotary_gathers` == 0; `rope_style` is the default, "interleaved"): the
    serving entry point of the rotary rolls lanes. On the parent of PR 57 it
    read 4 a layer, q's and k's even and odd channels, in every one of these
    programs.

    And NO instruction that writes a layer of a stacked weight matrix out
    again (`weight_copies` == 0, at both head shapes: step 1 of PR 62 read
    the per-third einsum faster at the 124M's widths too): the projection
    contracts the `(3, D, D)` layer where it lies, by `GPT._decode_layer_loop`'s
    choice and not this config's (`qkv_proj` is the default, "fused"). On the
    parent of PR 62 (the flat reshape of the indexed layer) it read 1
    here, one fusion of SERVE_L x `bf16[1,3,D,D]`, and 2 at the XL's 24
    layers (19 + 5 layers, 604 MB a decode step and a prefill call)."""
    from midgpt_tpu.analysis.hlo_audit import pool_relayouts, rotary_gathers, weight_copies
    from midgpt_tpu.sampling import serve

    quantized = program.endswith("int8")
    cfg, params, cache, arr = _serve_program_args(
        heads, one_chip, jnp.int8 if quantized else jnp.bfloat16
    )
    B, key = SERVE_SLOTS, arr((2,), jnp.uint32)
    table, lengths, active = arr((B, SERVE_TABLE)), arr((B,)), arr((B,), jnp.bool_)
    if program.startswith("decode"):
        n_steps = 1 if program == "decode1" else 8
        lowered = serve._serve_decode_chunk.lower(
            cfg, params, arr((B,)), cache, table, lengths, active, n_steps,
            0.8, None, None, "kernel", key,
        )
    elif program == "prefill":
        # the serving cells' batch: 16 slots' chunks of 16 tokens a program,
        # at the page bucket of a 1024-token row (serve_xl_chat's longest)
        W = serve.prefill_width(16, 16)
        lowered = serve._serve_prefill_chunk.lower(
            cfg, params, arr((W, 16)), arr((W,)), arr((W,)), cache,
            arr((W, 128)), None, "kernel", 0.8, None, None, key,
        )
    else:
        k = 4  # spec_k_max drafts + the pending token: 5 verify rows
        lowered = serve._spec_verify_chunk.lower(
            cfg, params, arr((B,)), arr((k, B)),
            arr((k, B, cfg.vocab_size), jnp.float32), cache, table, lengths,
            active, 0.8, None, None, "kernel", key,
        )
    text = _compiled_text(lowered)
    assert pool_relayouts(text, [cache.k.shape, cache.v.shape]) == 0
    assert rotary_gathers(text) == 0
    assert weight_copies(text, [a.shape for a in jax.tree.leaves(params.blocks)]) == 0


@pytest.mark.parametrize("spelling", ["flat_reshape_of_the_indexed_layer", "the_layer_as_it_lies"])
def test_weight_census_counts_the_flat_reshape_of_an_indexed_stack(spelling, one_chip):
    """The census of the census: the QKV projection of a Python-unrolled
    layer loop over STACKED `(L, 3, D, D)` parameters, compiled on its own at
    SERVE_L layers of the XL's D = 2,048 and a decode step's 16 rows. The
    parent of PR 62's spelling (index the stack, reshape the layer to
    `(3D, D)`, one matmul) counts: the slice and the reshape do not fuse into
    the matmul's operand, and one multi-output `slice` fusion writes every
    layer's `bf16[1,3,2048,2048]`. The per-third einsum over the layer as it
    lies (`qkv_proj="split3"`, what the serving programs' unrolled layer loop
    takes) counts 0."""
    from midgpt_tpu.analysis.hlo_audit import weight_copies

    D = 2048
    w = jax.ShapeDtypeStruct((SERVE_L, 3, D, D), jnp.bfloat16, sharding=one_chip)
    h = jax.ShapeDtypeStruct((16, 1, D), jnp.bfloat16, sharding=one_chip)

    def project(h, w):
        for i in range(SERVE_L):
            if spelling == "the_layer_as_it_lies":
                qkv = jnp.einsum("btd,xed->btxe", h, w[i])
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                q, k, v = jnp.split(jnp.einsum("btd,ed->bte", h, w[i].reshape(3 * D, D)), 3, axis=-1)
            h = jnp.tanh(q * k + v)
        return h

    text = jax.jit(project).lower(h, w).compile().as_text()
    assert (weight_copies(text, [w.shape]) > 0) == (spelling != "the_layer_as_it_lies"), text


@pytest.mark.parametrize("style", ["strided", "rolled"])
def test_rotary_census_counts_the_strided_spelling_s_channel_gathers(style, one_chip):
    """The census of the census: the interleaved rotation in its stride-2
    spelling (`rotate_interleaved_strided`: the training entry points', every
    program's up to PR 57), compiled on its own at the XL decode step's
    geometry, counts (one gather for the even channels, one for the odd);
    the rolls and select of `rotate_interleaved` count 0 beside it."""
    from midgpt_tpu.analysis.hlo_audit import rotary_gathers
    from midgpt_tpu.ops.rope import rotate_interleaved, rotate_interleaved_strided

    x = jax.ShapeDtypeStruct((16, 1, 16, 128), jnp.bfloat16, sharding=one_chip)
    rotate = rotate_interleaved_strided if style == "strided" else rotate_interleaved
    text = jax.jit(lambda x: x + rotate(x)).lower(x).compile().as_text()
    assert (rotary_gathers(text) > 0) == (style == "strided"), text


@pytest.mark.parametrize("heads", list(SERVE_HEADS))
@pytest.mark.parametrize("chunk", [16, 128])
def test_prefill_program_reads_the_pool_through_the_template(chunk, heads, one_chip, compiled_kernels):
    """The cells' `(16, 16)` prefill program at both head shapes, and one of
    128-token chunks (the template's block down to 8 pages): two Mosaic
    calls a layer, named `kv_write` and `prefill_attn` (the innermost scope:
    what benchmarks/metrics/prefill_attention.py finds, and NOT decode's
    `closed_call`), no XLA computation that takes the pool (the per-layer
    gather of every row's page bucket is gone), no scoped-VMEM request over
    the default, census 0."""
    import re

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.sampling import serve

    cfg, params, cache, arr = _serve_program_args(heads, one_chip, jnp.bfloat16)
    W = 16
    text = serve._serve_prefill_chunk.lower(
        cfg, params, arr((W, chunk)), arr((W,)), arr((W,)), cache,
        arr((W, 128)), None, "kernel", 0.8, None, None, arr((2,), jnp.uint32),
    ).compile().as_text()
    calls = re.findall(r"%([a-z_]+)\.\d+ = [^\n]*tpu_custom_call", text)
    assert sorted(calls) == sorted(["kv_write", "prefill_attn"] * SERVE_L)
    pool = "bf16[" + ",".join(map(str, cache.k.shape)) + "]"
    assert not [ln for ln in text.splitlines() if re.match(r"%\S+ \(", ln) and pool in ln]
    assert pool_relayouts(text, [cache.k.shape, cache.v.shape]) == 0
    assert set(re.findall(r'"scoped_memory_configs":(\[[^\]]*\])', text)) <= {"[]"}


def test_pool_census_counts_an_xla_scatter_on_the_pool(one_chip, compiled_kernels):
    """The census has teeth: the fallback write (an XLA scatter) in a
    program that also runs the attention kernel relays the pool out."""
    from midgpt_tpu.models.gpt import GPT

    cfg, params, cache, arr = _serve_program_args("h16c128", one_chip, jnp.bfloat16)
    B = SERVE_SLOTS

    def step(params, token, cache, table, lengths, active):
        # scatter write ('gather' path) ...
        _, cache = GPT.decode_step_paged(
            cfg, params, token, cache, table, lengths, active, attn_impl="gather"
        )
        # ... and the kernel read, in one program
        return GPT.decode_step_paged(
            cfg, params, token, cache, table, lengths, active, attn_impl="kernel"
        )

    lowered = jax.jit(step, donate_argnums=(2,)).lower(
        params, arr((B,)), cache, arr((B, SERVE_TABLE)), arr((B,)),
        arr((B,), jnp.bool_),
    )
    assert _pool_census(lowered, cache) >= 2


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


@pytest.mark.parametrize("schedule", ["authored", "compiler"])
def test_fsdp_schedule_collective_census_at_xl_widths(schedule, topo, compiled_kernels, monkeypatch):
    """`train_xl_fsdp4`'s step at its widths (D=2048, 16 heads of 128, V=50304,
    T=1024, 2 sequences a chip x G=2, bf16 over f32, remat 'dots', flash) and
    reduced depth, compiled for the 2x2 v5e under each collective schedule.

    authored (what a config that says nothing derives on this mesh): no
    all-to-all, the weight-sized gradients reduce-SCATTERED, no weight-sized
    all-reduce left. compiler (forced by name; the parent of PR 29): no
    reduce-scatter at all; the gradients it sums across chips it all-reduces.

    The precision rule (ISSUE 29): the cross-chip gradient sum is carried in
    the dtype the compiler's schedule carries it in. At these shapes its
    weight-sized gradient all-reduces run in bfloat16 on bf16-rounded
    per-chip partials (wqkv 3x2048x2048 and a 50304x2048 vocabulary matrix),
    so the authored reduce-scatter, the transpose of a tiled bf16 all_gather,
    sums the same bf16 partials in the same dtype. Both sides pin `bf16`: a
    toolchain that moves either one fails here, not in a loss curve."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh

    from midgpt_tpu.config import MeshConfig, load_config
    from midgpt_tpu.parallel.mesh import AXES
    from midgpt_tpu.utils.hlo import (
        collective_census,
        hlo_computations,
        lower_abstract_train_step,
        permute_overlap_census,
        while_body_names,
    )

    monkeypatch.setattr(fa, "RUN_INTERPRET_OFF_TPU", True)
    config = load_config("openwebtext_xl")
    config = config.replace(
        batch_size=8, g_accum_iters=2, mesh=MeshConfig(data=-1, fsdp=4, sp=1),
        # three layers: the backward over the stack peels the top one, and
        # two are left for its loop to stay a loop
        model_config=dataclasses.replace(config.model_config, n_layer=3),
        **({"fsdp_mode": "gspmd"} if schedule == "compiler" else {}),
    )
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1, 1, 1, 1), axis_names=AXES)
    assert config.fsdp_schedule(mesh.shape) == schedule
    hlo = lower_abstract_train_step(config, mesh=mesh).compile().as_text()
    # the tiled flash kernels at C=128 inside the step, under the names the
    # benchmark's flash metrics find them by
    assert len(_custom_call_names(hlo)) >= 2
    census = collective_census(hlo)
    weight_sized = [c for c in census if c[2] >= config.fsdp_min_size]
    assert {dtype for op, dtype, _ in weight_sized if op == "all-gather"} == {"bf16"}, census
    permutes = permute_overlap_census(hlo)
    if schedule == "compiler":
        assert {dtype for op, dtype, _ in weight_sized if op == "all-reduce"} == {"bf16"}, census
        assert not [c for c in weight_sized if c[0] == "reduce-scatter"], census
        assert not permutes and not [c for c in census if c[0] == "collective-permute"], census
        return
    assert not [c for c in census if c[0] == "all-to-all"], census
    assert not [c for c in weight_sized if c[0] == "all-reduce"], census
    # wte's gradient is the one the compiler's reduce-scatter still sums ...
    assert [c[1:] for c in weight_sized if c[0] == "reduce-scatter"] == [("bf16", 50304 * 512)], census
    # ... in the microstep's body: no loop over the layers holds one
    scatter_bodies = [
        name for name, lines in hlo_computations(hlo).items()
        if name in while_body_names(hlo) and any(" reduce-scatter(" in l for l in lines)
    ]
    assert len(scatter_bodies) == 1 and any(
        " while(" in l for l in hlo_computations(hlo)[scatter_bodies[0]]
    ), scatter_bodies
    # a layer's four leaves, packed, a destination: (2048 + 3*2048 + 8192 + 8192) x 512
    sent = sorted(c[1:] for c in weight_sized if c[0] == "collective-permute")
    assert sent == [("bf16", 24576 * 512)] * 6 + [("bf16", 50304 * 512)] * 3, census
    assert {d for entry in permutes for d in entry["dtypes"]} == {"bf16"}, permutes
    (backward_body,) = [e for e in permutes if e["loop_body"] and e["kind"] == "backward"]
    assert backward_body["pairs"] == backward_body["covered"] == 3, permutes
    # the microstep's own body: lm_head's three beside the top layer's
    # backward, layer 0's three after the loop beside nothing
    (microstep,) = [e for e in permutes if e is not backward_body]
    assert (microstep["pairs"], microstep["covered"]) == (6, 3), permutes


def test_shard_map_fsdp_gathers_overlap_compute_on_four_chips(topo):
    """The other half of the ZeRO-3 overlap claim (parallel/shard_map_fsdp.py;
    tests/test_shard_map_fsdp.py pins the dataflow half on the CPU mesh): in
    the text the chip's compiler emits for the shard_map FSDP step, every
    gather-bearing scan body, forward and backward, has at least one
    all-gather that is async-annotated or fused into a compute kernel. The
    CPU backend emits synchronous all-gathers, so only this compile shows it.
    Real-ish shapes, so the scheduler has matmuls worth hiding gathers behind,
    and no compiler option: launch.py sets none (docs/PARALLELISM.md
    "Overlap"). Seven layers: the backward over the stack peels the top one
    and at scan_unroll=2 a shorter loop is unrolled whole, leaving no
    backward body to grade."""
    import numpy as np
    from jax.sharding import Mesh

    from midgpt_tpu.config import ExperimentConfig, MeshConfig
    from midgpt_tpu.models.gpt import GPTConfig
    from midgpt_tpu.parallel.mesh import AXES
    from midgpt_tpu.utils.hlo import gather_overlap_census, lower_abstract_train_step

    config = ExperimentConfig(
        rundir="", data_dir="", learning_rate=1e-3, batch_size=16,
        warmup_steps=2, min_lr=1e-4, lr_decay_steps=10, max_steps=10,
        beta2=0.95, weight_decay=1e-4, eval_interval=5,
        param_dtype="float32", compute_dtype="bfloat16", g_accum_iters=1,
        shard_model=True, fsdp_min_size=0, fsdp_mode="shard_map",
        mesh=MeshConfig(data=1, fsdp=4, sp=1),
        model_config=GPTConfig(
            block_size=512, vocab_size=8192, n_layer=7, n_head=8, n_embd=512,
            attn_impl="naive", scan_unroll=2,
        ),
    )
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4, 1, 1, 1, 1), axis_names=AXES)
    with jax.default_matmul_precision("default"):
        hlo = lower_abstract_train_step(config, mesh=mesh).compile().as_text()
    census = gather_overlap_census(hlo)
    assert {b["kind"] for b in census} == {"forward", "backward"}, census
    serialized = [b for b in census if b["annotated"] + b["fused"] == 0]
    assert not serialized, f"scan bodies whose weight gathers all run behind compute: {serialized}"


@pytest.mark.parametrize("program", ["decode8", "prefill512"])
def test_two_kind_serving_program_never_relays_out_either_pool(program, one_chip, compiled_kernels):
    """models/mimo_v2.py at its published head widths (q/k 192, v 128; 4 K/V
    heads in the global layer, 8 in the window layers; three layers, two held
    experts): the decode program (the global layer through the template with
    `v_dim`, the window layers through an XLA gather) and the prefill program
    compile for the v5e with NO copy as large as either kind's pool or one
    layer of it, and every layer's K/V write is the in-place Pallas one."""
    import dataclasses

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    mc = dataclasses.replace(load_config("mimo_v2_5").model_config, n_layer=3, n_experts_held=2, vocab_size=512)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    # 4,097 global pages: a V pool of 134 MB. At 2,049 (67 MB) the compiler may park the WHOLE toy pool in VMEM around
    # the prefill program (a copy-start / copy-done pair in one layout: no relayout, and nothing a cell's GBs can meet)
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (4097, 673), 32, jnp.bfloat16, kernel_layout=True)))
    assert cache.pools[0][0].shape[-1] == 256 and cache.pools[0][1].shape[-1] == 128  # K 192 at whole lanes, V 128
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    B, T = 32, 64
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, (arr((B, T)), arr((B, T))), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 2)
    else:
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((1, 512)), arr(()), arr(()), cache, (arr((1, T)), arr((1, T))), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    text = lowered.compile().as_text()
    # 3 writes (+ the global layer's attention) and ONE grouped matmul a routed layer
    assert text.count("tpu_custom_call") == (4 if program == "decode8" else 3) + len(mc.moe_layers)
    assert pool_relayouts(text, [a.shape for a in cache.pool_arrays()]) == 0


@pytest.mark.parametrize("program", ["decode8", "prefill512"])
def test_window_layers_decode_through_the_kernel_at_the_published_geometry(program, one_chip, compiled_kernels):
    """models/trinity.py as the benchmark's cell runs it (5 layers, 128 experts,
    32 q heads over 4 K/V heads of 128, window 2,048, pages of 32, 64 slots, a
    table of 512 pages, split-K 8 for the global layer): in the decode program
    BOTH kinds' attention are Mosaic custom calls (four under `attn_window`: no
    gathered copy of the window's pages; one under `attn_global`) beside the
    five in-place writes, the prefill program has the five writes alone, and
    neither copies or relays out a pool. The prefill program is the BATCHED
    one, `(W, 512)` at the width the family's rows give (4: the chunks of four
    slots read the 128 experts once): every layer's attention is ONE loop over
    the rows (the global layer's around its loop over a row's key blocks), and
    its temporaries (the experts' buffer of 49,152 rows; ONE row's window
    scores at a time) are recorded in the assertion: 0.68 GB here, held under
    1 GB, beside 8.5 GB of weights and 2.7 of pools (0.29 GB at one row)."""
    import dataclasses
    import re

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    mc = dataclasses.replace(load_config("trinity_mini").model_config, n_layer=5, n_dense_layers=1)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (20481, 5185), 32, jnp.bfloat16, kernel_layout=True)))
    assert cache.pools[0][0].shape == (1, 4, 20481, 32, 128) and cache.pools[1][0].shape == (4, 4, 5185, 32, 128)
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    B, T = 64, 512
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, (arr((B, T)), arr((B, T))), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 8)
    else:
        W = serve.prefill_width(B, 512, model.prefill_rows(mc, serve.PREFILL_ROWS))
        assert model.prefill_batched and W == 4
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((W, 512)), arr((W,)), arr((W,)), cache, (arr((W, T)), arr((W, T))), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    compiled = lowered.compile()
    text = compiled.as_text()
    paths = re.findall(r'custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', text)
    attention = [p for p in paths if "kv_write" not in p and "moe_experts" not in p]
    assert sum("kv_write" in p for p in paths) == 5
    assert sum("moe_experts" in p for p in paths) == len(mc.moe_layers) == 4  # ONE grouped matmul a routed layer
    loops = re.findall(r' while\([^\n]*op_name="([^"]*)"', text)
    if program == "decode8":  # the step loop is the program's ONLY loop: the expert loop, whose trip count was data, is gone
        assert loops == ["jit(_serve_decode_chunk)/while"]
    else:  # the rows in turn, a layer: four window layers' gathers, the global layer's sweep over the key blocks a row sees
        assert sorted(p.split("/")[2] for p in loops) == ["attn_global"] * 2 + ["attn_window"] * 4, loops
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert 0.5e9 < temp < 1e9, f"prefill ({W}, 512) temporaries: {temp / 1e9:.2f} GB"
    assert sorted(p.split("/")[-2] for p in attention) == (["attn_global"] + ["attn_window"] * 4 if program == "decode8" else [])
    assert pool_relayouts(text, [a.shape for a in cache.pool_arrays()]) == 0


@pytest.mark.parametrize("program", ["decode8", "prefill512"])
def test_latent_serving_program_compiles_and_never_relays_out_the_pool(program, one_chip, compiled_kernels):
    """models/pangu_ultra.py at its published widths (hidden 7,680, 128 heads,
    q_lora 1,536, kv_lora 512 + rope 64; one dense and one expert layer, two
    held experts): the decode program (the ABSORBED attention through the
    template with `v_lanes`: 128 query rows against the pool's one head of 640
    lanes, split-K 8 at a 1,024-page table) and the prefill program (XLA over
    blocks of cached latents) compile for the v5e with NO copy as large as the
    latent pool or one layer of it, and every layer's write is the in-place
    Pallas one over the ONE pool array."""
    import dataclasses

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    mc = dataclasses.replace(load_config("openpangu_ultra_moe").model_config, n_layer=2, first_k_dense=1,
                             n_experts_held=2, vocab_size=512)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (8449,), 32, jnp.bfloat16, kernel_layout=True)))
    (pool,) = cache.pool_arrays()
    assert pool.shape == (2, 1, 8449, 32, 640)  # 576 values at whole 128-lane rows, stored once
    assert pool.dtype.itemsize * pool.shape[-1] == 1280  # bytes a token a layer
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    B, T = 16, 1024
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, arr((B, T)), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 8)
    else:
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((1, 512)), arr(()), arr(()), cache, arr((1, T)), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    text = lowered.compile().as_text()
    # a write a layer (+ decode's attention) and the routed layer's ONE grouped matmul
    assert text.count("tpu_custom_call") == (4 if program == "decode8" else 2) + len(mc.moe_layers)
    assert pool_relayouts(text, [pool.shape]) == 0


@pytest.mark.parametrize("program", ["decode8", "prefill512"])
def test_sparse_serving_program_compiles_and_reads_only_the_selected_rows(program, one_chip, compiled_kernels):
    """models/dots3.py at its published widths (hidden 5,120; a full layer of 128
    heads with its indexer and a dense FFN, a sliding layer of 64 heads over a
    latent of 1,088 with two held experts), 32 slots, a table of 2,048 pages:
    the decode program's sliding layer runs the template with `v_lanes` 1,024
    AND `sliding_window` 513 together (one Mosaic call under `attn_window`, a
    pool head of 1,152 lanes), its full layer GATHERS 2,048 latent rows a slot
    (`bf16[32,2048,640]`) and no more, whatever the 65,536-token table holds;
    the prefill program's full layer attends in ONE Mosaic call under
    `attn_select` (kernels/latent_prefill.py) inside the default scoped VMEM,
    and its temporaries are smaller than the XLA sweep's were; both programs
    write each of the three pool arrays in place and hold NO copy as large as
    a pool array or one layer of it."""
    import dataclasses
    import re

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.models.dots3 import FULL, SLIDING
    from midgpt_tpu.sampling import serve

    mc = dataclasses.replace(load_config("dots3_note").model_config, n_layer=2, layer_types=(FULL, SLIDING),
                             n_experts_held=2, vocab_size=512)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (32769, 1089), 32, jnp.bfloat16, kernel_layout=True)))
    assert [a.shape for a in cache.pool_arrays()] == [(1, 1, 32769, 32, 640), (1, 1, 32769, 32, 128), (1, 1, 1089, 32, 1152)]
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    B, T = 32, 2048
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, (arr((B, T)), arr((B, T))), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 8)
    else:
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((1, 512)), arr(()), arr(()), cache, (arr((1, T)), arr((1, T))), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    compiled = lowered.compile()
    text = compiled.as_text()
    paths = re.findall(r'custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', text)
    assert sum("kv_write" in p for p in paths) == 3 and sum("moe_experts" in p for p in paths) == 1
    attention = [p.split("/")[-2] for p in paths if "kv_write" not in p and "moe_experts" not in p]
    assert attention == (["attn_window"] if program == "decode8" else ["attn_select"])
    if program == "decode8":
        gathered = set(re.findall(r"= bf16\[32,(\d+),640\]\S* gather\(", text))
        assert gathered == {"2048"}, gathered  # the selected rows, through the table; never the context
    else:
        call, = re.findall(r'[^\n]*tpu_custom_call[^\n]*attn_select/pallas_call[^\n]*', text)
        assert set(re.findall(r'"scoped_memory_configs":\[[^\]]*"size":"(\d+)"', call)) == {str(16 * 2 ** 20)}  # no vmem_limit_bytes
        # 443,075,072 B with the XLA sweep in the kernel's place (AOT compile, PR 60: this program at e5b6b25): the
        # selection's mask is built with no (T, S) count and no second copy of the scores' bits (`selection_mask`)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.8 * 443_075_072
    assert pool_relayouts(text, [a.shape for a in cache.pool_arrays()]) == 0


@pytest.mark.parametrize("form,pages", [("causal_chunk_no_mask_operand", 512), ("mask_operand_a_table_of_one_block", 16)])
def test_latent_prefill_kernel_compiles_at_the_published_widths(form, pages, one_chip, compiled_kernels):
    """kernels/latent_prefill.py alone at the widths dots3-note's full layers
    and openPangu-Ultra share (128 heads, latent 512 + 64 in rows of 640 lanes,
    qk 128 + 64, v 128; a chunk of 512 rows): the form WITHOUT the mask operand
    (a causal chunk's visibility from two prefetched scalars: the sweep of
    models/pangu_ultra.py, which no program runs yet), and the masked form
    over a table of one key block. One Mosaic call, the default scoped VMEM."""
    import math
    import re

    arr = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    S, i32 = pages * 32, jnp.int32

    def attend(q, lat, w, n_keys, keep, start, n_valid):
        keep, start, n_valid = (None, start, n_valid) if form.startswith("causal") else (keep, None, None)
        return lp.latent_prefill_attention(q, lat, w, n_keys, keep, start, n_valid, nope=128, scale=1 / math.sqrt(192))

    text = jax.jit(attend).lower(arr((512, 128, 192)), arr((S, 640)), arr((128, 256, 512)), arr((), i32),
                                 arr((512, S), jnp.int8), arr((), i32), arr((), i32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert set(re.findall(r'"scoped_memory_configs":\[[^\]]*"size":"(\d+)"', text)) <= {str(16 * 2 ** 20)}


@pytest.mark.parametrize("program", ["decode8", "prefill2x128"])
def test_looped_serving_program_carries_the_pool_through_its_loops_without_a_copy(program, one_chip, compiled_kernels):
    """models/ouro.py at the PUBLISHED preset and the cell's real sizes (48
    layers applied 4 times, 12 slots, a 157-page pool of 192 cache layers:
    7.90 GB beside 5.34 GB of weights): the 8-step decode chunk (steps x
    passes x layers, three nested rolled loops with the pools in every carry)
    and the prefill program compile for the v5e with NO copy as large as a
    pool or one layer of it (`pool_relayouts`), no pool-shaped copy in any
    while body, the WHOLE pool aliased from argument to result, ONE write and
    ONE attention custom call for the 192 applications a step, and temporaries
    of a few MB: the rolled loop costs no pool copy (PERF.md section 6 PR 41)."""
    from midgpt_tpu.analysis.hlo_audit import pool_relayouts, while_body_pool_copies
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    mc = load_config("ouro_2p6b").model_config
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (157,), 32, jnp.bfloat16, kernel_layout=True)))
    pools = cache.pool_arrays()
    assert [a.shape for a in pools] == [(192, 16, 157, 32, 128)] * 2
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pools)
    assert pool_bytes == 157 * 32 * 1_572_864  # 7.90 GB
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    B, T = 12, 32
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, arr((B, T)), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 1)
    else:
        W = serve.prefill_width(12, 128)
        assert W == 2
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((W, 128)), arr((W,)), arr((W,)), cache, arr((W, T)), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    assert len(lowered.as_text()) < 200_000  # 123 k characters whatever n_layer and n_loop
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (2 if program == "decode8" else 1)
    assert pool_relayouts(text, [a.shape for a in pools]) == 0
    census = while_body_pool_copies(text, "bf16[192,16,157,32,128]")
    assert len(census) >= (3 if program == "decode8" else 2) and not any(census.values()), census
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes and mem.temp_size_in_bytes < 64 << 20, mem
    assert mem.argument_size_in_bytes < 13.3e9  # 5.34 GB of weights + the pools


@pytest.fixture(scope="module")
def kda_programs(one_chip):
    """Forward and gradient of the chunked KDA recurrence through
    kernels/kda.py at the published shape (`train_kimi_linear_t8k`: B=1,
    T=8,192, 32 heads of 128, bf16 q/k/v, float32 decay and beta), inside the
    `kda_scan` scope as models/kimi_linear.py opens it, compiled ONCE each for
    the v5e: the optimized HLO of both, shared by the assertions below."""
    import re

    kk = importlib.import_module("midgpt_tpu.kernels.kda")
    from midgpt_tpu.ops.kda import CHUNK, SUB

    B, T, Hk, d = 1, 8192, 32, 128
    arr = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (arr((B, T, Hk, d), jnp.bfloat16),) * 3 + (arr((B, T, Hk, d), jnp.float32), arr((B, T, Hk), jnp.float32))

    def scan(*a):
        with jax.named_scope("kda_scan"):
            return kk.kda_scan(*a, chunk=CHUNK, sub=SUB)

    def loss(*a):
        o, s = scan(*a)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(s)

    was = kk._interpret
    kk._interpret = lambda: False  # Mosaic, not the interpreter (module scope: no monkeypatch fixture here)
    try:
        with jax.default_matmul_precision("default"):
            programs = {
                "forward": jax.jit(scan).lower(*args).compile(),
                "gradient": jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*args).compile(),
            }
    finally:
        kk._interpret = was
        kk._forward.clear_cache(); kk._backward.clear_cache()  # no trace made for Mosaic outlives this fixture
    under_scope = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ([^\n]*?)metadata=\{[^}\n]*?op_name="[^"]*kda_scan[^"]*"', re.M)
    return {name: (c, under_scope.findall(c.as_text())) for name, c in programs.items()}


@pytest.mark.parametrize("program,calls", [("forward", 1), ("gradient", 2)])
def test_kda_kernels_compile_at_the_published_shape_and_carry_the_scope(program, calls, kda_programs):
    """One Mosaic call forward; forward + backward in the gradient. Each is
    named `kda_scan.<n>`, what benchmarks/metrics/kda_kernel.py sums."""
    import re

    _, ops = kda_programs[program]
    mosaic = [name for name, rest in ops if "tpu_custom_call" in rest]
    assert len(mosaic) == calls, mosaic
    assert all(re.fullmatch(r"kda_scan(\.\d+)?", n) for n in mosaic), mosaic


@pytest.mark.parametrize("program", ["forward", "gradient"])
def test_kda_chunk_terms_and_state_never_reach_hbm(program, kda_programs):
    """What the jnp body leaves in HBM is gone from under the scope: no
    (sub, sub, d_k) decay tensor, no `convolution` (its sub-block products, its
    solve, its scan's products with the state), no while loop; and the
    program's temporaries are the residuals (the chunk-start states, G) plus
    the cotangents, well under the jnp body's 1.23 GB of backward scratch
    beside them."""
    import re

    compiled, ops = kda_programs[program]
    assert ops, "no op under kda_scan"
    for name, rest in ops:
        assert not re.search(r"f32\[[\d,]*16,16,128\]", rest), (name, rest[:200])
        assert "convolution(" not in rest and " while(" not in rest, (name, rest[:200])
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


SERVING_EXPERTS = {  # family: D, F, experts held, router width (bf16, top-8): the published widths, the cells' shares
    "trinity_1024x2048_128_whole": (2048, 1024, 128, 128),
    "mimo_2048x4096_16_of_256": (4096, 2048, 16, 256),
    "pangu_2048x7680_16_of_256": (7680, 2048, 16, 256),
}


@pytest.mark.parametrize("rows", [64, 512], ids=["decode64", "chunk512"])
@pytest.mark.parametrize("family", list(SERVING_EXPERTS))
def test_serving_experts_are_one_mosaic_call_at_the_published_widths(family, rows, one_chip, compiled_kernels):
    """ops/moe.py `moe_experts_serving` at the three served families' expert
    widths, for a decode step's rows and a prefill chunk's, at the row block
    and the F slice the shapes give: ONE Mosaic call a routed layer inside the
    VMEM limit the kernel sets (Mosaic refuses a call past it), and no loop."""
    from midgpt_tpu.ops import moe

    D, F, held, n_experts = SERVING_EXPERTS[family]
    block = moe.moe_row_block(rows, 8, n_experts, 2)
    bf = gm.f_slice(block, D, F, 2)
    assert F % bf == 0 and 6 * bf * D * 2 <= gm.VMEM_BLOCKS < gm.VMEM_LIMIT <= 32 << 20  # of a v5e core's 128 MiB: XLA sets the limit aside
    arr = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda *a: moe.moe_experts_serving(*a, offset=0, block_rows=block)).lower(
        arr((rows, D)), arr((rows, 8), jnp.int32), arr((rows, 8), jnp.float32),
        arr((held, F, D)), arr((held, F, D)), arr((held, D, F))).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text


@pytest.mark.parametrize("program", ["decode8", "prefill512"])
def test_state_kind_serving_programs_keep_pools_and_state_rows_in_place(program, one_chip, compiled_kernels, monkeypatch):
    """models/olmo_hybrid.py as the benchmark's cell runs it (16 layers at the
    published widths: 30 heads of 96 x 192 in a linear layer, 30 of 128 in a
    full one; 24 slots and the sink row; pages of 32): the decode program (8
    steps, a table of 128 pages, split-K 8) and the one-row prefill program (a
    chunk of 512 through a table of 512 pages) compile for the v5e; neither
    copies or relays out a K/V pool OR the state arrays (the delta-rule states
    lie as the chunked kernels hold them, (d_v, d_k): stored the other way the
    prefill program relaid the WHOLE 0.88 GB of rows out and back a call); the
    prefill's scan is the Pallas kernel under `linear_state`, three calls a
    period, its attention four 128-row calls a full layer, inside a scope of
    their own (`prefill_attn`, the decode kernel's `attn_global` alone); and the programs'
    temporaries stay under 0.25 GB beside 14.4 GB of weights, pools and rows (a
    period's matrices handed to the loop as its scanned input were copied out
    of the stack first: 0.8 and 1.3 GB of temporaries)."""
    import dataclasses
    import re

    import midgpt_tpu.ops.kda as ops_kda
    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    kk = importlib.import_module("midgpt_tpu.kernels.kda")
    monkeypatch.setattr(kk, "_interpret", lambda: False)
    # `kda_chunked` asks the backend, which here is the CPU under a described TPU: steer it to the kernels
    monkeypatch.setattr(ops_kda.jax, "default_backend", lambda: "tpu")
    mc = dataclasses.replace(load_config("olmo_hybrid_7b").model_config, n_layer=16, block_size=16384)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    B = 24
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (B * 112 + 1, B + 1), 32, jnp.bfloat16, kernel_layout=True)))
    assert cache.pools[0][0].shape == (4, 30, 2689, 32, 128) and [a.shape for a in cache.state] == [(12, 25, 30, 192, 96), (12, 25, 3 * 11520)]
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, (arr((B, 128)), arr((B,))), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 8)
    else:
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((1, 512)), arr(()), arr(()), cache, (arr((1, 512)), arr((1,))), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    compiled = lowered.compile()
    text = compiled.as_text()
    paths = re.findall(r'custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', text)
    assert sum("kv_write" in p for p in paths) == 1  # one rolled period: the full layer's in-place write
    attention = [p for p in paths if "attn_global" in p and "kv_write" not in p]
    scans = [p for p in paths if "linear_state" in p]
    # the prefill's attention calls sit in an innermost scope of their own and are named after it (what
    # benchmarks/metrics/prefill_attention.py sums, and what keeps them out of the decode kernel's roofline:
    # serve_kinds_scopes.py puts a custom call to the innermost listed scope of its path)
    named = re.findall(r"%(prefill_attn[\w.]*) = [^\n]*custom-call\(", text)
    if program == "decode8":
        assert len(attention) == 1 and not scans  # the one-token update is XLA: two sweeps of the rows' states and one write
        assert "prefill_attn" not in attention[0] and not named
    else:
        assert len(attention) == 4 and len(scans) == 3 and all("/attn_linear/linear_state/" in p and "kda_scan" in p for p in scans)
        assert all("/attn_global/prefill_attn/" in p for p in attention) and len(named) == 4, (attention, named)
        assert all(re.fullmatch(r"prefill_attn\.\d+", n) for n in named), named
    assert len(paths) == 1 + len(attention) + len(scans)
    assert pool_relayouts(text, [a.shape for a in cache.pool_arrays()]) == 0
    assert pool_relayouts(text, [a.shape for a in cache.state]) == 0
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25e9


@pytest.mark.parametrize("program", ["decode8", "logits", "prefill512"])
def test_state_space_serving_programs_keep_pools_and_state_rows_in_place(program, one_chip, compiled_kernels):
    """models/granite_hybrid.py as the benchmark's cell runs it (all 40 layers at
    the published widths: 64 mamba heads of 64 x 128, 32 query heads on 8 K/V
    heads of 64; 64 slots and the sink row; pages of 32): the decode program
    (8 steps, a table of 128 pages, split-K 8), the program behind
    `ServeEngine.next_logits` (one step that commits no state) and the one-row
    prefill program (a chunk of 512 through a table of 512 pages) compile for
    the v5e; none copies or relays out a K/V pool OR the state arrays, and the
    arguments are 15.65 GB (6.38 weights + 4.30 K/V at 128 lanes a 64-channel
    head + 4.91 states + 0.06 history). The logits program holds NO write of a
    state row: with the one-token update written inside the layers' loop it
    kept a copy of the whole 4.9 GB state array beside it and did not fit the
    chip (PERF.md section 6 PR 63). The prefill's attention is 16 calls of 32
    rows an attention layer (the template folds the 4 query heads of a pool
    head into its rows, and Mosaic refuses more than 128 of them), inside a
    scope of their own (`prefill_attn`; the decode kernel's `attn_global` alone)."""
    import dataclasses
    import re

    from midgpt_tpu.analysis.hlo_audit import pool_relayouts
    from midgpt_tpu.config import load_config
    from midgpt_tpu.sampling import serve

    mc = dataclasses.replace(load_config("granite_4_0_h_micro").model_config, block_size=16384)
    model = mc.model()
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree.map(sds, jax.eval_shape(lambda k: model.cast_params(model.init(mc, k), jnp.bfloat16), jax.random.PRNGKey(0)))
    B = 64
    cache = jax.tree.map(sds, jax.eval_shape(lambda: model.init_cache(mc, (B * 128 + 1, B + 1), 32, jnp.bfloat16, kernel_layout=True)))
    assert cache.pools[0][0].shape == (4, 8, 8193, 32, 128) and [a.shape for a in cache.state] == [(36, 65, 64, 64, 128), (36, 65, 3 * 4352)]
    arr = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    if program == "decode8":
        lowered = serve._serve_decode_chunk.lower(
            mc, params, arr((B,)), cache, (arr((B, 128)), arr((B,))), arr((B,)), arr((B,), jnp.bool_), 8,
            0.8, None, None, "kernel", arr((2,), jnp.uint32), None, 8)
    elif program == "logits":
        lowered = serve._serve_decode_logits.lower(
            mc, params, arr((B,)), cache, (arr((B, 128)), arr((B,))), arr((B,)), arr((B,), jnp.bool_), "kernel", None, 8)
    else:
        lowered = serve._serve_prefill_chunk.lower(
            mc, params, arr((1, 512)), arr(()), arr(()), cache, (arr((1, 512)), arr((1,))), None, "kernel",
            0.8, None, None, arr((2,), jnp.uint32))
    compiled = lowered.compile()
    text = compiled.as_text()
    paths = re.findall(r'custom-call\([^\n]*tpu_custom_call[^\n]*?op_name="([^"]*)"', text)
    assert sum("kv_write" in p for p in paths) == 1  # one rolled period: the attention layer's in-place write
    attention = [p for p in paths if "attn_global" in p and "kv_write" not in p]
    named = re.findall(r"%(prefill_attn[\w.]*) = [^\n]*custom-call\(", text)
    if program == "prefill512":
        assert len(attention) == 16 and all("/attn_global/prefill_attn/" in p for p in attention) and len(named) == 16, (attention, named)
    else:
        assert len(attention) == 1 and "prefill_attn" not in attention[0] and not named
    assert len(paths) == 1 + len(attention)  # the recurrence is XLA in both programs
    assert pool_relayouts(text, [a.shape for a in cache.pool_arrays()]) == 0
    assert pool_relayouts(text, [a.shape for a in cache.state]) == 0
    # a state row's write is a dynamic-update-slice of the (36, 65, 64, 64, 128) array: the logits program has none
    writes = len(re.findall(r"f32\[36,65,64,64,128\][^=\n]* dynamic-update-slice\(", text))
    assert (writes == 0) if program == "logits" else (writes >= 1), writes
    memory = compiled.memory_analysis()
    assert 15.6e9 < memory.argument_size_in_bytes < 15.7e9 and memory.temp_size_in_bytes < 0.25e9
