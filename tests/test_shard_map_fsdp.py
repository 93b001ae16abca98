"""Numerical parity: explicit shard_map FSDP vs the implicit GSPMD path.

Same params, same batch → same loss and same gradients (fp32 tolerance) on
the 8-device CPU mesh. This is the acceptance test for the authored
per-layer all-gather / reduce-scatter schedule (parallel/shard_map_fsdp.py).
"""

import jax
import numpy as np

from midgpt_tpu.config import ExperimentConfig, MeshConfig
from midgpt_tpu.models.gpt import GPT, GPTConfig
from midgpt_tpu.ops.loss import fused_linear_cross_entropy
from midgpt_tpu.parallel.data import make_global_batch
from midgpt_tpu.parallel.fsdp import constrain, fsdp_param_specs
from midgpt_tpu.parallel.mesh import batch_spec, make_mesh
from midgpt_tpu.parallel.shard_map_fsdp import make_shard_map_loss

import pytest

CHUNK = 1 << 30  # no loss chunking: keeps the comparison single-variable


def _setup(dropout=0.0):
    cfg = GPTConfig(
        block_size=64,
        vocab_size=128,
        n_layer=2,
        n_head=2,
        n_embd=32,
        dropout=dropout,
        remat=True,
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=4, sp=1))
    params = GPT.init(cfg, jax.random.PRNGKey(0))
    specs = fsdp_param_specs(params, mesh, shard_model=True, min_size=0)
    params = jax.jit(lambda p: constrain(p, specs, mesh))(params)

    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False))
    return cfg, mesh, params, specs, xg, yg


def test_loss_and_grads_match_gspmd():
    cfg, mesh, params, specs, xg, yg = _setup()

    def gspmd_loss(p, x, y):
        h = GPT.hidden(cfg, p, x, inference=True)
        return fused_linear_cross_entropy(h, p.lm_head, y, CHUNK)

    sm_loss = make_shard_map_loss(cfg, mesh, specs, CHUNK)

    ref_l, ref_g = jax.jit(jax.value_and_grad(gspmd_loss))(params, xg, yg)
    sm_l, sm_g = jax.jit(
        jax.value_and_grad(lambda p, x, y: sm_loss(p, x, y, None))
    )(params, xg, yg)

    np.testing.assert_allclose(float(sm_l), float(ref_l), rtol=1e-6)
    for ref, got, path in zip(
        jax.tree.leaves(ref_g), jax.tree.leaves(sm_g), jax.tree.leaves(specs)
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-4
        )


def test_grads_sharded_like_params():
    """Grads must come back in the FSDP layout (reduce-scattered, not dense)."""
    cfg, mesh, params, specs, xg, yg = _setup()
    sm_loss = make_shard_map_loss(cfg, mesh, specs, CHUNK)
    grads = jax.jit(
        jax.grad(lambda p, x, y: sm_loss(p, x, y, None))
    )(params, xg, yg)
    # tree_util spelling: jax.tree.flatten_with_path arrived later than
    # this container's jax; the tree_util alias exists in both.
    flat_g, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(params)
    for (path, g), (_, p) in zip(flat_g, flat_p):
        assert g.sharding == p.sharding, f"{path}: {g.sharding} != {p.sharding}"


def test_train_step_e2e_shard_map():
    """One full training step with fsdp_mode='shard_map' runs and is finite."""
    from midgpt_tpu.training.train import init_state, make_train_step

    config = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
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
        fsdp_mode="shard_map",
        mesh=MeshConfig(data=2, fsdp=4, sp=1),
        model_config=GPTConfig(
            block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=32
        ),
    )
    mesh = make_mesh(config.mesh)
    params, opt_state, specs, optimizer = init_state(config, mesh)
    step, *_ = make_train_step(config, optimizer, mesh, specs)

    rng = np.random.default_rng(1)
    G, B, T = config.g_accum_iters, config.batch_size, config.model_config.block_size
    x = rng.integers(0, config.model_config.vocab_size, (G, B, T), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec())
    yg = make_global_batch(y, mesh, batch_spec())
    params, opt_state, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


from midgpt_tpu.utils.hlo import (  # noqa: E402
    hlo_computations as _hlo_computations,
    in_shard_map_scope,
    is_forward_shmap_line,
)


def _fusion_calls_dot(line, comps, _seen=None):
    """Does this fusion instruction's called computation (transitively,
    through nested fusions/calls) contain a dot?"""
    import re

    _seen = _seen if _seen is not None else set()
    for callee in re.findall(r"calls=%([\w.\-]+)", line):
        if callee in _seen or callee not in comps:
            continue
        _seen.add(callee)
        for inner in comps[callee]:
            if " dot(" in inner:
                return True
            if "calls=%" in inner and _fusion_calls_dot(inner, comps, _seen):
                return True
    return False


def test_zero3_gathers_schedulable_ahead_of_compute():
    """Structural pin of the ZeRO-3 overlap claim (shard_map_fsdp.py header;
    VERDICT r4 weak #2): in the compiled layer-scan body at scan_unroll=2,
    EVERY weight all-gather's transitive operand chain is free of compute
    (dot, or fusion-calling-dot) from the same body. That is the dataflow
    property that lets XLA's latency-hiding scheduler issue the gather of
    layer l+1 during layer l's compute; if a refactor ever made the gathers
    depend on activations (serializing the stream), this fails. The actual
    async overlap (all-gather-start/-done split around compute) is a TPU
    scheduler behavior — asserted against the chip's compiler by
    tests/test_chip_compile.py (an AOT compile for described TPU devices);
    the CPU backend emits synchronous all-gathers.

    Also pins that unroll=2 exposes BOTH layers' gathers in one body (the
    precondition for cross-layer overlap): 2 layers x 6 block leaves = 12."""
    import re

    from midgpt_tpu.utils.hlo import lower_abstract_train_step

    config = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=2,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        eval_interval=5,
        beta2=0.95,
        weight_decay=1e-4,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        fsdp_mode="shard_map",
        mesh=MeshConfig(data=1, fsdp=8, sp=1),
        model_config=GPTConfig(
            block_size=64, vocab_size=64, n_layer=4, n_head=2, n_embd=64,
            scan_unroll=2,
        ),
    )
    txt = lower_abstract_train_step(config).compile().as_text()

    comps = _hlo_computations(txt)
    # Computations containing shard_map weight gathers next to compute: the
    # forward layer-scan body (jvp) and the backward one (transpose(jvp),
    # ZeRO-3 re-gather under remat). XLA may fully unroll the short forward
    # scan into its caller on some backends/versions — the gathers keep
    # their shard_map provenance metadata either way, so match on that
    # rather than on living inside a while body.
    bodies = {
        name: lines
        for name, lines in comps.items()
        if any(" all-gather(" in l and in_shard_map_scope(l) for l in lines)
        and any(" dot(" in l for l in lines)
    }
    assert bodies, "no computation with shard_map all-gathers found — did lowering change?"

    fwd_counts = []
    for name, lines in bodies.items():
        defs = {}
        for line in lines:
            m = re.match(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
            if not m:
                continue
            iname = m.group(1)
            deps = [r for r in re.findall(r"%([\w.\-]+)", line) if r != iname]
            defs[iname] = (line, deps)
        gathers = [n for n, (l, _) in defs.items() if " all-gather(" in l]
        n_fwd = sum(
            1
            for n, (l, _) in defs.items()
            if " all-gather(" in l and is_forward_shmap_line(l)
        )
        if n_fwd:
            fwd_counts.append(n_fwd)
        for g in gathers:
            seen, stack = set(), list(defs[g][1])
            while stack:
                d = stack.pop()
                if d in seen or d not in defs:
                    continue
                seen.add(d)
                line, deps = defs[d]
                assert " dot(" not in line, (
                    f"{name}: gather %{g} depends on compute %{d} — the "
                    "ZeRO-3 weight stream is serialized behind layer compute"
                )
                assert not (" fusion(" in line and _fusion_calls_dot(line, comps)), (
                    f"{name}: gather %{g} depends on dot-fusion %{d}"
                )
                stack.extend(deps)
    # Both unrolled layers' gathers live in one forward body: 2 x 6 leaves.
    assert any(c >= 12 for c in fwd_counts), (
        f"forward body gather counts {fwd_counts} — expected >= 12 "
        "(scan_unroll=2 no longer exposes both layers' gathers in one body)"
    )


def test_train_step_shard_map_tp_matches_gspmd():
    """r5: the explicit ZeRO-3 body composes with Megatron tp — 'tp' rides
    a GSPMD auto axis inside the shard_map (parallel/shard_map_fsdp.py)
    while the authored per-layer gathers stay on 'fsdp'. One full train
    step on a (data=2, fsdp=2, tp=2) mesh matches BOTH the GSPMD tp step
    and the fsdp-only oracle on the same batch/seed."""
    from midgpt_tpu.training.train import init_state, make_train_step

    base = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=2,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        beta2=0.95,
        weight_decay=1e-4,
        eval_interval=5,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        mesh=MeshConfig(data=2, fsdp=2, sp=1, tp=2),
        model_config=GPTConfig(
            block_size=32, vocab_size=64, n_layer=2, n_head=2, n_embd=32
        ),
    )
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (1, 8, 32), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    losses = {}
    for name, cfg in {
        "shard_map_tp": base.replace(fsdp_mode="shard_map"),
        "gspmd_tp": base,
        "fsdp_only": base.replace(mesh=MeshConfig(data=2, fsdp=4, sp=1)),
    }.items():
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, *_ = make_train_step(cfg, optimizer, mesh, specs)
        xg = make_global_batch(x, mesh, batch_spec())
        yg = make_global_batch(y, mesh, batch_spec())
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)
    np.testing.assert_allclose(losses["shard_map_tp"], losses["gspmd_tp"], rtol=1e-5)
    np.testing.assert_allclose(losses["shard_map_tp"], losses["fsdp_only"], rtol=1e-5)


def test_loss_and_grads_match_gspmd_with_ring():
    """The composition: explicit shard_map FSDP x ring sequence parallelism
    in ONE shard_map body (per-layer weight gathers on 'fsdp', K/V rotation
    on 'sp') against the dense unsharded oracle — loss AND grads."""
    import dataclasses

    cfg = GPTConfig(
        block_size=64, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
        attn_impl="ring", remat=True,
    )
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, sp=2))
    params = GPT.init(cfg, jax.random.PRNGKey(0))
    specs = fsdp_param_specs(params, mesh, shard_model=True, min_size=0)
    params = jax.jit(lambda p: constrain(p, specs, mesh))(params)

    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)
    xg = make_global_batch(x, mesh, batch_spec(with_accum=False, shard_seq=True))
    yg = make_global_batch(y, mesh, batch_spec(with_accum=False, shard_seq=True))

    oracle_cfg = dataclasses.replace(cfg, attn_impl="naive")

    def gspmd_loss(p, x, y):
        h = GPT.hidden(oracle_cfg, p, x, inference=True)
        return fused_linear_cross_entropy(h, p.lm_head, y, CHUNK)

    sm_loss = make_shard_map_loss(cfg, mesh, specs, CHUNK, sequence_parallel="ring")

    ref_l, ref_g = jax.jit(jax.value_and_grad(gspmd_loss))(params, xg, yg)
    sm_l, sm_g = jax.jit(
        jax.value_and_grad(lambda p, x, y: sm_loss(p, x, y, None))
    )(params, xg, yg)

    np.testing.assert_allclose(float(sm_l), float(ref_l), rtol=1e-6)
    for ref, got in zip(jax.tree.leaves(ref_g), jax.tree.leaves(sm_g)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-4
        )


@pytest.mark.slow  # heavy long-tail: full suite only, per the tier-1 870 s gate budget (CLAUDE.md)
def test_train_step_shard_map_ring_matches_gspmd_sp1():
    """One full training step: fsdp_mode='shard_map' + ring/sp=2 produces
    the same loss as the implicit-GSPMD naive sp=1 step on the same batch
    and seed — a third independently-authored parallelization schedule
    computing the same math."""
    import dataclasses

    base = ExperimentConfig(
        rundir="",
        data_dir="",
        learning_rate=1e-3,
        batch_size=8,
        warmup_steps=2,
        min_lr=1e-4,
        lr_decay_steps=10,
        max_steps=10,
        beta2=0.95,
        weight_decay=1e-4,
        eval_interval=5,
        param_dtype="float32",
        compute_dtype="float32",
        g_accum_iters=1,
        shard_model=True,
        fsdp_min_size=0,
        fsdp_mode="shard_map",
        mesh=MeshConfig(data=2, fsdp=2, sp=2),
        model_config=GPTConfig(
            block_size=64, vocab_size=128, n_layer=2, n_head=2, n_embd=32,
            attn_impl="ring",
        ),
    )
    from midgpt_tpu.training.train import init_state, make_train_step

    rng = np.random.default_rng(0)
    x = rng.integers(0, 128, (1, 8, 64), dtype=np.int32)
    y = np.roll(x, -1, axis=-1)

    losses = {}
    for name, cfg in {
        "shard_map_ring": base,
        "gspmd_naive_sp1": base.replace(
            fsdp_mode="gspmd",
            mesh=MeshConfig(data=2, fsdp=4, sp=1),
            model_config=dataclasses.replace(base.model_config, attn_impl="naive"),
        ),
    }.items():
        mesh = make_mesh(cfg.mesh)
        params, opt_state, specs, optimizer = init_state(cfg, mesh)
        step, *_ = make_train_step(cfg, optimizer, mesh, specs)
        sp = batch_spec(shard_seq=cfg.mesh.sp > 1)
        xg = make_global_batch(x, mesh, sp)
        yg = make_global_batch(y, mesh, sp)
        _, _, loss = step(params, opt_state, xg, yg, jax.random.PRNGKey(0))
        losses[name] = float(loss)

    assert np.isfinite(losses["shard_map_ring"])
    np.testing.assert_allclose(
        losses["shard_map_ring"], losses["gspmd_naive_sp1"], rtol=1e-5
    )
